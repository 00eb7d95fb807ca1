"""The device path's Pallas kernels compile for a TPU v5e at real widths.

Each test compiles one kernel with the TPU compiler installed on the
host, for a v5e that is described and not attached, so Mosaic refusals
(block shapes, VMEM, unsupported ops) surface without a chip.  Nothing
runs: these say nothing about results or speed.  Widths are yi-6b's
(d_model 4096, d_ff 11008, 32 query / 4 KV heads of 128) and, for the
kernels yi-6b does not use, those of the configs that do (olmoe-1b-7b
experts, recurrentgemma-2b RG-LRU, rwkv6-7b heads and its serving
decode state).  One more compiles rwkv6-7b's serving steps, a prefill
slice and a decode step, at published widths and the chip cell's batch.

The topology is described inside a fixture only: the TPU library may be
loaded by one process at a time, so it is never touched while modules
are imported, and only the worker that runs this file loads it.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fusion import Epilogue, EpilogueOperands
from repro.core.task import BiasType
from repro.kernels.attention.ops import flash_attention
from repro.kernels.matmul.ops import fused_matmul
from repro.kernels.moe.ops import grouped_matmul
from repro.kernels.quant.ops import quantize_rowwise
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rwkv6.ops import rwkv6_decode_step

BF, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32
TOKENS = 2048                      # 4 prompts of 512 tokens


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache
    off: an entry written here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _glu(act="silu", **kw):
    return Epilogue(activation=act, glu=True, out_dtype=BF, **kw)


#: name -> (kernel call, argument (shape, dtype) list)
CASES = {
    "matmul_bf16_glu_4096x22016": (
        lambda a, b: fused_matmul(a, b, epilogue=_glu(), interpret=False),
        [((TOKENS, 4096), BF), ((4096, 2 * 11008), BF)]),
    "matmul_bf16_down_11008x4096": (
        lambda a, b: fused_matmul(a, b, epilogue=Epilogue(out_dtype=BF),
                                  interpret=False),
        [((TOKENS, 11008), BF), ((11008, 4096), BF)]),
    "matmul_bf16_row_bias_4096x4096": (
        lambda a, b, bias: fused_matmul(
            a, b, epilogue=Epilogue(bias_type=BiasType.ROW, out_dtype=BF),
            operands=EpilogueOperands(bias=bias), interpret=False),
        [((TOKENS, 4096), BF), ((4096, 4096), BF), ((4096,), BF)]),
    "matmul_bf16_logits_4096x64000": (
        lambda a, b: fused_matmul(a, b, epilogue=Epilogue(out_dtype=F32),
                                  interpret=False),
        [((8, 4096), BF), ((4096, 64000), BF)]),
    "matmul_int8_w8a8_4096x11008": (
        lambda a, b, sa, sb: fused_matmul(
            a, b, epilogue=Epilogue(has_scale_a=True, has_scale_b=True,
                                    out_dtype=BF),
            operands=EpilogueOperands(scale_a=sa, scale_b=sb),
            interpret=False),
        [((TOKENS, 4096), I8), ((4096, 11008), I8), ((TOKENS,), F32),
         ((11008,), F32)]),
    "matmul_int8_glu_4096x22016": (
        lambda a, b, sa, sb: fused_matmul(
            a, b, epilogue=_glu(has_scale_a=True, has_scale_b=True),
            operands=EpilogueOperands(scale_a=sa, scale_b=sb),
            interpret=False),
        [((TOKENS, 4096), I8), ((4096, 2 * 11008), I8), ((TOKENS,), F32),
         ((2 * 11008,), F32)]),
    "flash_attention_gqa_32q_4kv_2048": (
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        [((1, 32, 2048, 128), BF), ((1, 4, 2048, 128), BF),
         ((1, 4, 2048, 128), BF)]),
    # The served prefill shapes (ds67b-6l.prefill, yi6b.decode), causal
    # at the default blocks: 4080 keys pad to whole blocks, and the last
    # query block runs past the queries.
    "flash_attention_prefill_64q_8kv_4080": (
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        [((2, 64, 4080, 128), BF), ((2, 8, 4080, 128), BF),
         ((2, 8, 4080, 128), BF)]),
    "flash_attention_prefill_32q_4kv_1024": (
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        [((8, 32, 1024, 128), BF), ((8, 4, 1024, 128), BF),
         ((8, 4, 1024, 128), BF)]),
    "grouped_matmul_glu_64x2048x2048": (
        lambda x, w: grouped_matmul(x, w, epilogue=_glu(), interpret=False),
        [((64, 320, 2048), BF), ((64, 2048, 2 * 1024), BF)]),
    "grouped_matmul_down_64x1024x2048": (
        lambda x, w: grouped_matmul(x, w, interpret=False),
        [((64, 320, 1024), BF), ((64, 1024, 2048), BF)]),
    "quantize_rowwise_2048x4096": (
        lambda x: quantize_rowwise(x, interpret=False),
        [((TOKENS, 4096), F32)]),
    "rglru_scan_2560": (
        lambda log_a, x: rglru_scan(log_a, x, interpret=False),
        [((1, 2048, 2560), F32), ((1, 2048, 2560), F32)]),
    # rwkv6-16l.decode's state: 16 layers, batch 128, 64 heads of 64;
    # and a batch that 8 does not divide (a serving chunk's leftover).
    **{f"rwkv6_decode_step_16x{b}x64x4096": (
        lambda stack, layer, r, k, v, lw, u: rwkv6_decode_step(
            stack, layer, r, k, v, lw, u, interpret=False),
        [((16, b, 64, 4096), F32), ((), I32)] + [((b, 4096), BF)] * 3
        + [((b, 4096), F32), ((64, 64), F32)]) for b in (128, 100)},
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: a copy or broadcast of the 2-layer stacked WKV state at batch 128, or
#: of a per-key operand of one layer's state size
STATE_SIZED = re.compile(r"= f32\[(2,128,64,4096|128,64,64,64)\]\S* "
                         r"(copy|broadcast)\(")


def test_rwkv6_prefill_slice_and_decode_step_compile_for_v5e(one_chip,
                                                            monkeypatch):
    """rwkv6-7b at published widths, cut to 2 layers: one prefill slice
    and one decode step of the serving cell's batch of 128 compile for a
    v5e, the recurrent state updated in place (it is donated).  Decode
    runs the WKV state through the step kernel: no copy or broadcast of
    the stacked state, or of a per-key state-sized operand, is left."""
    import repro.kernels.rwkv6.ops as wkv_ops
    from repro.configs.registry import get_config
    from repro.models import rwkv6
    # The host's backend is the CPU, where kernels would be interpreted:
    # compile them with Mosaic, for the described chip.
    monkeypatch.setattr(wkv_ops, "resolve_interpret", lambda _=None: False)
    jax.clear_caches()
    cfg = get_config("rwkv6-7b").with_(n_layers=2)
    b = 128

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: rwkv6.init(cfg, k),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: rwkv6.init_cache(cfg, b, 0)))
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    steps = {
        "prefill": (lambda p, t, c: rwkv6.prefill(cfg, p, {"tokens": t}, c),
                    rwkv6.PREFILL_SLICE),
        "decode": (lambda p, t, c: rwkv6.decode_step(cfg, p, t, c, 0), 1)}
    for name, (fn, t) in steps.items():
        tokens = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip)
        compiled = jax.jit(fn, donate_argnums=2).lower(
            params, tokens, cache).compile()
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= state_bytes, name
        if name == "decode":
            text = compiled.as_text()
            assert "tpu_custom_call" in text
            assert not STATE_SIZED.findall(text)
    jax.clear_caches()


#: a copy of yi-6b's stacked K or V cache (32 layers, batch 8, 4 KV
#: heads, 1280 positions of 128), in any layout
YI_STACK_COPY = re.compile(r"= bf16\[32,8,4,1280,128\]\S* (copy|copy-start)\(")


def test_yi6b_generate_decodes_without_copying_the_kv_cache(one_chip,
                                                            monkeypatch):
    """yi-6b at published widths, batch 8, prompt 1024 and 256 new tokens
    (a 1280-position cache): the compiled ``generate`` program, prefill
    through the flash kernel as on the chip, writes each decode step's
    K/V rows into the stacked caches in place.  No copy of a whole
    stacked cache is left in it."""
    import repro.kernels
    import repro.kernels.attention.ops as attn_ops
    from repro.configs.registry import get_config
    from repro.models import transformer
    from repro.serving.engine import lower_generate
    for mod in (repro.kernels, attn_ops):
        monkeypatch.setattr(mod, "resolve_interpret", lambda _=None: False)
    jax.clear_caches()
    cfg = get_config("yi-6b")
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: transformer.init(cfg, k),
                       jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
    text = lower_generate(cfg, params, {"tokens": tokens},
                          max_new_tokens=256, cache_len=1280) \
        .compile().as_text()
    jax.clear_caches()
    assert "tpu_custom_call" in text
    assert "bf16[32,8,4,1280,128]" in text
    assert not YI_STACK_COPY.findall(text)
