"""Serving engine: greedy generation consistency + batching façade."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import concrete_batch, get_config
from repro.models.base import family_module
from repro.serving.engine import (GenerateResult, ServingEngine, generate,
                                  lower_generate)


def _cfg(name="yi-6b"):
    return get_config(name, reduced=True).with_(
        remat="none", dtype=jnp.float32, kv_cache_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    mod = family_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    return cfg, mod, params


def test_greedy_generation_matches_forward_argmax(model):
    """Decode-loop greedy tokens == teacher-forced argmax re-derivation."""
    cfg, mod, params = model
    prompt = concrete_batch(cfg, 2, 12, "prefill")
    res = generate(cfg, params, prompt, max_new_tokens=4)
    assert res.tokens.shape == (2, 4)

    # Re-derive: append generated tokens and check each was the argmax of
    # the forward logits at its position.
    toks = jnp.concatenate([prompt["tokens"], res.tokens], axis=1)
    logits = mod.forward(cfg, params, {"tokens": toks})
    for i in range(4):
        expect = jnp.argmax(logits[:, 12 + i - 1], axis=-1)
        np.testing.assert_array_equal(np.asarray(res.tokens[:, i]),
                                      np.asarray(expect))


def test_generate_deterministic_at_zero_temperature(model):
    cfg, mod, params = model
    prompt = concrete_batch(cfg, 1, 8, "prefill")
    a = generate(cfg, params, prompt, max_new_tokens=3)
    b = generate(cfg, params, prompt, max_new_tokens=3)
    np.testing.assert_array_equal(np.asarray(a.tokens), np.asarray(b.tokens))


def test_temperature_sampling_runs(model):
    cfg, mod, params = model
    prompt = concrete_batch(cfg, 2, 8, "prefill")
    res = generate(cfg, params, prompt, max_new_tokens=3, temperature=1.0,
                   key=jax.random.PRNGKey(7))
    assert res.tokens.shape == (2, 3)
    assert bool(jnp.all((res.tokens >= 0)
                        & (res.tokens < cfg.padded_vocab)))


@pytest.mark.parametrize("keep_logits", [False, True])
def test_generate_logits_match_last_step(model, keep_logits):
    """``logits_last`` is the last step's logits; the per-step stack is
    returned only on request."""
    cfg, mod, params = model
    prompt = concrete_batch(cfg, 2, 8, "prefill")
    res = generate(cfg, params, prompt, max_new_tokens=3,
                   keep_logits=keep_logits)
    assert res.logits_last.shape == (2, cfg.padded_vocab)
    if not keep_logits:
        assert res.logits is None
        return
    assert res.logits.shape == (2, 3, cfg.padded_vocab)
    np.testing.assert_array_equal(np.asarray(res.logits[:, -1]),
                                  np.asarray(res.logits_last))
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(res.logits, axis=-1)), np.asarray(res.tokens))


def test_generate_recompiles_when_matmul_route_changes(model):
    """The matmul route is process-wide state read while tracing:
    switching it must give a new program, and switching back the old one."""
    from repro import backend
    cfg, mod, params = model
    prompt = concrete_batch(cfg, 1, 8, "prefill")
    xla = lower_generate(cfg, params, prompt, max_new_tokens=2).as_text()
    prev = backend.set_default_matmul_backend("pallas")
    try:
        pallas = lower_generate(cfg, params, prompt,
                                max_new_tokens=2).as_text()
    finally:
        backend.set_default_matmul_backend(prev)
    again = lower_generate(cfg, params, prompt, max_new_tokens=2).as_text()
    assert pallas != xla
    assert again == xla


def test_device_path_imports_no_simulator():
    """Serving both chip families imports nothing of the paper
    reproduction: the backend registry, the simulator or the tuner."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    prog = """
import sys
import jax
import repro.models.rwkv6, repro.models.transformer
from repro.configs.registry import concrete_batch, get_config
from repro.models.base import family_module
from repro.serving.engine import generate
for name in ("yi-6b", "rwkv6-7b"):
    cfg = get_config(name, reduced=True)
    params = family_module(cfg).init(cfg, jax.random.PRNGKey(0))
    generate(cfg, params, concrete_batch(cfg, 2, 8, "prefill"),
             max_new_tokens=2).tokens.block_until_ready()
print(sorted(m for m in sys.modules
             if m.split(".")[:2] in (["repro", "backend"], ["repro", "sim"],
                                     ["repro", "tune"])))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_serving_engine_batches_requests(model):
    cfg, mod, params = model
    eng = ServingEngine(cfg, params, max_batch=2, cache_len=64)
    for length in (5, 7, 6):
        eng.submit(jnp.arange(length) % cfg.vocab_size)
    outs = eng.run(max_new_tokens=3)
    assert len(outs) == 3
    for o in outs:
        assert o.shape == (3,)


LAYER_SCOPES = {"embed", "norm", "qkv", "cache_update", "attention",
                "attn_out", "mlp", "head"}


def test_every_compiled_dot_lies_in_one_step_and_one_layer_scope():
    """The compiled program names its dots by ``jax.named_scope``:
    each under ``prefill`` or ``decode`` and exactly one layer scope."""
    import re
    cfg = get_config("yi-6b", reduced=True)
    mod = family_module(cfg)
    params = jax.eval_shape(lambda k: mod.init(cfg, k),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    text = lower_generate(cfg, params, batch, max_new_tokens=4,
                          cache_len=20).compile().as_text()
    dots = re.findall(r"= \S+ (?:dot|convolution)\(.*op_name=\"([^\"]*)\"",
                      text)
    assert len(dots) >= 2 * 8        # prefill and decode, 8 dots a layer
    layers = []
    for name in dots:
        parts = name.split("/")
        assert (("prefill" in parts) + ("decode" in parts)) == 1, name
        inner = [p for p in parts if p in LAYER_SCOPES]
        assert len(inner) == 1, name
        layers.append(inner[0])
    assert set(layers) == {"qkv", "attention", "attn_out", "mlp", "head"}


def test_cached_prefill_takes_the_flash_kernel_where_pallas_compiles(
        model, monkeypatch):
    """The cached prefill attends through the flash kernel where Pallas
    compiles (a TPU backend, which ``resolve_interpret`` reads), through
    the chunked scan elsewhere, and counts the route's blocks; the
    training forward under ``jax.grad`` keeps the chunked scan."""
    from repro.kernels.attention.ops import band_blocks
    from repro.obs import default_registry
    cfg, mod, params = model
    batch = concrete_batch(cfg, 2, 12, "prefill")
    cache = mod.init_cache(cfg, 2, 16)

    def routes():
        reg.clear()
        prefill = str(jax.make_jaxpr(
            lambda p: mod.prefill(cfg, p, batch, cache))(params))
        c = reg.snapshot()["counters"]["attention_prefill_blocks_total"]
        grad = str(jax.make_jaxpr(jax.grad(
            lambda p: mod.forward(cfg, p, batch).sum()))(params))
        return ("pallas_call" in prefill, "pallas_call" in grad,
                {(x["labels"]["route"], x["labels"]["kind"]): x["value"]
                 for x in c})

    reg = default_registry()
    was = reg.enabled
    reg.enable()
    try:
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        assert routes() == (False, False, {
            ("chunked", "computed"): 2 * hkv * -(-12 // cfg.attn_chunk),
            ("chunked", "skipped"): 0})
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        computed, skipped = band_blocks((2, cfg.n_heads, 12, hd),
                                        (2, hkv, 12, hd))
        assert routes() == (True, False, {("flash", "computed"): computed,
                                          ("flash", "skipped"): skipped})
    finally:
        reg.clear()
        reg.enabled = was


def test_engine_counts_batches_rows_padding_and_new_programs(model):
    from repro.obs import MetricsRegistry
    from repro.serving.engine import _generate
    cfg, mod, params = model
    reg = MetricsRegistry()
    eng = ServingEngine(cfg, params, max_batch=2, cache_len=64, metrics=reg)
    lengths = (5, 7, 6, 4, 3)
    _generate.clear_cache()
    for rounds in (1, 2):
        for n in lengths:
            eng.submit(jnp.arange(n) % cfg.vocab_size)
        assert len(eng.run(max_new_tokens=2)) == len(lengths)
        c = reg.snapshot()["counters"]
        # Batches [5, 7], [6, 4], [3]: 2 + 2 + 0 padding tokens.
        assert c["serving_batches_total"][0]["value"] == 3 * rounds
        assert c["serving_rows_total"][0]["value"] == 5 * rounds
        assert c["serving_pad_tokens_total"][0]["value"] == 4 * rounds
        # Three shapes, each a new program in the first round only.
        progs = {(x["labels"]["rows"], x["labels"]["prompt_len"]):
                 x["value"] for x in c["serving_new_programs_total"]}
        assert progs == {("2", "7"): 1, ("2", "6"): 1, ("1", "3"): 1}


def test_a_disabled_registry_records_nothing(model):
    from repro.obs import MetricsRegistry
    cfg, mod, params = model
    reg = MetricsRegistry(enabled=False)
    eng = ServingEngine(cfg, params, max_batch=2, cache_len=64, metrics=reg)
    for n in (5, 7, 6):
        eng.submit(jnp.arange(n) % cfg.vocab_size)
    assert len(eng.run(max_new_tokens=2)) == 3
    reg.enable()
    assert reg.snapshot()["counters"] == {}


def test_generate_on_stateful_family():
    """RWKV-family generation exercises the O(1)-state serving path."""
    cfg = _cfg("rwkv6-7b")
    mod = family_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    prompt = concrete_batch(cfg, 1, 8, "prefill")
    res = generate(cfg, params, prompt, max_new_tokens=3)
    toks = jnp.concatenate([prompt["tokens"], res.tokens], axis=1)
    logits = mod.forward(cfg, params, {"tokens": toks})
    for i in range(3):
        expect = jnp.argmax(logits[:, 8 + i - 1], axis=-1)
        np.testing.assert_array_equal(np.asarray(res.tokens[:, i]),
                                      np.asarray(expect))


def test_every_rwkv_decode_step_takes_the_in_place_kernel():
    """A traced serving call counts its decode step once under the
    recurrent route and once as an in-place kernel step: engagement 1."""
    from repro.obs import default_registry
    cfg = _cfg("rwkv6-7b")
    params = jax.eval_shape(lambda k: family_module(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    reg = default_registry()
    was = reg.enabled
    reg.enable()
    try:
        reg.clear()
        jax.clear_caches()
        lower_generate(cfg, params,
                       {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)},
                       max_new_tokens=4, cache_len=12)
        c = reg.snapshot()["counters"]
        decode = [x["value"] for x in c["rwkv_wkv_calls_total"]
                  if x["labels"] == {"route": "recurrent", "step": "decode"}]
        assert decode == [1]
        assert [x["value"] for x in c["rwkv_wkv_inplace_steps_total"]] == [1]
    finally:
        reg.clear()
        if not was:
            reg.disable()


def test_every_transformer_decode_step_writes_the_cache_in_place():
    """A traced serving call counts its decode step once as an in-place
    cache write; its prompt, which writes whole slices, counts nothing."""
    from repro.obs import default_registry
    cfg = _cfg()
    params = jax.eval_shape(lambda k: family_module(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    reg = default_registry()
    was = reg.enabled
    reg.enable()
    try:
        reg.clear()
        jax.clear_caches()
        lower_generate(cfg, params,
                       {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)},
                       max_new_tokens=4, cache_len=12)
        c = reg.snapshot()["counters"]
        assert [x["value"] for x in c["kv_cache_inplace_steps_total"]] == [1]
    finally:
        reg.clear()
        if not was:
            reg.disable()


def _serve(argv, monkeypatch):
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    serve.main(argv)


def test_serve_launcher_fits_default_prompt_to_decoder_positions(
        monkeypatch, capsys):
    """Learned decoder positions (whisper) cap the default prompt: the
    reduced encdec config serves at the launcher's defaults."""
    _serve(["--arch", "whisper-tiny", "--reduced", "--requests", "2",
            "--max-new", "2"], monkeypatch)
    assert "served 2 requests, 4 tokens" in capsys.readouterr().out


def test_serve_launcher_refuses_prompt_past_decoder_positions(
        monkeypatch, capsys):
    with pytest.raises(SystemExit):
        _serve(["--arch", "whisper-tiny", "--reduced", "--requests", "1",
                "--prompt-len", "255", "--max-new", "2"], monkeypatch)
    assert "exceeds the 256 decoder positions" in capsys.readouterr().err
