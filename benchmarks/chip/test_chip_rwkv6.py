"""RWKV-6 on the serving path against the float32 reference, at a small
size on the CPU: agreement, sliced prefill, planted faults, the weights'
layout, and the counts behind the RWKV metrics."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import rwkv_counts  # noqa: E402
import traffic  # noqa: E402

#: a configuration file at the widths of ``get_config("rwkv6-7b",
#: reduced=True)``: d 128, heads of 32, channel mix 256, ranks 8 and 8.
TINY = {"name": "rwkv6-tiny", "family": "rwkv6", "registry": "rwkv6-7b",
        "hidden_size": 128, "head_size": 32, "head_size_divisor": 8,
        "intermediate_size": 256, "num_hidden_layers": 3,
        "vocab_size": 512, "layer_norm_epsilon": 1e-5,
        "time_mix_extra_dim": 8, "time_decay_extra_dim": 8,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "hidden_act": "relu2", "rms_norm_eps": 1e-5, "rope_theta": 1e4,
        "attention_bias": False, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16"}
SLICE = 16                  # prefill slice: prompts of 40 take 8 + 2 x 16


def tiny_cfg(**kw):
    from repro.configs.registry import get_config
    return harness.program_config(
        TINY, base=get_config("rwkv6-7b", reduced=True)).with_(**kw)


@pytest.fixture
def fresh_programs(monkeypatch):
    """Prefill in slices of ``SLICE``; programs traced under a patched
    model are not reused elsewhere."""
    import jax
    from repro.models import rwkv6
    monkeypatch.setattr(rwkv6, "PREFILL_SLICE", SLICE)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_serving_agrees_with_the_reference(fresh_programs):
    """Prefill (a remainder and two slices) then decode, through
    ``ServingEngine``, against the reference's full forward."""
    import jax.numpy as jnp
    from repro.serving.engine import ServingEngine, generate
    ref = harness.reference_module(TINY)
    seed, prompt, new = 2 ** 31 + 5, 40, 16
    key = harness.seed_key(seed)
    prompts = traffic.prompts(seed, 0, 4, prompt, TINY["vocab_size"])
    params = ref.program_params(TINY, key)
    cfg = tiny_cfg()
    engine = ServingEngine(cfg, params, max_batch=4, cache_len=prompt + new)
    for p in prompts:
        engine.submit(p)
    served = np.stack([np.asarray(t) for t in engine.run(new)])
    res = generate(cfg, params, {"tokens": jnp.asarray(prompts)},
                   max_new_tokens=new, cache_len=prompt + new,
                   keep_logits=True)
    np.testing.assert_array_equal(served, np.asarray(res.tokens))
    seq = np.concatenate([prompts, served[:, :-1]], axis=1)
    want = np.asarray(ref.logits(TINY, key, seq, prompt - 1))
    # The logits have a spread of about 1; bfloat16 weights and
    # activations move them by a few hundredths (0.045 to 0.057 read
    # over three seeds), the float32 state by nothing that shows.
    assert want.std() == pytest.approx(1.0, abs=0.2)
    assert np.abs(want - np.asarray(res.logits)).max() < 0.08
    gaps = ref.served_gaps(TINY, key, prompts, served)
    assert gaps.shape == served.shape and gaps.min() >= 0
    assert gaps.max() < 0.05


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sliced_prefill_equals_one_shot(dtype, monkeypatch, fresh_programs):
    """Slices of 16 (a remainder of 8 first) and the whole prompt at
    once give the same logits and the same three states."""
    import jax
    import jax.numpy as jnp
    from repro.models import rwkv6
    ref = harness.reference_module(TINY)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          ref.program_params(TINY, harness.seed_key(3)))
    tokens = jnp.asarray(traffic.prompts(3, 0, 2, 40, TINY["vocab_size"]))
    out = {}
    cfg = tiny_cfg(dtype=jnp.dtype(dtype))
    for size in (SLICE, 64):
        monkeypatch.setattr(rwkv6, "PREFILL_SLICE", size)
        out[size] = jax.jit(lambda p, t, c: rwkv6.prefill(
            cfg, p, {"tokens": t}, c))(
            params, tokens, rwkv6.init_cache(cfg, 2, 64))
    (l1, c1), (l2, c2) = out[SLICE], out[64]
    # Only the order of float32 sums differs (the chunked scan's chunks
    # start where the slices do); bfloat16 activations round that
    # difference to at most a unit in the last place.
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(l1, l2, atol=tol, rtol=tol)
    for name in ("tm_shift", "cm_shift", "wkv"):
        assert c1[name].shape == c2[name].shape
        np.testing.assert_allclose(np.asarray(c1[name], np.float32),
                                   np.asarray(c2[name], np.float32),
                                   atol=tol, rtol=tol)


def _round_state(s):
    import jax.numpy as jnp
    return s.astype(jnp.bfloat16).astype(jnp.float32)


def _faulty(fault, chunked, recurrent):
    """The program's two serving WKV routes with ``fault`` planted."""
    import jax.numpy as jnp
    if fault == "bonus_dropped":
        return (lambda r, k, v, lw, u, s, **kw:
                chunked(r, k, v, lw, jnp.zeros_like(u), s, **kw),
                lambda r, k, v, lw, u, s:
                recurrent(r, k, v, lw, jnp.zeros_like(u), s))
    if fault == "state_not_carried_across_slices":
        return (lambda r, k, v, lw, u, s, **kw:
                chunked(r, k, v, lw, u, jnp.zeros_like(s), **kw), recurrent)
    if fault == "state_in_bfloat16":
        def held(route):
            def run(r, k, v, lw, u, s, **kw):
                o, s = route(r, k, v, lw, u, _round_state(s), **kw)
                return o, _round_state(s)
            return run
        return held(chunked), held(recurrent)
    return chunked, recurrent


@pytest.mark.parametrize("fault", [
    None, "bonus_dropped", "state_not_carried_across_slices",
    "state_in_bfloat16"])
def test_planted_fault_fails_the_gap_check(fault, monkeypatch,
                                           fresh_programs):
    """The program in float32, so that its only difference from the
    reference is the order of float32 sums (logits within 1e-5, every
    served token the reference's best): the greedy gap check, at a
    limit of 1e-3, passes it and fails each fault."""
    import jax
    import jax.numpy as jnp
    from repro.models import rwkv6
    from repro.serving.engine import generate
    chunked, recurrent = _faulty(fault, rwkv6._chunked, rwkv6._recurrent)
    monkeypatch.setattr(rwkv6, "_chunked", chunked)
    monkeypatch.setattr(rwkv6, "_recurrent", recurrent)
    ref = harness.reference_module(TINY)
    seed, prompt, new = 1, 48, 48
    key = harness.seed_key(seed)
    prompts = traffic.prompts(seed, 0, 8, prompt, TINY["vocab_size"])
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.program_params(TINY, key))
    served = generate(tiny_cfg(dtype=jnp.float32), params,
                      {"tokens": jnp.asarray(prompts)},
                      max_new_tokens=new, cache_len=prompt + new).tokens
    gap = ref.served_gaps(TINY, key, prompts, np.asarray(served)).max()
    assert (gap <= 1e-3) == (fault is None), gap


def test_program_params_lay_out_the_programs_tree():
    """``program_params`` gives ``rwkv6.init``'s tree: the same leaves,
    shapes and dtypes, at the small size and at the cell's widths."""
    import jax
    from repro.configs.registry import get_config
    from repro.models import rwkv6
    from test_chip_harness import bench
    cell = harness.resolve(bench(), "rwkv6-16l.decode")
    for conf, cfg in ((TINY, tiny_cfg()),
                      (cell.conf, harness.program_config(cell.conf))):
        ref = harness.reference_module(conf)
        want = jax.eval_shape(lambda k, cfg=cfg: rwkv6.init(cfg, k),
                              jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda k, conf=conf: ref.program_params(conf, k),
                             harness.seed_key(0))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert (g.shape, g.dtype) == (w.shape, w.dtype)
        assert cfg.param_count() == sum(
            x.size for x in jax.tree.leaves(want))
    assert get_config("rwkv6-7b").n_layers == cell.conf["published"][
        "num_hidden_layers"]


def test_program_params_are_the_layerwise_draws():
    import jax
    ref = harness.reference_module(TINY)
    key = harness.seed_key(11)
    params = ref.program_params(TINY, key)
    t = ref.dims(TINY)
    for i in range(t.layers):
        w = ref.layer_weights(t, key, i)
        for name, leaf in w.items():
            np.testing.assert_allclose(
                np.asarray(params["layers"][name][i], np.float32),
                np.asarray(leaf, np.float32), rtol=1e-6, atol=1e-6)
    top = ref.top_weights(t, key)
    for name, leaf in top.items():
        np.testing.assert_array_equal(params[name], leaf)
    # The published decay base: -6 at the first channel, -1 at the last.
    w0 = np.asarray(params["layers"]["w0"])
    assert w0[:, 0] == pytest.approx(-6.0) and w0[:, -1] == pytest.approx(
        -1.0)
    assert jax.numpy.dtype(params["layers"]["u"].dtype) == np.float32


def test_counter_names_the_routes_and_slices(fresh_programs):
    import jax
    import jax.numpy as jnp
    from repro.obs import default_registry
    from repro.serving.engine import lower_generate
    ref = harness.reference_module(TINY)
    params = jax.eval_shape(lambda k: ref.program_params(TINY, k),
                            harness.seed_key(0))
    reg = default_registry()
    was = reg.enabled
    reg.enable()
    try:
        reg.clear()
        lower_generate(tiny_cfg(), params,
                       {"tokens": jax.ShapeDtypeStruct((2, 40), jnp.int32)},
                       max_new_tokens=4, cache_len=44)
        c = reg.snapshot()["counters"]["rwkv_wkv_calls_total"]
        assert {(x["labels"]["route"], x["labels"]["step"]): x["value"]
                for x in c} == {("chunked", "prefill"): 3,
                                ("recurrent", "decode"): 1}
    finally:
        reg.clear()
        if not was:
            reg.disable()


def test_counts_match_a_hand_count():
    """One sequence of 3 prompt tokens and 2 served ones through 2 layers
    at d 8, heads of 4, channel mix 16, ranks 2 and 3, vocab 32."""
    conf = {"hidden_size": 8, "head_size": 4, "intermediate_size": 16,
            "vocab_size": 32, "num_hidden_layers": 2,
            "time_mix_extra_dim": 2, "time_decay_extra_dim": 3}
    # A token through a layer: r, k, v, g, o 5 * 2 * 64; the token mixes
    # 2 * 8 * 10 + 5 * 2 * 2 * 8; the decay 2 * 8 * 3 * 2; the channel
    # mix 2 * 8 * 16 * 2 + 2 * 64; the recurrence 2 heads * (5 * 16 + 16).
    per_token_layer = 640 + 160 + 160 + 96 + 512 + 128 + 192
    assert per_token_layer == 1888
    head = 2 * 8 * 32
    # Prefill: 3 tokens, 2 layers, one head; one decode step likewise.
    want = 3 * 2 * per_token_layer + head + 1 * 2 * per_token_layer + head
    assert rwkv_counts.request_flops(conf, 3, 2) == want
    dots = {x.name: x for x in rwkv_counts.generate_dots(conf, 1, 3, 2)}
    # The decode step's recurrence: the float32 state of 2 heads of 4 x 4
    # read and written (256 bytes), r, k, v, o in bfloat16 and the log
    # decay in float32 for one token of 8 channels (96 bytes), per layer.
    assert dots["decode.wkv"].bytes == 256 + 96
    assert dots["decode.wkv"].count == 2
    # The output projection of one token: its 8 x 8 weight and 8 + 8
    # activations in bfloat16.
    assert dots["decode.o"].bytes == 2 * 64 + 2 * 16
