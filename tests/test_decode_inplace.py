"""Transformer decode writes its K/V row into the stacked cache in place.

The oracle is the form decode had before: each layer's caches sliced out
of the stack as the layer scan's ``xs`` and given back whole as its
``ys``.  Both forms run the same layer, so ``generate``'s tokens and
logits agree bit for bit.  Configs are the registry's reduced ones at
their own dtypes (bf16 weights and cache, remat on): a dense GQA
decoder, gemma2's (local, global) pairs (two cache groups, a window
shorter than the sequence) and a mixture of experts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import concrete_batch, get_config
from repro.models import common as cm
from repro.models import transformer
from repro.serving.engine import generate

ARCHS = ["yi-6b", "gemma2-2b", "olmoe-1b-7b"]
PROMPT, NEW = 12, 8                  # gemma2's reduced window is 16


def _xs_ys_decode_step(cfg, params, tokens, cache, pos):
    """decode_step with each layer's caches scanned as xs/ys."""
    x = cm.embed_tokens(cfg, params["embedding"], tokens)
    positions = jnp.full((tokens.shape[0], 1), pos, jnp.int32)

    def body(x, layer):
        lps, kvs = layer
        new = []
        for lp, (k, v), window in zip(lps, kvs, transformer._windows(cfg)):
            x, (k, v) = transformer.block_apply(
                cfg, lp, x, positions=positions, window=window,
                kv_cache=((k[None], v[None]), 0), cache_pos=pos)
            new.append((k[0], v[0]))
        return x, tuple(new)

    x, cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = transformer._norm(cfg, x, params["ln_final"])
    return cm.logits_out(cfg, params, x[:, -1]), cache


@pytest.fixture(scope="module", params=ARCHS)
def model(request):
    cfg = get_config(request.param, reduced=True)
    return cfg, transformer.init(cfg, jax.random.PRNGKey(0))


def test_generate_matches_the_xs_ys_form_bit_for_bit(model, monkeypatch):
    cfg, params = model
    prompt = concrete_batch(cfg, 2, PROMPT, "prefill")

    def run():
        jax.clear_caches()
        return generate(cfg, params, prompt, max_new_tokens=NEW,
                        keep_logits=True)

    got = run()
    with monkeypatch.context() as m:
        m.setattr(transformer, "decode_step", _xs_ys_decode_step)
        want = run()
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    for a, b in ((got.logits_last, want.logits_last),
                 (got.logits, want.logits)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_step_changes_every_layer_only_at_its_row(model):
    """From a random cache, one step rewrites row ``pos`` of every layer
    of every group, as the oracle does, and leaves every other row."""
    cfg, params = model
    b, pos = 2, 5
    shapes = jax.eval_shape(lambda: transformer.init_cache(cfg, b, 10))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    cache = jax.tree.map(
        lambda s: jax.random.normal(next(keys), s.shape, s.dtype), shapes)
    tokens = concrete_batch(cfg, b, 1, "prefill")["tokens"]
    step = jax.jit(transformer.decode_step, static_argnums=0)
    logits, new = step(cfg, params, tokens, cache, jnp.int32(pos))
    want_logits, want = jax.jit(_xs_ys_decode_step, static_argnums=0)(
        cfg, params, tokens, cache, jnp.int32(pos))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    others = np.arange(10) != pos
    for old, got, exp in zip(jax.tree.leaves(cache), jax.tree.leaves(new),
                             jax.tree.leaves(want)):
        old, got, exp = (np.asarray(a.astype(jnp.float32))
                         for a in (old, got, exp))
        np.testing.assert_array_equal(got[:, :, :, others],
                                      old[:, :, :, others])
        np.testing.assert_array_equal(got[:, :, :, pos], exp[:, :, :, pos])
        assert (got[:, :, :, pos] != old[:, :, :, pos]).any(axis=(1, 2, 3)) \
            .all(), "a layer's row was not written"
