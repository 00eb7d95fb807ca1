"""RWKV-6 / RG-LRU kernels: RWKV-6's chunked jnp form and RG-LRU's
Pallas scan against naive-scan oracles, and RWKV-6's in-place decode
step against the per-token oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.rwkv6.ops as wkv_ops
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_decode_step, rglru_ref
from repro.kernels.rwkv6.ops import rwkv6_decode_step
from repro.kernels.rwkv6.ref import rwkv6_ref
from repro.models.rwkv6 import _recurrent, rwkv6_chunked_jnp


def _rwkv_inputs(B=2, H=3, T=96, C=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r = jax.random.normal(ks[0], (B, H, T, C))
    k = jax.random.normal(ks[1], (B, H, T, C))
    v = jax.random.normal(ks[2], (B, H, T, C))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, C)) * 0.5)
    u = jax.random.normal(ks[4], (H, C)) * 0.5
    return r, k, v, lw, u


class TestRwkv6:
    @pytest.mark.parametrize("t", [32, 70, 80, 96])
    def test_chunked_jnp_vs_oracle(self, t):
        r, k, v, lw, u = _rwkv_inputs(T=t)
        out, state = rwkv6_chunked_jnp(r, k, v, lw, u, chunk=32)
        ref, state_ref = rwkv6_ref(r, k, v, lw, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_initial_state_continuation(self):
        """chunked(T) == chunked(T/2) ∘ chunked(T/2) with carried state."""
        r, k, v, lw, u = _rwkv_inputs(T=64)
        full, state_full = rwkv6_chunked_jnp(r, k, v, lw, u, chunk=32)
        h1, s1 = rwkv6_chunked_jnp(r[:, :, :32], k[:, :, :32], v[:, :, :32],
                                   lw[:, :, :32], u, chunk=32)
        h2, s2 = rwkv6_chunked_jnp(r[:, :, 32:], k[:, :, 32:], v[:, :, 32:],
                                   lw[:, :, 32:], u, chunk=32,
                                   initial_state=s1)
        np.testing.assert_allclose(np.asarray(h2),
                                   np.asarray(full[:, :, 32:]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(state_full),
                                   rtol=1e-4, atol=1e-4)

    def test_strong_decay_forgets_beyond_one_token(self):
        """Property: with decay ≈ 0, S_{t-1} ≈ k_{t-1}ᵀ v_{t-1}, so each
        output sees exactly the previous token + its own bonus term."""
        r, k, v, lw, u = _rwkv_inputs(T=32)
        lw_hard = jnp.full_like(lw, -30.0)          # w = e^-30 ≈ 0
        out, _ = rwkv6_ref(r, k, v, lw_hard, u)
        bonus = jnp.sum(r * u[None, :, None, :] * k, axis=-1,
                        keepdims=True) * v
        prev = (jnp.sum(r[:, :, 1:] * k[:, :, :-1], axis=-1, keepdims=True)
                * v[:, :, :-1])
        expect = bonus.at[:, :, 1:].add(prev)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-3, atol=1e-3)


def _decode_inputs(b, d, c, steps, case, seed=0):
    """A stack of 3 layers' states and ``steps`` tokens' r/k/v/lw, with
    u; ``case`` "strong_decay" sets lw = -e^6, "no_bonus" u = 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(x, (steps, b, d)) for x in ks[:3])
    lw = -jnp.exp(jax.random.normal(ks[3], (steps, b, d)))
    if case == "strong_decay":
        lw = jnp.full_like(lw, -np.exp(6.0))
    u = jax.random.normal(ks[4], (d // c, c)) * 0.5
    if case == "no_bonus":
        u = jnp.zeros_like(u)
    stack = jax.random.normal(ks[5], (3, b, c, d))
    return stack, (r, k, v, lw), u


def _decode_chain(step, stack, layer, tokens, u):
    outs = []
    for r, k, v, lw in zip(*tokens):
        o, stack = step(stack, layer, r, k, v, lw, u)
        outs.append(o)
    return jnp.stack(outs), stack


def _oracle_chain(stack, layer, tokens, u):
    o, s = _recurrent(*(jnp.moveaxis(z, 0, 1) for z in tokens), u,
                      stack[layer])
    return jnp.moveaxis(o, 1, 0), stack.at[layer].set(s)


def _within_f32_rounding(got, want):
    """Outputs and states agree to float32 rounding: a few units in the
    last place of the largest value (sums over C key channels)."""
    return all(np.allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                           atol=1e-5 * float(np.abs(w).max()))
               for g, w in zip(got, want))


def _bonus_dropped(stack, i, r, k, v, lw, u):
    return rwkv6_decode_step(stack, i, r, k, v, lw, jnp.zeros_like(u))


def _state_in_bfloat16(stack, i, *tokens_and_u):
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    o, stack = rwkv6_decode_step(bf16(stack), i, *tokens_and_u)
    return o, bf16(stack)


#: planted faults in the decode step
DECODE_FAULTS = {"bonus_dropped": _bonus_dropped,
                 "state_in_bfloat16": _state_in_bfloat16}


@pytest.fixture(scope="class")
def small_decode_blocks():
    """Decode blocks of 4 heads and 512 KiB: at d 512 and heads of 64,
    two lane blocks and batch blocks of 8 rows, or of 6 at a batch of
    12; at d 128 and heads of 32, the whole state in one block."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(wkv_ops, "DECODE_BLOCK_HEADS", 4)
        mp.setattr(wkv_ops, "DECODE_BLOCK_BYTES", 1 << 19)
        wkv_ops.rwkv6_decode_step.clear_cache()
        yield
    wkv_ops.rwkv6_decode_step.clear_cache()


def test_decode_blocks_are_chosen_from_the_shape():
    """8 heads by 1 MiB of state where they divide the shape; else all of
    d, and the most rows, a divisor of B, that keep to 1 MiB."""
    blocks = wkv_ops._decode_blocks
    assert blocks(128, 64, 4096) == (8, 512)
    assert blocks(100, 64, 4096) == (5, 512)
    assert blocks(7, 64, 4096) == (7, 512)
    assert blocks(97, 64, 4096) == (1, 512)
    assert blocks(8, 32, 128) == (8, 128)


@pytest.mark.usefixtures("small_decode_blocks")
class TestRwkv6DecodeStep:
    @pytest.mark.parametrize("case", ["random", "strong_decay", "no_bonus"])
    @pytest.mark.parametrize("steps", [1, 8])
    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("d,c", [(128, 32), (512, 64)])
    @pytest.mark.parametrize("b", [2, 8, 12, 16])
    def test_matches_the_recurrence(self, b, d, c, layer, steps, case):
        """The kernel against ``_recurrent`` in float32; the other layers
        of the stack are left bit for bit as they were."""
        stack, tokens, u = _decode_inputs(b, d, c, steps, case)
        o, out = _decode_chain(rwkv6_decode_step, stack, layer, tokens, u)
        want_o, want = _oracle_chain(stack, layer, tokens, u)
        assert _within_f32_rounding((o, out[layer]), (want_o, want[layer]))
        others = np.arange(3) != layer
        np.testing.assert_array_equal(np.asarray(out)[others],
                                      np.asarray(stack)[others])

    @pytest.mark.parametrize("fault", sorted(DECODE_FAULTS))
    @pytest.mark.parametrize("d,c", [(128, 32), (512, 64)])
    def test_planted_fault_fails_the_comparison(self, d, c, fault):
        stack, tokens, u = _decode_inputs(8, d, c, 8, "random", seed=1)
        got = _decode_chain(DECODE_FAULTS[fault], stack, 1, tokens, u)
        want = _oracle_chain(stack, 1, tokens, u)
        assert not _within_f32_rounding((got[0], got[1][1]),
                                        (want[0], want[1][1]))


class TestRgLru:
    @pytest.mark.parametrize("t,c", [(64, 128), (100, 192), (32, 64)])
    def test_pallas_vs_oracle(self, t, c):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        log_a = -jax.nn.softplus(jax.random.normal(ks[0], (2, t, c)))
        x = jax.random.normal(ks[1], (2, t, c))
        out = rglru_scan(log_a, x, chunk=32, block_c=64)
        ref, _ = rglru_ref(log_a, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_decode_step_matches_scan(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        log_a = -jax.nn.softplus(jax.random.normal(ks[0], (2, 8, 16)))
        x = jax.random.normal(ks[1], (2, 8, 16))
        seq, final = rglru_ref(log_a, x)
        h = jnp.zeros((2, 16))
        for t in range(8):
            out, h = rglru_decode_step(h, log_a[:, t], x[:, t])
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(seq[:, t]),
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h), np.asarray(final),
                                   rtol=1e-5, atol=1e-5)

    def test_a_one_is_pure_integrator_limit(self):
        """log_a = 0 => a=1, beta=0: state never changes from 0."""
        x = jnp.ones((1, 16, 8))
        out = rglru_scan(jnp.zeros((1, 16, 8)), x, chunk=8, block_c=8)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)
