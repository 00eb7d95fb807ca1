"""Flash-attention kernel + chunked-XLA path vs the dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.ops import decode_attention, flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.models.common import attention_xla_chunked


def _check(out, ref, rtol=2e-2):
    o, r = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    err = np.abs(o - r).max() / (np.abs(r).max() + 1e-9)
    assert err < rtol, err


CASES = [
    dict(B=2, H=4, HKV=4, SQ=128, SK=128, D=64),
    dict(B=2, H=8, HKV=2, SQ=128, SK=128, D=64),              # GQA
    dict(B=1, H=4, HKV=4, SQ=100, SK=100, D=64),              # unaligned
    dict(B=1, H=4, HKV=4, SQ=256, SK=256, D=64, window=64),   # local
    dict(B=1, H=4, HKV=2, SQ=128, SK=128, D=64, softcap=50.0),
    dict(B=1, H=4, HKV=4, SQ=96, SK=96, D=64, causal=False),  # encoder
    dict(B=1, H=4, HKV=4, SQ=64, SK=192, D=64, causal=False), # cross
    dict(B=1, H=4, HKV=4, SQ=64, SK=192, D=64, q_start=128),  # chunked
    dict(B=1, H=8, HKV=4, SQ=160, SK=160, D=32, window=32, softcap=50.0),
]


def _mk(case, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (case["B"], case["H"], case["SQ"],
                                  case["D"]), dtype)
    k = jax.random.normal(ks[1], (case["B"], case["HKV"], case["SK"],
                                  case["D"]), dtype)
    v = jax.random.normal(ks[2], (case["B"], case["HKV"], case["SK"],
                                  case["D"]), dtype)
    kw = {k_: case[k_] for k_ in ("causal", "window", "softcap", "q_start")
          if k_ in case}
    return q, k, v, kw


@pytest.mark.parametrize("case", CASES, ids=lambda c: str(sorted(c.items())))
def test_pallas_kernel_vs_oracle(case):
    q, k, v, kw = _mk(case)
    out = flash_attention(q, k, v, block_q=64, block_kv=64, **kw)
    ref = attention_ref(q, k, v, **kw)
    _check(out, ref)


@pytest.mark.parametrize("case", CASES, ids=lambda c: str(sorted(c.items())))
def test_xla_chunked_vs_oracle(case):
    """The distributed/dry-run attention path computes the same function."""
    q, k, v, kw = _mk(case)
    out = attention_xla_chunked(q, k, v, sm_scale=q.shape[-1] ** -0.5,
                                chunk=64, **kw)
    ref = attention_ref(q, k, v, **kw)
    _check(out, ref, rtol=1e-3)


def test_bf16(case=CASES[1]):
    q, k, v, kw = _mk(case, jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_kv=64, **kw)
    ref = attention_ref(q, k, v, **kw)
    _check(out, ref, rtol=4e-2)


#: The banded kernel in bf16 against the float32 oracle: grids whose
#: causal/window band skips KV blocks, with blocks of unequal size.
BANDED = [
    # causal GQA, keys not a whole number of blocks (4080 at small size)
    dict(B=2, H=8, HKV=2, SQ=255, SK=255, D=64, BQ=64, BKV=64),
    dict(B=1, H=4, HKV=2, SQ=64, SK=192, D=64, q_start=128, BQ=32,
         BKV=64),
    dict(B=1, H=4, HKV=4, SQ=256, SK=256, D=64, window=64, BQ=64,
         BKV=32),
    dict(B=1, H=4, HKV=2, SQ=128, SK=128, D=64, softcap=50.0, BQ=32,
         BKV=64),
    dict(B=1, H=4, HKV=4, SQ=96, SK=200, D=64, causal=False, BQ=32,
         BKV=64),
    dict(B=1, H=4, HKV=1, SQ=200, SK=200, D=32, BQ=128, BKV=32),
    dict(B=1, H=4, HKV=1, SQ=200, SK=200, D=32, BQ=16, BKV=128),
    dict(B=1, H=8, HKV=4, SQ=160, SK=160, D=32, window=40, softcap=50.0,
         BQ=32, BKV=16),
]


@pytest.mark.parametrize("case", BANDED, ids=lambda c: str(sorted(c.items())))
def test_banded_bf16_kernel_vs_oracle(case):
    q, k, v, kw = _mk(case, jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=case["BQ"], block_kv=case["BKV"],
                          **kw)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), **kw)
    _check(out, ref, rtol=4e-2)


def _pallas_call(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            inner = getattr(inner, "jaxpr", inner)
            found = inner is not None and _pallas_call(inner)
            if found:
                return found
    return None


@pytest.mark.parametrize("case", BANDED, ids=lambda c: str(sorted(c.items())))
def test_kv_index_map_stays_in_the_band(case):
    """Every grid step's K and V blocks lie in its query block's band,
    the band holds every block with an unmasked (query, key) pair, and
    ``band_blocks`` counts the band's steps as computed."""
    from repro.kernels.attention.ops import band_blocks
    q, k, v, kw = _mk(case, jnp.bfloat16)
    blocks = dict(block_q=case["BQ"], block_kv=case["BKV"])
    eqn = _pallas_call(jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, **blocks, **kw))(
            q, k, v).jaxpr)
    gm = eqn.params["grid_mapping"]
    n_bh, n_q, n_kv = gm.grid
    bq, bkv = case["BQ"], case["BKV"]
    sq, sk = case["SQ"], case["SK"]
    qpos = kw.get("q_start", 0) + np.arange(n_q * bq)[:, None]
    kpos = np.arange(n_kv * bkv)[None, :]
    mask = (kpos < sk) & (qpos < kw.get("q_start", 0) + sq)
    if kw.get("causal", True):
        mask &= kpos <= qpos
    if kw.get("window", 0):
        mask &= (qpos - kpos) < kw["window"]
    needed = mask.reshape(n_q, bq, n_kv, bkv).any(axis=(1, 3))

    computed = 0
    for iq in range(n_q):
        visited = set()
        for bm in gm.block_mappings[1:3]:                  # k, v
            m = bm.index_map_jaxpr
            for jk in range(n_kv):
                _, blk, _ = jax.core.eval_jaxpr(m.jaxpr, m.consts, 0, iq, jk)
                visited.add(int(blk))
        band = set(range(min(visited), max(visited) + 1))
        assert visited == band
        assert set(np.flatnonzero(needed[iq])) <= band, iq
        computed += len(band)
    band_kw = {k_: kw[k_] for k_ in ("causal", "window", "q_start")
               if k_ in kw}
    assert band_blocks(q.shape, k.shape, **blocks, **band_kw) == (
        n_bh * computed, n_bh * (n_q * n_kv - computed))


def test_decode_matches_prefix_oracle():
    B, H, HKV, S, D, L = 2, 8, 2, 64, 32, 40
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, 1, D))
    kc = jax.random.normal(ks[1], (B, HKV, S, D))
    vc = jax.random.normal(ks[2], (B, HKV, S, D))
    out = decode_attention(q, kc, vc, jnp.array([L, L]))
    ref = attention_ref(q, kc[:, :, :L], vc[:, :, :L], q_start=L - 1)
    _check(out, ref, rtol=1e-4)


def test_decode_window():
    B, H, HKV, S, D, L, W = 1, 4, 1, 64, 32, 50, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, 1, D))
    kc = jax.random.normal(ks[1], (B, HKV, S, D))
    vc = jax.random.normal(ks[2], (B, HKV, S, D))
    out = decode_attention(q, kc, vc, jnp.array([L]), window=W)
    ref = attention_ref(q, kc[:, :, L - W:L], vc[:, :, L - W:L],
                        q_start=W - 1)
    _check(out, ref, rtol=1e-4)


def test_grad_flows_through_chunked_attention():
    """The remat'd chunk body must be differentiable (training path)."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 64, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 64, 32))

    def f(q, k, v):
        return attention_xla_chunked(q, k, v, sm_scale=0.17, chunk=32).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for gi in g:
        assert bool(jnp.all(jnp.isfinite(gi)))

    # grad matches dense-attention grad
    def f_ref(q, k, v):
        return attention_ref(q, k, v, sm_scale=0.17).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gi, gr in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(gi), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)
