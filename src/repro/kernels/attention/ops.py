"""jit'd wrapper for the flash-attention kernel (+ decode attention).

Pads Sk to a block multiple (padded keys are masked via ``seq_len_k``;
the last query block may run past Sq, whose rows are never written back),
reshapes (B, H, S, D) → (B·H, S, D) for the head grid axis, and maps GQA
query heads onto their KV head through the BlockSpec index map.

``decode_attention`` (one query against a long cache) is deliberately a
pure-jnp path: decode is HBM-bandwidth-bound gather work with no MXU
reuse, so a Pallas kernel buys nothing on TPU — see DESIGN.md §4.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.attention.attention import (_STATS_LANES,
                                               flash_attention_kernel,
                                               kv_band)


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _round_up(x, m):
    return x + (-x) % m


def block_sizes(d: int) -> "tuple[int, int]":
    """Default (block_q, block_kv) for heads of width ``d``.

    A grid step costs a fixed overhead besides its work, so the largest
    blocks that fit the v5e's 16 MiB of scoped VMEM win (block sweep at
    both served prefill shapes on a TPU v5e: PERF.md).  At 1024 × 1024
    the float32 score tile and its exponentials take most of it; heads
    wider than 128 double the q/k/v tiles, so they take 512 keys."""
    return 1024, (1024 if d <= 128 else 512)


def grid_plan(sq: int, sk: int, d: int, *, block_q=None, block_kv=None):
    """(bq, bkv, n_q, n_kv) of one call: the block sizes, clipped to the
    sequence, and the query and KV extents of the grid.  A query block
    longer than the queries is the whole of them; the last query block
    may run past ``sq`` (its rows are never written back), while keys
    are padded to a whole number of KV blocks."""
    dq, dkv = block_sizes(d)
    bq = min(block_q or dq, sq)
    bkv = min(block_kv or dkv, _round_up(sk, 8))
    return bq, bkv, -(-sq // bq), -(-sk // bkv)


def band_blocks(q_shape, k_shape, *, causal: bool = True,
                window: int = 0, q_start: int = 0, block_q=None,
                block_kv=None) -> "tuple[int, int]":
    """(computed, skipped) grid steps of a ``flash_attention`` call:
    steps inside the causal/window band of KV blocks, and the rest."""
    b, h, sq, d = q_shape
    bq, bkv, n_q, n_kv = grid_plan(sq, k_shape[2], d, block_q=block_q,
                                   block_kv=block_kv)
    lo, hi = kv_band(np.arange(n_q), bq=bq, bkv=bkv, n_kv=n_kv,
                     causal=causal, window=window, q_start=q_start, xp=np)
    per_head = int(np.broadcast_to(np.maximum(hi - lo + 1, 0), n_q).sum())
    return b * h * per_head, b * h * (n_q * n_kv - per_head)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "causal", "window", "softcap", "q_start", "block_q",
    "block_kv", "interpret"))
def flash_attention(q, k, v, *, sm_scale: Optional[float] = None,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_start: int = 0,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, H, Sq, D).

    Block sizes default to :func:`block_sizes` of the head width."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert h % hkv == 0, f"GQA needs H % Hkv == 0, got {h}, {hkv}"
    group = h // hkv
    if sm_scale is None:
        sm_scale = float(1.0 / (d ** 0.5))

    bq, bkv, n_q, n_kv = grid_plan(sq, sk, d, block_q=block_q,
                                   block_kv=block_kv)
    kp = _pad_axis(k.reshape(b * hkv, sk, d), 1, bkv)
    vp = _pad_axis(v.reshape(b * hkv, sk, d), 1, bkv)
    grid = (b * h, n_q, n_kv)
    band = functools.partial(kv_band, bq=bq, bkv=bkv, n_kv=n_kv,
                             causal=causal, window=window, q_start=q_start)

    def kv_index(bh, iq, jk):
        # Outside the band, the block the step before held: no new DMA.
        lo, hi = band(iq)
        return ((bh // h) * hkv + (bh % h) // group,
                jnp.minimum(jnp.maximum(jk, lo), hi), 0)

    kernel = functools.partial(
        flash_attention_kernel, sm_scale=sm_scale, causal=causal,
        window=window, softcap=softcap, seq_len_k=sk, q_start=q_start,
        n_kv=n_kv, bq=bq, bkv=bkv)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, bkv, d), kv_index),
            pl.BlockSpec((1, bkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), q.dtype),                  # scaled q
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # m
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),              # acc
        ],
        compiler_params=compiler_params,
        interpret=resolve_interpret(interpret),
    )(q.reshape(b * h, sq, d), kp, vp)
    return out.reshape(b, h, sq, d)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     sm_scale: Optional[float] = None, window: int = 0,
                     softcap: float = 0.0):
    """Single-token decode: q (B, H, 1, D) vs cache (B, Hkv, S, D).

    ``cache_len`` (scalar or (B,)) marks the valid prefix; the new token
    is assumed already written at position cache_len - 1.
    """
    b, h, _, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    if sm_scale is None:
        sm_scale = float(1.0 / (d ** 0.5))
    group = h // hkv
    qe = q.reshape(b, hkv, group, d).astype(jnp.float32)
    scores = jnp.einsum("bngd,bnsd->bngs", qe,
                        k_cache.astype(jnp.float32)) * sm_scale
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    pos = jnp.arange(s)
    cache_len = jnp.asarray(cache_len)
    valid = pos[None, :] < cache_len.reshape(-1, 1)          # (B, S)
    if window > 0:
        valid &= pos[None, :] >= (cache_len.reshape(-1, 1) - window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngs,bnsd->bngd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, 1, d).astype(q.dtype)
