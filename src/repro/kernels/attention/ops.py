"""jit'd wrapper for the flash-attention kernel (+ decode attention).

Pads Sq/Sk to block multiples (padded keys are masked via ``seq_len_k``),
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.attention.attention import (_STATS_LANES,
                                               flash_attention_kernel)


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "causal", "window", "softcap", "q_start", "block_q",
    "block_kv", "interpret"))
def flash_attention(q, k, v, *, sm_scale: Optional[float] = None,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_start: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert h % hkv == 0, f"GQA needs H % Hkv == 0, got {h}, {hkv}"
    group = h // hkv
    if sm_scale is None:
        sm_scale = float(1.0 / (d ** 0.5))

    bq = min(block_q, _round_up(sq, 8))
    bkv = min(block_kv, _round_up(sk, 8))
    qp = _pad_axis(q.reshape(b * h, sq, d), 1, bq)
    kp = _pad_axis(k.reshape(b * hkv, sk, d), 1, bkv)
    vp = _pad_axis(v.reshape(b * hkv, sk, d), 1, bkv)
    sq_p, sk_p = qp.shape[1], kp.shape[1]
    grid = (b * h, sq_p // bq, sk_p // bkv)

    def kv_index(bh, iq, jk):
        return (bh // h) * hkv + (bh % h) // group, jk, 0

    kernel = functools.partial(
        flash_attention_kernel, sm_scale=sm_scale, causal=causal,
        window=window, softcap=softcap, seq_len_k=sk, q_start=q_start,
        n_kv=grid[2], bq=bq, bkv=bkv)
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
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # m
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),              # acc
        ],
        compiler_params=compiler_params,
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp)
    return out[:, :sq].reshape(b, h, sq, d)


def _round_up(x, m):
    return x + (-x) % m


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
