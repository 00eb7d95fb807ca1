"""Blockwise (flash) attention Pallas kernel for the model zoo.

Attention is the second GEMM hot-spot the paper's fusion argument applies
to: QK^T and PV are matrix-unit work while softmax (exp + the divide the
paper calls out as expensive on vector units, §5.4) is vector work.  The
online-softmax formulation interleaves them at block granularity — the
same matrix/vector software pipeline as Listing 1, realised in VMEM.

Features needed by the assigned architectures:
  * causal masking (all decoder LMs),
  * local sliding-window masking (gemma2 alternating layers, window 4096;
    recurrentgemma local-attention blocks, window 2048),
  * logit soft-capping (gemma2: 50.0 on attention scores),
  * GQA — H query heads share H_kv KV heads,
  * key-padding mask (``seq_len_k``) so the wrapper can pad freely,
  * ``q_start`` offset for chunked prefill.

Grid: (B·H, Sq/bq, Sk/bkv), KV innermost; online-softmax stats (m, l)
and the output accumulator live in VMEM scratch across the KV sweep.
Only the band of KV blocks a query block can see (:func:`kv_band`:
up to the causal diagonal, from the window's start) is fetched and
computed: the wrapper clamps the KV index map to the band, so a step
outside it holds the block it already has, and the step computes
nothing.  Blocks wholly inside the band skip the element mask.

Both dots take their operands in the input dtype (bf16 on the chip)
with float32 accumulation; the softmax statistics and the accumulator
stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_STATS_LANES = 128     # m/l stats replicated across one lane register


def kv_band(iq, *, bq: int, bkv: int, n_kv: int, causal: bool, window: int,
            q_start: int, xp=jnp):
    """First and last KV block that query block ``iq`` attends to.

    ``iq`` is a traced grid index (``xp=jnp``) or a numpy array of block
    indices (``xp=np``); the band is empty where ``lo > hi``."""
    lo, hi = 0, n_kv - 1
    if causal:
        hi = xp.minimum(hi, (q_start + (iq + 1) * bq - 1) // bkv)
    if window > 0:
        lo = xp.maximum(lo, (q_start + iq * bq - window + 1) // bkv)
    return lo, hi


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref, q_s, m_ref, l_ref,
                           acc_ref, *, sm_scale: float, causal: bool,
                           window: int, softcap: float, seq_len_k: int,
                           q_start: int, n_kv: int, bq: int, bkv: int):
    iq, jk = pl.program_id(1), pl.program_id(2)
    lo, hi = kv_band(iq, bq=bq, bkv=bkv, n_kv=n_kv, causal=causal,
                     window=window, q_start=q_start)

    @pl.when(jk == 0)
    def _init():
        # The scaled query block, once per KV sweep.
        q_s[...] = (q_ref[0].astype(jnp.float32) * sm_scale).astype(
            q_s.dtype)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        s = jax.lax.dot_general(q_s[...], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        if masked:
            qpos = q_start + iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 0)
            kpos = jk * bkv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 1)
            mask = kpos < seq_len_k
            if causal:
                mask &= kpos <= qpos
            if window > 0:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]                     # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                    # (bq, bkv)
        if masked:                                # rows with no key yet
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)           # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    # A block needs the element mask where it holds padded keys or
    # crosses the causal diagonal or the window's start.
    q_first, k_first = q_start + iq * bq, jk * bkv
    edge = k_first + bkv > seq_len_k
    if causal:
        edge |= k_first + bkv - 1 > q_first
    if window > 0:
        edge |= q_first + bq - 1 - k_first >= window
    run = (jk >= lo) & (jk <= hi)

    @pl.when(run & edge)
    def _edge():
        step(masked=True)

    @pl.when(run & jnp.logical_not(edge))
    def _inner():
        step(masked=False)

    @pl.when(jk == n_kv - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows -> 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
