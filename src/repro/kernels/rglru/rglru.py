"""RG-LRU (Griffin / RecurrentGemma) gated linear recurrence kernel.

    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ x_t,   a_t = exp(log_a_t) ≤ 1

``log_a`` and the gated input are computed by the surrounding block
(matmuls through ``cute_matmul``); the kernel is the pure recurrence —
vector-unit work in the paper's taxonomy, overlapped with the
projection GEMMs at the layer level (DESIGN.md §4).

Channels are independent, so the grid parallelises (batch × channel
blocks) and walks chunks of time sequentially with the carry in VMEM.
Inside a chunk a ``fori_loop`` runs the exact recurrence (L small); a
production variant would use the associative-scan form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def one_minus_exp(y):
    """``1 - exp(y)`` for ``y <= 0`` without cancellation near 0 (what
    ``-expm1(y)`` computes; the TPU kernel compiler has no ``expm1``).
    Below |y| = 0.1 a 5-term Taylor series is exact to float32."""
    series = -y * (1.0 + y * (0.5 + y * (1.0 / 6.0 + y * (
        1.0 / 24.0 + y * (1.0 / 120.0)))))
    return jnp.where(y > -0.1, series, 1.0 - jnp.exp(y))


def rglru_kernel(log_a_ref, x_ref, o_ref, h_ref, a_ref, gx_ref, out_ref, *,
                 chunk: int):
    """Scratch: ``h_ref`` (1, bc) carry; ``a_ref``/``gx_ref``/``out_ref``
    (L, bc) float32 staging, so the time loop indexes 32-bit refs one row
    at a time (a dynamic row of a value, or of a packed bf16 block, is
    not something the TPU kernel compiler lowers)."""
    t0 = pl.program_id(2)

    @pl.when(t0 == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    log_a = log_a_ref[0].astype(jnp.float32)      # (L, bc)
    a_ref[...] = jnp.exp(log_a)
    gx_ref[...] = jnp.sqrt(one_minus_exp(2.0 * log_a)) * x_ref[0].astype(
        jnp.float32)

    def body(t, h):
        row = pl.ds(t, 1)
        h = a_ref[row, :] * h + gx_ref[row, :]    # (1, bc)
        out_ref[row, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, body, h_ref[...])
    o_ref[0] = out_ref[...].astype(o_ref.dtype)
