"""Per-row absmax int8 quantization kernel (SmoothQuant-O1 pipeline).

The paper evaluates Llama3.2-1B quantized with SmoothQuant-O1 (§5.1);
activation quantization is per-token (per-row) dynamic absmax, weights
are per-channel static.  Quantize is pure vector work — in the fused
pipeline it is a *prologue* overlapped with the previous tile's matmul
(Fig. 5); dequant rides the matmul epilogue (``scale_a``/``scale_b`` in
``cute_matmul``).

Grid: (M/bm,) — each program reduces its rows' absmax and emits int8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def quantize_rowwise_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                      # (bm, K)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)   # (bm, 1)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale
