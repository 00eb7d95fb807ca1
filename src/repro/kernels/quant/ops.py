"""jit'd wrapper for the row-wise quantization kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.quant.quant import quantize_rowwise_kernel


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def quantize_rowwise(x, *, block_m: int = 256,
                     interpret: Optional[bool] = None):
    """x: (M, K) -> (q int8 (M, K), scale f32 (M,))."""
    m, k = x.shape
    bm = min(block_m, m)
    pad = (-m) % bm
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    mp = xp.shape[0]

    q, scale = pl.pallas_call(
        quantize_rowwise_kernel,
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, k), jnp.int8),
                   jax.ShapeDtypeStruct((mp, 1), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xp)
    return q[:m], scale[:m, 0]
