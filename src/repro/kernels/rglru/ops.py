"""jit'd wrapper for the RG-LRU kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.rglru.rglru import rglru_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "block_c", "interpret"))
def rglru_scan(log_a, x, *, chunk: int = 64, block_c: int = 512,
               interpret: Optional[bool] = None):
    """log_a, x: (B, T, C) -> h sequence (B, T, C), zero initial state."""
    b, t, c = x.shape
    pad_t = (-t) % chunk
    if pad_t:
        widths = ((0, 0), (0, pad_t), (0, 0))
        log_a = jnp.pad(log_a, widths)
        x = jnp.pad(x, widths)
    bc = min(block_c, c)
    pad_c = (-c) % bc
    if pad_c:
        widths = ((0, 0), (0, 0), (0, pad_c))
        log_a = jnp.pad(log_a, widths)
        x = jnp.pad(x, widths)
    tp, cp = t + pad_t, c + pad_c
    grid = (b, cp // bc, tp // chunk)     # time innermost (sequential)

    kernel = functools.partial(rglru_kernel, chunk=chunk)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bc), lambda bi, ci, ti: (bi, ti, ci)),
            pl.BlockSpec((1, chunk, bc), lambda bi, ci, ti: (bi, ti, ci)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bc),
                               lambda bi, ci, ti: (bi, ti, ci)),
        out_shape=jax.ShapeDtypeStruct((b, tp, cp), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)]
        + [pltpu.VMEM((chunk, bc), jnp.float32)] * 3,
        compiler_params=compiler_params,
        interpret=resolve_interpret(interpret),
    )(log_a, x)
    return o[:, :t, :c]
