"""jit'd wrapper for the chunked RWKV-6 WKV kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.rwkv6.rwkv6 import rwkv6_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, lw, u, *, chunk: int = 32,
               interpret: Optional[bool] = None):
    """r/k/v/lw: (B, H, T, C); u: (H, C) -> o (B, H, T, C).

    T must be a multiple of ``chunk`` (the wrapper pads with zero decay /
    zero keys, which leaves the state untouched, then slices).
    """
    b, h, t, c = r.shape
    pad = (-t) % chunk
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        r, k, v = (jnp.pad(x, widths) for x in (r, k, v))
        lw = jnp.pad(lw, widths)          # lw=0 => w=1, but k=0 => no-op
    tp = t + pad
    shp = (b * h, tp, c)
    r2, k2, v2, lw2 = (x.reshape(shp) for x in (r, k, v, lw))
    grid = (b * h, tp // chunk)

    kernel = functools.partial(rwkv6_kernel, n_chunks=grid[1])
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, c), lambda bh, ch: (bh, ch, 0)),
            pl.BlockSpec((1, chunk, c), lambda bh, ch: (bh, ch, 0)),
            pl.BlockSpec((1, chunk, c), lambda bh, ch: (bh, ch, 0)),
            pl.BlockSpec((1, chunk, c), lambda bh, ch: (bh, ch, 0)),
            pl.BlockSpec((1, 1, c), lambda bh, ch: (bh % h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, c), lambda bh, ch: (bh, ch, 0)),
        out_shape=jax.ShapeDtypeStruct(shp, r.dtype),
        scratch_shapes=[pltpu.VMEM((c, c), jnp.float32)]
        + [pltpu.VMEM((chunk, c), jnp.float32)] * 3,
        compiler_params=compiler_params,
        interpret=resolve_interpret(interpret),
    )(r2, k2, v2, lw2, u.reshape(h, 1, c))
    return o.reshape(b, h, tp, c)[:, :, :t]
