"""jit'd wrapper for the grouped MoE GEMM kernel."""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fusion import Epilogue
from repro.kernels import resolve_interpret
from repro.kernels.moe.grouped_matmul import grouped_matmul_kernel


def _pad(x, axis, mult):
    p = (-x.shape[axis]) % mult
    if not p:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w)


@functools.partial(jax.jit, static_argnames=("epilogue", "block_shape",
                                             "interpret"))
def grouped_matmul(x, w, *, epilogue: Epilogue = Epilogue(),
                   block_shape=(128, 128, 128),
                   interpret: Optional[bool] = None):
    """x: (E, C, K); w: (E, K, N) (or (E, K, 2, N/2) for GLU) -> (E, C, N')."""
    e, cap, k = x.shape
    if epilogue.glu and w.ndim == 3:
        w = w.reshape(e, k, 2, w.shape[-1] // 2)
    n_logical = w.shape[-1] * (2 if w.ndim == 4 else 1)
    acc_dtype = jnp.int32 if x.dtype == jnp.int8 else jnp.float32
    if epilogue.out_dtype is None:
        epilogue = dataclasses.replace(
            epilogue, out_dtype=x.dtype if x.dtype != jnp.int8 else jnp.int32)

    bm, bn, bk = block_shape
    bm = min(bm, _round_up(cap, 8))
    if w.ndim == 4:
        # each GLU half of the column block is a whole number of lanes
        bn = 2 * min(_round_up(bn // 2, 128), _round_up(n_logical // 2, 128))
    else:
        bn = min(bn, _round_up(n_logical, 128))
    bk = min(bk, _round_up(k, 128))
    x = _pad(_pad(x, 1, bm), 2, bk)
    if w.ndim == 4:
        w = _pad(_pad(w, 1, bk), 3, bn // 2)
    else:
        w = _pad(_pad(w, 1, bk), 2, bn)
    cp, kp = x.shape[1], x.shape[2]
    np_ = w.shape[-1] * (2 if w.ndim == 4 else 1)
    grid = (e, cp // bm, np_ // bn, kp // bk)
    n_out = np_ // 2 if epilogue.glu else np_
    bn_out = bn // 2 if epilogue.glu else bn

    w_spec = (pl.BlockSpec((1, bk, 2, bn // 2),
                           lambda ei, i, j, kk: (ei, kk, 0, j))
              if w.ndim == 4 else
              pl.BlockSpec((1, bk, bn), lambda ei, i, j, kk: (ei, kk, j)))

    kernel = functools.partial(grouped_matmul_kernel, ep=epilogue,
                               n_k=grid[3])
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda ei, i, j, kk: (ei, i, kk)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((1, bm, bn_out),
                               lambda ei, i, j, kk: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, cp, n_out), epilogue.out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=compiler_params,
        interpret=resolve_interpret(interpret),
    )(x, w)
    return out[:, :cap, : (n_logical // 2 if epilogue.glu else n_logical)]


def _round_up(x, m):
    return x + (-x) % m
