"""jit'd wrapper around the fused matmul kernel.

Responsibilities: flatten batch dims, pick tile sizes from the Eq.2
solver (clamped to the problem), pad every axis to tile multiples
(zero K-padding is exact for both int and float accumulation), assemble
the optional epilogue-operand BlockSpecs, and slice the padding back off.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import constraint
from repro.core.fusion import Epilogue, EpilogueOperands
from repro.core.precision import PrecisionPolicy
from repro.core.task import BiasType
from repro.kernels import resolve_interpret
from repro.kernels.matmul.matmul import fused_matmul_kernel

_LANE = 128


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def default_tiles(m: int, n: int, k: int, policy: PrecisionPolicy):
    """Eq.2-solved tile, clamped to the (padded) problem size."""
    tc = constraint.solve_tiles(policy.data_type)
    bm = min(tc.bm, _round_up(m, _LANE))
    bn = min(tc.bn, _round_up(n, _LANE))
    bk = min(tc.bk, _round_up(k, _LANE))
    return bm, bn, bk


def _round_up(x, m):
    return x + (-x) % m


def supports(a_shape, b_shape, epilogue: Epilogue) -> bool:
    """Kernel contract: >=2D a, 2D (or GLU-3D) b, lane-sized inner dims."""
    if len(b_shape) not in (2, 3):
        return False
    n = b_shape[-1] * (2 if len(b_shape) == 3 else 1)
    return (a_shape[-1] % _LANE == 0 and n % _LANE == 0)


@functools.partial(jax.jit, static_argnames=("epilogue", "policy",
                                             "block_shape", "interpret"))
def fused_matmul(a: jax.Array, b: jax.Array, *,
                 epilogue: Epilogue = Epilogue(),
                 operands: EpilogueOperands = EpilogueOperands(),
                 policy: Optional[PrecisionPolicy] = None,
                 block_shape: Optional[tuple] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """epilogue(a @ b).  a: (..., M, K); b: (K, N) or (K, 2, N/2) for GLU."""
    from repro.core.fusion import _infer_policy   # cycle-free at call time
    if policy is None:
        policy = _infer_policy(a)
    import dataclasses
    if epilogue.out_dtype is None:
        epilogue = dataclasses.replace(epilogue, out_dtype=policy.output_dtype)

    lead = a.shape[:-2]
    m, k = a.shape[-2], a.shape[-1]
    a2 = a.reshape((-1, k)) if lead else a
    if lead:
        m = a2.shape[0]
    if epilogue.glu and b.ndim == 2:
        b = b.reshape(k, 2, b.shape[-1] // 2)
    n_logical = b.shape[-1] * (2 if b.ndim == 3 else 1)

    bm, bn, bk = block_shape or default_tiles(m, n_logical, k, policy)
    if epilogue.glu and block_shape is None:
        # each GLU half of the column block is a whole number of lanes
        bn = 2 * _round_up(bn // 2, _LANE)
    a2 = _pad_to(_pad_to(a2, 0, bm), 1, bk)
    if b.ndim == 3:
        b_p = _pad_to(_pad_to(b, 0, bk), 2, bn // 2)
    else:
        b_p = _pad_to(_pad_to(b, 0, bk), 1, bn)
    mp, kp = a2.shape
    n_p = b_p.shape[-1] * (2 if b.ndim == 3 else 1)
    grid = (mp // bm, n_p // bn, kp // bk)

    acc_dtype = policy.accum_dtype
    n_out = n_p // 2 if epilogue.glu else n_p
    bn_out = bn // 2 if epilogue.glu else bn

    in_arrays = [a2, b_p]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        (pl.BlockSpec((bk, 2, bn // 2), lambda i, j, kk: (kk, 0, j))
         if b.ndim == 3 else
         pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))),
    ]

    def _add_col_operand(x, width):
        """(N,)-shaped epilogue operand as a 2-D row, padded & blocked
        along columns ((2, N/2) under GLU, like ``b``)."""
        rows = 2 if epilogue.glu else 1
        x = _pad_to(x.reshape(rows, -1), 1, width // rows)
        in_specs.append(pl.BlockSpec((rows, width // rows),
                                     lambda i, j, kk: (0, j)))
        in_arrays.append(x)

    if epilogue.bias_type == BiasType.ROW:
        _add_col_operand(operands.bias, bn)
    elif epilogue.bias_type == BiasType.FULL:
        in_arrays.append(_pad_to(_pad_to(operands.bias, 0, bm), 1, bn))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
    if epilogue.has_scale_a:
        in_arrays.append(_pad_to(operands.scale_a.reshape(-1, 1), 0, bm))
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)))
    if epilogue.has_scale_b:
        _add_col_operand(operands.scale_b, bn)
    if epilogue.has_residual:
        res = operands.residual.reshape((-1, operands.residual.shape[-1]))
        in_arrays.append(_pad_to(_pad_to(res, 0, bm), 1, bn_out))
        in_specs.append(pl.BlockSpec((bm, bn_out), lambda i, j, kk: (i, j)))

    kernel = functools.partial(fused_matmul_kernel, ep=epilogue,
                               n_k=grid[2], acc_dtype=acc_dtype)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn_out), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n_out), epilogue.out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=compiler_params,
        interpret=resolve_interpret(interpret),
    )(*in_arrays)

    out = out[:m, : (n_logical // 2 if epilogue.glu else n_logical)]
    if lead:
        out = out.reshape(*lead, a.shape[-2], out.shape[-1])
    return out
