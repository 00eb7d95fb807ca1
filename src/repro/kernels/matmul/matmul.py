"""Fused GEMM + epilogue Pallas kernel — the TPU body of CUTEv2.

This kernel *is* the paper's matrix unit, re-expressed for the TPU
memory hierarchy:

* the fp32/int32 accumulator tile lives in VMEM scratch across the whole
  K sweep — the paper's output-stationary, accumulator-resident
  scratchpad (§4.1);
* the Pallas grid pipeline double-buffers A/B block DMA against MXU
  compute — the paper's multi-bank scratchpad + Memory Loader;
* the epilogue (dequant scales, bias zero/row/full, soft-cap,
  activation, GLU gating, residual) executes on the VPU *inside* the
  same kernel while the MXU pipeline streams the next tiles — the
  paper's matrix–vector overlap (Fig. 5), realised without an HBM
  round-trip for the intermediate;
* tile sizes come from ``core.constraint.solve_tiles`` — Eq. 2 with HBM
  bandwidth and MXU throughput substituted in.

Supported input precisions (paper §4.1): int8 (int32 accumulate),
fp8 e4m3/e5m2, fp16, bf16 (fp32 accumulate), fp32.  TF32 maps to fp32
(DESIGN.md §2).

Operand layout for GLU epilogues: ``b`` is passed as ``(K, 2, N/2)`` —
gate and up projections interleaved on a leading sub-axis so one output
tile sees both halves (the wrapper reshapes a concatenated ``(K, N)``
weight).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fusion import Epilogue, EpilogueOperands, apply_epilogue
from repro.core.task import BiasType


def fused_matmul_kernel(*refs, ep: Epilogue, n_k: int, acc_dtype):
    """Kernel body.  refs = a, b, [bias], [scale_a], [scale_b], [residual],
    o, acc_scratch — optional operands present iff the epilogue uses them.
    Grid: (m_tiles, n_tiles, k_tiles), K innermost ('arbitrary')."""
    it = iter(refs)
    a_ref = next(it)
    b_ref = next(it)
    bias_ref = next(it) if ep.bias_type != BiasType.ZERO else None
    scale_a_ref = next(it) if ep.has_scale_a else None
    scale_b_ref = next(it) if ep.has_scale_b else None
    residual_ref = next(it) if ep.has_residual else None
    o_ref = next(it)
    acc_ref = next(it)

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if ep.glu:
        # (bk, 2, bn/2) -> (bk, bn): gate columns then up columns.
        b = b.reshape(b.shape[0], -1)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_dtype)

    @pl.when(k == n_k - 1)
    def _epilogue():
        def _cols(ref):
            # Per-column operands arrive as (1, bn) blocks, or under GLU
            # as (2, bn/2) blocks riding the same column split as ``b``:
            # gate half then up half, joined along lanes.
            if ref is None:
                return None
            x = ref[...]
            if ep.glu:
                x = jnp.concatenate([x[0:1], x[1:2]], axis=1)
            return x

        ops = EpilogueOperands(
            bias=_cols(bias_ref),
            scale_a=None if scale_a_ref is None else scale_a_ref[...],
            scale_b=_cols(scale_b_ref),
            residual=None if residual_ref is None else residual_ref[...],
        )
        o_ref[...] = apply_epilogue(acc_ref[...], ep, ops,
                                    tile_operands=True)
