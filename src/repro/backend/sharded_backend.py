"""The sharded execution backend: the partitioned graph, run for real.

``sharded`` executes the *identical* partitioned TaskGraph the
``desim-cluster`` backend times: ``sim.partition`` decides which unit
owns which tiles, and execution maps units onto a ``(units,)`` mesh axis
— ``distributed.sharding.shard_map_gemm`` computes each unit's output
block under ``shard_map`` (``launch.mesh``) with one device per unit.
The modelled units are matrix units, not chips, so where the host has
fewer devices than units this backend runs the units' spans as a loop
on one device (``sliced_gemm``), and int8 results are bit-exact against the
``jax`` backend either way.
Epilogue-carrying vector nodes are applied to the assembled accumulator
through the same region walk the single-device lowering uses
(``sim.lower.apply_graph_epilogues``).
"""

from __future__ import annotations

from typing import Callable

import jax

from repro.backend.base import (ExecResult, GraphOperands,
                                MatMulOperands, NO_MATMUL_OPERANDS)
from repro.backend.cluster_backend import PartitionedBackend
from repro.backend.registry import register
from repro.core.fusion import Epilogue, NO_EPILOGUE
from repro.core.task import MatMulTask
from repro.obs import instrument


@register("sharded")
class ShardedBackend(PartitionedBackend):
    """Cluster-partitioned execution over ``launch.mesh`` + shard_map."""

    executes = True
    matmul_string = "xla"

    @property
    def shard_dim(self):
        from repro.sim.partition import STRATEGY_DIM
        return STRATEGY_DIM[self.strategy]

    def _stage(self, task: MatMulTask, operands: MatMulOperands,
               epilogue: Epilogue) -> Callable[[], ExecResult]:
        if not operands.concrete:
            raise ValueError(
                f"backend {self.name!r} executes numbers: dispatch needs "
                "MatMulOperands(a=..., b=...)")
        ep = None if epilogue is NO_EPILOGUE else epilogue
        part = self.partition(self.lower(task, epilogue=ep))
        return lambda: self.run_graph(part, operands)

    @instrument("run_graph")
    def run_graph(self, graph, operands: GraphOperands = None) -> ExecResult:
        from repro.sim.lower import (_subgraph_for_gemm, gemm_labels,
                                     iter_gemm_operands)
        part = self.partition(graph)
        g = part.graph
        detail = {"partition": {"strategy": part.strategy,
                                "n_units": part.n_units,
                                "transfers": part.n_transfers}}
        if isinstance(operands, dict):
            outs = {}
            for label, a, b, eops in iter_gemm_operands(g, operands):
                outs[label] = self._execute_gemm(
                    _subgraph_for_gemm(g, label), a, b, eops,
                    part.spans.get(label))
            return ExecResult(outputs=outs, detail=detail)
        ops = operands or NO_MATMUL_OPERANDS
        if not ops.concrete:
            raise ValueError(
                f"backend {self.name!r} needs concrete operands: pass "
                "MatMulOperands(a, b) or a {gemm label: (a, b)} dict")
        labels = gemm_labels(g)
        if len(labels) > 1:
            raise ValueError(
                f"graph spans {len(labels)} GEMMs; pass a "
                "{gemm label: (a, b)} operand dict")
        out = self._execute_gemm(g, ops.a, ops.b, ops.epilogue,
                                 part.spans.get(labels[0]))
        return ExecResult(output=out, detail=detail)

    def _execute_gemm(self, graph, a, b, eops, spans=None):
        """One GEMM's partitioned subgraph on real arrays; ``spans`` is
        the partition's per-unit extent list, so execution reproduces
        the exact unit-to-data mapping the DES timed."""
        from repro.core.fusion import _infer_policy
        from repro.distributed.sharding import shard_map_gemm, sliced_gemm
        from repro.sim.lower import apply_graph_epilogues
        policy = _infer_policy(a)
        dim = self.shard_dim
        # layer-pipeline keeps each whole GEMM on one unit: within a
        # single GEMM there is nothing to shard.
        n = self.units if dim is not None else 1
        gemm = shard_map_gemm if jax.device_count() >= n else sliced_gemm
        acc = gemm(a, b, n, dim=dim or "m", accum_dtype=policy.accum_dtype,
                   precision=policy.dot_precision,
                   bounds=spans if dim is not None else None)
        return apply_graph_epilogues(graph, acc, operands=eops,
                                     in_dtype=a.dtype)
