"""The compute–bandwidth constraint model (paper Eq. 2) — both levels.

Level 1 (the paper's): size the scratchpad so that, under output-
stationary scheduling, the memory loader can keep the PE array busy.
Per unit of K, a resident ``(M_scp, N_scp)`` output tile costs

    compute cycles = M_scp · N_scp / (M_pe · N_pe · K_pe_elems)
    memory  cycles = (M_scp + N_scp) · elem_bytes / bytes_per_cycle

The utilization-guaranteeing direction is ``memory ≤ compute`` (PE never
starves), which yields a *minimum* scratchpad tile.  The paper's Eq. 2 is
printed with the opposite inequality ("compute ≤ memory"); as written it
would bound the scratchpad from *above* and would contradict Fig. 7
(lower bandwidth ⇒ larger scratchpad).  We implement the physical
direction and keep ``paper_eq2_lhs_rhs`` so the reproduction tests can
exercise the printed form too.  See DESIGN.md §2.

Level 2 (the TPU adaptation): the same inequality applied twice —
  * HBM→VMEM: choose the Pallas GEMM tile ``(bm, bn, bk)`` so that the
    MXU time of one tile ≥ its DMA time, under the VMEM capacity bound.
  * ICI: choose how much of a weight matrix to keep chip-resident vs.
    re-gather, comparing matmul time against link time.
"""

from __future__ import annotations

import dataclasses
import math

from repro.core.config import MatrixUnitConfig
from repro.core.hardware import TpuChip, TARGET_CHIP
from repro.core.precision import DataType, policy


# ---------------------------------------------------------------------------
# Level 1: the paper's scratchpad constraint.
# ---------------------------------------------------------------------------

def compute_cycles_per_k(cfg: MatrixUnitConfig, dt: DataType,
                         m_scp: int = None, n_scp: int = None) -> float:
    m = cfg.m_scp if m_scp is None else m_scp
    n = cfg.n_scp if n_scp is None else n_scp
    return m * n / (cfg.m_pe * cfg.n_pe * cfg.k_pe_elems(dt))


def memory_cycles_per_k(cfg: MatrixUnitConfig, dt: DataType,
                        m_scp: int = None, n_scp: int = None) -> float:
    m = cfg.m_scp if m_scp is None else m_scp
    n = cfg.n_scp if n_scp is None else n_scp
    return (m + n) * policy(dt).bytes_per_elem / cfg.bytes_per_cycle()


def feeds_pe_array(cfg: MatrixUnitConfig, dt: DataType = DataType.INT8) -> bool:
    """True iff the memory system can keep the PE array saturated."""
    return memory_cycles_per_k(cfg, dt) <= compute_cycles_per_k(cfg, dt)


def ideal_utilization(cfg: MatrixUnitConfig, dt: DataType = DataType.INT8) -> float:
    """Steady-state PE utilization bound implied by the constraint model."""
    c = compute_cycles_per_k(cfg, dt)
    m = memory_cycles_per_k(cfg, dt)
    return min(1.0, c / m) if m > c else 1.0


def paper_eq2_lhs_rhs(cfg: MatrixUnitConfig, dt: DataType = DataType.INT8):
    """Eq. 2 exactly as printed: (M·N·K)/(F·Mpe·Npe·Kpe) vs ((M+N)·K)/BW.

    Returned in seconds, K = K_scp.  (K cancels in the comparison; we keep
    it for fidelity to the printed form.)
    """
    k = cfg.k_scp_bytes / policy(dt).bytes_per_elem
    lhs = (cfg.m_scp * cfg.n_scp * k) / (
        cfg.freq_hz * cfg.m_pe * cfg.n_pe * cfg.k_pe_elems(dt))
    rhs = ((cfg.m_scp + cfg.n_scp) * k * policy(dt).bytes_per_elem) / cfg.bandwidth
    return lhs, rhs


def solve_scratchpad(cfg: MatrixUnitConfig, dt: DataType = DataType.INT8,
                     max_tile: int = 1024) -> "tuple[int, int]":
    """Smallest square power-of-two (M_scp, N_scp) that saturates the PEs.

    Square tiles minimise (M+N) loads per output element, matching the
    paper's symmetric choices (64×64 for the case study).
    """
    t = 16
    while t <= max_tile:
        if (memory_cycles_per_k(cfg, dt, t, t)
                <= compute_cycles_per_k(cfg, dt, t, t)):
            return t, t
        t *= 2
    return max_tile, max_tile


# ---------------------------------------------------------------------------
# Level 2a: TPU tile solver (HBM → VMEM).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Pallas GEMM tile — the TPU-side 'scratchpad configuration'."""

    bm: int
    bn: int
    bk: int
    vmem_bytes: int
    compute_s: float      # per-tile MXU time at peak
    dma_s: float          # per-tile HBM time at peak

    @property
    def compute_bound(self) -> bool:
        return self.compute_s >= self.dma_s

    @property
    def ideal_utilization(self) -> float:
        return min(1.0, self.compute_s / max(self.dma_s, 1e-30))


def tile_vmem_bytes(bm: int, bn: int, bk: int, in_bytes: float,
                    accum_bytes: int = 4, buffers: int = 2) -> int:
    """VMEM working set: double-buffered A/B blocks, the resident fp32
    accumulator, and the double-buffered output block (counted at
    accumulator width, its widest case)."""
    return int(buffers * (bm * bk + bk * bn) * in_bytes
               + (1 + buffers) * bm * bn * accum_bytes)


def tile_times(bm: int, bn: int, bk: int, dt: DataType,
               chip: TpuChip = TARGET_CHIP) -> "tuple[float, float]":
    pol = policy(dt)
    peak = chip.peak_int8 if dt == DataType.INT8 else chip.peak_bf16
    compute_s = 2.0 * bm * bn * bk / peak
    dma_s = (bm * bk + bk * bn) * pol.bytes_per_elem / chip.hbm_bw
    return compute_s, dma_s


def solve_tiles(dt: DataType = DataType.BF16, chip: TpuChip = TARGET_CHIP,
                vmem_frac: float = 0.5, bk: int = 512,
                lane: int = 128) -> TileConfig:
    """Pick (bm, bn, bk) under Eq. 2 logic with TPU constants.

    Grow the square output tile in MXU-aligned steps until compute per
    tile covers DMA per tile, subject to the VMEM budget: ``vmem_frac`` of
    the chip's scoped VMEM, the share one kernel may use without raising
    the compiler's limit.  ``bk`` defaults to a K-panel deep enough to
    amortise the MXU pipeline (≥ 128, several lanes of the systolic
    array).
    """
    budget = chip.scoped_vmem_bytes * vmem_frac
    pol = policy(dt)
    best = None
    t = lane
    while True:
        vm = tile_vmem_bytes(t, t, bk, pol.bytes_per_elem)
        if vm > budget:
            break
        c, d = tile_times(t, t, bk, dt, chip)
        best = TileConfig(t, t, bk, vm, c, d)
        if c >= d:          # constraint satisfied — smallest such tile
            return best
        t += lane
    if best is None:
        raise ValueError("even the minimal tile exceeds the VMEM budget")
    return best             # bandwidth-bound: biggest tile that fits


# ---------------------------------------------------------------------------
# Level 2b: ICI shard constraint (the cross-chip reapplication).
# ---------------------------------------------------------------------------

def ici_gather_is_hidden(flops_per_chip: float, gather_bytes: float,
                         dt: DataType = DataType.BF16,
                         chip: TpuChip = TARGET_CHIP) -> bool:
    """Can an all-gather of ``gather_bytes`` hide behind the matmul?

    The distributed analogue of Eq. 2: collective time ≤ compute time
    means a weight-gathering sharding (e.g. ZeRO-3-style) costs nothing
    extra once overlapped; otherwise prefer keeping that operand resident
    (the 'scratchpad' at cluster scale is chip HBM).
    """
    peak = chip.peak_int8 if dt == DataType.INT8 else chip.peak_bf16
    compute_s = flops_per_chip / peak
    link_s = gather_bytes / chip.ici_bw_total
    return link_s <= compute_s


def arithmetic_intensity_needed(dt: DataType = DataType.BF16,
                                chip: TpuChip = TARGET_CHIP) -> float:
    """FLOP/byte at which a chip flips memory→compute bound (ridge point)."""
    peak = chip.peak_int8 if dt == DataType.INT8 else chip.peak_bf16
    return peak / chip.hbm_bw
