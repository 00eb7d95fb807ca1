"""Hardware descriptions for both sides of the CUTEv2 adaptation.

Two families live here:

* ``CpuPlatform`` — the four open-source RISC-V CPUs the paper integrates
  into (Rocket / Shuttle / BOOM / XiangShan-Kunminghu), plus the three
  commercial baselines of Table 5 (Xeon 8580 AMX, IBM S1022 MMA, Apple M4
  SME).  These feed the cycle-approximate simulator that reproduces the
  paper's figures.

* ``TpuChip`` — the TPU v5e target of the JAX/Pallas adaptation.  The
  constraint model (``core.constraint``) reads its constants from here.

All bandwidths are bytes/second, frequencies in Hz, throughputs in ops/s
(1 MAC = 2 ops, matching the paper's Eq. 1).
"""

from __future__ import annotations

import dataclasses

GIGA = 1e9
TERA = 1e12
MEBI = 2**20
GIBI = 2**30


@dataclasses.dataclass(frozen=True)
class CpuPlatform:
    """A CPU front-end + memory system hosting the matrix extension.

    ``dispatch_cycles`` models the cost of programming the interface
    registers (paper Table 1) and firing one ``asyncMatMul``: a handful of
    cycles over RoCC, noticeably more over the CSR path used for
    XiangShan (paper §4.4).  ``dram_efficiency`` derates the nominal
    DRAMSim bandwidth for strided access patterns (paper §5.4 notes the
    GEMM fluctuations come from exactly this).
    """

    name: str
    microarch: str
    interface: str            # "RoCC" | "CSR"
    freq_hz: float
    dispatch_cycles: int      # per asyncMatMul task
    check_cycles: int         # per checkMatmul poll
    dram_efficiency: float    # achieved / nominal bandwidth
    l2_bytes: float = 1 * MEBI  # unfused intermediates below this stay on-chip

    # Vector unit attached to this CPU (the paper pairs Saturn 512-bit RVV).
    vector_bits: int = 512
    vector_issue: int = 1     # vector ops issued per cycle


# ---------------------------------------------------------------------------
# The four integration platforms (paper Table 3 / §5.2).
# Dispatch costs: RoCC is a tightly-coupled custom-instruction port (a few
# cycles); the CSR mailbox on Kunminghu costs a CSR write per field.
# ---------------------------------------------------------------------------
ROCKET = CpuPlatform("rocket", "in-order 1-issue", "RoCC", 2.0 * GIGA,
                     dispatch_cycles=24, check_cycles=6, dram_efficiency=0.92)
SHUTTLE = CpuPlatform("shuttle", "in-order 3-issue", "RoCC", 2.0 * GIGA,
                      dispatch_cycles=16, check_cycles=4, dram_efficiency=0.92)
BOOM = CpuPlatform("boom", "OoO 4-issue", "RoCC", 2.0 * GIGA,
                   dispatch_cycles=12, check_cycles=3, dram_efficiency=0.92)
KUNMINGHU = CpuPlatform("kunminghu", "OoO 6-issue", "CSR", 2.0 * GIGA,
                        dispatch_cycles=96, check_cycles=12, dram_efficiency=0.92)

PLATFORMS = {p.name: p for p in (ROCKET, SHUTTLE, BOOM, KUNMINGHU)}


@dataclasses.dataclass(frozen=True)
class CommercialBaseline:
    """Paper Table 5: commercial matrix extensions we compare against.

    ``sync_overhead`` models the fine-grained synchronous-instruction
    execution model (no matrix/vector overlap, per-tile issue pressure in
    the CPU instruction window) as a multiplicative derate on achievable
    matrix throughput on large GEMM (Fig. 8 regime).

    ``op_coverage`` is the per-workload *framework efficiency* the paper
    measures (§5.4 commentary): SME/ORT has **no convolution support**
    (ResNet falls back to scalar/NEON paths), MMA/ORT operator coverage
    is far behind OpenVINO on ResNet, OpenVINO pays softmax/SiLU costs on
    Llama3, etc.  These nine scalars are calibrated once against the
    paper's *unfused* column of Table 6 and then held fixed — the
    fused/unfused ratios and the overlap-contribution split remain
    genuine model predictions (benchmarks/run.py reports both raw and
    coverage-calibrated numbers).
    """

    name: str
    ise: str
    framework: str
    bandwidth: float          # bytes/s per core (MLC / STREAM measured)
    int8_peak: float          # ops/s per core
    sync_overhead: float      # fraction of peak reachable on large GEMM
    vector_relative: float    # vector-unit throughput relative to Saturn-512
    op_coverage: tuple = ()   # ((workload, efficiency), ...)

    def coverage(self, workload: "str | None") -> float:
        return dict(self.op_coverage).get(workload, 1.0)


XEON_8580 = CommercialBaseline(
    "xeon8580", "AMX", "OpenVINO", 49.48 * GIGA, 4.6 * TERA,
    sync_overhead=0.72, vector_relative=2.0,
    # Best operator support of the three (§5.4); Llama3 pays SmoothQuant
    # (de)quant + softmax overheads OpenVINO does not fuse.
    op_coverage=(("resnet50", 0.60), ("bert", 0.55), ("llama3", 0.45)))
IBM_S1022 = CommercialBaseline(
    "ibms1022", "MMA", "ONNXRuntime", 52.37 * GIGA, 2.0 * TERA,
    sync_overhead=0.35, vector_relative=1.0,
    # ORT+OpenBLAS coverage is weak on conv (Fig. 9 commentary).
    op_coverage=(("resnet50", 0.28), ("bert", 0.80), ("llama3", 1.0)))
APPLE_M4 = CommercialBaseline(
    "applem4", "SME", "ONNXRuntime", 131.31 * GIGA, 4.0 * TERA,
    sync_overhead=0.80, vector_relative=1.5,
    # "Currently, SME lacks support for convolution operators" (§5.4).
    op_coverage=(("resnet50", 0.16), ("bert", 0.40), ("llama3", 0.30)))

BASELINES = {b.name: b for b in (XEON_8580, IBM_S1022, APPLE_M4)}


# ---------------------------------------------------------------------------
# TPU target (the hardware-adaptation side).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TpuChip:
    """Per-chip constants for the tile and ICI constraint model."""

    name: str
    peak_bf16: float          # FLOP/s
    peak_int8: float          # OP/s
    hbm_bw: float             # bytes/s
    hbm_bytes: float          # capacity
    ici_bw: float             # bytes/s per link
    ici_links: int            # links per chip in a 2D torus
    scoped_vmem_bytes: float  # vector memory one kernel may use by default
    mxu_shape: tuple = (128, 128)   # systolic array dims
    vpu_lanes: int = 8 * 128        # VPU ALUs

    @property
    def ici_bw_total(self) -> float:
        return self.ici_bw * self.ici_links


# TPU v5e (assignment-provided constants: 197 TFLOP/s bf16, 819 GB/s HBM,
# ~50 GB/s per ICI link).
TPU_V5E = TpuChip(
    name="tpu_v5e",
    peak_bf16=197 * TERA,
    peak_int8=394 * TERA,
    hbm_bw=819 * GIGA,
    hbm_bytes=16 * GIBI,
    ici_bw=50 * GIGA,
    ici_links=4,
    # The TPU kernel compiler's default scoped-VMEM limit on v5e (of 128
    # MiB physical): a kernel whose buffers exceed it is refused.
    scoped_vmem_bytes=16 * MEBI,
)

TARGET_CHIP = TPU_V5E
