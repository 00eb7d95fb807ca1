"""Paper Eq.1/Eq.2 and the two-level constraint model."""

import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import constraint
from repro.core.config import CASE_STUDY, PLATFORM_2TOPS, MatrixUnitConfig, \
    scaled_config, scaling_sweep
from repro.core.hardware import GIGA, TERA, TPU_V5E
from repro.core.precision import DataType


class TestEq1:
    def test_case_study_is_4tops_int8(self):
        # Table 2: 2 GHz x 4x4 PEs x (512b/8b) x 2 = 4.096 TOPS.
        assert CASE_STUDY.throughput(DataType.INT8) == pytest.approx(
            4.096 * TERA)

    def test_platform_config_is_2tops(self):
        assert PLATFORM_2TOPS.throughput(DataType.INT8) == pytest.approx(
            2.048 * TERA)

    def test_halving_precision_doubles_throughput(self):
        t8 = CASE_STUDY.throughput(DataType.INT8)
        t16 = CASE_STUDY.throughput(DataType.BF16)
        assert t8 == pytest.approx(2 * t16)

    def test_envelope_covers_half_to_32_tops(self):
        tops = [c.throughput(DataType.INT8) / TERA for c in scaling_sweep()]
        assert min(tops) <= 0.6
        assert max(tops) >= 32.0


class TestEq2:
    def test_paper_printed_form_case_study(self):
        # As printed, Eq.2 holds for the case study (compute <= memory):
        lhs, rhs = constraint.paper_eq2_lhs_rhs(CASE_STUDY)
        assert lhs <= rhs

    def test_case_study_is_memory_limited(self):
        # ...which means the PE array is NOT saturated: ideal util = 75%.
        assert constraint.ideal_utilization(CASE_STUDY) == pytest.approx(
            0.75, abs=0.01)

    def test_2tops_config_saturates(self):
        assert constraint.feeds_pe_array(PLATFORM_2TOPS)
        assert constraint.ideal_utilization(PLATFORM_2TOPS) == 1.0

    def test_solver_direction(self):
        # Saturating direction: the solved scratchpad feeds the PEs.
        m, n = constraint.solve_scratchpad(CASE_STUDY)
        cfg = CASE_STUDY.with_(m_scp=m, n_scp=n)
        assert constraint.feeds_pe_array(cfg)

    @given(bw_gb=st.integers(4, 128))
    @settings(max_examples=20, deadline=None)
    def test_lower_bandwidth_needs_larger_scratchpad(self, bw_gb):
        lo = MatrixUnitConfig(bandwidth=bw_gb * GIGA)
        hi = MatrixUnitConfig(bandwidth=2 * bw_gb * GIGA)
        m_lo, _ = constraint.solve_scratchpad(lo)
        m_hi, _ = constraint.solve_scratchpad(hi)
        assert m_lo >= m_hi

    def test_scaled_configs_satisfy_constraint(self):
        for cfg in scaling_sweep():
            assert constraint.feeds_pe_array(cfg), cfg.describe()


class TestTpuTiles:
    def test_solved_tile_fits_vmem_and_saturates(self):
        tc = constraint.solve_tiles(DataType.BF16)
        # within half the compiler's scoped-VMEM limit, not the chip's
        # 128 MiB of physical VMEM
        assert tc.vmem_bytes <= 0.5 * TPU_V5E.scoped_vmem_bytes
        assert tc.compute_bound

    def test_int8_needs_bigger_tiles_than_bf16(self):
        # Double the OPS at the same bandwidth => higher required AI.
        t8 = constraint.solve_tiles(DataType.INT8)
        t16 = constraint.solve_tiles(DataType.BF16)
        assert t8.bm >= t16.bm

    def test_ridge_point(self):
        ai = constraint.arithmetic_intensity_needed(DataType.BF16)
        assert 200 < ai < 300          # 197e12 / 819e9 ≈ 240

    def test_ici_hiding(self):
        # A big matmul hides its weight gather; a tiny one does not.
        assert constraint.ici_gather_is_hidden(
            flops_per_chip=1e12, gather_bytes=1e8)
        assert not constraint.ici_gather_is_hidden(
            flops_per_chip=1e9, gather_bytes=1e9)
