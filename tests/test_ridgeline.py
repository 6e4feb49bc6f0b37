"""Ridgeline model unit + property tests (the paper's §II math)."""
import math

import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (CLX, TPU_V5E, HardwareSpec, Resource, WorkUnit,
                        analyze, analyze_multilink, ascii_plot,
                        classify_by_quadrant, classify_by_times, region_at,
                        svg_plot)

HW = st.sampled_from([CLX, TPU_V5E,
                      HardwareSpec("toy", 1e12, 1e11, 1e10)])
POS = st.floats(min_value=1e-3, max_value=1e18, allow_nan=False,
                allow_infinity=False)
NONNEG = st.one_of(st.just(0.0), POS)


class TestBalancePoints:
    def test_clx_matches_paper(self):
        # §III: x* = 105/12, y* = 4200/105 = 40, k* = 4200/12 = 350
        assert CLX.ridge_memory == pytest.approx(105 / 12)
        assert CLX.ridge_arithmetic == pytest.approx(40.0)
        assert CLX.ridge_network == pytest.approx(350.0)

    def test_ridge_identity(self):
        for hw in (CLX, TPU_V5E):
            assert hw.ridge_network == pytest.approx(
                hw.ridge_memory * hw.ridge_arithmetic)


class TestIntensities:
    def test_table1_definitions(self):
        w = WorkUnit("w", flops=100.0, mem_bytes=20.0, net_bytes=5.0)
        assert w.arithmetic_intensity == pytest.approx(5.0)     # F/B_M
        assert w.memory_intensity == pytest.approx(4.0)         # B_M/B_N
        assert w.network_intensity == pytest.approx(20.0)       # F/B_N = x*y

    def test_xy_identity(self):
        w = WorkUnit("w", 123.0, 7.0, 3.0)
        assert w.network_intensity == pytest.approx(
            w.arithmetic_intensity * w.memory_intensity)

    def test_negative_rejected(self):
        with pytest.raises(ValueError):
            WorkUnit("w", -1.0, 1.0, 1.0)


class TestClassificationEquivalence:
    """The paper's quadrant construction == argmax of resource times.

    This is the central correctness claim of the 2D projection; we check it
    as a hypothesis property over 6 orders of magnitude, including zero
    traffic edge cases.
    """

    @given(f=NONNEG, bm=NONNEG, bn=NONNEG, hw=HW)
    @settings(max_examples=500, deadline=None)
    def test_quadrant_equals_argmax(self, f, bm, bn, hw):
        w = WorkUnit("w", f, bm, bn)
        assert classify_by_quadrant(w, hw) == classify_by_times(w, hw)

    @given(f=POS, bm=POS, bn=POS, hw=HW)
    @settings(max_examples=300, deadline=None)
    def test_runtime_is_max_of_times(self, f, bm, bn, hw):
        a = analyze(WorkUnit("w", f, bm, bn), hw)
        assert a.runtime == pytest.approx(
            max(a.t_compute, a.t_memory, a.t_network))
        # bound runtime >= every individual term
        assert a.runtime >= a.t_compute - 1e-18
        assert a.peak_fraction <= 1.0 + 1e-9

    @given(f=POS, bm=POS, bn=POS, hw=HW, scale=st.floats(1e-3, 1e3))
    @settings(max_examples=200, deadline=None)
    def test_scale_invariance(self, f, bm, bn, hw, scale):
        """Intensities (and hence the region) are invariant to unit scaling."""
        w1 = WorkUnit("a", f, bm, bn)
        w2 = WorkUnit("b", f * scale, bm * scale, bn * scale)
        assert classify_by_quadrant(w1, hw) == classify_by_quadrant(w2, hw)


class TestPaperCaseStudy:
    """Quantitative claims from §III reproduced analytically."""

    @staticmethod
    def mlp_unit(batch, width=4096, layers=1, dtype_bytes=4):
        from repro.models.mlp_dlrm import analytic_work_unit
        f, bm, bn = analytic_work_unit(batch, width, layers, dtype_bytes)
        return WorkUnit(f"mlp_b{batch}", f, bm, bn)

    def test_batch_512_near_ridge(self):
        # paper: "MLP with batch size 512 is indeed on the ridgeline"
        w = self.mlp_unit(512)
        # on the compute-network ridge x*y ~ k* = 350
        assert w.network_intensity == pytest.approx(384, rel=0.15)

    def test_1024_compute_bound_256_network_bound(self):
        assert classify_by_quadrant(self.mlp_unit(1024), CLX) == Resource.COMPUTE
        assert classify_by_quadrant(self.mlp_unit(256), CLX) == Resource.NETWORK

    def test_arithmetic_intensity_crosses_ridge_at_32(self):
        # paper Fig 4a/4b: batch >= 32 can reach peak flops (I_A >= 40)
        assert self.mlp_unit(32).arithmetic_intensity >= CLX.ridge_arithmetic
        assert self.mlp_unit(16).arithmetic_intensity < CLX.ridge_arithmetic

    def test_allreduce_dominates_until_512(self):
        # paper Fig 4c: all-reduce takes longer than compute up to batch 512
        for b in (32, 128, 256):
            a = analyze(self.mlp_unit(b), CLX)
            assert a.t_network > a.t_compute, b
        a = analyze(self.mlp_unit(1024), CLX)
        assert a.t_compute > a.t_network


class TestMultilink:
    def test_slowest_link_dominates(self):
        w_ici = WorkUnit("w", 1e12, 1e9, 1e9)
        w_dci = WorkUnit("w", 1e12, 1e9, 6e8)   # fewer bytes, slower link
        a = analyze_multilink({"ici": w_ici, "pod": w_dci}, TPU_V5E)
        # pod link: 6e8/25e9 = 24ms > ici 1e9/50e9 = 20ms
        assert a.t_network == pytest.approx(6e8 / 25e9)


class TestDeviceKindLookup:
    @pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
    def test_v5e_kind_maps_to_its_preset(self, kind):
        from repro.core.hardware import hardware_for_device_kind
        assert hardware_for_device_kind(kind) is TPU_V5E

    @pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "cpu"])
    def test_unlisted_kind_raises(self, kind):
        from repro.core.hardware import hardware_for_device_kind
        with pytest.raises(KeyError, match="no peaks for device_kind"):
            hardware_for_device_kind(kind)

    def test_report_on_cpu_is_a_projection(self):
        from repro.launch.train import report_peaks
        hw, basis = report_peaks()
        assert hw is TPU_V5E
        assert basis.startswith("projection onto tpu_v5e")


class TestPlots:
    def test_ascii_plot_renders_regions_and_points(self):
        a = analyze(WorkUnit("pt", 1e12, 1e10, 1e8), CLX)
        s = ascii_plot([a], CLX)
        assert "pt" in s and "=" in s and "|" in s
        for glyph in (".", "-", "+"):
            assert glyph in s

    def test_svg_plot_is_valid_svg(self):
        a = analyze(WorkUnit("pt", 1e12, 1e10, 1e8), TPU_V5E)
        s = svg_plot([a], TPU_V5E)
        assert s.startswith("<svg") and s.endswith("</svg>")

    def test_region_at_corners(self):
        hw = CLX
        eps = 1e3
        assert region_at(hw.ridge_memory * eps, hw.ridge_arithmetic * eps,
                         hw) == Resource.COMPUTE
        assert region_at(hw.ridge_memory * eps, hw.ridge_arithmetic / eps,
                         hw) == Resource.MEMORY
        assert region_at(hw.ridge_memory / eps, hw.ridge_arithmetic / eps,
                         hw) == Resource.NETWORK
