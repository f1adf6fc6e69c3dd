"""Tests for the vectorized multi-chip measurement substrate:

  * analyze_batch == per-config analyze, exactly, on both registered chips;
  * measure_batch is statistically identical to the sequential scalar loop;
  * config_features_batch == per-config config_features;
  * the chip registry resolves names/aliases and rejects unknown chips;
  * the RTX-4070 spec yields plausible roofline behaviour;
  * profiler -> predictor -> autotuner round-trips per chip.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.autotuner import GemmAutotuner, build_default_predictor
from repro.core.chips import (RTX_4070, TPU_V5E, available_chips,
                              chip_for_device_kind, get_chip)
from repro.core.features import (
    NUMERIC_FEATURES,
    config_features,
    config_features_batch,
)
from repro.core.hwsim import (
    GemmConfig,
    TpuGemmSimulator,
    telemetry_row,
)
from repro.core.predictor import PerfPredictor
from repro.core.profiler import collect_dataset, profile_configs, sweep_configs

CHIPS = ("tpu_v5e", "rtx4070")

_FLOAT_FIELDS = (
    "runtime_ms", "power_w", "energy_j", "tflops", "compute_time_ms",
    "memory_time_ms", "overhead_ms", "mxu_utilization", "hbm_utilization",
    "arithmetic_intensity", "temperature_c",
)
_EXACT_FIELDS = (
    "vmem_working_set_bytes", "max_inflight_buffers", "pipelined",
    "grid_steps", "bound", "valid",
)


def _sample_configs(n=200, seed=11):
    cfgs = sweep_configs(n_configs=n, seed=seed)
    # include edge cases: invalid (VMEM OOM), sub-sublane, misaligned
    cfgs += [
        GemmConfig(8192, 8192, 8192, 4096, 4096, 4096),   # OOM -> invalid
        GemmConfig(2048, 2048, 2048, 8, 8, 8),            # VPU fallback
        GemmConfig(4096, 4096, 4096, 100, 100, 500),      # misaligned
        GemmConfig(2048, 2048, 256, 256, 256, 256, beta=1.0),
        GemmConfig(512, 512, 512, 128, 128, 128, layout="tt", dtype="f32"),
    ]
    return cfgs


class TestBatchScalarParity:
    @pytest.mark.parametrize("chip", CHIPS)
    def test_analyze_batch_matches_scalar_exactly(self, chip):
        cfgs = _sample_configs()
        batch = TpuGemmSimulator(chip=chip, seed=0).analyze_batch(cfgs)
        scalar_sim = TpuGemmSimulator(chip=chip, seed=0)
        for i, cfg in enumerate(cfgs):
            want = scalar_sim.analyze(cfg)
            got = telemetry_row(batch, i)
            for f in _EXACT_FIELDS:
                assert getattr(got, f) == getattr(want, f), (f, cfg)
            for f in _FLOAT_FIELDS:
                a, b = getattr(want, f), getattr(got, f)
                if np.isnan(a):
                    assert np.isnan(b), (f, cfg)
                else:
                    assert a == b, (f, cfg)  # bit-exact, not approx

    def test_batch_invariant_to_batch_size(self):
        """Splitting a batch must not change the analytical telemetry."""
        cfgs = _sample_configs(n=64)
        sim = TpuGemmSimulator(seed=0)
        whole = sim.analyze_batch(cfgs)
        halves = [sim.analyze_batch(cfgs[:32]), sim.analyze_batch(cfgs[32:])]
        for key in ("runtime_ms", "power_w", "grid_steps"):
            merged = np.concatenate([h[key] for h in halves])
            np.testing.assert_array_equal(merged[whole["valid"]],
                                          whole[key][whole["valid"]])

    def test_measure_batch_statistically_matches_scalar_loop(self):
        cfgs = sweep_configs(n_configs=400, seed=5)
        batch = TpuGemmSimulator(seed=9).measure_batch(cfgs)
        scalar_sim = TpuGemmSimulator(seed=9)
        scalar_rt = np.array([scalar_sim.measure(c).runtime_ms for c in cfgs])
        scalar_pw = np.array([scalar_sim.measure(c).power_w for c in cfgs])
        # same noise law, different draw order: compare noise distributions
        # relative to the shared noise-free oracle
        oracle = TpuGemmSimulator(seed=0).analyze_batch(cfgs)
        ratio_batch = batch["runtime_ms"] / oracle["runtime_ms"]
        ratio_scalar = scalar_rt / oracle["runtime_ms"]
        assert abs(np.median(ratio_batch) - np.median(ratio_scalar)) < 0.01
        assert abs(np.std(np.log(ratio_batch))
                   - np.std(np.log(ratio_scalar))) < 0.015
        dp_batch = batch["power_w"] - oracle["power_w"]
        dp_scalar = scalar_pw - oracle["power_w"]
        assert abs(np.mean(dp_batch) - np.mean(dp_scalar)) < 1.5

    def test_measure_batch_thermal_state_walks(self):
        sim = TpuGemmSimulator(seed=0)
        hot = [GemmConfig(8192, 8192, 8192, 256, 256, 512)] * 50
        out = sim.measure_batch(hot)
        assert out["temperature_c"][-1] > out["temperature_c"][0]
        assert sim._temp_c == pytest.approx(out["temperature_c"][-1])

    @pytest.mark.parametrize("chip", CHIPS)
    def test_config_features_batch_matches_scalar(self, chip):
        cfgs = _sample_configs(n=100, seed=3)
        cols = config_features_batch(cfgs, chip=chip)
        assert set(cols) >= set(NUMERIC_FEATURES)
        for i, cfg in enumerate(cfgs[:40]):
            want = config_features(cfg, chip=chip)
            for key in NUMERIC_FEATURES:
                assert float(cols[key][i]) == want[key], (key, cfg)


class TestChipRegistry:
    def test_known_chips(self):
        assert set(available_chips()) >= {"tpu_v5e", "rtx4070"}
        assert get_chip("tpu_v5e") is TPU_V5E
        assert get_chip("rtx4070") is RTX_4070
        assert get_chip("rtx_4070") is RTX_4070  # alias
        assert get_chip(RTX_4070) is RTX_4070    # pass-through

    def test_unknown_chip_raises(self):
        with pytest.raises(ValueError, match="unknown chip"):
            get_chip("h100")

    def test_device_kind_resolves_or_raises(self):
        assert chip_for_device_kind("TPU v5 lite") is TPU_V5E
        with pytest.raises(ValueError, match="no chip spec"):
            chip_for_device_kind("TPU v4")

    def test_rtx4070_spec_matches_paper(self):
        assert RTX_4070.ridge_point("f32") == pytest.approx(57.8, rel=0.02)
        assert 80.0 <= RTX_4070.idle_power_w <= 100.0
        assert RTX_4070.tdp_w == 200.0
        assert RTX_4070.n_compute_units == 46
        assert RTX_4070.vmem_bytes == 48 * 2**10 * 46

    def test_rtx4070_roofline_split_plausible(self):
        """Big well-blocked GEMMs are compute-bound, skinny ones
        memory-bound, on the paper's chip."""
        sim = TpuGemmSimulator(chip="rtx4070", seed=0)
        big = sim.analyze(GemmConfig(4096, 4096, 4096, 128, 256, 512))
        skinny = sim.analyze(GemmConfig(16, 4096, 4096, 16, 256, 512))
        assert big.valid and big.bound == "compute"
        assert skinny.valid and skinny.bound == "memory"
        assert RTX_4070.idle_power_w <= big.power_w <= RTX_4070.tdp_w

    def test_sweep_produces_both_bounds_per_chip(self):
        for chip in CHIPS:
            table = collect_dataset(n_configs=400, seed=2, chip=chip)
            bounds = set(str(b) for b in table["bound"])
            assert {"compute", "memory"} <= bounds, (chip, bounds)


class TestCrossChipPipeline:
    @pytest.mark.parametrize("chip", CHIPS)
    def test_profile_fit_tune_roundtrip(self, chip, tmp_path):
        table = collect_dataset(n_configs=600, seed=1, chip=chip)
        pred = PerfPredictor(model="rf", residual=True, fast=True,
                             chip=chip).fit(table)
        assert pred.chip_name == chip
        tuner = GemmAutotuner(pred, chip=chip,
                              cache_path=str(tmp_path / f"{chip}.json"))
        assert tuner.chip.name == get_chip(chip).name
        best = tuner.best_config(2048, 2048, 2048)
        assert tuner.sim.analyze(
            GemmConfig(2048, 2048, 2048, best.block_m, best.block_n,
                       best.block_k)).valid
        rep = tuner.tune_report(4096, 4096, 4096)
        assert rep["chip"] == get_chip(chip).name
        assert rep["speedup"] > 0.9

    def test_chips_disagree_on_telemetry(self):
        """The same config must measure differently across substrates —
        otherwise per-chip datasets/predictors are pointless."""
        cfg = GemmConfig(4096, 4096, 4096, 128, 256, 512)
        v5e = TpuGemmSimulator(chip="tpu_v5e", seed=0).analyze(cfg)
        ada = TpuGemmSimulator(chip="rtx4070", seed=0).analyze(cfg)
        assert ada.runtime_ms > 2 * v5e.runtime_ms  # ~7x peak-FLOPs gap
        assert ada.power_w != v5e.power_w

    def test_build_default_predictor_per_chip_artifacts(self, tmp_path):
        art = str(tmp_path)
        p1 = build_default_predictor(art, n_train=300, chip="tpu_v5e")
        p2 = build_default_predictor(art, n_train=300, chip="rtx4070")
        assert (tmp_path / "perf_predictor_tpu_v5e.npz").exists()
        assert (tmp_path / "perf_predictor_rtx4070.npz").exists()
        assert p1.chip_name == "tpu_v5e"
        assert p2.chip_name == "rtx4070"
        # reload path hits the per-chip artifact, not a retrain
        p1b = build_default_predictor(art, n_train=300, chip="tpu_v5e")
        assert p1b.chip_name == "tpu_v5e"


class TestBatchProfilerSpeed:
    @pytest.mark.slow
    def test_batch_collect_faster_than_scalar_loop(self):
        """Acceptance: the batched sweep is >=5x the per-config loop."""
        import time

        cfgs = sweep_configs(n_configs=2000, seed=0)
        sim_b = TpuGemmSimulator(seed=0)
        t0 = time.perf_counter()
        profile_configs(cfgs, sim_b)
        batch_s = time.perf_counter() - t0

        sim_s = TpuGemmSimulator(seed=0)
        t0 = time.perf_counter()
        profile_configs(cfgs, sim_s, measure_fn=sim_s.measure)
        scalar_s = time.perf_counter() - t0
        assert scalar_s > 5 * batch_s, (scalar_s, batch_s)

    def test_measure_fn_override_still_supported(self):
        """Real-hardware path: a per-config callable drives the profiler."""
        sim = TpuGemmSimulator(seed=0)
        calls = []

        def fake_hw(cfg):
            calls.append(cfg)
            tel = sim.analyze(cfg)
            return dataclasses.replace(tel, runtime_ms=tel.runtime_ms * 2)

        cfgs = sweep_configs(n_configs=30, seed=0)
        table = profile_configs(cfgs, sim, measure_fn=fake_hw)
        assert len(calls) == 30
        oracle = TpuGemmSimulator(seed=0).analyze_batch(cfgs)
        np.testing.assert_allclose(table["runtime_ms"],
                                   2 * oracle["runtime_ms"][oracle["valid"]])
