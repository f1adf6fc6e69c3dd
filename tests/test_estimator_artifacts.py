"""Tests for the versioned, jit-served estimator layer:

  * pickle-free artifact round-trips (all five model names, both chips);
  * load-time rejection of tampered schemas/arrays and legacy pickles;
  * vectorized stacked-descent prediction == per-tree-loop parity;
  * chip-derived anchor power (no hardcoded 130 W);
  * tuner: batched tune_many, fingerprint-keyed winner cache, cached
    BASELINE fallback, and rank latency vs the pre-refactor loop path.
"""

import json
import pickle
import time

import numpy as np
import pytest

from repro.core.autotuner import BASELINE, GemmAutotuner
from repro.core.chips import get_chip
from repro.core.features import features_matrix, table_from_configs
from repro.core.hwsim import TpuGemmSimulator
from repro.core.predictor import (
    ARTIFACT_SCHEMA_VERSION,
    MODEL_NAMES,
    ArtifactError,
    PerfPredictor,
)
from repro.core.profiler import collect_dataset, sweep_configs

CHIPS = ("tpu_v5e", "rtx4070")


@pytest.fixture(scope="module")
def tables():
    return {chip: collect_dataset(n_configs=800, seed=0, chip=chip)
            for chip in CHIPS}


@pytest.fixture(scope="module")
def rf_pred(tables):
    return PerfPredictor(model="rf", residual=True, fast=True,
                         chip="tpu_v5e").fit(tables["tpu_v5e"])


def _tamper(path, mutate):
    """Rewrite an artifact after applying `mutate(meta, arrays)`."""
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(str(arrays.pop("__meta__")[()]))
    mutate(meta, arrays)
    with open(path, "wb") as f:
        np.savez_compressed(f, __meta__=np.array(json.dumps(meta)), **arrays)


class TestArtifactRoundTrip:
    @pytest.mark.parametrize("chip", CHIPS)
    @pytest.mark.parametrize("model", MODEL_NAMES)
    def test_save_load_predict_parity(self, model, chip, tables, tmp_path):
        pred = PerfPredictor(model=model, residual=True, fast=True,
                             chip=chip).fit(tables[chip])
        path = str(tmp_path / f"{model}_{chip}.npz")
        pred.save(path)
        back = PerfPredictor.load(path)
        assert back.model_name == model
        assert back.chip_name == chip
        assert back.nominal_power_w == get_chip(chip).nominal_power_w
        assert back.fingerprint() == pred.fingerprint()
        np.testing.assert_allclose(back.predict_matrix(tables[chip]),
                                   pred.predict_matrix(tables[chip]),
                                   rtol=1e-12)

    def test_no_pickle_in_predictor_module(self):
        import repro.core.predictor as mod

        src = open(mod.__file__).read()
        assert "import pickle" not in src
        assert "pickle.load" not in src
        assert "pickle.dump" not in src

    def test_artifact_loads_without_pickle_support(self, rf_pred, tmp_path):
        """np.load(allow_pickle=False) must be sufficient: no object
        arrays anywhere in the artifact."""
        path = str(tmp_path / "a.npz")
        rf_pred.save(path)
        with np.load(path, allow_pickle=False) as z:
            for k in z.files:
                assert z[k].dtype != object, k


class TestArtifactValidation:
    def test_tampered_feature_schema_rejected(self, rf_pred, tmp_path):
        path = str(tmp_path / "a.npz")
        rf_pred.save(path)

        def drop_feature(meta, arrays):
            meta["feature_names"] = meta["feature_names"][:-1]

        _tamper(path, drop_feature)
        with pytest.raises(ArtifactError, match="feature schema"):
            PerfPredictor.load(path)

    def test_tampered_arrays_rejected(self, rf_pred, tmp_path):
        path = str(tmp_path / "a.npz")
        rf_pred.save(path)

        def poison_threshold(meta, arrays):
            key = "model/threshold"
            arrays[key] = arrays[key] * 1.5

        _tamper(path, poison_threshold)
        with pytest.raises(ArtifactError, match="fingerprint"):
            PerfPredictor.load(path)

    def test_wrong_schema_version_rejected(self, rf_pred, tmp_path):
        path = str(tmp_path / "a.npz")
        rf_pred.save(path)
        _tamper(path, lambda meta, arrays: meta.update(
            schema_version=ARTIFACT_SCHEMA_VERSION + 1,
        ))
        with pytest.raises(ArtifactError, match="schema version"):
            PerfPredictor.load(path)

    def test_old_schema_without_upgrader_rejected(self, rf_pred, tmp_path):
        path = str(tmp_path / "a.npz")
        rf_pred.save(path)
        _tamper(path, lambda meta, arrays: meta.update(schema_version=0))
        with pytest.raises(ArtifactError, match="no upgrade path"):
            PerfPredictor.load(path)

    def test_old_schema_loads_through_registered_upgrader(
            self, rf_pred, tmp_path, tables):
        """The v(N-1) -> v(N) migration story: an artifact one schema
        behind loads through its registered upgrader — including one that
        rewrites arrays, provided it restamps the fingerprint."""
        from repro.core.predictor import (
            _SCHEMA_UPGRADERS,
            artifact_fingerprint,
        )

        path = str(tmp_path / "a.npz")
        rf_pred.save(path)
        # simulate a legacy artifact: old version tag + a renamed array
        # key the upgrader must translate back
        _tamper(path, lambda meta, arrays: (
            meta.update(schema_version=0),
            arrays.update(legacy_marker=np.zeros(1))))

        def upgrade(meta, state):
            state = dict(state)
            state.pop("legacy_marker")
            meta = dict(meta, schema_version=1,
                        fingerprint=artifact_fingerprint(meta, state))
            return meta, state

        _SCHEMA_UPGRADERS[0] = upgrade
        try:
            loaded = PerfPredictor.load(path)
        finally:
            del _SCHEMA_UPGRADERS[0]
        te = tables["tpu_v5e"]
        np.testing.assert_allclose(loaded.predict_matrix(te),
                                   rf_pred.predict_matrix(te), rtol=1e-12)

    def test_legacy_pickle_rejected(self, rf_pred, tmp_path):
        path = str(tmp_path / "legacy.pkl")
        with open(path, "wb") as f:
            pickle.dump({"anything": 1}, f)
        with pytest.raises(ArtifactError):
            PerfPredictor.load(path)

    def test_build_default_predictor_retrains_over_bad_artifact(
            self, tmp_path):
        from repro.core.autotuner import build_default_predictor

        art = str(tmp_path)
        bad = tmp_path / "perf_predictor_tpu_v5e.npz"
        bad.write_bytes(b"not an artifact")
        pred = build_default_predictor(art, n_train=300, chip="tpu_v5e")
        assert pred.chip_name == "tpu_v5e"
        # the retrain overwrote the corrupt file with a loadable artifact
        assert PerfPredictor.load(str(bad)).fingerprint() == pred.fingerprint()


class TestVectorizedPredict:
    def test_forest_stacked_equals_per_tree_loop(self, rf_pred, tables):
        X = rf_pred.scaler.transform(
            np.stack([tables["tpu_v5e"][k] for k in rf_pred.feature_names],
                     axis=1))
        np.testing.assert_allclose(rf_pred.model.predict(X),
                                   rf_pred.model.predict_per_tree_loop(X),
                                   rtol=1e-12)

    def test_gbdt_stacked_equals_per_tree_loop(self, tables):
        pred = PerfPredictor(model="gbdt", residual=True, fast=True,
                             chip="tpu_v5e").fit(tables["tpu_v5e"])
        X = pred.scaler.transform(
            np.stack([tables["tpu_v5e"][k] for k in pred.feature_names],
                     axis=1))
        np.testing.assert_allclose(pred.model.predict(X),
                                   pred.model.predict_per_tree_loop(X),
                                   rtol=1e-10)

    def test_x64_jit_scorer_matches_numpy(self, rf_pred, tables):
        table = {k: v[:200] for k, v in tables["tpu_v5e"].items()}
        X = np.stack([table[k] for k in rf_pred.feature_names], axis=1)
        got = np.asarray(rf_pred.jax_predictor(x64=True)(X))
        want = rf_pred.predict_matrix(table)
        np.testing.assert_allclose(got, want, rtol=1e-9)

    def test_jax_predictor_cached_per_precision(self, rf_pred):
        assert rf_pred.jax_predictor(x64=True) is rf_pred.jax_predictor(x64=True)
        assert rf_pred.jax_predictor() is rf_pred.jax_predictor()
        assert rf_pred.jax_predictor() is not rf_pred.jax_predictor(x64=True)


class TestChipAnchors:
    def test_nominal_power_follows_chip(self):
        assert PerfPredictor(chip="tpu_v5e").nominal_power_w == 130.0
        assert PerfPredictor(chip="rtx4070").nominal_power_w == 142.5
        assert PerfPredictor().nominal_power_w == 130.0  # default chip

    def test_energy_anchor_uses_chip_power(self, tables):
        table = tables["rtx4070"]
        p_ada = PerfPredictor(chip="rtx4070")
        p_tpu = PerfPredictor(chip="tpu_v5e")
        a_ada = p_ada._anchors(table)["energy_j"]
        a_tpu = p_tpu._anchors(table)["energy_j"]
        np.testing.assert_allclose(a_ada / a_tpu, 142.5 / 130.0)


class TestTunerServing:
    @pytest.fixture(scope="class")
    def tuner(self, rf_pred):
        return GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3))

    def test_tune_many_matches_cached_best_config(self, tuner):
        shapes = [(1024, 1024, 1024), (4096, 4096, 1024), (16, 2048, 2048)]
        fleet = tuner.tune_many(shapes)
        assert len(fleet) == len(shapes)
        for s, cfg in zip(shapes, fleet):
            assert tuner.best_config(*s) == cfg

    def test_empty_candidates_fallback_cached(self, rf_pred):
        tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3))
        calls = []
        orig = tuner.candidate_configs
        tuner.candidate_configs = lambda *a, **k: (calls.append(a), [])[1]
        assert tuner.best_config(3, 3, 3) == BASELINE
        assert tuner.best_config(3, 3, 3) == BASELINE
        assert len(calls) == 1, "BASELINE fallback must be cached"
        tuner.candidate_configs = orig

    def test_winner_cache_keyed_by_artifact_fingerprint(
            self, rf_pred, tables, tmp_path):
        cache = str(tmp_path / "cache.json")
        t1 = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                           cache_path=cache)
        t1.best_config(2048, 2048, 2048)
        # same artifact -> winners survive
        t2 = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                           cache_path=cache)
        assert t2._cache
        # retrained artifact -> stale winners discarded
        retrained = PerfPredictor(model="rf", residual=True, fast=True,
                                  chip="tpu_v5e",
                                  random_state=9).fit(tables["tpu_v5e"])
        assert retrained.fingerprint() != rf_pred.fingerprint()
        t3 = GemmAutotuner(retrained, TpuGemmSimulator(seed=3),
                           cache_path=cache)
        assert t3._cache == {}

    def test_trace_dtype_strings_canonicalized(self, tuner):
        """ops.matmul keys tuning by str(a.dtype) ("bfloat16"); the tuner
        must resolve that to the substrate's dtype grid, not crash."""
        cfg = tuner.best_config(512, 512, 512, dtype="bfloat16")
        assert cfg == tuner.best_config(512, 512, 512, dtype="bf16")

    def test_rank_parity_both_scorers(self, rf_pred):
        cfgs = sweep_configs(n_configs=512, seed=1)
        ref = rf_pred.predict_matrix_reference(table_from_configs(cfgs))
        for scorer in ("numpy", "jit"):
            tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                                  scorer=scorer)
            X = features_matrix(cfgs, chip=tuner.chip)
            got = tuner._predict_features(X)
            rel = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-12)
            assert rel.max() < 1e-4, (scorer, rel.max())

    @pytest.mark.slow
    def test_rank_512_beats_per_tree_loop(self, rf_pred):
        """The refactored rank path (cached candidate features + stacked
        descent) vs the pre-refactor path (per-call table build + per-tree
        loop). Quiet-machine ratio is ~5-6x (see benchmarks/rank_smoke.py
        and bench_autotune); assert 4x best-of-interleaved so CI noise
        can't flake the suite."""
        tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3))
        cfgs = sweep_configs(n_configs=512, seed=1)
        X = features_matrix(cfgs, chip=tuner.chip)

        def rank_new():
            return tuner.rank(cfgs, features=X)

        def rank_reference():
            t = table_from_configs(cfgs, chip=tuner.chip)
            # stable, matching rank()'s deterministic tie-break
            return np.argsort(rf_pred.predict_matrix_reference(t)[:, 0],
                              kind="stable")

        rank_new(), rank_reference()
        t_new, t_ref = [], []
        for _ in range(20):  # interleaved so load spikes hit both paths
            t0 = time.perf_counter()
            rank_new()
            t_new.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            rank_reference()
            t_ref.append(time.perf_counter() - t0)
        assert min(t_ref) > 4.0 * min(t_new), (min(t_ref), min(t_new))
        np.testing.assert_array_equal(rank_new(), rank_reference())


class TestWinnerCacheLRU:
    def _shape(self, i):
        return (128 * (i + 1), 256, 512)

    def test_memory_eviction_lru_order(self, rf_pred, tmp_path):
        tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                              winner_cache_size=4)
        for i in range(6):
            tuner.best_config(*self._shape(i))
        assert len(tuner._cache) == 4
        # oldest two evicted, newest four retained
        keys = list(tuner._cache)
        assert keys == [tuner._key(*self._shape(i), "bf16", "runtime")
                        for i in range(2, 6)]

    def test_hit_refreshes_recency(self, rf_pred):
        tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                              winner_cache_size=2)
        a, b, c = self._shape(0), self._shape(1), self._shape(2)
        tuner.best_config(*a)
        tuner.best_config(*b)
        tuner.best_config(*a)      # refresh a: b becomes the LRU entry
        tuner.best_config(*c)      # evicts b, not a
        keys = set(tuner._cache)
        assert tuner._key(*a, "bf16", "runtime") in keys
        assert tuner._key(*b, "bf16", "runtime") not in keys

    def test_sidecar_bounded_and_reloadable(self, rf_pred, tmp_path):
        cache = str(tmp_path / "cache.json")
        tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                              cache_path=cache, winner_cache_size=3)
        for i in range(6):
            tuner.best_config(*self._shape(i))
        with open(cache) as f:
            payload = json.load(f)
        assert len(payload["entries"]) == 3  # sidecar stays bounded

        # reload: entries survive in order, and a tighter bound trims the
        # oldest on load
        t2 = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                           cache_path=cache, winner_cache_size=3)
        assert list(t2._cache) == list(payload["entries"])
        t3 = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3),
                           cache_path=cache, winner_cache_size=2)
        assert list(t3._cache) == list(payload["entries"])[-2:]


class TestMeasureFn:
    """`tune_many(measure_fn=...)`: the wall-clock verification hook."""

    def test_fake_clock_overrides_simulator(self, rf_pred):
        tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3))
        tuner.sim.measure_batch = lambda *a, **k: pytest.fail(
            "simulator must not measure when measure_fn is given")
        calls = []

        def fake_clock(cfgs):
            calls.append(list(cfgs))
            n = len(cfgs)
            # the "clock" says the LAST verified candidate is fastest
            rt = np.arange(n, 0, -1, dtype=np.float64)
            return {"runtime_ms": rt, "power_w": np.full(n, 100.0),
                    "energy_j": rt * 0.1}

        best = tuner.best_config(1024, 1024, 1024, measure_fn=fake_clock)
        assert len(calls) == 1
        assert 1 <= len(calls[0]) <= tuner.verify_top_k
        w = calls[0][-1]
        assert best.as_tuple() == (w.block_m, w.block_n, w.block_k)

    def test_fake_clock_winner_cached(self, rf_pred):
        tuner = GemmAutotuner(rf_pred, TpuGemmSimulator(seed=3))
        seen = []

        def fake_clock(cfgs):
            seen.append(len(cfgs))
            n = len(cfgs)
            rt = np.arange(1, n + 1, dtype=np.float64)
            return {"runtime_ms": rt, "power_w": np.full(n, 90.0),
                    "energy_j": rt}

        a = tuner.best_config(512, 512, 512, measure_fn=fake_clock)
        b = tuner.best_config(512, 512, 512, measure_fn=fake_clock)
        assert a == b
        assert len(seen) == 1, "cached winner must not re-measure"


class TestWarmGemmCache:
    def test_warm_primes_trace_time_cache(self, rf_pred):
        from repro.core import autotuner as at
        from repro.kernels import ops

        at.set_tuner(GemmAutotuner(rf_pred, TpuGemmSimulator(seed=0)))
        ops._tuned_config.cache_clear()
        try:
            shapes = [(256, 512, 1024), (128, 256, 512)]
            out = ops.warm_gemm_cache(shapes, dtype="bfloat16")
            assert set(out) == set(shapes)
            for (m, n, k), cfg in out.items():
                assert ops._tuned_config(
                    m, n, k, "bfloat16", "runtime", "tpu_v5e") == cfg
        finally:
            at.set_tuner(None)
            ops._tuned_config.cache_clear()

    def test_tuner_failure_raises_through_matmul(self):
        """A tuner that cannot rank must stop the trace, not hand the
        kernel an untuned DEFAULT_CONFIG."""
        import jax.numpy as jnp

        from repro.core import autotuner as at
        from repro.kernels import ops

        class Broken:
            chip = get_chip("tpu_v5e")

            def best_config(self, *a, **kw):
                raise RuntimeError("predictor unavailable")

            tune_many = best_config

        at.set_tuner(Broken(), chip="tpu_v5e")
        ops._tuned_config.cache_clear()
        ops.force_mode("pallas_interpret")
        try:
            a = jnp.ones((24, 136), jnp.float32)
            b = jnp.ones((136, 40), jnp.float32)
            with pytest.raises(RuntimeError, match="predictor unavailable"):
                ops.matmul(a, b)
            with pytest.raises(RuntimeError, match="predictor unavailable"):
                ops.warm_gemm_cache([(24, 40, 136)], dtype="float32")
        finally:
            ops.force_mode("auto")
            at.set_tuner(None)
            ops._tuned_config.cache_clear()
