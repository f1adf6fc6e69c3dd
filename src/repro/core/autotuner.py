"""Predictor-guided GEMM block-config autotuner — the paper's payoff.

For a GEMM shape (m, n, k, dtype), enumerate VMEM-valid Pallas block configs,
rank them with the trained multi-output predictor (one batched model call),
verify the top-k against the measurement substrate, and cache the winner.
Objectives mirror the paper's findings: "runtime" (3.2x speedup claim),
"energy"/"power" (22% power-reduction claim), "edp" (energy-delay product).

Prediction is the serving hot path, so `rank()` runs through a compiled
scorer: every estimator family in the zoo (forest, GBDT, linreg/ridge,
stacking) scores via the cached x64 jit path (bit-identical accumulations
vs numpy, one XLA call for the whole candidate grid), and the candidate
list + feature table for each (shape, dtype) bucket is computed once and
cached. `rank_in_graph()` goes further: the candidate feature grid is built
with jnp ops and argmin'd *inside* `jax.jit`, with the GEMM extents as
traced values — zero Python in the ranking loop and no retrace per shape —
which `tune_many()` uses by default on accelerator backends. `tune_many()`
tunes a whole fleet of shapes with one scorer call and one batched
verification sweep (optionally through a wall-clock `measure_fn` for
on-device tuning). The winner cache (in memory and the JSON sidecar) is
keyed by the predictor's artifact fingerprint and LRU-bounded, so
retraining invalidates stale winners and long-lived processes can't grow
the sidecar without limit.

Everything is chip-aware: the tuner's candidate filter, feature builder, and
verification all run against the chip backing its simulator, and predictor
artifacts plus tuner caches are keyed per chip so "tpu_v5e" and "rtx4070"
tuners coexist.

`get_tuner(chip=...)` is the per-chip process-wide singleton consulted by
`kernels.ops.matmul` at trace time. On first use it loads (or trains and
persists) the predictor artifact under artifacts/.
"""

from __future__ import annotations

import json
import math
import os
import threading
from collections import OrderedDict
from collections.abc import Sequence

import numpy as np

from repro.core.chips import DTYPE_BYTES, TPU_V5E, ChipSpec, canon_dtype, get_chip
from repro.core.features import features_matrix, graph_candidate_features
from repro.core.hwsim import VMEM_USABLE_FRACTION, GemmConfig, TpuGemmSimulator
from repro.core.mlperf.compiled import precision_scope
from repro.core.predictor import ArtifactError, PerfPredictor
from repro.kernels.tiled_matmul import BlockConfig

_BM = (8, 16, 32, 64, 128, 256, 512, 1024)
_BN = (128, 256, 512, 1024)
_BK = (128, 256, 512, 1024, 2048)

DEFAULT_ARTIFACTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), "artifacts")
BASELINE = BlockConfig(128, 128, 128)  # untuned default (paper's baseline)

_CACHE_FILE_VERSION = 1


def baseline_configs(shapes) -> dict[tuple, BlockConfig]:
    """Map every (m, n, k) shape to the paper's BASELINE block config —
    the degraded-mode tuning table the serving engine installs when a
    predictor artifact is corrupt (`ServingEngine.retune`): pricing and
    scheduling keep working on the untuned baseline instead of raising
    mid-serve, and the fallback is explicit in reports rather than an
    absent-config default."""
    return {tuple(int(x) for x in s): BASELINE for s in shapes}


def _roundup(x: int, q: int) -> int:
    return max(q, math.ceil(x / q) * q)


def _next_pow2(n: int) -> int:
    return 1 << max(3, (n - 1).bit_length())


class GemmAutotuner:
    def __init__(
        self,
        predictor: PerfPredictor,
        sim: TpuGemmSimulator | None = None,
        verify_top_k: int = 3,
        cache_path: str | None = None,
        chip: ChipSpec | str | None = None,
        candidate_cache_size: int = 512,
        scorer: str = "auto",
        winner_cache_size: int = 4096,
    ):
        """`scorer` selects the batched prediction path for `rank`:
        "jit" (the cached x64 jax_predictor — one XLA call per candidate
        grid), "numpy" (the vectorized stacked-descent estimator), or
        "auto" (jit on accelerator backends; numpy on CPU, where per-call
        XLA dispatch overhead exceeds the descent itself at candidate-grid
        sizes). Both paths predict within 1e-9 relative of each other.

        `winner_cache_size` bounds the tuned-winner cache (memory + JSON
        sidecar) with LRU eviction, mirroring the candidate-table cache,
        so a long-lived serving process sweeping many shapes can't grow
        the sidecar unboundedly.
        """
        self.predictor = predictor
        self.sim = sim or TpuGemmSimulator(
            chip=chip if chip is not None else TPU_V5E, seed=0)
        self.chip = self.sim.chip
        self.verify_top_k = verify_top_k
        self.cache_path = cache_path
        if scorer not in ("auto", "jit", "numpy"):
            raise ValueError(f"unknown scorer {scorer!r}")
        self.scorer = scorer
        self.artifact_fingerprint = predictor.fingerprint()
        self._winner_cache_size = winner_cache_size
        self._cache: OrderedDict[str, tuple[int, int, int]] = OrderedDict()
        # in-graph ranking state: static candidate block grid, jitted
        # rankers keyed by (objective, x64, k), device-resident predictor
        # params per precision, and a trace counter (tests assert no
        # retrace across shape fleets).
        self._graph_block_grid: np.ndarray | None = None
        self._graph_fns: dict = {}
        self._graph_params: dict = {}
        self.graph_traces = 0
        # (m, n, k, dtype) -> (candidate configs, feature table) — one bucket
        # per GEMM-call signature on this tuner's (chip, dtype) grid.
        self._cand_cache: OrderedDict[
            tuple[int, int, int, str], tuple[list[GemmConfig], np.ndarray]
        ] = OrderedDict()
        self._cand_cache_size = candidate_cache_size
        self._lock = threading.Lock()
        # (m, n, k, dtype) shapes no candidate block fits: tuned to
        # BASELINE instead of a predictor pick (serving checks this stays
        # empty)
        self.fallback_shapes: set[tuple[int, int, int, str]] = set()
        if cache_path and os.path.exists(cache_path):
            self._cache = self._load_cache_file(cache_path)

    # ---------- winner cache (fingerprint-versioned, LRU-bounded) ----------
    def _load_cache_file(self, path: str
                         ) -> "OrderedDict[str, tuple[int, int, int]]":
        """Read the winner sidecar; discard it when it predates the current
        artifact (or the pre-versioned flat format). Entries keep their
        file order (oldest first) and are trimmed to the LRU bound."""
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            return OrderedDict()
        if (not isinstance(payload, dict)
                or payload.get("cache_version") != _CACHE_FILE_VERSION
                or payload.get("artifact_fingerprint")
                != self.artifact_fingerprint):
            return OrderedDict()
        entries = OrderedDict(
            (k, tuple(v)) for k, v in payload.get("entries", {}).items())
        while len(entries) > self._winner_cache_size:
            entries.popitem(last=False)
        return entries

    def _cache_get(self, key: str) -> tuple[int, int, int] | None:
        """LRU lookup (caller holds self._lock)."""
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
        return hit

    def _cache_put(self, key: str, val: tuple[int, int, int]) -> None:
        """LRU insert + eviction (caller holds self._lock)."""
        self._cache[key] = val
        self._cache.move_to_end(key)
        while len(self._cache) > self._winner_cache_size:
            self._cache.popitem(last=False)

    def _write_cache_locked(self) -> None:
        """Persist the winner cache (caller holds self._lock)."""
        if not self.cache_path:
            return
        os.makedirs(os.path.dirname(self.cache_path) or ".", exist_ok=True)
        with open(self.cache_path, "w") as f:
            json.dump({
                "cache_version": _CACHE_FILE_VERSION,
                "artifact_fingerprint": self.artifact_fingerprint,
                "chip": self.chip.name,
                "entries": self._cache,
            }, f, indent=0)

    # ---------- candidates ----------
    def candidate_configs(self, m: int, n: int, k: int,
                          dtype: str = "bf16") -> list[GemmConfig]:
        """VMEM-valid blocks, clipped to the (padded) problem extents."""
        dtype = canon_dtype(dtype)
        bm_cap = _roundup(m, 8)
        bn_cap = _roundup(n, 128)
        bk_cap = _roundup(k, 128)
        cand = [
            GemmConfig(m=m, n=n, k=k, block_m=bm, block_n=bn, block_k=bk,
                       dtype=dtype)
            for bm in _BM if bm <= bm_cap * 2
            for bn in _BN if bn <= bn_cap * 2
            for bk in _BK if bk <= bk_cap * 2
        ]
        if not cand:
            return []
        valid = self.sim.analyze_batch(cand)["valid"]
        return [cfg for cfg, ok in zip(cand, valid) if ok]

    def candidate_table(self, m: int, n: int, k: int, dtype: str
                             ) -> tuple[list[GemmConfig], np.ndarray]:
        """Candidate list + precomputed feature table for one shape bucket
        (LRU-cached: the grid is static per (chip, dtype), so repeat calls
        — cache misses after retraining, other objectives — skip both the
        validity filter and feature building)."""
        dtype = canon_dtype(dtype)
        key = (m, n, k, dtype)
        with self._lock:
            hit = self._cand_cache.get(key)
            if hit is not None:
                self._cand_cache.move_to_end(key)
                return hit
        cfgs = self.candidate_configs(m, n, k, dtype)
        X = (features_matrix(cfgs, chip=self.chip) if cfgs
             else np.zeros((0, len(self.predictor.feature_names))))
        with self._lock:
            self._cand_cache[key] = (cfgs, X)
            self._cand_cache.move_to_end(key)
            while len(self._cand_cache) > self._cand_cache_size:
                self._cand_cache.popitem(last=False)
        return cfgs, X

    # ---------- scoring ----------
    @staticmethod
    def _objective_scores(pred: dict[str, np.ndarray], objective: str
                          ) -> np.ndarray:
        if objective == "runtime":
            return pred["runtime_ms"]
        if objective in ("energy", "power"):
            return pred["energy_j"] if objective == "energy" else pred["power_w"]
        if objective == "edp":
            return pred["energy_j"] * pred["runtime_ms"]
        raise ValueError(f"unknown objective {objective!r}")

    def _use_jit_scorer(self) -> bool:
        if not self.predictor.supports_jax():
            return False
        if self.scorer != "auto":
            return self.scorer == "jit"
        import jax

        return jax.default_backend() != "cpu"

    def _predict_features(self, X: np.ndarray) -> np.ndarray:
        """(N, F) raw features -> (N, T) predictions via the compiled x64
        scorer (forest models on accelerators) or the vectorized
        stacked-descent estimator — see the `scorer` constructor arg.

        The jit path pads the batch to the next power of two so XLA
        compiles one kernel per size bucket instead of one per candidate
        count."""
        if self._use_jit_scorer():
            fn = self.predictor.jax_predictor(x64=True)
            n = len(X)
            pad = _next_pow2(n)
            if pad != n:
                X = np.concatenate([X, np.tile(X[-1:], (pad - n, 1))])
            return np.asarray(fn(X))[:n]
        table = {name: X[:, i]
                 for i, name in enumerate(self.predictor.feature_names)}
        return self.predictor.predict_matrix(table)

    def _scores_from_matrix(self, Y: np.ndarray, objective: str) -> np.ndarray:
        idx = {t: i for i, t in enumerate(self.predictor.target_names)}
        pred = {t: Y[:, i] for t, i in idx.items()}
        return self._objective_scores(pred, objective)

    def rank(self, cfgs: Sequence[GemmConfig], objective: str = "runtime",
             features: np.ndarray | None = None) -> np.ndarray:
        """Ascending-score candidate order from one batched scorer call."""
        X = (features if features is not None
             else features_matrix(cfgs, chip=self.chip))
        Y = self._predict_features(X)
        # stable: coarse tree predictors tie often, and in-graph top-k
        # breaks ties by index — keep both paths' orders identical.
        return np.argsort(self._scores_from_matrix(Y, objective),
                          kind="stable")

    # ---------- fully in-graph ranking ----------
    def _graph_blocks(self) -> np.ndarray:
        """The static (C, 3) candidate block grid. Shape-dependent pruning
        (extent clipping, VMEM fit) happens in-graph via the validity
        mask, so one compiled ranker serves every GEMM shape."""
        if self._graph_block_grid is None:
            self._graph_block_grid = np.array(
                [(bm, bn, bk) for bm in _BM for bn in _BN for bk in _BK],
                dtype=np.int64)
        return self._graph_block_grid

    def _graph_consts(self, dtype: str) -> dict[str, np.ndarray]:
        """Chip/dtype scalars for `graph_candidate_features`, as 0-d
        arrays: traced (not baked) so XLA can't constant-fold them into
        reciprocal multiplies that drift vs the numpy feature builder."""
        c = self.chip
        return {
            "peak": np.asarray(c.peak_flops[dtype]),
            "hbm_bw": np.asarray(c.hbm_bw),
            "vmem_usable": np.asarray(c.vmem_bytes * VMEM_USABLE_FRACTION),
            "mxu": np.asarray(c.mxu_dim, dtype=np.int64),
            "dtype_bytes": np.asarray(int(DTYPE_BYTES[dtype]),
                                      dtype=np.int64),
            "step_cost": np.asarray(1e-7),
        }

    def _graph_rank_fn(self, objective: str, x64: bool, top_k: int):
        """Build (once per (objective, precision, k)) the jitted ranker:
        feature grid -> scale -> compiled predictor -> decode -> objective
        -> masked top-k, all in one XLA program."""
        key = (objective, x64, top_k)
        hit = self._graph_fns.get(key)
        if hit is not None:
            return hit
        import jax
        import jax.numpy as jnp

        # validate the objective before baking it into a trace
        self._objective_scores(
            {t: np.zeros(1) for t in ("runtime_ms", "power_w", "energy_j")},
            objective)
        # lower + upload once per precision; extra (objective, k) variants
        # only re-trace the thin ranker around the shared apply/params
        cached = self._graph_params.get(x64)
        if cached is None:
            params, apply = self.predictor.jax_components(x64=x64)
            with precision_scope(x64):
                cached = (jax.tree.map(jnp.asarray, params), apply)
            self._graph_params[x64] = cached
        device_params, apply = cached
        t_idx = {t: i for i, t in enumerate(self.predictor.target_names)}
        tuner = self

        def ranker(mnk, blocks, consts, mean, scale, pparams):
            tuner.graph_traces += 1  # python side effect: counts traces
            feats, valid = graph_candidate_features(mnk, blocks, consts,
                                                    exact=x64)
            S, C, F = feats.shape
            flat = feats.reshape(S * C, F)
            Y = apply(pparams, (flat - mean) / scale, flat)
            if objective == "edp":
                score = (Y[:, t_idx["energy_j"]]
                         * Y[:, t_idx["runtime_ms"]])
            else:
                col = {"runtime": "runtime_ms", "energy": "energy_j",
                       "power": "power_w"}[objective]
                score = Y[:, t_idx[col]]
            score = jnp.where(valid, score.reshape(S, C), jnp.inf)
            neg, idx = jax.lax.top_k(-score, top_k)
            return -neg, idx

        entry = (jax.jit(ranker), device_params)
        self._graph_fns[key] = entry
        return entry

    def rank_in_graph(self, shapes: Sequence[tuple[int, int, int]], *,
                      dtype: str = "bf16", objective: str = "runtime",
                      top_k: int | None = None, x64: bool = True
                      ) -> tuple[list[list[GemmConfig]], np.ndarray]:
        """Rank the candidate grid for a fleet of shapes *inside* jax.jit.

        The candidate feature table is built with jnp ops over the static
        block grid, scored through the compiled predictor, and the
        objective argmin'd in-graph — the GEMM extents are traced array
        values, so new shapes reuse the compiled ranker (no retrace; shape
        fleets are padded to power-of-two buckets). ``x64=True`` (default)
        runs the whole graph in scoped float64: features, scaling, and
        descent are bit-identical to the trace-time `rank()` path, so both
        return the same winners. ``x64=False`` is the approximate f32 mode
        for embedding in fp32 programs.

        Returns ``(top_cfgs, top_scores)``: per shape, up to `top_k`
        candidate `GemmConfig`s in ascending predicted-objective order
        (fewer when the valid set is smaller; empty when no candidate
        fits) and the (S, top_k) score matrix (+inf past the valid set).
        """
        import jax
        import jax.numpy as jnp

        dtype = canon_dtype(dtype)
        blocks = self._graph_blocks()
        k = min(top_k if top_k is not None else self.verify_top_k,
                len(blocks))
        S = len(shapes)
        if S == 0:
            return [], np.zeros((0, k))
        pad = _next_pow2(S)
        mnk = np.zeros((pad, 3), dtype=np.int64)
        mnk[:S] = [tuple(int(x) for x in s) for s in shapes]
        mnk[S:] = mnk[S - 1]
        jitted, device_params = self._graph_rank_fn(objective, x64, k)
        consts = self._graph_consts(dtype)
        # The production call path reaches here *during* an outer jit
        # trace (ops.matmul tunes at trace time): every input is a
        # trace-constant, so escape the ambient trace and run the ranker
        # as a normal compiled dispatch — otherwise the pjit call would
        # inline into the caller's graph and hand back tracers.
        with precision_scope(x64), jax.ensure_compile_time_eval():
            scores, idx = jitted(
                jnp.asarray(mnk), jnp.asarray(blocks),
                {name: jnp.asarray(v) for name, v in consts.items()},
                jnp.asarray(self.predictor.scaler.mean_),
                jnp.asarray(self.predictor.scaler.scale_),
                device_params)
        scores = np.asarray(scores)[:S]
        idx = np.asarray(idx)[:S]
        top_cfgs: list[list[GemmConfig]] = []
        for i, (m, n, kk) in enumerate(shapes):
            row = []
            for j in range(k):
                if np.isfinite(scores[i, j]):
                    bm, bn, bk = blocks[idx[i, j]]
                    row.append(GemmConfig(
                        m=int(m), n=int(n), k=int(kk), block_m=int(bm),
                        block_n=int(bn), block_k=int(bk), dtype=dtype))
            top_cfgs.append(row)
        return top_cfgs, scores

    # ---------- tuning ----------
    @staticmethod
    def _key(m: int, n: int, k: int, dtype: str, objective: str) -> str:
        return f"{m},{n},{k},{dtype},{objective}"

    def best_config(self, m: int, n: int, k: int, *, dtype: str = "bf16",
                    objective: str = "runtime", rank_mode: str = "auto",
                    measure_fn=None) -> BlockConfig:
        return self.tune_many([(m, n, k)], dtype=dtype, objective=objective,
                              rank_mode=rank_mode, measure_fn=measure_fn)[0]

    def tune_many(self, shapes: Sequence[tuple[int, int, int]], *,
                  dtype: str = "bf16", objective: str = "runtime",
                  rank_mode: str = "auto", measure_fn=None
                  ) -> list[BlockConfig]:
        """Tune a fleet of (m, n, k) shapes in one pass: all uncached
        shapes share one batched ranking pass and one batched top-k
        verification sweep, then land in the winner cache together.

        `rank_mode` selects the ranking path: "graph" scores candidates
        fully in-graph (`rank_in_graph`: jnp feature grid + compiled
        predictor + in-jit top-k — the accelerator serving path), "trace"
        ranks in Python over the cached candidate tables, and "auto"
        (default) picks "graph" exactly when the compiled scorer is the
        rank backend (accelerator backends; see `scorer`). Both modes
        produce the same winners — the graph path runs scoped-x64.

        `measure_fn`, when given, replaces the simulator for the
        verification sweep — the real-hardware hook. It is called once
        with the flat list of top-k `GemmConfig`s (all shapes
        concatenated) and must return a telemetry-like mapping with
        "runtime_ms", "power_w", and "energy_j" arrays aligned with the
        input order (e.g. wall-clock timings of the actual kernels).
        """
        dtype = canon_dtype(dtype)
        if rank_mode not in ("auto", "graph", "trace"):
            raise ValueError(f"unknown rank_mode {rank_mode!r}")
        out: list[BlockConfig | None] = [None] * len(shapes)
        todo: list[int] = []
        with self._lock:
            for i, (m, n, k) in enumerate(shapes):
                hit = self._cache_get(self._key(m, n, k, dtype, objective))
                if hit is not None:
                    out[i] = BlockConfig(*hit)
                else:
                    todo.append(i)
        if not todo:
            return out  # type: ignore[return-value]

        use_graph = (rank_mode == "graph"
                     or (rank_mode == "auto" and self._use_jit_scorer()))
        # rank: per-uncached-shape top-k candidates, ascending predicted
        # objective. An empty top list means no candidate fits (BASELINE
        # fallback — cached too: the empty set is deterministic per
        # bucket, so never re-enumerate).
        groups: list[tuple[int, list[GemmConfig]]] = []
        if use_graph:
            tops_all, _ = self.rank_in_graph(
                [shapes[i] for i in todo], dtype=dtype, objective=objective)
            for i, top in zip(todo, tops_all):
                if top:
                    groups.append((i, top))
                else:
                    out[i] = BASELINE
                    self.fallback_shapes.add((*shapes[i], dtype))
        else:
            trace_groups: list[tuple[int, list[GemmConfig], np.ndarray]] = []
            for i in todo:
                m, n, k = shapes[i]
                cfgs, X = self.candidate_table(m, n, k, dtype)
                if not cfgs:
                    out[i] = BASELINE
                    self.fallback_shapes.add((m, n, k, dtype))
                else:
                    trace_groups.append((i, cfgs, X))
            if trace_groups:
                # one compiled scorer call over every candidate of every
                # shape
                scores = self._scores_from_matrix(
                    self._predict_features(
                        np.concatenate([X for _, _, X in trace_groups])),
                    objective)
                off = 0
                for i, cfgs, _X in trace_groups:
                    # stable sort: tie-break by index like in-graph top-k
                    order = np.argsort(scores[off:off + len(cfgs)],
                                       kind="stable")
                    groups.append(
                        (i, [cfgs[j] for j in order[:self.verify_top_k]]))
                    off += len(cfgs)

        winners: dict[int, tuple[int, int, int]] = {}
        if groups:
            # one batched verification sweep across all shapes
            flat = [c for _, top in groups for c in top]
            tel = (measure_fn(flat) if measure_fn is not None
                   else self.sim.measure_batch(flat))
            meas = self._objective_scores(
                {t: np.asarray(tel[t], dtype=np.float64)
                 for t in ("runtime_ms", "power_w", "energy_j")},
                objective)
            off = 0
            for i, top in groups:
                s = meas[off:off + len(top)]
                w = top[int(np.argmin(s))]
                winners[i] = (w.block_m, w.block_n, w.block_k)
                out[i] = BlockConfig(*winners[i])
                off += len(top)

        with self._lock:
            for i in todo:
                m, n, k = shapes[i]
                best = winners.get(i)
                if best is None:  # BASELINE fallback
                    best = (BASELINE.block_m, BASELINE.block_n,
                            BASELINE.block_k)
                self._cache_put(self._key(m, n, k, dtype, objective), best)
            self._write_cache_locked()
        return out  # type: ignore[return-value]

    def tune_report(self, m: int, n: int, k: int, *, dtype: str = "bf16",
                    objective: str = "runtime") -> dict:
        """Tuned-vs-baseline gains (the paper's 3.2x / 22% claims)."""
        dtype = canon_dtype(dtype)
        best = self.best_config(m, n, k, dtype=dtype, objective=objective)
        base_cfg = GemmConfig(m=m, n=n, k=k, block_m=BASELINE.block_m,
                              block_n=BASELINE.block_n,
                              block_k=BASELINE.block_k, dtype=dtype)
        best_cfg = GemmConfig(m=m, n=n, k=k, block_m=best.block_m,
                              block_n=best.block_n, block_k=best.block_k,
                              dtype=dtype)
        tb = self.sim.analyze(base_cfg)
        tt = self.sim.analyze(best_cfg)
        return {
            "m": m, "n": n, "k": k, "dtype": dtype, "objective": objective,
            "chip": self.chip.name,
            "artifact_fingerprint": self.artifact_fingerprint,
            "baseline": BASELINE.as_tuple(),
            "best": best.as_tuple(),
            "baseline_runtime_ms": tb.runtime_ms,
            "tuned_runtime_ms": tt.runtime_ms,
            "speedup": tb.runtime_ms / tt.runtime_ms,
            "baseline_power_w": tb.power_w,
            "tuned_power_w": tt.power_w,
            "power_reduction_pct": 100.0 * (1 - tt.power_w / tb.power_w),
            "baseline_energy_j": tb.energy_j,
            "tuned_energy_j": tt.energy_j,
            "energy_reduction_pct": 100.0 * (1 - tt.energy_j / tb.energy_j),
        }


# ---------- process-wide per-chip tuners ----------
_GLOBAL: dict[str, GemmAutotuner] = {}
_GLOBAL_LOCK = threading.Lock()


def build_default_predictor(artifacts_dir: str = DEFAULT_ARTIFACTS_DIR,
                            n_train: int = 4000,
                            force_retrain: bool = False,
                            chip: ChipSpec | str = TPU_V5E,
                            seed: int = 0) -> PerfPredictor:
    """Load the persisted per-chip predictor artifact or train one on a
    fresh sweep drawn from `seed`. Invalid/legacy/tampered artifacts
    trigger a retrain."""
    chip = get_chip(chip)
    os.makedirs(artifacts_dir, exist_ok=True)
    path = os.path.join(artifacts_dir, f"perf_predictor_{chip.name}.npz")
    if os.path.exists(path) and not force_retrain:
        try:
            return PerfPredictor.load(path)
        except ArtifactError:
            pass
    from repro.core.profiler import collect_dataset

    table = collect_dataset(n_configs=n_train, seed=seed, chip=chip)
    pred = PerfPredictor(model="rf", residual=True, fast=True,
                         chip=chip.name).fit(table)
    pred.save(path)
    return pred


def get_tuner(artifacts_dir: str = DEFAULT_ARTIFACTS_DIR,
              chip: ChipSpec | str = TPU_V5E) -> GemmAutotuner:
    chip = get_chip(chip)
    with _GLOBAL_LOCK:
        tuner = _GLOBAL.get(chip.name)
        if tuner is None:
            predictor = build_default_predictor(artifacts_dir, chip=chip)
            tuner = GemmAutotuner(
                predictor,
                chip=chip,
                cache_path=os.path.join(
                    artifacts_dir, f"tuner_cache_{chip.name}.json"),
            )
            _GLOBAL[chip.name] = tuner
        return tuner


def set_tuner(tuner: GemmAutotuner | None,
              chip: ChipSpec | str | None = None) -> None:
    """Install (or clear, with tuner=None and chip=None) global tuners."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if tuner is None and chip is None:
            _GLOBAL = {}
        elif tuner is None:
            _GLOBAL.pop(get_chip(chip).name, None)
        else:
            _GLOBAL[get_chip(chip).name if chip is not None
                    else tuner.chip.name] = tuner
