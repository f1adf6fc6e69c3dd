"""The benchmark's harness: build one cell from its files, warm it up,
drive an open loop for a fixed window, drain, and check what was served.

Everything a cell needs is found by name: the cell's entry in
`BENCHMARK.json`, `bench/configs/<config>.json`, `bench/traffic/<mix>.json`,
`bench/cells/<workload>.json` (the offered rate),
`bench/reference/<family>.py`, `bench/work/<family>.py` and
`bench/metrics/<metric>.py`. A new cell, mix or metric is new files.

A configuration file names its `family`, and two modules of that name
stand for it:

- `reference/<family>.py`, the plain float32 reference, which imports
  nothing of the program. It must define `specs(m)`, `{leaf path:
  (shape, scale)}` of the parameter tree (the weights both sides get, made
  by `reference.common.make_params`). It must define either `forward(params,
  tokens, m, f8)`, the final-normed hidden states (S, d) in float32 of one
  sequence at positions 0..S-1, or `make_ffn(m)`, the feed-forward of a
  decoder that `reference.common.lm_forward` runs (GQA attention with
  rotary positions, one uniform scan over `params["blocks"]`); `forward`
  wins where both are there. It may define `logits(params, h, f8)`; the
  default is the untied head, `h @ params["head"]["w"]`. With `f8` every
  matmul goes through float8, the control of `check.py`.
- `work/<family>.py`, the operations and bytes a step must spend, from
  the model's equations. `kernel_gemms(m)`, the (N, K, count) per row and
  layer that the tiled kernel runs, feeds `tiled_matmul_roofline.*`;
  `row_flops(m)`, the GEMM operations of one row through every layer,
  feeds `model_mfu` and `step_mfu.*`; `weight_bytes(m, rows)` feeds
  `decode_step_hbm_share`. Attention's operations and the cached bytes a
  position holds come from `work/common.py`'s GQA formulas over
  `n_layers`, `n_heads`, `n_kv_heads` and `head_dim`, not from the family.

The program is reached only through `repro.configs.get_config`,
`repro.models.registry.get_model`, `ServingEngine` (`submit`,
`serve_step`, `has_work`, `queue`, `lane_view`, `backlog_tokens`,
`report`) and the autotuner's public set-up calls.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import loadgen  # noqa: E402

# longest a run waits past the window's close for the requests due in it:
# the mix's longest answer (448 tokens) behind a full set of slots can
# need more than a minute there
DRAIN_LIMIT_S = 120.0
# the traced part of a --trace 1 window: from TRACE_START of the window,
# for TRACE_SECONDS (or to the close, where the window is shorter)
TRACE_START = 0.25
TRACE_SECONDS = 5.0


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(workload: str) -> dict:
    """Everything the benchmark declares about one cell."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = read_json(ROOT, conf["file"])

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "workload": w,
        "config_name": w["config"],
        "config": config,
        "traffic_name": w["traffic"],
        "traffic": loadgen.load_mix(w["traffic"]),
        "cell": read_json(BENCH, "cells", f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def family_module(kind: str, family: str):
    """`bench/<kind>/<family>.py` (kind: reference or work)."""
    return importlib.import_module(f"{kind}.{family}")


def metric_reader(name: str):
    """The `read(record)` function of `bench/metrics/<name>.py`."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts XLA backend compiles (JAX's monitoring event) as they
    happen; a persistent-cache hit is not a compile."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


@dataclasses.dataclass
class Step:
    """One `serve_step` call: host times, prompt tokens it prefilled, the
    decode rows it ran, and the results it returned."""

    t0: float
    t1: float
    prompt_tokens: int
    decode_rows: int
    finished: list
    waiting: int = 0
    kv_positions: int = 0
    first_tokens: int = 0


class Cell:
    """One cell of the benchmark, built from its files on this device."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.seed = int(seed)
        conf = spec["config"]
        self.m = conf["model"]
        self.family = conf["family"]
        self.ref = family_module("reference", self.family)
        self.work = family_module("work", self.family)
        self.geo = conf["engine"]
        self.engine = None
        self.params = None
        self.results: dict = {}

    # ------------------------------------------------------------ set-up
    def model_config(self):
        """The program's ModelConfig: the registry entry with the file's
        overrides, checked against every size the file states."""
        from repro.configs import get_config

        cfg = dataclasses.replace(get_config(self.spec["config"]["registry"]),
                                  **self.spec["config"]["overrides"])
        have = {"n_kv_heads": cfg.kv_heads, "head_dim": cfg.hd}
        for key, want in self.m.items():
            got = have[key] if key in have else getattr(cfg, key)
            if got != want:
                raise ValueError(f"{self.spec['config_name']}: the program "
                                 f"runs {key}={got!r}, the file states "
                                 f"{want!r}")
        return cfg

    def install_tuner(self, chip):
        """Train (or load, in a checkout that already holds it) the GEMM
        predictor from the configuration's fixed tuner seed, and install
        its tuner for `chip`. `--seed` never reaches it, so every seed
        runs the same block configs."""
        from repro.core.autotuner import (GemmAutotuner,
                                          build_default_predictor, set_tuner)
        from repro.core.hwsim import TpuGemmSimulator

        ts = int(self.spec["config"]["tuner_seed"])
        art = os.path.join(ROOT, "artifacts", "bench",
                           f"{chip.name}-tuner{ts}")
        pred = build_default_predictor(art, chip=chip, seed=ts)
        tuner = GemmAutotuner(
            pred, sim=TpuGemmSimulator(chip=chip, seed=ts),
            cache_path=os.path.join(art, f"tuner_cache_{chip.name}.json"))
        set_tuner(tuner, chip)
        return tuner

    def build(self, chip) -> float:
        """Weights from the seed, the engine with its pretune; returns the
        seconds of the engine's construction (the pretune)."""
        import jax

        from repro.models.registry import get_model
        from repro.serving.engine import ServingEngine

        self.install_tuner(chip)
        cfg = self.model_config()
        model = get_model(cfg)
        specs = self.ref.specs(self.m)
        want = jax.eval_shape(lambda: model.init(jax.random.key(0), cfg))
        self.params = self.ref_params()
        got = jax.eval_shape(lambda: self.params)
        if (jax.tree.structure(want) != jax.tree.structure(got)
                or jax.tree.leaves(want) != jax.tree.leaves(got)):
            raise ValueError("the program's parameter tree differs from "
                             f"{self.family}.specs: {len(specs)} leaves")
        t0 = time.perf_counter()
        self.engine = ServingEngine(
            model, self.params, cfg, max_batch=self.geo["max_batch"],
            max_len=self.geo["max_len"], admission=self.geo["admission"],
            chunk_tokens=self.geo["chunk_tokens"],
            kv_layout=self.geo["kv_layout"], pretune=True, chip=chip.name,
            seed=self.seed, greedy=True)
        return time.perf_counter() - t0

    def ref_params(self):
        from reference import common

        return common.make_params(self.ref.specs(self.m), self.seed)

    def widths(self) -> list[int]:
        """The admission lane's power-of-two widths up to its cap."""
        out, w = [1], 1
        while w < 2 * self.geo["max_batch"]:
            w *= 2
            out.append(w)
        return out

    def buckets(self) -> list[int]:
        """Chunk sizes the lane may use: powers of two from 8 to the
        chunk cap."""
        out, b = [], 8
        while b <= self.geo["chunk_tokens"]:
            out.append(b)
            b *= 2
        return out

    def _serve(self, reqs, steps: int | None = None) -> None:
        from repro.serving.engine import Request

        for uid, n, budget in reqs:
            self.engine.submit(Request(
                uid=uid, prompt=np.full(n, 7, np.int32),
                max_new_tokens=budget))
        k = 0
        while self.engine.has_work and (steps is None or k < steps):
            self.engine.serve_step()
            k += 1

    def warm_up(self) -> None:
        """Run every program the window can use through the engine's own
        entry points: each (lane width, chunk bucket) chunk call, the
        decode step, the splices of a lane row into a decode slot, vacated
        lane rows being zeroed for reuse at each width, and the lane
        growing from each width to each wider one with rows mid-prompt."""
        uid = 10**9
        B = self.geo["max_batch"]
        for w in self.widths():
            for c in self.buckets():
                self._serve([(uid + i, c, 2) for i in range(w)])
                uid += w
        for w in self.widths():
            # rows vacated while the lane lives on are zeroed for reuse
            self._serve([(uid + j, 8, 2) for j in range(w)], 1)
            uid += w
            self._serve([(uid + j, 8, 2) for j in range(w)])
            uid += w
        long_p = 4 * self.geo["chunk_tokens"]
        for i, w_old in enumerate(self.widths()):
            for w_new in self.widths()[i + 1:]:
                self._serve([(uid + j, long_p, 2) for j in range(w_old)], 1)
                uid += w_old
                add = w_new - w_old
                self._serve([(uid + j, 8, 2) for j in range(add)], 2)
                uid += add
                reuse = min(add, B)
                self._serve([(uid + j, 8, 2) for j in range(reuse)])
                uid += reuse

    # ------------------------------------------------------------ window
    def run(self, seconds: float, arrivals, counter, trace_dir=None
            ) -> dict:
        """Open loop over `seconds`, then a drain. Each request is
        submitted between steps once due, stamped with its due time, so
        its TTFT counts the submission's lateness."""
        import jax

        from repro.serving.engine import Request

        eng = self.engine
        steps: list[Step] = []
        late: list[float] = []
        rep0 = eng.report()
        c0 = counter.n
        trace_on = trace_off = None
        if trace_dir is not None:
            trace_on = TRACE_START * seconds
            trace_off = min(seconds, trace_on + TRACE_SECONDS)
        tracing = False
        # no garbage collection pauses inside the window: one would make
        # the submissions due during it late
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        due = [t0 + a.due_s for a in arrivals]
        i, n = 0, len(arrivals)
        t_close = t0 + seconds
        close = None
        trace_span = None
        while True:
            now = time.perf_counter()
            # every arrival is due inside the window: one that fell due
            # while the last step ran goes in before the close is taken
            while i < n and due[i] <= now:
                a = arrivals[i]
                eng.submit(Request(uid=a.uid, prompt=a.prompt,
                                   max_new_tokens=a.max_new_tokens,
                                   submit_s=due[i]))
                late.append(now - due[i])
                i += 1
            if close is None and now >= t_close:
                close = {"t": now, "report": eng.report(),
                         "compiles": counter.n - c0,
                         "queue": len(eng.queue),
                         "lane": eng.lane_view,
                         "backlog": eng.backlog_tokens,
                         "submitted": i}
            if trace_dir is not None and not tracing and trace_span is None \
                    and now >= t0 + trace_on:
                import tracereduce as tr

                jax.profiler.start_trace(trace_dir,
                                         profiler_options=tr.start_options())
                tracing = True
                trace_span = [time.perf_counter(), None]
            if tracing and now >= t0 + trace_off:
                # the span ends where tracing ends, not where writing the
                # trace out (tens of seconds) does
                trace_span[1] = time.perf_counter()
                jax.profiler.stop_trace()
                tracing = False
            if close is not None and (not eng.has_work
                                      or now > t_close + DRAIN_LIMIT_S):
                break
            if eng.has_work:
                before = eng.backlog_tokens
                r0 = eng.report()["resident_slot_steps"]
                s0 = time.perf_counter()
                if tracing:
                    with jax.profiler.TraceAnnotation("bench:serve_step"):
                        done = eng.serve_step()
                else:
                    done = eng.serve_step()
                s1 = time.perf_counter()
                lane = eng.lane_view
                steps.append(Step(
                    s0, s1, before - eng.backlog_tokens,
                    int(round(eng.report()["resident_slot_steps"] - r0)),
                    [(r.uid, s1) for r in done],
                    len(eng.queue) + lane["pending"] + lane["parked"]))
                for r in done:
                    self.results[r.uid] = r
            elif close is None:
                nxt = min(due[i] if i < n else t_close, t_close)
                # wake where the traced span starts or ends, so that the
                # span is as long as asked
                if trace_dir is not None and trace_span is None:
                    nxt = min(nxt, t0 + trace_on)
                elif tracing:
                    nxt = min(nxt, t0 + trace_off)
                time.sleep(max(0.0, nxt - time.perf_counter()))
        gc.enable()
        if tracing:
            trace_span[1] = time.perf_counter()
            jax.profiler.stop_trace()
        return {"t0": t0, "close": close, "steps": steps, "late": late,
                "report0": rep0, "trace_span": trace_span}

    def serve(self, seconds: float, trace_dir=None, counter=None) -> dict:
        """The measured window and its drain; returns the run record."""
        self.results = {}
        arrivals = loadgen.schedule(self.spec["traffic"],
                                    self.spec["cell"]["rate_per_s"],
                                    seconds, self.seed, self.m["vocab"])
        run = self.run(seconds, arrivals, counter, trace_dir)
        run["arrivals"] = arrivals
        run["results"] = self.results
        return run

    def free(self) -> None:
        """Drop the engine and its weights so the reference has the
        device to itself."""
        self.engine = None
        self.params = None
        gc.collect()


def annotate_steps(steps: list[Step], arrivals, results, t0: float) -> None:
    """Give each step the cached positions its decode read and the first
    tokens it produced, from the finished requests: a request with n
    tokens decoded in the n - 1 steps up to the one that returned it (a
    resident slot decodes in every step), reading P + k positions at its
    k-th; its first token came from the step whose span holds it."""
    import bisect

    index = {}
    for i, st in enumerate(steps):
        for uid, _ in st.finished:
            index[uid] = i
    starts = [st.t0 for st in steps]
    for a in arrivals:
        r = results.get(a.uid)
        if r is None or a.uid not in index:
            continue
        e, n, P = index[a.uid], r.n_tokens, len(a.prompt)
        for k in range(1, n):
            j = e - (n - 1) + k
            if 0 <= j < len(steps):
                steps[j].kv_positions += P + k
        t_first = t0 + a.due_s + r.ttft_s
        j = bisect.bisect_right(starts, t_first) - 1
        if 0 <= j < len(steps):
            steps[j].first_tokens += 1
