"""Chip smoke: serve starcoder2-3b at full width on one TPU through the
normal entry points, with every GEMM on the predictor-tuned Pallas kernel.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --tp 4 [--seed N]   # four chips: the tensor-
                                             # parallel phase only

The one-chip run builds the GEMM predictor from ``--seed`` in an emptied
directory, checks the tuned kernel against ``lax.dot_general`` on a few
serving shapes, serves eight requests through ``ServingEngine`` (chunked
admission, dense KV cache, random weights from ``--seed``) and compares
prefill logits under Pallas and XLA dispatch. ``--tp 4`` serves
qwen2.5-14b over a (1, 4) mesh and compares a 4-layer cut of it at tp=4
and tp=1 on the same weights.

It exits non-zero, printing no result line, when JAX finds no TPU or when
it is not run from a checkout of the repository, and when any check fails.
Wall times it prints are smoke timings, not benchmark results. The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# one-chip serving geometry: prompts of 32..1500 tokens and budgets of
# 16..64 tokens. All eight requests are admitted together, so the lane is
# 8 rows wide throughout; every prompt but 32 and 1500 is a multiple of
# the chunk, so only the (8, 32) and (8, 64) chunk programs compile.
MAX_BATCH, MAX_LEN, CHUNK = 8, 2048, 64
PROMPTS = (32, 64, 128, 256, 512, 768, 1024, 1500)
BUDGETS = (16, 64, 24, 56, 32, 48, 40, 64)
# decode projection, decode GQA k/v projection, chunk FFN up and down
# (8 lane rows x 64-token bucket), LM head over the decode rows
KERNEL_SHAPES = ((8, 3072, 3072), (8, 256, 3072), (512, 12288, 3072),
                 (512, 3072, 12288), (8, 49152, 3072))
# the kernel rounds its fp32 accumulator to bf16 once: half a bf16 ulp is
# up to 2^-8 of an element at the bottom of its binade, so 2^-7 of the
# output's scale leaves as much again for fp32 summation-order
# differences; a dropped or repeated K block errs by tens of percent
KERNEL_TOL = 2.0 ** -7
# Pallas and XLA run the same bf16 GEMMs with fp32 accumulation in
# different K orders; a GEMM output then differs by one bf16 ulp where
# the two sums straddle a rounding boundary (~2e-4 of the elements of a
# 256x3072x3072 GEMM), and a random-weight network amplifies those flips
# with depth. Relative L2 distance of the logits, Pallas interpret vs XLA
# on the CPU at starcoder2-3b widths: 4.9e-3 (2 layers), 6.1e-3 (4),
# 7.7e-3 (8), 9.9e-3 (16), so about 1.2e-2 at 30. 5e-2 leaves four times
# that; a kernel that drops or repeats a block errs by O(1).
LOGITS_TOL = 5e-2
PARITY_PROMPT = 256

# --tp 4 geometry: all prompts multiples of the chunk (one chunk program)
TP_MAX_BATCH, TP_MAX_LEN = 4, 1024
TP_PROMPTS = (64, 256, 512, 960)
TP_BUDGETS = (16, 32, 24, 16)
TP_PARITY_LAYERS = 4


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    """Print one check's verdict; a failed check ends the run."""
    print(f"[{'pass' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA backend compiles (by jitted function name)."""

    def __init__(self):
        """Register the listener (process-wide, for the run's life)."""
        import jax

        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(kw.get("fun_name", "?"))

    def since(self, mark: int) -> list[str]:
        """Names compiled after `mark` compiles had been counted."""
        return self.names[mark:]


def fresh_tuner(chip, seed: int):
    """Train the GEMM predictor from `seed` into an emptied directory and
    install its tuner for `chip` (nothing an earlier run left is read)."""
    from repro.core.autotuner import (GemmAutotuner, build_default_predictor,
                                      set_tuner)
    from repro.core.hwsim import TpuGemmSimulator

    art = os.path.join(ROOT, "artifacts", "chip_smoke")
    shutil.rmtree(art, ignore_errors=True)
    os.makedirs(art)
    t0 = time.perf_counter()
    pred = build_default_predictor(art, chip=chip, seed=seed)
    tuner = GemmAutotuner(
        pred, sim=TpuGemmSimulator(chip=chip, seed=seed),
        cache_path=os.path.join(art, f"tuner_cache_{chip.name}.json"))
    set_tuner(tuner, chip)
    print(f"tuner: predictor {pred.fingerprint()[:12]} trained from seed "
          f"{seed} in {time.perf_counter() - t0:.2f}s (smoke timing)")
    return tuner


def init_params(model, cfg, seed: int, out_shardings=None):
    """Random weights from `seed`, made on the device (directly into
    `out_shardings` when given)."""
    import jax

    t0 = time.perf_counter()
    params = jax.jit(lambda k: model.init(k, cfg),
                     out_shardings=out_shardings)(jax.random.key(seed))
    jax.block_until_ready(params)
    n = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{n} parameter bytes, initialised in "
          f"{time.perf_counter() - t0:.2f}s (smoke timing)")
    return params


def serve(engine, cfg, prompts, budgets, seed: int, counter) -> list:
    """Serve one request per prompt; every request must return its whole
    budget (no EOS is set) with tokens inside the vocabulary."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    for uid, (n, budget) in enumerate(zip(prompts, budgets)):
        engine.submit(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
            max_new_tokens=budget))
    mark = len(counter.names)
    t0 = time.perf_counter()
    results = sorted(engine.run_until_empty(), key=lambda r: r.uid)
    wall = time.perf_counter() - t0
    compiled = counter.since(mark)
    chunks = sorted(k[1:] for k in engine._step_energy_cache
                    if k[0] == "chunk")
    print(f"served {len(results)} requests, "
          f"{sum(r.n_tokens for r in results)} tokens in {wall:.2f}s "
          f"(smoke timing, compiles included); {len(compiled)} programs "
          f"compiled while serving; chunk (lane width, bucket) programs: "
          f"{chunks}")
    check(sorted(r.uid for r in results) == list(range(len(prompts))),
          "every request answered")
    for r, budget in zip(results, budgets):
        check(r.n_tokens == budget
              and bool(np.all((r.tokens >= 0) & (r.tokens < cfg.vocab))),
              f"request {r.uid}: prompt {r.prompt_len}, {r.n_tokens}/"
              f"{budget} tokens inside vocab {cfg.vocab}")
    return results


def check_tuning(tuner, engine) -> None:
    """0 tuner fallbacks: every traced Pallas GEMM runs the block config
    the predictor picked for its shape, and no shape fell back to the
    untuned baseline."""
    from repro.kernels import ops

    traced = ops.traced_configs()
    off = [s for s, c in traced.items()
           if c != tuner.best_config(*s[:3], dtype=s[3])]
    unwarmed = sorted(s[:3] for s in traced if s[:3] not in engine.pretuned)
    print(f"traced GEMM shapes: {len(traced)} ({len(unwarmed)} tuned at "
          f"trace time, not pretuned: {unwarmed}); distinct block configs used: "
          f"{sorted({c.as_tuple() for c in traced.values()})}")
    fallbacks = len(tuner.fallback_shapes) + len(off)
    check(bool(traced) and fallbacks == 0,
          f"{fallbacks} tuner fallbacks over {len(traced)} traced GEMMs")


def ranker_parity(tuner, shapes, dtype: str = "bfloat16") -> None:
    """The in-graph ranker (scoped float64, on the device) against the
    host's numpy ranking with the same predictor: the same top-k
    candidates per shape, and how far the device's scores are from
    numpy's."""
    import numpy as np

    from repro.core.autotuner import GemmAutotuner

    pred = tuner.predictor
    host = GemmAutotuner(pred, sim=tuner.sim, scorer="numpy")
    col = list(pred.target_names).index("runtime_ms")
    tops, scores = tuner.rank_in_graph(shapes, dtype=dtype)
    same, worst = 0, 0.0
    for (m, n, k), top, dev in zip(shapes, tops, scores):
        cfgs, X = host.candidate_table(m, n, k, dtype)
        order = host.rank(cfgs, features=X)[:len(top)]
        same += [(cfgs[i].block_m, cfgs[i].block_n, cfgs[i].block_k)
                 for i in order] == [(c.block_m, c.block_n, c.block_k)
                                     for c in top]
        want = pred.predict_matrix(dict(zip(pred.feature_names,
                                            X[order].T)))[:, col]
        worst = max(worst, float(np.max(
            np.abs(dev[:len(top)] - want) / np.abs(want))))
    check(same == len(shapes),
          f"device float64 ranking == host numpy ranking: top-"
          f"{tuner.verify_top_k} equal for {same} of {len(shapes)} shapes; "
          f"largest relative score difference {worst:.3e}")


def kernel_check(engine, seed: int) -> None:
    """Tuned and DEFAULT_CONFIG kernels against lax.dot_general with fp32
    accumulation, on serving shapes of this engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.tiled_matmul import DEFAULT_CONFIG

    fleet = set(ops.serving_gemm_fleet(
        engine.cfg, max_batch=engine.max_batch, max_len=engine.max_len,
        chunk_tokens=engine.chunk_tokens, lane_width=engine.lane_width))
    key = jax.random.key(seed)
    for m, n, k in KERNEL_SHAPES:
        check((m, n, k) in fleet, f"kernel shape {(m, n, k)} is served")
        ka, kb, key = jax.random.split(key, 3)
        a = jax.random.normal(ka, (m, k), jnp.bfloat16)
        b = (jax.random.normal(kb, (k, n), jnp.float32)
             / np.sqrt(k)).astype(jnp.bfloat16)
        ref = np.asarray(jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        scale = float(np.abs(ref).max())
        for name, cfg in (("tuned", engine.pretuned[(m, n, k)]),
                          ("default", DEFAULT_CONFIG)):
            out = np.asarray(ops.gemm(a, b, config=cfg), np.float32)
            err = float(np.abs(out - ref).max()) / scale
            check(err <= KERNEL_TOL,
                  f"kernel {m}x{n}x{k} {name} {cfg.as_tuple()}: max error "
                  f"{err:.3e} of scale {scale:.3e} <= {KERNEL_TOL:.3e}")


def logits_distance(a, b) -> float:
    """Relative L2 distance of two logit arrays."""
    import numpy as np

    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def pallas_vs_xla(model, cfg, params, seed: int) -> None:
    """Prefill logits of one prompt under Pallas and under XLA dispatch
    (the dispatch mode is read at trace time: one fresh jit each)."""
    import jax
    import numpy as np

    from repro.kernels import ops

    rng = np.random.default_rng(seed + 1)
    toks = rng.integers(0, cfg.vocab, (1, PARITY_PROMPT)).astype(np.int32)
    logits = {}
    for mode in ("pallas", "xla"):
        ops.force_mode(mode)
        try:
            fn = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, cfg)[0])
            logits[mode] = np.asarray(fn(params, toks), np.float32)
        finally:
            ops.force_mode("auto")
    d = logits_distance(logits["pallas"], logits["xla"])
    same = int(logits["pallas"].argmax()) == int(logits["xla"].argmax())
    check(bool(np.isfinite(logits["pallas"]).all()) and d <= LOGITS_TOL,
          f"prefill logits ({PARITY_PROMPT} tokens) Pallas vs XLA: relative "
          f"L2 {d:.3e} <= {LOGITS_TOL:.0e}; same argmax: {same}")


def run_one_chip(chip, seed: int, counter) -> None:
    """starcoder2-3b at full config on one chip: fresh tuning, kernel
    check, serving, tuning check, Pallas vs XLA logits."""
    import jax

    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.serving.engine import ServingEngine

    tuner = fresh_tuner(chip, seed)
    cfg = get_config("starcoder2-3b")
    model = get_model(cfg)
    params = init_params(model, cfg, seed)
    t0 = time.perf_counter()
    engine = ServingEngine(
        model, params, cfg, max_batch=MAX_BATCH, max_len=MAX_LEN,
        admission="chunked", chunk_tokens=CHUNK, kv_layout="dense",
        pretune=True, chip=chip.name, seed=seed)
    print(f"engine set-up (pretune of {len(engine.pretuned)} GEMM shapes): "
          f"{time.perf_counter() - t0:.2f}s (smoke timing)")
    ranker_parity(tuner, sorted(engine.pretuned))
    kernel_check(engine, seed)
    serve(engine, cfg, PROMPTS, BUDGETS, seed, counter)
    check_tuning(tuner, engine)
    pallas_vs_xla(model, cfg, params, seed)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}; "
          f"{len(counter.names)} programs compiled in all")


def first_logits(engine, prompts, seed: int):
    """Last-position prefill logits of `prompts` through the engine's own
    jitted prefill, under the engine's mesh."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    toks = np.zeros((len(prompts), max(prompts)), np.int32)
    for i, n in enumerate(prompts):
        toks[i, :n] = rng.integers(0, engine.cfg.vocab, n)
    engine._activate()
    logits, _ = engine._prefill(
        engine.params, {"tokens": jnp.asarray(toks),
                        "lengths": jnp.asarray(prompts, jnp.int32)})
    return np.asarray(logits, np.float32)


def run_tp(chip, seed: int, tp: int, counter) -> None:
    """qwen2.5-14b over a (1, tp) mesh, initialised into its shardings;
    then a TP_PARITY_LAYERS cut of it at tp and at 1 on the same
    weights."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.distributed.sharding import param_shardings
    from repro.launch.mesh import make_serving_mesh
    from repro.models.registry import get_model
    from repro.serving.engine import ServingEngine

    check(len(jax.devices()) >= tp, f"{len(jax.devices())} devices >= {tp}")
    tuner = fresh_tuner(chip, seed)
    cfg = get_config("qwen2.5-14b")
    model = get_model(cfg)
    mesh = make_serving_mesh(tp)
    shapes = jax.eval_shape(lambda k: model.init(k, cfg), jax.random.key(0))
    params = init_params(model, cfg, seed, out_shardings=param_shardings(
        shapes, mesh, tp_reduce="gather"))
    kw = dict(max_batch=TP_MAX_BATCH, max_len=TP_MAX_LEN,
              admission="chunked", chunk_tokens=CHUNK, kv_layout="dense",
              pretune=True, chip=chip.name, seed=seed)
    t0 = time.perf_counter()
    engine = ServingEngine(model, params, cfg, tp=tp, mesh=mesh, **kw)
    print(f"tp={tp} engine set-up: {time.perf_counter() - t0:.2f}s "
          f"(smoke timing)")
    serve(engine, cfg, TP_PROMPTS, TP_BUDGETS, seed, counter)
    check_tuning(tuner, engine)
    held = dict.fromkeys(mesh.devices.flat, 0)
    for leaf in jax.tree.leaves(engine.params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    share = sum(x.nbytes for x in jax.tree.leaves(params)) / tp
    print(f"parameter bytes per mesh device: {list(held.values())} "
          f"(1/{tp} of all: {share:.0f}); bytes_in_use: "
          f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in held]}")
    check(all(b >= share for b in held.values()),
          f"each of the {tp} mesh devices holds at least 1/{tp} of the "
          f"parameters")
    del engine, params

    cut = dataclasses.replace(cfg, n_layers=TP_PARITY_LAYERS)
    params = init_params(model, cut, seed)
    engines = {t: ServingEngine(model, params, cut, tp=t,
                                mesh=mesh if t > 1 else None, **kw)
               for t in (1, tp)}
    logits = {t: first_logits(e, TP_PROMPTS, seed)
              for t, e in engines.items()}
    d = logits_distance(logits[tp], logits[1])
    check(d <= LOGITS_TOL, f"{TP_PARITY_LAYERS}-layer first-step logits "
          f"tp={tp} vs tp=1: relative L2 {d:.3e} <= {LOGITS_TOL:.0e}")
    streams = {t: serve(e, cut, TP_PROMPTS, TP_BUDGETS, seed, counter)
               for t, e in engines.items()}
    same = sum(np.array_equal(a.tokens, b.tokens)
               for a, b in zip(streams[1], streams[tp]))
    print(f"greedy streams identical at tp={tp} and tp=1: {same} of "
          f"{len(TP_PROMPTS)} (tuned per-shard block configs may differ "
          f"from the whole-GEMM ones, so bit identity is not required)")


def main(argv: list[str] | None = None) -> int:
    """Check the device, run the phase asked for, print the result."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase on this many "
                         "chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}: run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found only "
              f"{dev.platform!r} devices", file=sys.stderr)
        return 1
    from repro.core.chips import chip_for_device_kind
    from repro.kernels import ops

    chip = chip_for_device_kind(dev.device_kind)
    ops.force_chip(chip.name)
    print(f"device: {dev.device_kind} x{len(devices)} -> chip spec "
          f"{chip.name}; compile cache {cache_dir}")
    counter = CompileCounter()
    try:
        if args.tp > 1:
            run_tp(chip, args.seed, args.tp, counter)
        else:
            run_one_chip(chip, args.seed, counter)
    except SmokeFailure:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
