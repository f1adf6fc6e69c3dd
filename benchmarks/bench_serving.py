"""Serving-engine benchmark: chunked admission vs serial slot prefill vs
the legacy wave loop.

Serves one adversarial mixed workload — long prompts queued *ahead of* a
burst of short ones (the shape that makes serialized slot prefill stall
TTFT hardest) with mixed decode budgets (the Racing-to-Idle shape) —
through three engine configurations over the same tiny dense LM:

  * ``wave``: legacy batch-of-waves loop;
  * ``serial``: continuous batching, PR 4 single-shot slot prefill;
  * ``chunked``: continuous batching, chunked admission fused into the
    decode loop (this PR's tentpole).

Reports tokens/s, J/token, slot occupancy, executed step totals, and
TTFT / queue-time **percentiles** (mean, p50, p95) per mode. The JSON
artifact (artifacts/bench/serving.json) is the regression surface CI
uploads; with ``--smoke`` the run exits non-zero if chunked-admission
mean TTFT regresses past the pinned threshold vs serial admission
(``SMOKE_TTFT_RATIO_MAX``). The prefill-once admit families (encdec,
vlm) run the same chunked-vs-serial comparison — admission extras,
stream-parity assert, and the shared TTFT gate — landing in the
``admit_families`` block of the JSON payload.

A second comparison serves a **shared-prefix workload** (every request
starts with one of a few long system prompts) through the dense and the
paged KV layouts *at the same KV HBM byte budget*: the dense engine
spends a full ``max_len`` row per in-flight request, the paged engine
spends pages proportional to actual length and maps shared prefixes
copy-on-write, so the same bytes sustain strictly more concurrent
requests. Smoke gates: paged concurrency > dense, paged mean TTFT
(model clock) below ``PAGED_TTFT_RATIO_MAX`` x dense, and paged J/token
within ``PAGED_JTOK_RATIO_MAX`` x dense.

``--fleet`` serves a seeded adversarial mix (long best-effort prompts
ahead of a burst of short SLO-bound ones) through a two-chip
`FleetScheduler` and through each member as a forced single-engine
baseline at equal streams; smoke gates pin interactive SLO attainment
(``FLEET_SLO_ATTAIN_MIN``) and the fleet-vs-best-baseline J/token ratio
(``FLEET_JTOK_RATIO_MAX``), dumping artifacts/bench/serving_fleet.json.
A second scenario routes an encdec fleet (admission extras through the
scheduler) and asserts placement never changes tokens.

``--chaos`` replays the fleet mix under a deterministic fault plan
(one stall caught by the straggler detector, one state-preserved crash
whose in-flight requests migrate, plus a predictor-artifact-corruption
scenario that degrades tuning to BASELINE configs); smoke gates pin
interactive attainment under faults (``CHAOS_SLO_ATTAIN_MIN``), zero
lost requests, the faulted-vs-healthy J/token ratio
(``CHAOS_JTOK_RATIO_MAX``), and bit-identical streams, dumping
artifacts/bench/serving_chaos.json (the plan and seed included).

``--seed N`` re-seeds every workload generator and is recorded in each
JSON payload, so an artifact diff across seeds is a one-flag experiment.

Run:  PYTHONPATH=src python benchmarks/bench_serving.py [--smoke]
      [--seed N] [--fleet | --chaos | --tp N | --grain]
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

# allow `python benchmarks/bench_serving.py` from anywhere (run.py inserts
# the repo root itself)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import dump, row  # noqa: E402

BUDGETS = (4, 8, 32)
MAX_BATCH = 4
MAX_LEN = 512
CHUNK_TOKENS = 64
SHORT_LEN = (8, 16)       # short-prompt length range (inclusive)
LONG_LEN = 384            # adversarial long prompt (6 chunk calls)

# CI gate: chunked-admission mean TTFT must stay at or below this
# fraction of serial-admission mean TTFT on the smoke mix (the tentpole
# acceptance is >= 2x lower, i.e. ratio <= 0.5)
SMOKE_TTFT_RATIO_MAX = float(os.environ.get("SMOKE_TTFT_RATIO_MAX", "0.5"))

# ---- paged-vs-dense shared-prefix comparison (fixed KV HBM budget) ----
PAGE_SIZE = 32
PREFIX_LEN = 64           # shared system-prompt length (2 full pages)
TAIL_LEN = (16, 32)       # per-request unique suffix range (inclusive)
PAGED_BUDGETS = (4, 8, 16)
# paged mean model-clock TTFT must beat dense by this factor, and J/token
# must stay within this factor of dense, on the shared-prefix mix
PAGED_TTFT_RATIO_MAX = float(os.environ.get("PAGED_TTFT_RATIO_MAX", "0.75"))
PAGED_JTOK_RATIO_MAX = float(os.environ.get("PAGED_JTOK_RATIO_MAX", "1.0"))


def _build(smoke: bool):
    import jax

    from repro.models.config import ModelConfig
    from repro.models.registry import get_model

    # the smoke model must make a long-prompt prefill *compute-bound*
    # (a stall worth killing), not dispatch-bound, while staying small
    # enough for CPU CI
    cfg = ModelConfig(
        name="serve-bench", kind="dense",
        n_layers=3 if smoke else 4,
        d_model=128 if smoke else 256,
        n_heads=4 if smoke else 8, n_kv_heads=2 if smoke else 4,
        d_ff=256 if smoke else 1024, vocab=512 if smoke else 4096,
        param_dtype="float32", activation_dtype="float32", remat=False,
    )
    model = get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    return cfg, model, params


def _workload(cfg, n_long: int, n_short: int, seed: int = 0):
    """Long prompts first — the adversarial ordering for serialized
    admission — then a burst of short prompts with mixed budgets."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_long):
        reqs.append((uid, rng.integers(0, cfg.vocab, LONG_LEN)
                     .astype(np.int32), int(rng.choice(BUDGETS))))
    for uid in range(n_long, n_long + n_short):
        n = int(rng.integers(SHORT_LEN[0], SHORT_LEN[1] + 1))
        reqs.append((uid, rng.integers(0, cfg.vocab, n).astype(np.int32),
                     int(rng.choice(BUDGETS))))
    return reqs


def _percentiles(values) -> dict:
    v = np.asarray(sorted(values), np.float64)
    if len(v) == 0:
        return {"mean": 0.0, "p50": 0.0, "p95": 0.0}
    return {"mean": float(v.mean()),
            "p50": float(np.percentile(v, 50)),
            "p95": float(np.percentile(v, 95))}


def _serve(cfg, model, params, reqs, label: str):
    from repro.serving.engine import Request, ServingEngine

    mode, admission = {
        "wave": ("wave", "serial"),
        "serial": ("continuous", "serial"),
        "chunked": ("continuous", "chunked"),
    }[label]
    eng = ServingEngine(model, params, cfg, max_batch=MAX_BATCH,
                        max_len=MAX_LEN, mode=mode, admission=admission,
                        chunk_tokens=CHUNK_TOKENS)
    # warm-up pass over the identical workload so every jit shape the
    # timed region traces (wave prefill, slot/chunk buckets, admission
    # widths, splices, decode) is compiled — then reset counters so the
    # comparison charges compilation to no mode
    for uid, prompt, mnt in reqs:
        eng.submit(Request(uid=100_000 + uid, prompt=prompt.copy(),
                           max_new_tokens=mnt))
    eng.run_until_empty()
    eng.reset_stats()
    for uid, prompt, mnt in reqs:
        eng.submit(Request(uid=uid, prompt=prompt.copy(),
                           max_new_tokens=mnt))
    t0 = time.perf_counter()
    results = eng.run_until_empty()
    wall = time.perf_counter() - t0
    rep = eng.report()
    rep["mode"] = label
    rep["wall_s"] = wall
    rep["tokens_per_s"] = (rep["generated_tokens"] / wall if wall > 0
                           else 0.0)
    rep["ttft_s"] = _percentiles([r.ttft_s for r in results])
    rep["ttft_model_s"] = _percentiles([r.ttft_model_s for r in results])
    rep["queue_s"] = _percentiles([r.queue_s for r in results])
    return results, rep


def _prefix_workload(cfg, n_reqs: int, n_prefixes: int, seed: int = 1):
    """Every request opens with one of ``n_prefixes`` shared system
    prompts (round-robin) followed by a unique tail — the workload shape
    shared-prefix page reuse exists for."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab, PREFIX_LEN).astype(np.int32)
                for _ in range(n_prefixes)]
    reqs = []
    for uid in range(n_reqs):
        tail = rng.integers(0, cfg.vocab,
                            int(rng.integers(TAIL_LEN[0], TAIL_LEN[1] + 1)))
        reqs.append((uid,
                     np.concatenate([prefixes[uid % n_prefixes],
                                     tail]).astype(np.int32),
                     int(rng.choice(PAGED_BUDGETS))))
    return reqs


def _serve_layout(cfg, model, params, reqs, *, max_batch: int,
                  max_len: int, label: str, **engine_kw):
    """One warmed + timed pass of the shared-prefix workload through a
    continuous chunked-admission engine in the given KV layout."""
    from repro.serving.engine import Request, ServingEngine

    eng = ServingEngine(model, params, cfg, max_batch=max_batch,
                        max_len=max_len, mode="continuous",
                        admission="chunked", chunk_tokens=CHUNK_TOKENS,
                        **engine_kw)
    for uid, prompt, mnt in reqs:
        eng.submit(Request(uid=100_000 + uid, prompt=prompt.copy(),
                           max_new_tokens=mnt))
    eng.run_until_empty()
    eng.reset_stats()
    for uid, prompt, mnt in reqs:
        eng.submit(Request(uid=uid, prompt=prompt.copy(),
                           max_new_tokens=mnt))
    t0 = time.perf_counter()
    results = eng.run_until_empty()
    wall = time.perf_counter() - t0
    rep = eng.report()
    rep["mode"] = label
    rep["wall_s"] = wall
    rep["tokens_per_s"] = (rep["generated_tokens"] / wall if wall > 0
                           else 0.0)
    rep["ttft_s"] = _percentiles([r.ttft_s for r in results])
    rep["ttft_model_s"] = _percentiles([r.ttft_model_s for r in results])
    rep["concurrency"] = max_batch + eng.lane_width
    return results, rep


def run_paged(smoke: bool, cfg, model, params,
              seed: int = 0) -> tuple[list[dict], dict]:
    """Paged vs dense KV layout on the shared-prefix mix at one fixed KV
    HBM byte budget: the dense engine's budget is (max_batch + lane) full
    ``max_len`` rows; the paged engine gets exactly those bytes as pages
    and spends them on twice the decode slots + lane width."""
    from repro.models.config import kv_cache_bytes

    n_reqs, n_prefixes = (16, 2) if smoke else (32, 4)
    dense_batch = 2 if smoke else 4
    dense_rows = 3 * dense_batch             # max_batch + 2x admission lane
    hbm_budget = kv_cache_bytes(cfg, dense_rows * MAX_LEN)
    num_pages = dense_rows * MAX_LEN // PAGE_SIZE   # same bytes, in pages
    reqs = _prefix_workload(cfg, n_reqs, n_prefixes, seed=seed + 1)

    dense_out, rd = _serve_layout(cfg, model, params, reqs,
                                  max_batch=dense_batch, max_len=MAX_LEN,
                                  label="dense")
    paged_out, rp = _serve_layout(cfg, model, params, reqs,
                                  max_batch=2 * dense_batch,
                                  max_len=MAX_LEN, label="paged",
                                  kv_layout="paged", page_size=PAGE_SIZE,
                                  num_pages=num_pages + 1)

    # layout parity is a hard invariant: same greedy streams, per request
    by_uid = {r.uid: r for r in dense_out}
    for r in paged_out:
        if not np.array_equal(r.tokens, by_uid[r.uid].tokens):
            raise AssertionError(
                f"paged stream mismatch for request {r.uid}")

    paged_hbm = (rp["paging"]["peak_in_use"]
                 * kv_cache_bytes(cfg, PAGE_SIZE))
    ttft_ratio = (rp["ttft_model_s"]["mean"] / rd["ttft_model_s"]["mean"]
                  if rd["ttft_model_s"]["mean"] > 0 else 0.0)
    jtok_ratio = (rp["j_per_token"] / rd["j_per_token"]
                  if rd["j_per_token"] else 0.0)
    payload = {
        "seed": seed,
        "n_requests": n_reqs,
        "n_prefixes": n_prefixes,
        "prefix_len": PREFIX_LEN,
        "page_size": PAGE_SIZE,
        "max_len": MAX_LEN,
        "kv_hbm_budget_bytes": float(hbm_budget),
        "paged_peak_hbm_bytes": float(paged_hbm),
        "dense": rd,
        "paged": rp,
        "concurrency_dense": rd["concurrency"],
        "concurrency_paged": rp["concurrency"],
        "ttft_ratio_paged_vs_dense": ttft_ratio,
        "jtok_ratio_paged_vs_dense": jtok_ratio,
        "paged_ttft_gate_max_ratio": PAGED_TTFT_RATIO_MAX,
        "paged_jtok_gate_max_ratio": PAGED_JTOK_RATIO_MAX,
    }
    dump("serving_paged", payload)
    rows = [
        row("serve_paged", rp["wall_s"] * 1e6,
            f"tok/s={rp['tokens_per_s']:.0f} "
            f"J/tok={rp['j_per_token']:.2e} "
            f"conc={rp['concurrency']} "
            f"model-ttft={rp['ttft_model_s']['mean'] * 1e3:.2f}ms "
            f"prefix-hits={rp['paging']['prefix_hits']} "
            f"hit-tokens={rp['paging']['prefix_hit_tokens']}"),
        row("serve_paged_vs_dense", 0.0,
            f"fixed KV budget={hbm_budget / 1e6:.2f}MB: concurrency "
            f"{rd['concurrency']} -> {rp['concurrency']}, paged/dense "
            f"mean TTFT ratio={ttft_ratio:.3f} (model clock, gate <= "
            f"{PAGED_TTFT_RATIO_MAX}), J/tok ratio={jtok_ratio:.3f} "
            f"(gate <= {PAGED_JTOK_RATIO_MAX}), paged peak HBM "
            f"{paged_hbm / 1e6:.2f}MB"),
    ]
    return rows, payload


# ---- admit families (encdec, vlm): chunked vs serial admission ----
# the prefill-once admission pass (encoder + cross-KV projection for
# encdec, image-patch prefix for vlm) is paid identically by both
# admissions and priced into model-clock TTFT, so the chunked/serial
# ratio shares the dense gate (SMOKE_TTFT_RATIO_MAX)
ADMIT_FAMILY_KW = {
    "encdec": dict(d_ff=256, n_encoder_layers=2, gated_mlp=False),
    "vlm": dict(d_ff=256, qkv_bias=True, mrope=True,
                mrope_sections=(8, 4, 4)),
}


def _build_admit(kind: str, smoke: bool):
    import jax

    from repro.models.config import ModelConfig
    from repro.models.registry import get_model

    cfg = ModelConfig(
        name=f"serve-{kind}", kind=kind,
        n_layers=2 if smoke else 3,
        d_model=128, n_heads=4, n_kv_heads=2, vocab=512,
        param_dtype="float32", activation_dtype="float32", remat=False,
        **ADMIT_FAMILY_KW[kind])
    model = get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    return cfg, model, params


def _admit_extras(cfg, uid: int, seed: int):
    """Deterministic per-request modality input: an encoder source for
    encdec (required), an image-patch grid for vlm (every third request
    is text-only and serves like a dense LM)."""
    rng = np.random.default_rng(seed * 1000 + uid)
    if cfg.kind == "encdec":
        t = 8 + 4 * (uid % 3)
        return {"src_embeds": rng.standard_normal(
            (t, cfg.d_model)).astype(np.float32)}
    grid = [(4, 4), (2, 3), None][uid % 3]
    if grid is None:
        return None
    gh, gw = grid
    return {"patch_embeds": rng.standard_normal(
        (gh * gw, cfg.d_model)).astype(np.float32), "grid_hw": grid}


def _serve_admit(cfg, model, params, reqs, label: str, seed: int):
    from repro.serving.engine import Request, ServingEngine

    eng = ServingEngine(model, params, cfg, max_batch=MAX_BATCH,
                        max_len=MAX_LEN, mode="continuous",
                        admission=label, chunk_tokens=CHUNK_TOKENS)
    for pass_uid0 in (100_000, 0):      # warm-up, then the timed pass
        for uid, prompt, mnt in reqs:
            eng.submit(Request(uid=pass_uid0 + uid, prompt=prompt.copy(),
                               max_new_tokens=mnt,
                               extras=_admit_extras(cfg, uid, seed)))
        if pass_uid0:
            eng.run_until_empty()
            eng.reset_stats()
    t0 = time.perf_counter()
    results = eng.run_until_empty()
    wall = time.perf_counter() - t0
    rep = eng.report()
    rep["mode"] = label
    rep["wall_s"] = wall
    rep["tokens_per_s"] = (rep["generated_tokens"] / wall if wall > 0
                           else 0.0)
    rep["ttft_s"] = _percentiles([r.ttft_s for r in results])
    rep["ttft_model_s"] = _percentiles([r.ttft_model_s for r in results])
    return results, rep


def run_admit(smoke: bool, cfg_kinds=("encdec", "vlm"),
              seed: int = 0) -> tuple[list[dict], dict]:
    """encdec and vlm on the adversarial long-ahead-of-shorts mix:
    chunked and serial admission must produce bit-identical greedy
    streams (admission is one-shot either way), and chunked mean TTFT
    on the model clock must clear the same gate as the dense smoke."""
    n_long, n_short = (1, 6) if smoke else (2, 12)
    families = {}
    rows = []
    for kind in cfg_kinds:
        cfg, model, params = _build_admit(kind, smoke)
        reqs = _workload(cfg, n_long, n_short, seed=seed + 3)
        out, reps = {}, {}
        for label in ("chunked", "serial"):
            out[label], reps[label] = _serve_admit(cfg, model, params,
                                                   reqs, label, seed)
        # stream parity across admissions is the hard invariant
        by_uid = {r.uid: r for r in out["serial"]}
        for r in out["chunked"]:
            if not np.array_equal(r.tokens, by_uid[r.uid].tokens):
                raise AssertionError(
                    f"{kind}: chunked/serial stream mismatch for "
                    f"request {r.uid}")
        rc, rs = reps["chunked"], reps["serial"]
        ratio = (rc["ttft_model_s"]["mean"] / rs["ttft_model_s"]["mean"]
                 if rs["ttft_model_s"]["mean"] > 0 else 0.0)
        families[kind] = {
            "chunked": rc,
            "serial": rs,
            "ttft_ratio_chunked_vs_serial": ratio,
        }
        rows.append(row(
            f"serve_{kind}", rc["wall_s"] * 1e6,
            f"tok/s={rc['tokens_per_s']:.0f} "
            f"J/tok={rc['j_per_token']:.2e} "
            f"model-ttft={rc['ttft_model_s']['mean'] * 1e3:.2f}ms "
            f"chunked/serial ratio={ratio:.3f} "
            f"(gate <= {SMOKE_TTFT_RATIO_MAX})"))
    payload = {
        "seed": seed,
        "n_requests": n_long + n_short,
        "ttft_gate_max_ratio": SMOKE_TTFT_RATIO_MAX,
        "families": families,
    }
    dump("serving_admit", payload)
    return rows, payload


# ---- sharded (tensor-parallel) serving smoke: --tp N ----
# fleet J/token at tp=N must stay within this factor of tp=1 (the fleet
# spends n_chips x a shorter step; the gate pins the regression surface)
TP_JTOK_RATIO_MAX = float(os.environ.get("TP_JTOK_RATIO_MAX", "3.0"))
TP_MAX_BATCH = 4
TP_MAX_LEN = 256
TP_LONG_LEN = 160


def _emulate_tp_devices(argv: list[str]) -> None:
    """`--tp N` needs N devices. Decided from the arguments before JAX is
    imported: the flag gives the CPU backend N emulated devices (CI and
    laptops) and leaves an accelerator's devices as they are."""
    if "--tp" in argv:
        n = int(argv[argv.index("--tp") + 1])
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))


def _build_tp():
    import jax

    from repro.models.config import ModelConfig
    from repro.models.registry import get_model

    # the sharded gate needs per-step time dominated by weight-streaming
    # GEMMs (1/tp of the weights per chip) rather than per-kernel launch
    # overhead (which does not shrink with tp) — so the tp bench model is
    # deliberately larger than the admission-bench one, and every sharded
    # dim (heads, kv heads, d_ff, vocab) divides tp=4
    cfg = ModelConfig(
        name="serve-tp", kind="dense", n_layers=4, d_model=512,
        n_heads=8, n_kv_heads=4, d_ff=2048, vocab=4096,
        param_dtype="float32", activation_dtype="float32", remat=False)
    model = get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    return cfg, model, params


def _serve_once(model, params, cfg, reqs, **engine_kw):
    """One warmed + timed chunked-admission pass; returns (results,
    report-with-measured-wall)."""
    from repro.serving.engine import Request, ServingEngine

    eng = ServingEngine(model, params, cfg, mode="continuous",
                        admission="chunked", **engine_kw)
    for uid, prompt, mnt in reqs:
        eng.submit(Request(uid=100_000 + uid, prompt=prompt.copy(),
                           max_new_tokens=mnt))
    eng.run_until_empty()
    eng.reset_stats()
    for uid, prompt, mnt in reqs:
        eng.submit(Request(uid=uid, prompt=prompt.copy(),
                           max_new_tokens=mnt))
    t0 = time.perf_counter()
    results = eng.run_until_empty()
    rep = eng.report()
    rep["wall_s"] = time.perf_counter() - t0
    return results, rep


def run_tp(tp: int, smoke: bool) -> tuple[list[dict], dict]:
    """Sharded vs single-chip serving of the same workload: greedy
    streams must be bit-identical, model-clock tokens/s strictly higher
    at tp, fleet J/token within the pinned ratio, and the collective
    overlap factor lands in the JSON artifact."""
    cfg, model, params = _build_tp()
    n_long, n_short = (1, 4) if smoke else (2, 8)
    rng = np.random.default_rng(7)
    reqs = []
    for uid in range(n_long):
        reqs.append((uid, rng.integers(0, cfg.vocab, TP_LONG_LEN)
                     .astype(np.int32), 4))
    for uid in range(n_long, n_long + n_short):
        n = int(rng.integers(SHORT_LEN[0], SHORT_LEN[1] + 1))
        reqs.append((uid, rng.integers(0, cfg.vocab, n).astype(np.int32),
                     int(rng.choice((4, 8)))))

    outs, reps = {}, {}
    for t in (1, tp):
        outs[t], reps[t] = _serve_once(
            model, params, cfg, reqs, max_batch=TP_MAX_BATCH,
            max_len=TP_MAX_LEN, chunk_tokens=CHUNK_TOKENS, tp=t)

    # bit parity is the hard sharding contract, not a benchmark stat
    by_uid = {r.uid: r for r in outs[1]}
    for r in outs[tp]:
        if not np.array_equal(r.tokens, by_uid[r.uid].tokens):
            raise AssertionError(
                f"sharded stream mismatch for request {r.uid} (tp={tp})")

    r1, rt = reps[1], reps[tp]
    speedup = (rt["model_tokens_per_s"] / r1["model_tokens_per_s"]
               if r1["model_tokens_per_s"] > 0 else 0.0)
    jtok_ratio = (rt["j_per_token"] / r1["j_per_token"]
                  if r1["j_per_token"] else 0.0)
    payload = {
        "tp": tp,
        "n_requests": len(reqs),
        "max_batch": TP_MAX_BATCH,
        "max_len": TP_MAX_LEN,
        "chunk_tokens": CHUNK_TOKENS,
        "tp1": r1,
        "tpN": rt,
        "model_speedup_tp_vs_1": speedup,
        "overlap_factor": rt["overlap_factor"],
        "collective_wire_s": rt["collective_wire_s"],
        "jtok_ratio_tp_vs_1": jtok_ratio,
        "tp_jtok_gate_max_ratio": TP_JTOK_RATIO_MAX,
    }
    dump("serving_tp", payload)
    rows = [row(
        "serve_tp", rt["wall_s"] * 1e6,
        f"tp={tp} model-tok/s={rt['model_tokens_per_s']:.0f} "
        f"(tp1={r1['model_tokens_per_s']:.0f}, x{speedup:.2f}) "
        f"fleet J/tok={rt['j_per_token']:.2e} (x{jtok_ratio:.2f} vs tp1, "
        f"gate <= {TP_JTOK_RATIO_MAX}) "
        f"overlap={rt['overlap_factor']:.3f}")]
    return rows, payload


# ---- predictor-driven fleet scheduling: --fleet ----
# two heterogeneous members: the scheduler must beat the best *single*
# engine (same ledger: served energy + idle-floor over the makespan for
# every member) while holding the interactive TTFT SLO
FLEET_CHIPS = {"v5e": "tpu_v5e", "ada": "rtx4070"}
FLEET_MAX_BATCH = 2
FLEET_MAX_LEN = 256
FLEET_LONG_LEN = 160
FLEET_CHUNK = 32
# interactive-class TTFT bound on the fleet model clock (submit -> first
# token, scheduler queue wait included)
FLEET_TTFT_SLO_S = float(os.environ.get("FLEET_TTFT_SLO_S", "0.05"))
# smoke gates: interactive SLO attainment, and fleet J/token vs the best
# single-engine baseline at equal streams
FLEET_SLO_ATTAIN_MIN = float(os.environ.get("FLEET_SLO_ATTAIN_MIN", "0.95"))
FLEET_JTOK_RATIO_MAX = float(os.environ.get("FLEET_JTOK_RATIO_MAX", "1.0"))


def _fleet_workload(cfg, n_long: int, n_short: int, seed: int):
    """Adversarial fleet mix: long best-effort ("batch") prompts queued
    ahead of a burst of short SLO-bound ("interactive") ones, mixed
    decode budgets — regenerated from `seed` for every scenario so the
    fleet and each single-engine baseline serve equal streams."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_long):
        reqs.append((uid, rng.integers(0, cfg.vocab, FLEET_LONG_LEN)
                     .astype(np.int32), int(rng.choice(BUDGETS)), "batch"))
    for uid in range(n_long, n_long + n_short):
        n = int(rng.integers(SHORT_LEN[0], SHORT_LEN[1] + 1))
        reqs.append((uid, rng.integers(0, cfg.vocab, n).astype(np.int32),
                     int(rng.choice(BUDGETS)), "interactive"))
    return reqs


def _serve_fleet(cfg, model, params, seed: int, n_long: int, n_short: int,
                 route_to: str | None = None, extras_fn=None):
    """One warmed + timed pass of the fleet mix through the scheduler;
    `route_to` forces the single-engine baseline (others parked, same
    ledger); `extras_fn(uid)` supplies per-request modality input for
    admit-family members (encdec source embeddings)."""
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.scheduler import FleetScheduler, SLAClass

    engines = {
        name: ServingEngine(model, params, cfg, max_batch=FLEET_MAX_BATCH,
                            max_len=FLEET_MAX_LEN, mode="continuous",
                            admission="chunked", chunk_tokens=FLEET_CHUNK,
                            chip=chip)
        for name, chip in FLEET_CHIPS.items()}
    sched = FleetScheduler(
        engines,
        sla={"interactive": SLAClass("interactive", FLEET_TTFT_SLO_S),
             "batch": SLAClass("batch", None)},
        route_to=route_to)
    for pass_uid0 in (100_000, 0):      # warm-up, then the timed pass
        for uid, prompt, mnt, sla in _fleet_workload(cfg, n_long,
                                                     n_short, seed):
            sched.submit(Request(uid=pass_uid0 + uid, prompt=prompt,
                                 max_new_tokens=mnt,
                                 extras=(extras_fn(uid) if extras_fn
                                         else None)), sla=sla)
        if pass_uid0:
            sched.run_until_empty()
            sched.reset_stats()
    t0 = time.perf_counter()
    results = sched.run_until_empty()
    rep = sched.report()
    rep["wall_s"] = time.perf_counter() - t0
    rep["label"] = route_to or "fleet"
    return results, rep


def run_fleet(smoke: bool, seed: int) -> tuple[list[dict], dict]:
    """Fleet scheduler vs every single-engine baseline on the same
    seeded adversarial mix: greedy streams must be bit-identical across
    scenarios (routing invariance), interactive SLO attainment and the
    fleet-vs-best-baseline J/token ratio land in the JSON artifact for
    the smoke gates."""
    cfg, model, params = _build(smoke)
    n_long, n_short = (2, 8) if smoke else (4, 16)

    fleet_out, fleet_rep = _serve_fleet(cfg, model, params, seed,
                                        n_long, n_short)
    by_uid = {r.uid: r for r in fleet_out}
    baselines = {}
    for name in FLEET_CHIPS:
        out, rep = _serve_fleet(cfg, model, params, seed, n_long, n_short,
                                route_to=name)
        # placement must never change tokens — only latency and energy
        for r in out:
            if not np.array_equal(r.tokens, by_uid[r.uid].tokens):
                raise AssertionError(
                    f"fleet stream mismatch for request {r.uid} "
                    f"(baseline {name})")
        baselines[name] = rep

    # one admit-family member scenario: an encdec fleet routes requests
    # whose admission (encoder + cross-KV projection) runs through the
    # scheduler's deferral/pricing machinery — placement must still
    # never change tokens
    ecfg, emodel, eparams = _build_admit("encdec", True)

    def _esrc(uid):
        rng = np.random.default_rng(4000 + uid)
        t = 8 + 2 * (uid % 3)
        return {"src_embeds": rng.standard_normal(
            (t, ecfg.d_model)).astype(np.float32)}

    e_long, e_short = (1, 3) if smoke else (2, 6)
    e_out, e_rep = _serve_fleet(ecfg, emodel, eparams, seed + 5,
                                e_long, e_short, extras_fn=_esrc)
    e_by = {r.uid: r for r in e_out}
    eb_out, eb_rep = _serve_fleet(ecfg, emodel, eparams, seed + 5,
                                  e_long, e_short, route_to="v5e",
                                  extras_fn=_esrc)
    for r in eb_out:
        if not np.array_equal(r.tokens, e_by[r.uid].tokens):
            raise AssertionError(
                f"fleet stream mismatch for encdec request {r.uid}")

    best_name = min(baselines,
                    key=lambda n: baselines[n]["fleet_j_per_token"])
    best_jtok = baselines[best_name]["fleet_j_per_token"]
    jtok_ratio = (fleet_rep["fleet_j_per_token"] / best_jtok
                  if best_jtok > 0 else 0.0)
    payload = {
        "seed": seed,
        "n_requests": n_long + n_short,
        "n_long": n_long,
        "max_batch": FLEET_MAX_BATCH,
        "max_len": FLEET_MAX_LEN,
        "chunk_tokens": FLEET_CHUNK,
        "chips": dict(FLEET_CHIPS),
        "ttft_slo_model_s": FLEET_TTFT_SLO_S,
        "fleet": fleet_rep,
        "baselines": baselines,
        "best_baseline": best_name,
        "attainment": fleet_rep["attainment"],
        "jtok_ratio_fleet_vs_best_baseline": jtok_ratio,
        "fleet_attain_gate_min": FLEET_SLO_ATTAIN_MIN,
        "fleet_jtok_gate_max_ratio": FLEET_JTOK_RATIO_MAX,
        "encdec_member": {
            "n_requests": e_long + e_short,
            "fleet": e_rep,
            "baseline_v5e": eb_rep,
        },
    }
    dump("serving_fleet", payload)
    cls = fleet_rep["sla"]["interactive"]
    rows = [
        row("serve_fleet", fleet_rep["wall_s"] * 1e6,
            f"J/tok={fleet_rep['fleet_j_per_token']:.2e} "
            f"(x{jtok_ratio:.3f} vs best single engine "
            f"[{best_name}], gate <= {FLEET_JTOK_RATIO_MAX}) "
            f"attainment={fleet_rep['attainment']:.3f} "
            f"(gate >= {FLEET_SLO_ATTAIN_MIN}) "
            f"interactive ttft p95={cls['ttft_fleet_p95_model_s'] * 1e3:.2f}"
            f"ms (slo={FLEET_TTFT_SLO_S * 1e3:.0f}ms) "
            f"parks={fleet_rep['parks']} drains={fleet_rep['drains']}"),
    ]
    return rows, payload


# ---- chaos smoke: --chaos ----
# deterministic fault schedule on the fleet model clock: one stall (the
# detector-and-evict path) and one state-preserved crash (the migration
# path), both pinned to fractions of the measured no-fault makespan.
# gates: interactive SLO attainment under faults, zero requests lost,
# fleet J/token within a bounded factor of the no-fault run, and token
# streams bit-identical to the no-fault run (migration preserves state;
# replay re-derives the same greedy stream).
CHAOS_SLO_ATTAIN_MIN = float(os.environ.get("CHAOS_SLO_ATTAIN_MIN", "0.90"))
CHAOS_JTOK_RATIO_MAX = float(os.environ.get("CHAOS_JTOK_RATIO_MAX", "1.3"))
CHAOS_STALL_FACTOR = float(os.environ.get("CHAOS_STALL_FACTOR", "8.0"))


def _serve_chaos(cfg, model, params, seed: int, n_long: int, n_short: int,
                 plan=None):
    """One warmed + timed fleet pass with an optional `FaultPlan` armed
    *after* the warm-up reset, so event times land on the measured run's
    clock. Interactive requests use a defer (not shed) overload policy:
    faults may stretch latency but must never drop work — the zero-lost
    gate depends on it."""
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.scheduler import FleetScheduler, SLAClass

    engines = {
        name: ServingEngine(model, params, cfg, max_batch=FLEET_MAX_BATCH,
                            max_len=FLEET_MAX_LEN, mode="continuous",
                            admission="chunked", chunk_tokens=FLEET_CHUNK,
                            chip=chip)
        for name, chip in FLEET_CHIPS.items()}
    sched = FleetScheduler(
        engines,
        sla={"interactive": SLAClass("interactive", FLEET_TTFT_SLO_S,
                                     policy="defer", defer_s=0.01,
                                     max_defers=2),
             "batch": SLAClass("batch", None)})
    for pass_uid0 in (100_000, 0):      # warm-up, then the timed pass
        for uid, prompt, mnt, sla in _fleet_workload(cfg, n_long,
                                                     n_short, seed):
            sched.submit(Request(uid=pass_uid0 + uid, prompt=prompt,
                                 max_new_tokens=mnt), sla=sla)
        if pass_uid0:
            sched.run_until_empty()
            sched.reset_stats()
    sched.arm_faults(plan)
    t0 = time.perf_counter()
    results = sched.run_until_empty()
    rep = sched.report()
    rep["wall_s"] = time.perf_counter() - t0
    return results, rep, sched


def run_chaos(smoke: bool, seed: int) -> tuple[list[dict], dict]:
    """Chaos smoke: the fleet mix served healthy, then under a seeded
    1-stall + 1-crash plan, then under mid-run predictor-artifact
    corruption. Faults may move work and stretch latency but must never
    lose a request or change a token."""
    from repro.serving.faults import FaultEvent, FaultPlan

    cfg, model, params = _build(smoke)
    n_long, n_short = (2, 8) if smoke else (4, 16)
    n_reqs = n_long + n_short

    base_out, base_rep, _ = _serve_chaos(cfg, model, params, seed,
                                         n_long, n_short)
    horizon = base_rep["makespan_model_s"]
    ref = {r.uid: np.asarray(r.tokens) for r in base_out}

    def _check(results, rep, label):
        if len(results) != n_reqs:
            raise AssertionError(
                f"{label}: {n_reqs - len(results)} request(s) lost "
                f"({len(results)}/{n_reqs} completed)")
        for r in results:
            if not np.array_equal(np.asarray(r.tokens), ref[r.uid]):
                raise AssertionError(
                    f"{label}: stream mismatch for request {r.uid} — "
                    f"faults changed tokens")
        assert rep["requests"] == n_reqs

    # stall early (straggler-detector eviction path), then crash the
    # other member with device state intact (migration path) once the
    # stalled one is back to absorb its in-flight work
    plan = FaultPlan([
        FaultEvent(0.15 * horizon, "stall", "ada",
                   factor=CHAOS_STALL_FACTOR, duration_s=0.25 * horizon),
        FaultEvent(0.55 * horizon, "crash", "v5e", state_lost=False),
    ], seed=seed)
    chaos_out, chaos_rep, _ = _serve_chaos(cfg, model, params, seed,
                                           n_long, n_short, plan=plan)
    _check(chaos_out, chaos_rep, "chaos")
    if chaos_rep["faults"]["crashes"] != 1:
        raise AssertionError("chaos plan's crash event did not fire")

    # separate scenario: predictor-artifact corruption mid-run must
    # degrade tuning to BASELINE configs and keep serving — flagged,
    # streams untouched
    corrupt = FaultPlan([
        FaultEvent(0.3 * horizon, "artifact_corruption", "v5e"),
    ], seed=seed)
    deg_out, deg_rep, _ = _serve_chaos(cfg, model, params, seed,
                                       n_long, n_short, plan=corrupt)
    _check(deg_out, deg_rep, "degraded")
    if deg_rep["faults"]["degraded_members"] != ["v5e"]:
        raise AssertionError(
            "artifact corruption did not flag the member as degraded: "
            f"{deg_rep['faults']['degraded_members']}")

    base_jtok = base_rep["fleet_j_per_token"]
    jtok_ratio = (chaos_rep["fleet_j_per_token"] / base_jtok
                  if base_jtok > 0 else 0.0)
    f = chaos_rep["faults"]
    payload = {
        "seed": seed,
        "n_requests": n_reqs,
        "n_long": n_long,
        "chips": dict(FLEET_CHIPS),
        "ttft_slo_model_s": FLEET_TTFT_SLO_S,
        "stall_factor": CHAOS_STALL_FACTOR,
        "plan": f["plan"],
        "no_fault": base_rep,
        "chaos": chaos_rep,
        "degraded": deg_rep,
        "attainment": chaos_rep["attainment"],
        "jtok_ratio_chaos_vs_no_fault": jtok_ratio,
        "requests_lost": n_reqs - len(chaos_out),
        "chaos_attain_gate_min": CHAOS_SLO_ATTAIN_MIN,
        "chaos_jtok_gate_max_ratio": CHAOS_JTOK_RATIO_MAX,
    }
    dump("serving_chaos", payload)
    rows = [
        row("serve_chaos", chaos_rep["wall_s"] * 1e6,
            f"crashes={f['crashes']} evictions={f['evictions']} "
            f"stalls={f['stalls']} migrations={f['migrations']} "
            f"replays={f['replays']} "
            f"lost_J={f['lost_energy_j']:.2e} "
            f"attainment={chaos_rep['attainment']:.3f} "
            f"(gate >= {CHAOS_SLO_ATTAIN_MIN}) "
            f"J/tok=x{jtok_ratio:.3f} vs no-fault "
            f"(gate <= {CHAOS_JTOK_RATIO_MAX})"),
        row("serve_chaos_degraded", deg_rep["wall_s"] * 1e6,
            f"degraded={deg_rep['faults']['degraded_members']} "
            f"streams bit-identical to healthy run"),
    ]
    return rows, payload


# ---- SSM serve-grain sweep: --grain ----
GRAINS = (8, 32, 64)
GRAIN_PROMPT_LEN = 448
GRAIN_MAX_LEN = 512
GRAIN_CHUNK = 128          # a multiple of every grain in the sweep


def _build_grain():
    import jax

    from repro.models.config import ModelConfig
    from repro.models.registry import get_model

    cfg = ModelConfig(
        name="serve-grain", kind="mamba2", n_layers=2, d_model=256,
        n_heads=4, n_kv_heads=2, d_ff=256, vocab=512,
        expand=2, ssm_state=16, ssm_headdim=64,
        param_dtype="float32", activation_dtype="float32", remat=False)
    model = get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    return cfg, model, params


def run_grain(smoke: bool) -> tuple[list[dict], dict]:
    """Long-prompt mamba2 prefill throughput vs the SSM serve-scan grain:
    the default 8-token block scans a 448-token prompt in 56 sequential
    `lax.scan` steps; grain 32/64 recovers throughput with 4x/8x fewer
    steps. Streams are asserted bit-identical between chunked admission
    and single-shot prefill *within* each grain (the serving parity
    contract — grain is part of the numerics, so streams are only
    comparable at equal grain)."""
    from repro.serving.engine import Request, ServingEngine

    cfg, model, params = _build_grain()
    n_reqs = 2 if smoke else 4
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, GRAIN_PROMPT_LEN)
               .astype(np.int32) for _ in range(n_reqs)]
    total_prompt = n_reqs * GRAIN_PROMPT_LEN

    per_grain = {}
    for g in GRAINS:
        streams = {}
        rep = None
        for adm in ("serial", "chunked"):
            eng = ServingEngine(model, params, cfg, max_batch=2,
                                max_len=GRAIN_MAX_LEN, mode="continuous",
                                admission=adm, chunk_tokens=GRAIN_CHUNK,
                                ssm_serve_grain=g)
            for uid, p in enumerate(prompts):   # warm-up (jit traces)
                eng.submit(Request(uid=100 + uid, prompt=p.copy(),
                                   max_new_tokens=1))
            eng.run_until_empty()
            eng.reset_stats()
            # budget 1: requests finish on their first sampled token, so
            # the timed pass is pure prefill — the surface grain targets
            for uid, p in enumerate(prompts):
                eng.submit(Request(uid=uid, prompt=p.copy(),
                                   max_new_tokens=1))
            t0 = time.perf_counter()
            res = eng.run_until_empty()
            wall = time.perf_counter() - t0
            streams[adm] = {r.uid: r.tokens.tolist() for r in res}
            if adm == "chunked":
                rep = eng.report()
                rep["wall_s"] = wall
        if streams["serial"] != streams["chunked"]:
            raise AssertionError(
                f"grain={g}: chunked/single-shot stream mismatch")
        per_grain[str(g)] = {
            "prefill_tokens_per_s_wall": (total_prompt / rep["wall_s"]
                                          if rep["wall_s"] > 0 else 0.0),
            "wall_s": rep["wall_s"],
            "model_s": rep["model_s"],
            "chunk_steps": rep["chunk_steps"],
        }
    base = per_grain[str(GRAINS[0])]["prefill_tokens_per_s_wall"]
    payload = {
        "n_requests": n_reqs,
        "prompt_len": GRAIN_PROMPT_LEN,
        "chunk_tokens": GRAIN_CHUNK,
        "grains": list(GRAINS),
        "per_grain": per_grain,
        "recovery_vs_grain8": {
            k: (v["prefill_tokens_per_s_wall"] / base if base > 0 else 0.0)
            for k, v in per_grain.items()},
    }
    dump("serving_ssm_grain", payload)
    rows = [row(
        f"serve_ssm_grain{g}",
        per_grain[str(g)]["wall_s"] * 1e6,
        f"prefill tok/s={per_grain[str(g)]['prefill_tokens_per_s_wall']:.0f}"
        f" (x{payload['recovery_vs_grain8'][str(g)]:.2f} vs grain 8, "
        f"{per_grain[str(g)]['chunk_steps']} chunk calls)")
        for g in GRAINS]
    return rows, payload


def run(smoke: bool | None = None, seed: int = 0) -> list[dict]:
    if smoke is None:
        # mirror benchmarks.common.default_n_configs: unset env = full scale
        smoke = int(os.environ.get("BENCH_N_CONFIGS", "16128")) <= 256
    cfg, model, params = _build(smoke)
    n_long, n_short = (2, 10) if smoke else (4, 20)
    reqs = _workload(cfg, n_long, n_short, seed=seed)

    out = {}
    reports = {}
    for label in ("chunked", "serial", "wave"):
        out[label], reports[label] = _serve(cfg, model, params, reqs, label)

    # identical greedy streams is a hard invariant, not a benchmark stat
    by_uid = {r.uid: r for r in out["wave"]}
    for label in ("chunked", "serial"):
        for r in out[label]:
            if not np.array_equal(r.tokens, by_uid[r.uid].tokens):
                raise AssertionError(
                    f"stream mismatch for request {r.uid} ({label})")

    rc, rs, rw = reports["chunked"], reports["serial"], reports["wave"]
    # the gated ratio uses the *model clock* (predicted step_s of every
    # dispatched call — deterministic, CI-machine independent); wall-clock
    # TTFT percentiles are reported alongside for the curious
    ttft_ratio = (rc["ttft_model_s"]["mean"] / rs["ttft_model_s"]["mean"]
                  if rs["ttft_model_s"]["mean"] > 0 else 0.0)
    ttft_wall_ratio = (rc["ttft_s"]["mean"] / rs["ttft_s"]["mean"]
                       if rs["ttft_s"]["mean"] > 0 else 0.0)
    payload = {
        "seed": seed,
        "n_requests": len(reqs),
        "n_long": n_long,
        "max_batch": MAX_BATCH,
        "max_len": MAX_LEN,
        "chunk_tokens": CHUNK_TOKENS,
        "budgets": list(BUDGETS),
        "chunked": rc,
        "serial": rs,
        "wave": rw,
        "ttft_ratio_chunked_vs_serial": ttft_ratio,
        "ttft_wall_ratio_chunked_vs_serial": ttft_wall_ratio,
        "ttft_gate_max_ratio": SMOKE_TTFT_RATIO_MAX,
        "slot_step_reduction": (
            1.0 - rc["slot_steps"] / rw["slot_steps"]
            if rw["slot_steps"] else 0.0),
        "j_per_token_reduction": (
            1.0 - rc["j_per_token"] / rw["j_per_token"]
            if rw["j_per_token"] else 0.0),
    }
    admit_rows, admit_payload = run_admit(smoke, seed=seed)
    run.last_admit_payload = admit_payload
    payload["admit_families"] = admit_payload["families"]
    dump("serving", payload)
    run.last_payload = payload
    # the chunked-mode report is also dumped standalone so CI artifact
    # diffs of the fused-admission path stay one file
    dump("serving_chunked", {"workload": payload["n_requests"],
                             "report": rc})
    dump("serving_wave", {"workload": payload["n_requests"],
                          "report": rw})

    def derived(rep):
        return (f"tok/s={rep['tokens_per_s']:.0f} "
                f"J/tok={rep['j_per_token']:.2e} "
                f"occ={rep['slot_occupancy']:.2f} "
                f"ttft(mean/p50/p95)="
                f"{rep['ttft_s']['mean'] * 1e3:.1f}/"
                f"{rep['ttft_s']['p50'] * 1e3:.1f}/"
                f"{rep['ttft_s']['p95'] * 1e3:.1f}ms "
                f"model-ttft={rep['ttft_model_s']['mean'] * 1e3:.2f}ms")

    paged_rows, paged_payload = run_paged(smoke, cfg, model, params,
                                          seed=seed)
    run.last_paged_payload = paged_payload

    return [
        row("serve_chunked", rc["wall_s"] * 1e6, derived(rc)),
        row("serve_serial", rs["wall_s"] * 1e6, derived(rs)),
        row("serve_wave", rw["wall_s"] * 1e6, derived(rw)),
        row("serve_ttft_ratio", 0.0,
            f"chunked/serial mean TTFT ratio={ttft_ratio:.3f} "
            f"(model clock; wall={ttft_wall_ratio:.3f}; "
            f"gate <= {SMOKE_TTFT_RATIO_MAX}); "
            f"{100 * payload['slot_step_reduction']:.1f}% fewer "
            f"decode-step*slots vs wave; J/tok "
            f"-{100 * payload['j_per_token_reduction']:.1f}%"),
    ] + admit_rows + paged_rows


def main(argv: list[str]) -> int:
    smoke = "--smoke" in argv
    seed = (int(argv[argv.index("--seed") + 1]) if "--seed" in argv
            else 0)
    special = False
    if "--fleet" in argv:
        special = True
        f_rows, fp = run_fleet(smoke, seed)
        for r in f_rows:
            print(f"{r['name']}: {r['derived']}")
        best = fp["baselines"][fp["best_baseline"]]
        if best["fleet_j_per_token"] <= 0.0:
            print("FLEET GATE FAILED: best single-engine baseline "
                  "J/token is 0 (energy model unavailable?) — gate "
                  "cannot assess")
            return 1
        if fp["attainment"] < FLEET_SLO_ATTAIN_MIN:
            print(f"FLEET GATE FAILED: interactive SLO attainment "
                  f"{fp['attainment']:.3f} < {FLEET_SLO_ATTAIN_MIN} "
                  f"(ttft slo {FLEET_TTFT_SLO_S * 1e3:.0f}ms on the "
                  f"fleet model clock)")
            return 1
        jr = fp["jtok_ratio_fleet_vs_best_baseline"]
        if jr > FLEET_JTOK_RATIO_MAX:
            print(f"FLEET GATE FAILED: fleet J/token is x{jr:.3f} of "
                  f"the best single engine ({fp['best_baseline']}) > "
                  f"{FLEET_JTOK_RATIO_MAX} at equal streams")
            return 1
        print(f"fleet gates ok: streams bit-identical across scenarios, "
              f"attainment {fp['attainment']:.3f} >= "
              f"{FLEET_SLO_ATTAIN_MIN}, J/tok x{jr:.3f} vs best single "
              f"engine [{fp['best_baseline']}] <= {FLEET_JTOK_RATIO_MAX}")
    if "--chaos" in argv:
        special = True
        c_rows, cp = run_chaos(smoke, seed)
        for r in c_rows:
            print(f"{r['name']}: {r['derived']}")
        if cp["no_fault"]["fleet_j_per_token"] <= 0.0:
            print("CHAOS GATE FAILED: no-fault fleet J/token is 0 "
                  "(energy model unavailable?) — gate cannot assess")
            return 1
        if cp["requests_lost"] != 0:
            print(f"CHAOS GATE FAILED: {cp['requests_lost']} request(s) "
                  f"lost under the fault plan")
            return 1
        if cp["attainment"] < CHAOS_SLO_ATTAIN_MIN:
            print(f"CHAOS GATE FAILED: interactive SLO attainment "
                  f"{cp['attainment']:.3f} < {CHAOS_SLO_ATTAIN_MIN} "
                  f"under 1 crash + 1 stall")
            return 1
        jr = cp["jtok_ratio_chaos_vs_no_fault"]
        if jr > CHAOS_JTOK_RATIO_MAX:
            print(f"CHAOS GATE FAILED: fleet J/token under faults is "
                  f"x{jr:.3f} of the no-fault run > "
                  f"{CHAOS_JTOK_RATIO_MAX}")
            return 1
        if cp["degraded"]["faults"]["degraded_members"] != ["v5e"]:
            print("CHAOS GATE FAILED: artifact corruption did not flag "
                  "the degraded member")
            return 1
        print(f"chaos gates ok (seed {cp['seed']}): streams "
              f"bit-identical to the no-fault run, 0 requests lost, "
              f"attainment {cp['attainment']:.3f} >= "
              f"{CHAOS_SLO_ATTAIN_MIN}, J/tok x{jr:.3f} <= "
              f"{CHAOS_JTOK_RATIO_MAX}, BASELINE downgrade flagged")
    if "--tp" in argv:
        tp = int(argv[argv.index("--tp") + 1])
        special = True
        tp_rows, tp_payload = run_tp(tp, smoke)
        for r in tp_rows:
            print(f"{r['name']}: {r['derived']}")
        if tp_payload["tp1"]["model_tokens_per_s"] <= 0.0:
            print("TP GATE FAILED: tp=1 model-clock tokens/s is 0 "
                  "(energy model unavailable?) — gate cannot assess")
            return 1
        if tp_payload["model_speedup_tp_vs_1"] <= 1.0:
            print(f"TP GATE FAILED: model-clock tokens/s at tp={tp} is "
                  f"x{tp_payload['model_speedup_tp_vs_1']:.3f} of tp=1 — "
                  f"not strictly higher at equal streams")
            return 1
        if not tp_payload["overlap_factor"] > 0.0:
            print("TP GATE FAILED: collective overlap factor is 0 — "
                  "row-parallel all-gathers are not being pipelined")
            return 1
        jr = tp_payload["jtok_ratio_tp_vs_1"]
        if jr > TP_JTOK_RATIO_MAX:
            print(f"TP GATE FAILED: fleet J/token at tp={tp} is "
                  f"x{jr:.3f} of tp=1 > {TP_JTOK_RATIO_MAX}")
            return 1
        print(f"tp gates ok: streams bit-identical, model tokens/s "
              f"x{tp_payload['model_speedup_tp_vs_1']:.2f}, J/tok "
              f"x{jr:.2f} <= {TP_JTOK_RATIO_MAX}, overlap "
              f"{tp_payload['overlap_factor']:.3f}")
    if "--grain" in argv:
        special = True
        g_rows, g_payload = run_grain(smoke)
        for r in g_rows:
            print(f"{r['name']}: {r['derived']}")
        top = max(g_payload["recovery_vs_grain8"].values())
        print(f"grain sweep ok: streams bit-identical per grain, best "
              f"long-prompt prefill recovery x{top:.2f} vs grain 8")
    if special:
        return 0
    rows = run(smoke=smoke or None, seed=seed)
    for r in rows:
        print(f"{r['name']}: {r['derived']}")
    if smoke:
        payload = run.last_payload
        ratio = payload["ttft_ratio_chunked_vs_serial"]
        if payload["serial"]["ttft_model_s"]["mean"] <= 0.0:
            # a broken/unavailable energy model zeroes the model clock —
            # that must fail the gate loudly, not pass it vacuously
            print("TTFT GATE FAILED: serial model-clock TTFT is 0 "
                  "(energy model unavailable?) — gate cannot assess")
            return 1
        if ratio > SMOKE_TTFT_RATIO_MAX:
            print(f"TTFT GATE FAILED: chunked/serial mean TTFT ratio "
                  f"{ratio:.3f} > {SMOKE_TTFT_RATIO_MAX} — chunked "
                  f"admission has regressed on the prefill-stall mix")
            return 1
        print(f"TTFT gate ok: ratio {ratio:.3f} <= "
              f"{SMOKE_TTFT_RATIO_MAX}")
        pp = run.last_paged_payload
        if pp["concurrency_paged"] <= pp["concurrency_dense"]:
            print("PAGED GATE FAILED: paged concurrency "
                  f"{pp['concurrency_paged']} not above dense "
                  f"{pp['concurrency_dense']} at the fixed KV budget")
            return 1
        if pp["paged_peak_hbm_bytes"] > pp["kv_hbm_budget_bytes"]:
            print("PAGED GATE FAILED: paged peak HBM "
                  f"{pp['paged_peak_hbm_bytes']:.0f}B exceeds the dense "
                  f"budget {pp['kv_hbm_budget_bytes']:.0f}B")
            return 1
        if pp["dense"]["ttft_model_s"]["mean"] <= 0.0:
            print("PAGED GATE FAILED: dense model-clock TTFT is 0 "
                  "(energy model unavailable?) — gate cannot assess")
            return 1
        pr = pp["ttft_ratio_paged_vs_dense"]
        if pr > PAGED_TTFT_RATIO_MAX:
            print(f"PAGED GATE FAILED: paged/dense mean TTFT ratio "
                  f"{pr:.3f} > {PAGED_TTFT_RATIO_MAX} on the "
                  f"shared-prefix mix")
            return 1
        jr = pp["jtok_ratio_paged_vs_dense"]
        if jr > PAGED_JTOK_RATIO_MAX:
            print(f"PAGED GATE FAILED: paged/dense J/token ratio "
                  f"{jr:.3f} > {PAGED_JTOK_RATIO_MAX}")
            return 1
        print(f"paged gates ok: concurrency {pp['concurrency_dense']} -> "
              f"{pp['concurrency_paged']}, TTFT ratio {pr:.3f} <= "
              f"{PAGED_TTFT_RATIO_MAX}, J/tok ratio {jr:.3f} <= "
              f"{PAGED_JTOK_RATIO_MAX}")
        ap = run.last_admit_payload
        for kind, fam in ap["families"].items():
            if fam["serial"]["ttft_model_s"]["mean"] <= 0.0:
                print(f"ADMIT GATE FAILED: {kind} serial model-clock "
                      f"TTFT is 0 (energy model unavailable?) — gate "
                      f"cannot assess")
                return 1
            fr = fam["ttft_ratio_chunked_vs_serial"]
            if fr > SMOKE_TTFT_RATIO_MAX:
                print(f"ADMIT GATE FAILED: {kind} chunked/serial mean "
                      f"TTFT ratio {fr:.3f} > {SMOKE_TTFT_RATIO_MAX} — "
                      f"chunked admission has regressed for the "
                      f"prefill-once family")
                return 1
        ratios = ", ".join(
            f"{k}={v['ttft_ratio_chunked_vs_serial']:.3f}"
            for k, v in ap["families"].items())
        print(f"admit gates ok: streams bit-identical across admissions, "
              f"TTFT ratios {ratios} <= {SMOKE_TTFT_RATIO_MAX}")
    return 0


if __name__ == "__main__":
    _emulate_tp_devices(sys.argv[1:])
    sys.exit(main(sys.argv[1:]))
