"""Run one cell of the benchmark on this machine's TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell named in BENCHMARK.json from its files, warms up every
program its traffic can use, serves an open loop for `--seconds`, drains,
checks the served tokens against the plain reference, and prints one JSON
line: the cell's end-to-end metrics (`--trace 0`) or its per-layer
metrics (`--trace 1`, which traces part of the window). With no TPU, an
unknown device or too few chips, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def err(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def device_or_none(chips: int):
    """(devices, peaks entry) of this machine's TPU, or None after saying
    why on stderr."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        err(f"bench: needs a TPU; JAX found {dev.platform!r} devices")
        return None
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if dev.device_kind not in peaks:
        err(f"bench: no peaks for device kind {dev.device_kind!r} in "
            f"bench/peaks.json")
        return None
    if len(devices) < chips:
        err(f"bench: the cell needs {chips} chips, JAX found "
            f"{len(devices)}")
        return None
    return devices[:chips], peaks[dev.device_kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        err(f"bench: no program under {ROOT}/src: run from a checkout")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness

    spec = harness.cell_spec(args.workload)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    found = device_or_none(int(spec["workload"]["chips"]))
    if found is None:
        return 1
    devices, peaks = found
    from repro.core.chips import chip_for_device_kind
    from repro.kernels import ops

    chip = chip_for_device_kind(devices[0].device_kind)
    ops.force_chip(chip.name)
    err(f"bench: compile cache {cache_dir}")
    out = run_cell(spec, args, devices, peaks, chip)
    print(json.dumps(out), flush=True)
    return 0


def run_cell(spec, args, devices, peaks, chip) -> dict:
    """Everything of a run after the device check: set-up, window,
    drain, metrics, the check against the reference. Returns the result
    line's object (and prints the numbers compared on stderr)."""
    import check
    import harness
    import loadgen
    import tracereduce

    dev = devices[0]
    counter = harness.CompileCounter()
    cell = harness.Cell(spec, args.seed)
    err(f"bench: {args.workload} seed {args.seed} on {dev.device_kind} "
        f"x{len(devices)}")
    pretune_s = cell.build(chip)
    cell.warm_up()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = cell.serve(args.seconds, trace_dir=trace_dir, counter=counter)
    setup_s = run["t0"] - PROCESS_START
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    harness.annotate_steps(run["steps"], run["arrivals"], run["results"],
                           run["t0"])
    reduced = None
    if trace_dir is not None and run["trace_span"] is not None:
        path = tracereduce.find_xplane(trace_dir)
        if path is not None:
            raw = tracereduce.align(tracereduce.load(path), run["steps"],
                                    run["trace_span"][0])
            reduced = tracereduce.reduce(raw, tuple(run["trace_span"]),
                                         run["steps"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    late = sorted(run["late"])
    if late:
        err(f"bench: generator lateness over {len(late)} submissions: "
            f"median {late[len(late) // 2]:.6f}s, max {late[-1]:.6f}s")
    record = {"spec": spec, "m": cell.m, "work": cell.work, "peaks": peaks,
              "run": run, "setup_s": setup_s, "pretune_s": pretune_s,
              "trace": reduced}
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec[kind]:
        value = harness.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---- correctness, after the window, with the program's state freed
    arrivals, results = run["arrivals"], run["results"]
    bad = check.failures(arrivals, results, cell.m["vocab"])
    for line in bad[:20]:
        err(f"bench: FAILED {line}")
    picked = check.sample(arrivals, results, args.seed)
    cell.free()
    params = cell.ref_params()
    t_ref = time.perf_counter()
    sc = check.score(
        cell.ref, cell.m, params, picked, results, cell.geo["max_len"],
        loadgen.longest(spec["traffic"]["output"]))
    del params
    scored = sc["tokens"]
    err(f"bench: reference over {len(picked)} requests, {scored} served "
        f"tokens in {time.perf_counter() - t_ref:.3f}s; mean gap "
        f"{sc['mean_gap']!r}, share not the reference's first "
        f"{sc['mismatch']!r}")
    compared = {"failed_requests": {"value": len(bad), "limit": 0}}
    for name, limit in spec["cell"]["limits"].items():
        compared[name] = {"value": sc[check.COMPARED[name]],
                          "limit": float(limit)}
    correct = (scored >= 1 and all(v["value"] <= v["limit"]
                                   for v in compared.values()))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(arrivals),
           "failed": len(bad), "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = sum(d["busy_s"] for d in
                               reduced["devices"].values()) / max(
                                   len(reduced["devices"]), 1)
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": tracereduce.top_ops(reduced),
                            "idle_gaps": tracereduce.idle_gaps(reduced)}
    out["compared"] = compared
    for k, v in compared.items():
        err(f"compared {k}: {v['value']!r} limit {v['limit']!r}")
    return out


if __name__ == "__main__":
    sys.exit(main())
