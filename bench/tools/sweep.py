"""Find a cell's knee on the chip: serve its traffic at each offered rate
for a window and report whether the backlog grew.

    python3 bench/tools/sweep.py WORKLOAD SECONDS SEED RATE [RATE ...]

One engine serves every rate in turn (drained in between). For each rate
it prints one JSON line: requests waiting (queued, prefilling or parked)
at the window's middle and at its close, the engine's decode slots, the
TTFT and TPOT tails, tokens per second and the drain time; `pick.py rate`
finds the knee in these lines. The sweep stops after two rates in a row
whose backlog grew.
"""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def main(workload: str, seconds: str, seed: str, *rates: str) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness
    import run as runmod

    spec = harness.cell_spec(workload)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    found = runmod.device_or_none(int(spec["workload"]["chips"]))
    if found is None:
        return 1
    from repro.core.chips import chip_for_device_kind
    from repro.kernels import ops

    chip = chip_for_device_kind(found[0][0].device_kind)
    ops.force_chip(chip.name)
    counter = harness.CompileCounter()
    cell = harness.Cell(spec, int(seed))
    t0 = time.perf_counter()
    cell.build(chip)
    cell.warm_up()
    print(f"set-up {time.perf_counter() - t0:.1f}s", file=sys.stderr,
          flush=True)
    secs = float(seconds)
    growing = 0
    for rate in rates:
        spec["cell"] = dict(spec["cell"], rate_per_s=float(rate))
        run = cell.serve(secs, counter=counter)
        rec = {"m": cell.m, "work": cell.work, "run": run}

        read = {n: harness.metric_reader(n) for n in
                ("ttft_p90_s", "tpot_p90_ms", "tokens_per_s")}
        t_close = run["close"]["t"]
        mid = run["t0"] + secs / 2

        def waiting_at(t):
            before = [s for s in run["steps"] if s.t1 <= t]
            return before[-1].waiting if before else 0

        last = max(s.t1 for s in run["steps"]) if run["steps"] else t_close
        print(json.dumps({
            "workload": workload, "rate_per_s": float(rate),
            "attempted": len(run["arrivals"]),
            "finished": len(run["results"]),
            "waiting_mid": waiting_at(mid), "waiting_close":
                waiting_at(t_close), "slots": cell.geo["max_batch"],
            **{k: f(rec) for k, f in read.items()},
            "drain_s": last - t_close,
            "window_compiles": run["close"]["compiles"]}), flush=True)
        # past the knee twice in a row: higher rates only queue longer
        grew = waiting_at(t_close) > max(waiting_at(mid),
                                          cell.geo["max_batch"])
        growing = growing + 1 if grew else 0
        if growing == 2:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
