"""Readings that a cell's correctness limit is set from, taken on the
chip in one process: for each seed, the cell's traffic served for a short
window at the cell's rate and drained, then the widest logit gap of the
served tokens (the program) and of the tokens that the float8 control
puts first, over the same sample of requests as a run compares.

    python3 bench/tools/calibrate.py WORKLOAD SECONDS SEED [SEED ...]

One engine serves every seed: its weights are swapped for the seed's
between windows (the compiled programs take the weights as an argument,
so nothing recompiles). Prints one JSON line per seed.
"""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def main(workload: str, seconds: str, *seeds: str) -> int:
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
    import check
    import loadgen
    from repro.core.chips import chip_for_device_kind
    from repro.kernels import ops

    chip = chip_for_device_kind(found[0][0].device_kind)
    ops.force_chip(chip.name)
    counter = harness.CompileCounter()
    seeds = [int(s) for s in seeds]
    cell = harness.Cell(spec, seeds[0])
    t0 = time.perf_counter()
    cell.build(chip)
    cell.warm_up()
    print(f"set-up {time.perf_counter() - t0:.1f}s", file=sys.stderr,
          flush=True)
    n_max = loadgen.longest(spec["traffic"]["output"])
    for seed in seeds:
        cell.seed = seed
        cell.engine.params = None
        cell.params = None
        cell.params = cell.ref_params()
        cell.engine.params = cell.params
        run = cell.serve(float(seconds), counter=counter)
        arrivals, results = run["arrivals"], run["results"]
        bad = check.failures(arrivals, results, cell.m["vocab"])
        picked = check.sample(arrivals, results, seed)
        t1 = time.perf_counter()
        sc = check.score(cell.ref, cell.m, cell.params, picked, results,
                         cell.geo["max_len"], n_max, control=True)
        print(json.dumps({
            "workload": workload, "seed": seed, "attempted": len(arrivals),
            "failed": len(bad), "requests_scored": len(picked), **sc,
            "score_s": time.perf_counter() - t1,
            "window_compiles": run["close"]["compiles"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
