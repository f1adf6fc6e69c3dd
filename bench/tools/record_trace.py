"""Run one cell like `bench/run.py --trace 1` with a short traced span and
keep what the reduction read: the device operations (each with its
scope) and host spans of the span (times relative to the window's
opening) and the benchmark's log of steps, as gzipped JSON. That file is
the recorded input of the trace-reduction test.

    python3 bench/tools/record_trace.py WORKLOAD SEED SECONDS OUT.json.gz
"""

import dataclasses
import gzip
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

TRACED_S = 0.5


def dump(raw: dict, span, steps, t0: float, out: str) -> None:
    """Write the part of `raw` inside `span`, and the steps, with times
    relative to `t0`."""
    a, b = span
    devices = {
        name: [[o.name, o.text[:300], o.run, o.start - t0, o.dur, o.scope]
               for o in ops if o.end > a and o.start < b]
        for name, ops in raw["devices"].items()}
    host = [[n, s - t0, d, ln] for n, s, d, ln in raw["host"]
            if s < b and s + d > a]
    rows = []
    for st in steps:
        d = dataclasses.asdict(st)
        d["t0"] -= t0
        d["t1"] -= t0
        d["finished"] = [[u, t - t0] for u, t in d["finished"]]
        rows.append(d)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with gzip.open(out, "wt") as f:
        json.dump({"span": [a - t0, b - t0], "devices": devices,
                   "host": host, "steps": rows}, f)


def main(workload: str, seed: str, seconds: str, out: str) -> int:
    import harness
    import run
    import tracereduce

    harness.TRACE_SECONDS = TRACED_S
    reduce = tracereduce.reduce

    def keep(raw, span, steps):
        t0 = min(s.t0 for s in steps)
        dump(raw, span, steps, t0, out)
        return reduce(raw, span, steps)

    tracereduce.reduce = keep
    return run.main(["--workload", workload, "--seed", seed,
                     "--seconds", seconds, "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
