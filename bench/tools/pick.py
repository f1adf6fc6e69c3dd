"""Set a cell's rate or correctness limit from the chip readings of
`sweep.py` or `calibrate.py` (their JSON lines on standard input), and
write it into `bench/cells/<workload>.json`.

    python3 bench/tools/pick.py rate WORKLOAD < sweep.jsonl
    python3 bench/tools/pick.py limit WORKLOAD < calibrate.jsonl

rate: the knee is the highest rate below the first whose backlog grew:
at the window's close more requests waited than at its middle, and more
than the engine's decode slots (fewer is a burst, not a backlog), or a
request was left unserved; the cell offers 0.8 of it. A sweep in which no rate fell
behind, or the lowest did, sets no rate: it is made again wider. limit: for each number compared (the widest and the
mean logit gap), the lower reading is the program's largest over the
seeds and the upper the control's smallest; the first number whose
upper reading is at least 3 times its lower gets a limit between them,
lower^0.4 * upper^0.6 (more room above the lower: fresh seeds read
higher), to three significant digits.
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NUMBERS = (("max_logit_gap", "max_gap"), ("mean_logit_gap", "mean_gap"))


def sig3(x: float) -> float:
    return float(f"{x:.3g}")


def pick_rate(rows: list[dict]) -> tuple[float, dict]:
    rows = sorted(rows, key=lambda r: r["rate_per_s"])
    behind = [i for i, r in enumerate(rows)
              if r["waiting_close"] > max(r["waiting_mid"], r["slots"])
              or r["finished"] < r["attempted"]]
    if not behind:
        raise SystemExit("every swept rate kept up: the knee lies above "
                         f"{rows[-1]['rate_per_s']} req/s; sweep higher")
    if behind[0] == 0:
        raise SystemExit("the lowest swept rate fell behind: the knee lies "
                         f"below {rows[0]['rate_per_s']} req/s; sweep lower")
    knee = rows[behind[0] - 1]["rate_per_s"]
    return sig3(0.8 * knee), {"knee_per_s": knee,
                              "first_behind_per_s":
                                  rows[behind[0]]["rate_per_s"]}


def pick_limit(rows: list[dict]) -> tuple[dict, dict]:
    info = {}
    for name, key in NUMBERS:
        lower = max(r[key] for r in rows)
        upper = min(r["ctrl_" + key] for r in rows)
        info[name] = {"lower": lower, "upper": upper}
        if lower > 0 and upper >= 3 * lower:
            return {name: sig3(lower ** 0.4 * upper ** 0.6)}, info
    raise SystemExit(f"no number separates program and control: {info}")


def main(what: str, workload: str) -> int:
    rows = [json.loads(line) for line in sys.stdin if line.startswith("{")]
    path = os.path.join(BENCH, "cells", f"{workload}.json")
    with open(path) as f:
        cell = json.load(f)
    if what == "rate":
        cell["rate_per_s"], info = pick_rate(rows)
    else:
        cell["limits"], info = pick_limit(rows)
    with open(path, "w") as f:
        json.dump(cell, f, indent=2)
        f.write("\n")
    print(json.dumps({"workload": workload, "set": what, "cell": cell,
                      **info}))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
