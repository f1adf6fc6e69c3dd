"""Open-loop request schedules from a traffic mix file.

A mix (`bench/traffic/<mix>.json`) gives the arrival process and the
lengths: `{"dist": "fixed", "tokens": n}` for every request. The schedule
itself (due times, prompt and output lengths, in order) is drawn once
from the mix's own `shape_seed`, and `--seed` draws only the token ids.
So every seed offers the same work at the same times, and the bounds of
a cell hold for that one arrangement of arrivals: on the chip, an
earlier mix of spread lengths in an order permuted per seed moved the
TTFT and TPOT tails by up to 2x between seeds (which long outputs held
the decode slots when decided who waited), while two runs of one seed
agreed within a few percent.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of the schedule: due `due_s` seconds after the window
    opens, with its prompt ids and its output budget (no EOS)."""

    uid: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def load_mix(name: str) -> dict:
    """The traffic mix `bench/traffic/<name>.json`."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def longest(spec: dict) -> int:
    """The most tokens a length distribution gives."""
    if spec["dist"] != "fixed":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(spec["tokens"])


def _lengths(spec: dict, n: int) -> np.ndarray:
    return np.full(n, longest(spec), np.int64)


def shapes(mix: dict, n: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The seed-independent shapes: (prompt lengths, output lengths) of
    `n` requests and `n + 1` unit-mean inter-arrival gaps."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rng = np.random.default_rng(int(mix["shape_seed"]))
    prompts = _lengths(mix["prompt"], n)
    outputs = _lengths(mix["output"], n)
    gaps = rng.exponential(1.0, n + 1)
    return prompts, outputs, gaps


def schedule(mix: dict, rate_per_s: float, seconds: float, seed: int,
             vocab: int) -> list[Arrival]:
    """`round(rate * seconds)` requests due inside `[0, seconds)`.

    The gaps are scaled so that `n + 1` of them span the window exactly:
    the offered rate is `n / seconds`. `seed` draws uniform token ids."""
    n = max(1, int(round(rate_per_s * seconds)))
    prompts, outputs, gaps = shapes(mix, n)
    due = np.cumsum(gaps[:n]) * (seconds / gaps.sum())
    rng = np.random.default_rng(int(seed))
    ids = rng.integers(0, vocab, int(prompts.sum()), dtype=np.int64)
    out, at = [], 0
    for uid in range(n):
        p = int(prompts[uid])
        out.append(Arrival(uid=uid, due_s=float(due[uid]),
                           prompt=ids[at:at + p].astype(np.int32),
                           max_new_tokens=int(outputs[uid])))
        at += p
    return out
