"""What the readers of the engine's spans share: device idle time inside
the `engine.*` spans the serving engine records (`docs/serving.md`,
"Tracing"), on the traced span's own clock."""

from __future__ import annotations

import bisect

STEP = "engine.step"


def spans(record, name: str) -> list[tuple[float, float]]:
    """The host spans called `name`, clipped to the traced span, in
    order. Spans of one name never overlap: one thread records them."""
    tr = record["trace"]
    t0, t1 = tr["span"]
    out = [(max(s, t0), min(s + d, t1)) for n, s, d, _ in tr["host"]
           if n == name]
    return sorted((a, b) for a, b in out if b > a)


def idle_s(busy: list[tuple[float, float]], a: float, b: float) -> float:
    """Seconds of [a, b] outside the sorted, disjoint `busy` intervals."""
    i = max(bisect.bisect_right(busy, (a, a)) - 1, 0)
    covered = 0.0
    while i < len(busy) and busy[i][0] < b:
        covered += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
        i += 1
    return (b - a) - covered


def step_idle_share(record, name: str) -> float | None:
    """Device idle inside the `name` spans over the summed length of the
    `engine.step` spans, in %; None where the trace holds no step span
    (a program that records none) or no device."""
    tr = record["trace"]
    if tr is None or not tr["devices"]:
        return None
    steps = spans(record, STEP)
    base = sum(b - a for a, b in steps)
    if base <= 0:
        return None
    busy = tr["devices"][sorted(tr["devices"])[0]]["busy"]
    idle = sum(idle_s(busy, a, b) for a, b in spans(record, name))
    return 100.0 * idle / base
