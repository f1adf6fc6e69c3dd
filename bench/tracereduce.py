"""Reduce a JAX profiler trace to what the per-layer metrics read.

The device planes (`/device:TPU:<n>`) give the operations that ran and
when; the host plane gives the benchmark's own spans (`bench:serve_step`)
and what the host was doing in each idle gap. Every time here is in
seconds on the host's `perf_counter` clock, so device time lines up with
the benchmark's own log of steps.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_SPAN = "bench:serve_step"
# operations that hold others (a layer scan's loop): their time is their
# body's, so the breakdown leaves them out
CONTROL_FLOW = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    """One device operation: its HLO instruction name, that name with the
    instruction's result type (what kernel patterns match), the program
    execution it ran in (`<program>#<run id>`), its start and duration."""

    name: str
    text: str
    run: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def start_options():
    """Profiler options of a traced window: the benchmark's own spans and
    the runtime's, no Python function tracing (it slows every call)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def _run_id(ev) -> str:
    for k, v in ev.stats:
        if k == "run_id":
            return str(v)
    return ""


def _device_ops(plane) -> list[Op]:
    """The plane's operations, each given the program execution (an
    event of the modules line) that holds it."""
    lines = {ln.name: ln for ln in plane.lines}
    mods = sorted(((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                    f"{ev.name}#{_run_id(ev)}")
                   for ev in lines[MODULES_LINE].events)
                  if MODULES_LINE in lines else [])
    ops, j = [], 0
    use = [lines[OPS_LINE]] if OPS_LINE in lines else list(lines.values())
    for line in use:
        for ev in line.events:
            t = ev.start_ns * 1e-9
            while j < len(mods) and mods[j][1] < t:
                j += 1
            run = mods[j][2] if j < len(mods) and mods[j][0] <= t else ""
            head, _, rest = ev.name.partition(" = ")
            ops.append(Op(head, f"{head} {rest.split(' ', 1)[0]}", run, t,
                          ev.duration_ns * 1e-9))
    return sorted(ops, key=lambda o: o.start)


def load(path: str) -> dict:
    """Device operations per device and the host spans of the trace at
    `path`, in seconds from the trace's own origin (see `align`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list[Op]] = {}
    host: list[tuple[str, float, float, str]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = _device_ops(plane)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9, line.name))
    return {"devices": devices, "host": host}


def align(raw: dict, steps: list, trace_start: float) -> dict:
    """Put the trace on the benchmark's clock. The loop is blocked while
    the profiler starts, so the first `bench:serve_step` span of the trace
    is the first logged step after `trace_start`: shift every time by the
    difference of their starts."""
    spans = sorted(s for n, s, _, _ in raw["host"] if n == STEP_SPAN)
    after = [st.t0 for st in steps if st.t0 >= trace_start]
    if not spans or not after:
        raise ValueError("the trace holds no step span to align on")
    d = min(after) - spans[0]
    for ops in raw["devices"].values():
        for op in ops:
            op.start += d
    raw["host"] = [(n, s + d, dur, ln) for n, s, dur, ln in raw["host"]]
    return raw


def busy_intervals(ops: list[Op], t0: float, t1: float
                   ) -> list[tuple[float, float]]:
    """The union of the operations' intervals, clipped to [t0, t1]."""
    out: list[list[float]] = []
    for op in ops:
        a, b = max(op.start, t0), min(op.end, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def executions(ops: list[Op]) -> list[list[Op]]:
    """Device operations grouped by program execution, in the order the
    executions started."""
    runs: dict[str, list[Op]] = {}
    for op in ops:
        runs.setdefault(op.run, []).append(op)
    return sorted(runs.values(), key=lambda r: r[0].start)


def step_executions(ops: list[Op], steps: list) -> list[list[list[Op]]]:
    """For each logged step, the executions that ran inside its span. The
    device clock and the host clock can disagree by a fraction of a
    millisecond, so the device times are first shifted by the offset
    (within +-5 ms) that puts the most executions wholly inside a step."""
    import bisect

    ex = executions(ops)
    starts = [s.t0 for s in steps]

    def where(run, d):
        i = bisect.bisect_right(starts, run[0].start + d) - 1
        end = max(o.end for o in run) + d
        return i if 0 <= i < len(steps) and end <= steps[i].t1 else -1

    best = max((sum(where(r, d * 1e-5) >= 0 for r in ex), -abs(d), d)
               for d in range(-500, 501, 5))[2] * 1e-5
    out: list[list[list[Op]]] = [[] for _ in steps]
    for run in ex:
        i = where(run, best)
        if i >= 0:
            out[i].append(run)
    return out


def reduce(raw: dict, span: tuple[float, float], steps: list) -> dict:
    """What the readers need from one traced span: per device, busy
    seconds and operations; the steps wholly inside the span with the
    executions each ran; the top operations and the longest idle gaps."""
    t0, t1 = span
    inside = [s for s in steps if s.t0 >= t0 and s.t1 <= t1]
    per_dev = {}
    for name, ops in raw["devices"].items():
        ops = [o for o in ops if o.end > t0 and o.start < t1]
        busy = busy_intervals(ops, t0, t1)
        per_dev[name] = {"ops": ops, "busy": busy,
                         "busy_s": sum(b - a for a, b in busy)}
    first = per_dev[sorted(per_dev)[0]] if per_dev else None
    return {"span": (t0, t1), "window_s": t1 - t0, "devices": per_dev,
            "steps": inside,
            "step_runs": (step_executions(first["ops"], inside)
                          if first else []),
            "host": [h for h in raw["host"] if h[1] < t1 and h[1] + h[2] > t0]}


def top_ops(reduced: dict, n: int = 10) -> list[list]:
    """The device operations that took most time (first device), by HLO
    instruction name with its instance number dropped."""
    if not reduced["devices"]:
        return []
    dev = reduced["devices"][sorted(reduced["devices"])[0]]
    tot: dict[str, float] = {}
    for op in dev["ops"]:
        key = re.sub(r"\.\d+$", "", op.name.lstrip("%"))
        if key in CONTROL_FLOW:
            continue
        tot[key] = tot.get(key, 0.0) + op.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(reduced: dict, n: int = 10) -> list[list]:
    """The longest gaps between device operations (first device), each
    named by the innermost host span covering its middle."""
    if not reduced["devices"]:
        return []
    busy = reduced["devices"][sorted(reduced["devices"])[0]]["busy"]
    t0, t1 = reduced["span"]
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        cover = [h for h in reduced["host"] if h[1] <= mid <= h[1] + h[2]]
        name = (min(cover, key=lambda h: h[2])[0] if cover
                else "no host span")
        out.append([name, b - a])
    return out
