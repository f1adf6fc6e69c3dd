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
# the stat of a device operation's event metadata that holds its HLO
# `op_name` path (the jit names, named scopes and primitive it was traced
# under) as `<path>:`; the metadata's `program_id` names its program
SCOPE_STAT = "tf_op"
PROGRAM_STAT = "program_id"


@dataclasses.dataclass
class Op:
    """One device operation: its HLO instruction name, that name with the
    instruction's result type (what kernel patterns match), the program
    execution it ran in (`<program>#<run id>`), its start and duration,
    and its scope: the `op_name` path it was traced under ("" where the
    trace does not say)."""

    name: str
    text: str
    run: str
    start: float
    dur: float
    scope: str = ""

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


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of each field of the protobuf message in
    `buf[i:end]`: a varint as its number, a length-delimited field as its
    (start, end) in `buf`; fixed-width fields are passed over."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _map_values(buf: bytes, entries: list) -> dict:
    """{key: (start, end) of the value} of protobuf map entries."""
    out = {}
    for a, b in entries:
        f = dict(_fields(buf, a, b))
        out[f.get(1, 0)] = f.get(2, (b, b))
    return out


def op_scopes(path: str) -> dict[str, dict[tuple[str, str], str]]:
    """Per device plane of the XSpace file at `path`, the scope of each
    operation it names: {(program id, operation name): op_name path},
    from the `SCOPE_STAT` of the plane's event metadata. `ProfileData`
    gives an event's own stats only, so this reads the metadata from the
    file (XSpace.planes 1; XPlane name 2, event_metadata 4, stat_metadata
    5; XEventMetadata name 2, stats 5; XStat metadata_id 1, uint64 3,
    int64 4, str 5, ref 7; XStatMetadata name 2)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for num, (a, b) in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        parts: dict[int, list] = {2: [], 4: [], 5: []}
        for n, v in _fields(buf, a, b):
            if n in parts:
                parts[n].append(v)
        name = "".join(buf[x:y].decode() for x, y in parts[2])
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for key, (x, y) in _map_values(buf, parts[5]).items():
            f = dict(_fields(buf, x, y))
            if 2 in f:
                stat_names[key] = buf[f[2][0]:f[2][1]].decode()
        scopes = {}
        for x, y in _map_values(buf, parts[4]).values():
            op, got = "", {}
            for n, v in _fields(buf, x, y):
                if n == 2:
                    op = buf[v[0]:v[1]].decode()
                elif n == 5:
                    st = dict(_fields(buf, *v))
                    which = stat_names.get(st.get(1))
                    if 5 in st:
                        got[which] = buf[st[5][0]:st[5][1]].decode()
                    elif 7 in st:
                        got[which] = stat_names.get(st[7], "")
                    else:
                        got[which] = str(st.get(3, st.get(4, "")))
            if got.get(SCOPE_STAT):
                scopes[(got.get(PROGRAM_STAT, ""), op)] = (
                    got[SCOPE_STAT].rpartition(":")[0] or got[SCOPE_STAT])
        out[name] = scopes
    return out


def _program_id(run: str) -> str:
    """The program id in an execution's name, `<program>(<id>)#<run>`."""
    return run.rpartition("#")[0].rpartition("(")[2].rstrip(")")


def _device_ops(plane, scopes: dict) -> list[Op]:
    """The plane's operations, each given the program execution (an
    event of the modules line) that holds it and its scope from
    `scopes` ({(program id, operation name): path}, `op_scopes`)."""
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
                          ev.duration_ns * 1e-9,
                          scopes.get((_program_id(run), ev.name), "")))
    return sorted(ops, key=lambda o: o.start)


def load(path: str) -> dict:
    """Device operations per device and the host spans of the trace at
    `path`, in seconds from the trace's own origin (see `align`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    scopes = op_scopes(path)
    devices: dict[str, list[Op]] = {}
    host: list[tuple[str, float, float, str]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = _device_ops(plane, scopes.get(plane.name, {}))
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
