"""The readers of the serving engine's spans and chunk counters, on a
hand-made trace with known idle inside known spans, and on one recorded
on a TPU v5e with the spans (bench/tests/data/, written by
bench/tools/record_trace.py from sc2-3b.azure-code)."""

import gzip
import json
import os

import pytest

import harness
import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "sc2-3b.spans.trace.json.gz")
SHARES = ("step_idle_share", "step_idle_share.fetch",
          "step_idle_share.sample")
# the engine's programs and the spans that dispatch and fetch them
PROGRAM_SPANS = {"jit_engine_decode": "engine.decode",
                 "jit_engine_chunk": "engine.chunk"}
# how far an execution may lie outside its span: the device and the host
# clocks of one trace, read by the profiler
CLOCK_SLACK_S = 0.5e-3


def read(name, record):
    return harness.metric_reader(name)(record)


def op(name, run, start, dur):
    return tr.Op(name, name, run, start, dur)


def synthetic(span=(0.0, 10.0), host=None):
    """Two engine steps, each with a fetch and a sample, and device work
    at known places: 1.2 s inside each step and 0.6 s between them."""
    ops = [op("fusion", "jit_engine_chunk(1)#1", 0.5, 1.2),
           op("copy", "jit_engine_splice(2)#2", 4.2, 0.6),
           op("fusion", "jit_engine_decode(3)#3", 6.2, 1.2)]
    if host is None:
        host = [("bench:serve_step", 0.0, 10.0, "python"),
                ("engine.step", 0.0, 4.0, "python"),
                ("engine.chunk", 0.5, 2.0, "python"),
                ("engine.fetch", 1.5, 0.5, "python"),
                ("engine.sample", 2.0, 0.5, "python"),
                ("engine.step", 5.0, 4.0, "python"),
                ("engine.decode", 6.0, 2.5, "python"),
                ("engine.fetch", 7.0, 1.0, "python"),
                ("engine.sample", 8.0, 0.5, "python")]
    reduced = tr.reduce({"devices": {"/device:TPU:0": ops}, "host": host},
                        span, [harness.Step(0.0, 10.0, 0, 0, [])])
    return {"trace": reduced}


def test_idle_inside_known_spans():
    rec = synthetic()
    # steps: 8 s, 1.2 s busy in each; fetches: 0.2 of 0.5 s and 0.4 of
    # 1 s busy; samples: all 1 s idle
    assert read("step_idle_share", rec) == pytest.approx(100 * 5.6 / 8)
    assert read("step_idle_share.fetch", rec) == pytest.approx(
        100 * 0.9 / 8)
    assert read("step_idle_share.sample", rec) == pytest.approx(
        100 * 1.0 / 8)


def test_spans_are_clipped_to_the_traced_span():
    rec = synthetic(span=(1.0, 8.5))
    # steps clipped to [1, 4] and [5, 8.5]: 6.5 s, busy 0.7 + 1.2 s;
    # fetches [1.5, 2] and [7, 8]; samples [2, 2.5] and [8, 8.5]
    assert read("step_idle_share", rec) == pytest.approx(100 * 4.6 / 6.5)
    assert read("step_idle_share.fetch", rec) == pytest.approx(
        100 * 0.9 / 6.5)
    assert read("step_idle_share.sample", rec) == pytest.approx(
        100 * 1.0 / 6.5)


def test_span_readers_find_nothing_without_engine_spans():
    rec = synthetic(host=[("bench:serve_step", 0.0, 10.0, "python")])
    assert all(read(name, rec) is None for name in SHARES)
    assert all(read(name, {"trace": None}) is None for name in SHARES)


def window_record(report0, report1):
    return {"run": {"t0": 0.0, "report0": report0,
                    "close": {"t": 10.0, "report": report1}}}


def test_chunk_fill_is_the_window_share_of_computed_positions():
    r0 = {"chunk_prompt_tokens": 100, "chunk_token_slots": 400}
    r1 = {"chunk_prompt_tokens": 1600, "chunk_token_slots": 2400}
    assert read("chunk_fill", window_record(r0, r1)) == pytest.approx(75.0)
    # no chunk call in the window, or an engine that counts neither
    assert read("chunk_fill", window_record(r0, r0)) is None
    assert read("chunk_fill", window_record({}, {})) is None


def load_recorded():
    with gzip.open(RECORDED, "rt") as f:
        d = json.load(f)
    devices = {k: [tr.Op(*row) for row in v] for k, v in d["devices"].items()}
    host = [tuple(h) for h in d["host"]]
    steps = [harness.Step(**{**s, "finished": [tuple(x) for x in
                                               s["finished"]]})
             for s in d["steps"]]
    reduced = tr.reduce({"devices": devices, "host": host},
                        tuple(d["span"]), steps)
    return {"trace": reduced}


def program_offsets(record) -> list[float]:
    """For each execution of the decode and chunk programs on the first
    device, how far it lies outside the nearest span of its program's
    kind (0 where it lies wholly inside one)."""
    trace = record["trace"]
    ops = trace["devices"][sorted(trace["devices"])[0]]["ops"]
    out = []
    for run in tr.executions(ops):
        kind = [s for p, s in PROGRAM_SPANS.items()
                if run[0].run.startswith(p + "(")]
        if not kind:
            continue
        a, b = run[0].start, max(o.end for o in run)
        out.append(min(max(0.0, s - a, b - (s + d))
                       for n, s, d, _ in trace["host"] if n == kind[0]))
    return out


recorded = pytest.mark.skipif(not os.path.exists(RECORDED),
                              reason="no recorded TPU trace with spans")


@recorded
def test_recorded_shares_are_sound():
    rec = load_recorded()
    got = {name: read(name, rec) for name in SHARES}
    assert all(v is not None and 0.0 <= v <= 100.0 for v in got.values())
    assert (got["step_idle_share.fetch"] + got["step_idle_share.sample"]
            <= got["step_idle_share"] + 1e-9)


@recorded
def test_recorded_executions_lie_inside_their_spans():
    offsets = program_offsets(load_recorded())
    assert len(offsets) >= 10
    inside = sum(x <= CLOCK_SLACK_S for x in offsets)
    assert inside >= 0.99 * len(offsets), (
        f"{inside} of {len(offsets)} inside; largest offset "
        f"{max(offsets) * 1e3:.3f} ms")
