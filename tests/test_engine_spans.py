"""The chunked stepper's profiler spans and chunk counters: spans nest as
`docs/serving.md` ("Tracing") draws them, their counts and arguments
agree with `report()`, the engine's programs carry stable names, and
tracing changes no served token."""

from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models.registry import get_model
from repro.serving.engine import Request, ServingEngine

# prompt lengths and budgets: two chunk buckets, a lane that grows, and a
# request that finishes on its first token (its row is re-admitted)
PROMPTS = (5, 23, 40, 9, 31, 17)
BUDGETS = (4, 1, 6, 3, 5, 2)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("starcoder2-3b", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    return cfg, model, params


def serve(served, trace_dir=None):
    """Serve the fixed request set through `serve_step`, optionally under
    the profiler. Returns (tokens by uid, report before, report after)."""
    cfg, model, params = served
    eng = ServingEngine(model, params, cfg, max_batch=2, max_len=128,
                        chunk_tokens=16)
    rng = np.random.default_rng(3)
    for uid, (n, budget) in enumerate(zip(PROMPTS, BUDGETS)):
        eng.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab, n).astype(np.int32), max_new_tokens=budget))
    r0 = eng.report()
    out = []

    def drive():
        while eng.has_work:
            out.extend(eng.serve_step())

    if trace_dir is None:
        drive()
    else:
        with jax.profiler.trace(trace_dir):
            drive()
    return {r.uid: r.tokens.tolist() for r in out}, r0, eng.report()


def host_events(trace_dir):
    """Every host event of the trace: (name, start ns, end ns, args); the
    arguments are read for the engine's spans only."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                args = (dict(ev.stats) if ev.name.startswith("engine.")
                        else {})
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, args))
    return out


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("engine-trace"))
    tokens, r0, r1 = serve(served, d)
    events = host_events(d)
    spans: dict[str, list] = {}
    for ev in events:
        if ev[0].startswith("engine."):
            spans.setdefault(ev[0], []).append(ev)
    return {"tokens": tokens, "r0": r0, "r1": r1, "spans": spans,
            "events": events}


def inside(ev, parents) -> bool:
    return any(p[1] <= ev[1] and ev[2] <= p[2] for p in parents)


@pytest.mark.parametrize("child, parents", [
    ("engine.chunk", ("engine.step",)),
    ("engine.decode", ("engine.step",)),
    ("engine.admit", ("engine.step",)),
    ("engine.splice", ("engine.step",)),
    ("engine.lane_grow", ("engine.admit",)),
    ("engine.fetch", ("engine.chunk", "engine.decode")),
    ("engine.sample", ("engine.chunk", "engine.decode")),
])
def test_spans_nest_in_their_parents(traced, child, parents):
    spans = traced["spans"]
    assert spans.get(child), f"no {child} span in the trace"
    outer = [p for name in parents for p in spans.get(name, [])]
    assert all(inside(ev, outer) for ev in spans[child])


def test_span_counts_match_the_counters(traced):
    spans, r0, r1 = traced["spans"], traced["r0"], traced["r1"]
    delta = {k: r1[k] - r0[k] for k in ("decode_steps", "chunk_steps",
                                        "lane_rebuilds",
                                        "resident_slot_steps")}
    assert len(spans["engine.decode"]) == delta["decode_steps"] > 0
    assert len(spans["engine.chunk"]) == delta["chunk_steps"] > 0
    assert len(spans["engine.lane_grow"]) == delta["lane_rebuilds"] > 0
    assert (sum(ev[3]["rows"] for ev in spans["engine.decode"])
            == delta["resident_slot_steps"])
    # one fetch and one sample per chunk call and per decode step
    calls = delta["decode_steps"] + delta["chunk_steps"]
    assert len(spans["engine.fetch"]) == len(spans["engine.sample"]) == calls


def test_chunk_counters_count_prompt_tokens_and_computed_slots(traced):
    r0, r1, chunks = traced["r0"], traced["r1"], traced["spans"][
        "engine.chunk"]
    prompt = r1["chunk_prompt_tokens"] - r0["chunk_prompt_tokens"]
    slots = r1["chunk_token_slots"] - r0["chunk_token_slots"]
    assert prompt == sum(PROMPTS)
    assert sum(ev[3]["tokens"] for ev in chunks) == prompt
    assert slots == sum(ev[3]["width"] * ev[3]["bucket"] for ev in chunks)
    assert slots >= prompt
    assert all(ev[3]["rows"] <= ev[3]["width"] for ev in chunks)


def test_programs_carry_stable_names(traced):
    called = {ev[0] for ev in traced["events"]
              if ev[0].startswith("PjitFunction(")}
    assert {"PjitFunction(engine_chunk)", "PjitFunction(engine_decode)",
            "PjitFunction(engine_splice)"} <= called
    assert "PjitFunction(<lambda>)" not in called


def test_tracing_changes_no_served_token(served, traced):
    untraced, _, _ = serve(served)
    assert sorted(untraced) == list(range(len(PROMPTS)))
    assert untraced == traced["tokens"]
    assert [len(untraced[u]) for u in sorted(untraced)] == list(BUDGETS)
