"""The trace reduction: busy time, idle gaps and the attribution of
device executions to the decode and chunk programs, on a hand-made trace
and on one recorded on a TPU v5e (bench/tests/data/, written by
bench/tools/record_trace.py from sc2-3b under a mix of spread lengths:
the programs are the cell's, whatever lengths they served)."""

import gzip
import json
import os

import pytest

import harness
import tracereduce as tr
from metrics import common as mc

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "sc2-3b.trace.json.gz")
CELL = "sc2-3b.azure-code"


def op(name, run, start, dur):
    return tr.Op(name, name, run, start, dur)


def test_busy_is_the_union_clipped_to_the_span():
    ops = [op("a", "1", 0.0, 2.0), op("b", "1", 1.0, 2.0),
           op("c", "2", 5.0, 1.0), op("d", "3", 9.5, 1.0)]
    assert tr.busy_intervals(ops, 0.5, 10.0) == [(0.5, 3.0), (5.0, 6.0),
                                                 (9.5, 10.0)]
    assert mc.busy_s(ops[:2]) == pytest.approx(3.0)


def test_steps_get_their_executions_and_decode_runs_last():
    steps = [harness.Step(0.0, 1.0, 64, 2, []),
             harness.Step(1.0, 2.0, 0, 2, [])]
    ops = [op("copy", "s1", 0.05, 0.01),
           op("tiled_matmul_8x512x512", "c1", 0.1, 0.2),
           op("fusion", "c1", 0.3, 0.1),
           op("tiled_matmul_8x512x512", "d1", 0.5, 0.1),
           op("tiled_matmul_8x512x512", "d2", 1.2, 0.1)]
    reduced = tr.reduce({"devices": {"/device:TPU:0": ops}, "host": []},
                        (0.0, 2.0), steps)
    rec = {"trace": reduced}
    got = list(mc.program_runs(rec))
    assert [len(c) for _, _, c in got] == [1, 0]
    assert [d[0].run for _, d, _ in got] == ["d1", "d2"]
    assert got[0][2][0][0].run == "c1"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    steps = [harness.Step(0.0, 4.0, 0, 1, [])]
    ops = [op("x", "1", 0.0, 1.0), op("y", "2", 3.0, 1.0)]
    host = [("bench:serve_step", 0.0, 4.0, "main"),
            ("PjitFunction(f)", 1.5, 1.0, "main")]
    reduced = tr.reduce({"devices": {"/device:TPU:0": ops}, "host": host},
                        (0.0, 4.0), steps)
    assert tr.idle_gaps(reduced) == [["PjitFunction(f)", 2.0]]
    assert reduced["devices"]["/device:TPU:0"]["busy_s"] == 2.0


def load_recorded():
    with gzip.open(RECORDED, "rt") as f:
        d = json.load(f)
    devices = {k: [tr.Op(*row) for row in v] for k, v in d["devices"].items()}
    host = [tuple(h) for h in d["host"]]
    steps = [harness.Step(**{**s, "finished": [tuple(x) for x in
                                               s["finished"]]})
             for s in d["steps"]]
    return {"devices": devices, "host": host}, tuple(d["span"]), steps


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded TPU trace")
def test_recorded_trace_reduces_to_sound_shares():
    raw, span, steps = load_recorded()
    reduced = tr.reduce(raw, span, steps)
    dev = reduced["devices"][sorted(reduced["devices"])[0]]
    assert 0 < dev["busy_s"] <= reduced["window_s"]
    runs = list(mc.program_runs({"trace": reduced}))
    decodes = [d for _, d, _ in runs if d is not None]
    assert decodes and all(any(mc.is_kernel(o) for o in d) for d in decodes)
    # the decode program is one compiled program, every step
    assert len({d[0].run.split("#")[0] for d in decodes}) == 1
    assert tr.top_ops(reduced) and tr.idle_gaps(reduced)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded TPU trace")
@pytest.mark.parametrize("name", [
    "device_idle_share", "decode_step_hbm_share", "step_mfu.decode",
    "step_mfu.prefill", "tiled_matmul_roofline.decode",
    "tiled_matmul_roofline.prefill"])
def test_recorded_trace_shares_lie_in_0_100(name):
    raw, span, steps = load_recorded()
    spec = harness.cell_spec(CELL)
    cell = harness.Cell(spec, 0)
    record = {"m": cell.m, "work": cell.work, "trace":
              tr.reduce(raw, span, steps),
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    value = harness.metric_reader(name)(record)
    assert value is not None and 0 < value <= 100, value


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded TPU trace")
def test_recorded_kernel_calls_carry_their_weight_staging():
    """In the recorded decode programs every projection's call is led by
    the dynamic-slice fusion that reads its layer's weight out of the
    stacked parameters, the LM head's by the pad of its weight; no
    activation (as many rows as the call) is taken for staging."""
    raw, span, steps = load_recorded()
    spec = harness.cell_spec(CELL)
    cell = harness.Cell(spec, 0)
    record = {"m": cell.m, "work": cell.work,
              "trace": tr.reduce(raw, span, steps)}
    k_min = mc.kernel_k_min(record)
    decodes = [d for _, d, _ in mc.program_runs(record) if d is not None]
    # four projections and the feed-forward's two a layer, and the head,
    # in every decode the span holds whole (its first may start before)
    whole = 6 * cell.m["n_layers"] + 1
    counts = [len(mc.kernel_calls(d, k_min)) for d in decodes]
    assert max(counts) == whole and counts[1:] == [whole] * (len(counts) - 1)
    for dec in decodes:
        calls = mc.kernel_calls(dec, k_min)
        for op, staged in calls:
            assert staged, op.text
            rows = mc.result_dims(op)[0]
            assert all(mc.result_dims(o)[0] > rows for o in staged)
            assert {o.name.lstrip("%").split(".")[0] for o in staged} <= {
                "dynamic-slice_bitcast_fusion", "pad"}
        staged_s = sum(o.dur for _, st in calls for o in st)
        assert staged_s > sum(op.dur for op, _ in calls)
