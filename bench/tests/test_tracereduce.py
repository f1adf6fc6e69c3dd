"""The trace reduction: busy time, idle gaps, each operation's scope and
the attribution of device executions to the decode and chunk programs,
on hand-made traces and on ones recorded on a TPU v5e (bench/tests/data/,
written by bench/tools/record_trace.py from sc2-3b: under a mix of
spread lengths, the programs being the cell's whatever lengths they
served; and, with each operation's scope, as `bench/tools/
record_trace.py sc2-3b.azure-code 1618033988749 34 OUT`)."""

import gzip
import json
import os
import struct
import types

import pytest

import harness
import tracereduce as tr
from metrics import common as mc

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "sc2-3b.trace.json.gz")
# recorded with each operation's scope
SCOPED = os.path.join(DATA, "sc2-3b.scope.trace.json.gz")
CELL = "sc2-3b.azure-code"
DECODE_SCOPE = "jit(engine_decode)"


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


def event(name, start_ns, dur_ns, **stats):
    """A profiler event as `ProfileData` gives one."""
    return types.SimpleNamespace(name=name, start_ns=start_ns,
                                 duration_ns=dur_ns,
                                 stats=list(stats.items()))


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    """One protobuf field: an int as a varint, a float as a double, text
    or bytes length-delimited."""
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def plane(name, ops, stat_names) -> bytes:
    """An XPlane: its name, a line of events the reader passes over, the
    stat names, and each op's event metadata with its stats
    ({stat id: value}; a ("ref", id) value refers to a stat name)."""
    out = field(2, name) + field(3, field(2, "XLA Ops") + field(4, b"x"))
    for sid, sname in stat_names.items():
        out += field(5, field(1, sid) + field(2, field(1, sid)
                                              + field(2, sname)))
    for mid, (op_name, stats) in enumerate(ops, 1):
        md = field(1, mid) + field(2, op_name)
        for sid, v in stats.items():
            val = (field(7, v[1]) if isinstance(v, tuple)
                   else field(5, v) if isinstance(v, str) else field(3, v))
            md += field(5, field(1, sid) + val)
        out += field(4, field(1, mid) + field(2, md))
    return out


def test_scope_is_read_where_the_metadata_has_it(tmp_path):
    scope = "jit(engine_decode)/jit(main)/while/body/pallas_call"
    kernel = "%tiled_matmul.3 = bf16[8,3072]{1,0} custom-call(%a, %b)"
    fusion = "%fusion.1 = f32[8]{0} fusion(%c)"
    copy = "%copy.2 = f32[8]{0} copy(%d)"
    names = {1: tr.SCOPE_STAT, 2: tr.PROGRAM_STAT, 3: "flops",
             4: "jit(engine_decode)/copy:"}
    device = plane("/device:TPU:0", [
        (kernel, {2: 42, 1: scope + ":", 3: 1.5}),
        (fusion, {2: 42, 3: 2.0}),
        (copy, {2: 42, 1: ("ref", 4)})], names)
    host = plane("/host:CPU", [(kernel, {1: "host/op:"})], names)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device) + field(4, "h"))
    scopes = tr.op_scopes(str(path))
    assert scopes == {"/device:TPU:0": {
        ("42", kernel): scope, ("42", copy): "jit(engine_decode)/copy"}}
    lines = [
        types.SimpleNamespace(name=tr.MODULES_LINE, events=[
            event("jit_engine_decode(42)", 0, 10_000, run_id=7)]),
        types.SimpleNamespace(name=tr.OPS_LINE, events=[
            event(kernel, 1000, 2000), event(fusion, 4000, 500)])]
    ops = tr._device_ops(types.SimpleNamespace(lines=lines),
                         scopes["/device:TPU:0"])
    assert [o.scope for o in ops] == [scope, ""]
    assert [o.run for o in ops] == ["jit_engine_decode(42)#7"] * 2
    assert ops[0].text == "%tiled_matmul.3 bf16[8,3072]{1,0}"
    assert mc.in_scope(ops, "jit(engine_decode)") == [ops[0]]
    # a whole component of the path, not a part of one
    assert mc.in_scope(ops, "engine_decode") == []


def test_scope_survives_the_saved_trace(tmp_path):
    from tools import record_trace

    ops = [tr.Op("%tiled_matmul.1", "%tiled_matmul.1 bf16[8,64]{1,0}",
                 "jit_engine_decode(1)#1", 10.5, 0.1,
                 "jit(engine_decode)/pallas_call"),
           tr.Op("%copy.2", "%copy.2 bf16[8]{0}", "jit_engine_decode(1)#1",
                 10.7, 0.1)]
    steps = [harness.Step(10.0, 11.0, 0, 8, [])]
    out = str(tmp_path / "t.json.gz")
    record_trace.dump({"devices": {"/device:TPU:0": ops}, "host": []},
                      (10.0, 11.0), steps, 10.0, out)
    raw, span, got_steps = load_recorded(out)
    reduced = tr.reduce(raw, span, got_steps)
    kept = reduced["devices"]["/device:TPU:0"]["ops"]
    assert [o.scope for o in kept] == [o.scope for o in ops]


def load_recorded(path=RECORDED):
    with gzip.open(path, "rt") as f:
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


@pytest.mark.skipif(not os.path.exists(SCOPED),
                    reason="no recorded TPU trace with scopes")
def test_recorded_kernel_ops_carry_their_program_scope():
    """Every tiled GEMM call of the engine's programs has a scope, and the
    decode program's scope picks out its own calls and no chunk call."""
    raw, span, steps = load_recorded(SCOPED)
    reduced = tr.reduce(raw, span, steps)
    dev = reduced["devices"][sorted(reduced["devices"])[0]]["ops"]
    engine = [o for o in dev if o.run.startswith("jit_engine_")]
    kernels = [o for o in engine if mc.is_kernel(o)]
    assert kernels and all(o.scope for o in kernels)
    runs = list(mc.program_runs({"trace": reduced}))
    decodes = [d for _, d, _ in runs if d is not None]
    chunks = [c for _, _, cs in runs for c in cs]
    assert decodes and chunks
    for dec in decodes:
        picked = mc.in_scope(dec, DECODE_SCOPE)
        assert [o for o in dec if mc.is_kernel(o)] == [
            o for o in picked if mc.is_kernel(o)]
    assert not any(mc.in_scope(c, DECODE_SCOPE) for c in chunks)
