"""What `BENCHMARK.json` promises about its metrics: every metric has a
reader, and each cell a per-layer metric lists reports the end-to-end
metric that it moves. A `<name>.ttft` reader reads as `<name>` does."""

import glob
import os
import types

import pytest

import harness

BENCH = harness.read_json(harness.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def reported(cell: str) -> set[str]:
    return {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.metric_reader(metric["name"]))


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_each_cell_of_a_layer_metric_reports_what_it_moves(metric):
    for cell in metric.get("workloads", CELLS):
        assert metric["moves"] in reported(cell), (cell, metric["moves"])


def test_every_cell_reports_setup_and_another_end_to_end_metric():
    for cell in CELLS:
        assert "setup_s" in reported(cell) and len(reported(cell)) >= 2


def ttft_names() -> list[str]:
    paths = glob.glob(os.path.join(harness.BENCH, "metrics", "*.ttft.py"))
    return sorted(os.path.basename(p)[:-len(".py")] for p in paths)


@pytest.mark.parametrize("name", ttft_names())
def test_ttft_reader_reads_its_base(name, monkeypatch):
    asked = []
    real = harness.metric_reader

    def spy(base):
        asked.append(base)
        return real(base)

    monkeypatch.setattr(harness, "metric_reader", spy)
    real(name)
    assert asked == [name[:-len(".ttft")]]


def test_ttft_tpot_reads_the_same_number():
    def result(decode_s, n):
        return types.SimpleNamespace(decode_s=decode_s, n_tokens=n)

    arrivals = [types.SimpleNamespace(uid=u) for u in range(12)]
    results = {u: result(0.01 * (u + 1), 13) for u in range(11)}
    record = {"run": {"arrivals": arrivals, "results": results}}
    base = harness.metric_reader("tpot_p90_ms")(record)
    assert base == pytest.approx(1e3 * 0.10 / 12)
    assert harness.metric_reader("tpot_p90_ms.ttft")(record) == base
