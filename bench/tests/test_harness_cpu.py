"""The harness end to end on the CPU at smoke size, through the same
`run_cell` the command runs after its device check; the command itself
refuses the CPU. Faults planted under the timed path must turn `correct`
false."""

import argparse
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import run
import smoke
from repro.core.chips import get_chip

SEED = 2**31 + 77


def run_smoke(family, seconds=2.0, trace=0):
    args = argparse.Namespace(workload=f"smoke-{family}", seed=SEED,
                              seconds=seconds, trace=trace)
    return run.run_cell(smoke.spec(family), args,
                        jax.devices(), smoke.PEAKS, get_chip("tpu_v5e"))


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_sound_run_is_correct(family):
    out = run_smoke(family)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 40 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_s", "tpot_p90_ms",
                                   "tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "compared"


def test_traced_run_reports_host_side_metrics():
    out = run_smoke("dense", trace=1)
    assert out["correct"]
    for name in ("slot_occupancy", "window_compiles", "model_mfu",
                 "pretune_s"):
        assert name in out["metrics"]
    assert 0 < out["metrics"]["slot_occupancy"]["value"] <= 100
    assert out["device"]["window_s"] > 0


def test_altered_token_is_caught(monkeypatch):
    from repro.serving.engine import ServingEngine

    sample = ServingEngine._sample
    calls = {"n": 0}

    def altered(self, logits, rngs=None):
        out = sample(self, logits, rngs)
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            out = (out + 1) % logits.shape[-1]
        return out

    monkeypatch.setattr(ServingEngine, "_sample", altered)
    out = run_smoke("dense")
    assert not out["correct"]
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_decode_step_that_keeps_its_state_is_caught(monkeypatch):
    from repro.models import registry

    get_model = registry.get_model

    def broken(cfg):
        api = get_model(cfg)

        def decode_step(p, t, s, c):
            logits, _ = api.decode_step(p, t, s, c)
            return logits, s

        return dataclasses.replace(api, decode_step=decode_step)

    monkeypatch.setattr(registry, "get_model", broken)
    out = run_smoke("dense")
    assert not out["correct"]


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(smoke.harness.BENCH, "run.py"),
         "--workload", "sc2-3b.azure-code", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_a_tree_without_the_program(tmp_path):
    import shutil

    shutil.copytree(smoke.harness.BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(smoke.harness.ROOT, "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"),
         "--workload", "sc2-3b.azure-code", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_step_log_accounts_for_every_decoded_token():
    """The reconstruction the decode metrics rest on: every decode row of
    every logged step belongs to a request, which decoded in the n - 1
    steps up to the one that returned it."""
    import harness

    cell = harness.Cell(smoke.spec("dense"), SEED)
    cell.build(get_chip("tpu_v5e"))
    cell.warm_up()
    run = cell.serve(2.0, counter=harness.CompileCounter())
    harness.annotate_steps(run["steps"], run["arrivals"], run["results"],
                           run["t0"])
    res = run["results"]
    assert len(res) == len(run["arrivals"])
    assert (sum(st.decode_rows for st in run["steps"])
            == sum(r.n_tokens - 1 for r in res.values()))
    assert sum(st.first_tokens for st in run["steps"]) == len(res)
    assert (sum(st.prompt_tokens for st in run["steps"])
            == sum(len(a.prompt) for a in run["arrivals"]))
    # per step: the requests the reconstruction puts in a step are the
    # rows the engine decoded there
    index = {uid: i for i, st in enumerate(run["steps"])
             for uid, _ in st.finished}
    rows = [0] * len(run["steps"])
    for uid, r in res.items():
        for k in range(1, r.n_tokens):
            rows[index[uid] - (r.n_tokens - 1) + k] += 1
    assert rows == [st.decode_rows for st in run["steps"]]
    want = sum((r.n_tokens - 1) * len(a.prompt)
               + r.n_tokens * (r.n_tokens - 1) // 2
               for a in run["arrivals"] for r in [res[a.uid]])
    assert sum(st.kv_positions for st in run["steps"]) == want
