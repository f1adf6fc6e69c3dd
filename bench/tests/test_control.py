"""The control of the correctness check: the plain reference computed in
float8 (the precision below the configuration's bfloat16) in the
program's place must read as not correct, while the program reads as
correct, on the same served requests. At smoke size on the CPU; the
cell-size readings are taken on the chip by bench/tools/calibrate.py."""

import jax
import pytest

import check
import harness
import loadgen
import smoke
from repro.core.chips import get_chip


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_float8_control_fails_where_the_program_passes(family):
    spec = smoke.spec(family)
    cell = harness.Cell(spec, 2**31 + 99)
    cell.build(get_chip("tpu_v5e"))
    cell.warm_up()
    run = cell.serve(2.0, counter=harness.CompileCounter())
    arrivals, results = run["arrivals"], run["results"]
    assert not check.failures(arrivals, results, cell.m["vocab"])
    picked = check.sample(arrivals, results, cell.seed)
    sc = check.score(cell.ref, cell.m, cell.params, picked, results,
                     cell.geo["max_len"],
                     loadgen.longest(spec["traffic"]["output"]),
                     control=True)
    assert sc["tokens"] >= 100
    for name, limit in spec["cell"]["limits"].items():
        key = check.COMPARED[name]
        assert sc[key] <= limit < sc["ctrl_" + key], (name, sc)
