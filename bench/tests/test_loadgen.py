"""The traffic generator: reproducible from the seed, the same work at
the same times on every seed, at the mix's lengths."""

import numpy as np
import pytest

import loadgen

MIX = loadgen.load_mix("azure-code")
SEED = 2**31 + 12345


def test_same_seed_same_schedule():
    a = loadgen.schedule(MIX, 2.0, 30.0, SEED, 49152)
    b = loadgen.schedule(MIX, 2.0, 30.0, SEED, 49152)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]


def test_seeds_change_only_the_token_ids():
    a = loadgen.schedule(MIX, 2.0, 30.0, SEED, 49152)
    b = loadgen.schedule(MIX, 2.0, 30.0, SEED + 1, 49152)
    assert [(len(x.prompt), x.max_new_tokens, x.due_s) for x in a] == [
        (len(x.prompt), x.max_new_tokens, x.due_s) for x in b]
    assert not np.array_equal(np.concatenate([x.prompt for x in a]),
                              np.concatenate([x.prompt for x in b]))


@pytest.mark.parametrize("name,prompt,output", [("azure-code", 1500, 13),
                                                ("azure-conv", 1020, 129)])
def test_rate_window_and_lengths(name, prompt, output):
    mix = loadgen.load_mix(name)
    s = loadgen.schedule(mix, 2.0, 30.0, SEED, 49152)
    assert len(s) == 60
    due = [x.due_s for x in s]
    assert due == sorted(due) and 0.0 < due[0] and due[-1] < 30.0
    assert {len(x.prompt) for x in s} == {prompt}
    assert {x.max_new_tokens for x in s} == {output}
    assert loadgen.longest(mix["output"]) == output
    ids = np.concatenate([x.prompt for x in s])
    assert ids.min() >= 0 and ids.max() < 49152 and ids.dtype == np.int32
    assert [x.uid for x in s] == list(range(60))


def test_gaps_are_unit_mean_exponential():
    _, _, g = loadgen.shapes(MIX, 4000)
    assert abs(g.mean() - 1.0) < 0.05
    assert abs(np.median(g) - np.log(2)) < 0.05


def test_unknown_length_distribution_is_refused():
    with pytest.raises(ValueError):
        loadgen.longest({"dist": "lognormal", "median": 40})
