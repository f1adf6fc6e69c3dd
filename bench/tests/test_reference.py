"""How `check.score` reaches a family's reference: the dense and moe
families, which define `make_ffn`, score exactly as the shared decoder
scored them before a family could own its forward pass; a family that
defines `forward` (and `logits`) and no `make_ffn` is scored through its
own. At smoke size on the CPU, on requests made up here."""

import functools
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import smoke
from reference import common
from reference import dense, moe

FAMILIES = {"dense": dense, "moe": moe}
MAX_LEN, N_MAX = 256, 6


def parent_gaps(params, tokens, at, served, m, ffn, control):
    """`reference.common.gaps` as it was when every family ran
    `lm_forward` with its `make_ffn` and the untied head."""
    with jax.default_matmul_precision("highest"):
        h = common.lm_forward(params, tokens, m, ffn, False)[at]
        ref = common.mm(h, params["head"]["w"], False)
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        hc = common.lm_forward(params, tokens, m, ffn, True)[at]
        top = common.mm(hc, params["head"]["w"], True).argmax(-1)
        ctrl = best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
        return gap, ctrl


@functools.lru_cache(maxsize=None)
def parent_scorer(family_mod, m_items, length, n_max, control):
    m = dict(m_items)
    ffn = family_mod.make_ffn(m)
    return jax.jit(lambda p, t, at, s: parent_gaps(p, t, at, s, m, ffn,
                                                   control))


def made_up(vocab: int, seed: int):
    """Three requests of different prompt lengths, each with N_MAX served
    tokens drawn at random: (picked, results)."""
    rng = np.random.default_rng(seed)
    picked, results = [], {}
    for uid, P in enumerate((40, 23, 97)):
        prompt = rng.integers(0, vocab, P).astype(np.int32)
        toks = rng.integers(0, vocab, N_MAX).astype(np.int32)
        picked.append(types.SimpleNamespace(uid=uid, prompt=prompt))
        results[uid] = types.SimpleNamespace(tokens=toks)
    return picked, results


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_make_ffn_families_score_as_before_to_the_bit(family, control,
                                                      monkeypatch):
    m = smoke.spec(family)["config"]["model"]
    mod = FAMILIES[family]
    params = common.make_params(mod.specs(m), 2**33 + 5)
    picked, results = made_up(m["vocab"], 11)
    got = check.score(mod, m, params, picked, results, MAX_LEN, N_MAX,
                      control=control)
    monkeypatch.setattr(check, "_scorer", parent_scorer)
    want = check.score(mod, m, params, picked, results, MAX_LEN, N_MAX,
                       control=control)
    assert got["tokens"] == 3 * N_MAX and got["max_gap"] > 0
    assert got == want


def toy_family(tied: bool):
    """A family module with `specs` and `forward` and no `make_ffn`: each
    position's hidden state is the normed running mean of the embeddings
    up to it. Tied, it defines `logits` through the embedding table and
    has no head of its own. `calls` logs the `f8` of each traced
    forward."""
    mod = types.ModuleType("toy_family")
    mod.calls = []

    def specs(m):
        V, d = m["vocab"], m["d_model"]
        s = {"embed/table": ((V, d), 1.0), "ln_f/scale": ((d,), None)}
        if not tied:
            s["head/w"] = ((d, V), 1 / math.sqrt(d))
        return s

    def forward(params, tokens, m, f8):
        mod.calls.append(f8)
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        n = jnp.arange(1, tokens.shape[0] + 1, dtype=jnp.float32)
        x = jnp.cumsum(x, axis=0) / n[:, None]
        return common.rmsnorm(x, params["ln_f"]["scale"], m["norm_eps"])

    mod.specs, mod.forward = specs, forward
    if tied:
        def logits(params, h, f8):
            return common.mm(h, params["embed"]["table"].T, f8)

        mod.logits = logits
    return mod


def toy_greedy(mod, m, params, prompt, served) -> np.ndarray:
    """The toy family's greedy next token at each served position."""
    head = getattr(mod, "logits", common.head_logits)
    seq = jnp.asarray(np.concatenate([prompt, served[:-1]]))
    lg = np.asarray(head(params, mod.forward(params, seq, m, False), False))
    return lg[len(prompt) - 1:].argmax(-1).astype(np.int32)


@pytest.mark.parametrize("tied", [False, True])
def test_family_forward_without_make_ffn_is_scored(tied):
    m = {"vocab": 64, "d_model": 32, "norm_eps": 1e-5}
    mod = toy_family(tied)
    assert not hasattr(mod, "make_ffn")
    params = common.make_params(mod.specs(m), 2**31 + 3)
    picked, results = made_up(m["vocab"], 5)
    # the served tokens made greedy one by one, as a server would
    for a in picked:
        toks = results[a.uid].tokens
        for k in range(N_MAX):
            toks[k] = toy_greedy(mod, m, params, a.prompt, toks)[k]
    mod.calls.clear()
    sc = check.score(mod, m, params, picked, results, MAX_LEN, N_MAX,
                     control=True)
    assert mod.calls == [False, True]
    assert sc["tokens"] == 3 * N_MAX
    assert sc["max_gap"] <= 1e-4 and sc["mismatch"] == 0.0
    # a last served token altered (no later position reads it): the
    # scorer sees that one
    results[1].tokens[-1] = (results[1].tokens[-1] + 1) % m["vocab"]
    bad = check.score(mod, m, params, picked, results, MAX_LEN, N_MAX)
    assert bad["max_gap"] > 1e-3 and bad["mismatch"] == 1 / (3 * N_MAX)
