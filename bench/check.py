"""Whether what the timed path served is correct.

Once the window has closed and the queue has drained, a sample of the
finished requests, drawn from the seed and holding the longest, is run
through the plain float32 reference (`bench/reference/<family>.py`):
one forward pass over each prompt with its served tokens. At each
position the gap is how far the served (greedy) token's reference logit
lies below the reference's best; a cell's `limits` name which of the
widest gap (`max_logit_gap`) and the mean gap (`mean_logit_gap`) it
compares. A request that did
not come back, or came back with a wrong number of tokens or ids outside
the vocabulary, fails outright.
"""

from __future__ import annotations

import functools

import numpy as np

# names a cell's `limits` may hold, and the score each compares
COMPARED = {"max_logit_gap": "max_gap", "mean_logit_gap": "mean_gap"}
# the sample holds at least this many served tokens and requests, and at
# most SAMPLE_MAX_REQUESTS requests
SAMPLE_TOKENS = 384
SAMPLE_MIN_REQUESTS = 8
SAMPLE_MAX_REQUESTS = 32
# the reference pads each sequence to the smallest of these lengths (and
# the cell's max_len) that holds it: a few compiled shapes, not one each
REF_LENGTHS = (1024, 2048)


def failures(arrivals, results, vocab: int) -> list[str]:
    """One line per request due in the window that failed."""
    bad = []
    for a in arrivals:
        r = results.get(a.uid)
        if r is None:
            bad.append(f"request {a.uid} never finished")
        elif r.n_tokens != a.max_new_tokens or len(r.tokens) != r.n_tokens:
            bad.append(f"request {a.uid}: {r.n_tokens} tokens of "
                       f"{a.max_new_tokens}")
        elif not np.all((r.tokens >= 0) & (r.tokens < vocab)):
            bad.append(f"request {a.uid}: token ids outside the vocabulary")
    return bad


def sample(arrivals, results, seed: int) -> list:
    """Finished requests to score: the one with most served tokens, the
    longest, then others in an order drawn from the seed, until the
    sample holds SAMPLE_TOKENS served tokens and SAMPLE_MIN_REQUESTS
    requests."""
    done = [a for a in arrivals if a.uid in results]
    if not done:
        return []
    pick = [max(done, key=lambda a: a.max_new_tokens)]
    longest = max(done, key=lambda a: len(a.prompt) + a.max_new_tokens)
    if longest is not pick[0]:
        pick.append(longest)
    rng = np.random.default_rng([int(seed), 17])
    for j in rng.permutation(len(done)):
        if ((sum(a.max_new_tokens for a in pick) >= SAMPLE_TOKENS
                and len(pick) >= SAMPLE_MIN_REQUESTS)
                or len(pick) >= SAMPLE_MAX_REQUESTS):
            break
        if done[j] not in pick:
            pick.append(done[j])
    return pick


@functools.lru_cache(maxsize=None)
def _scorer(family_mod, m_items: tuple, length: int, n_max: int,
            control: bool):
    import jax

    from reference import common

    m = dict(m_items)
    ffn = family_mod.make_ffn(m)
    return jax.jit(lambda p, t, at, s: common.gaps(p, t, at, s, m, ffn,
                                                   control))


def score(family_mod, m: dict, params, picked, results, max_len: int,
          n_max: int, control: bool = False) -> dict:
    """Gaps of the served tokens over the picked requests: the widest
    (`max_gap`), the mean (`mean_gap`) and the share of positions where the
    served token is not the reference's first (`mismatch`); with
    `control`, the same three of the tokens the float8 control puts first
    (`ctrl_*`). `tokens` counts the positions scored."""
    gaps, ctrls = [], []
    lengths = tuple(x for x in REF_LENGTHS if x < max_len) + (max_len,)
    for a in picked:
        served = np.asarray(results[a.uid].tokens, np.int32)
        seq = np.concatenate([a.prompt, served[:-1]]).astype(np.int32)
        L = next(x for x in lengths if x >= len(seq))
        toks = np.zeros(L, np.int32)
        toks[:len(seq)] = seq
        n = len(served)
        at = np.zeros(n_max, np.int32)
        at[:n] = np.arange(len(a.prompt) - 1, len(a.prompt) - 1 + n)
        want = np.zeros(n_max, np.int32)
        want[:n] = served
        fn = _scorer(family_mod, tuple(sorted(m.items())), L, n_max, control)
        g, c = fn(params, toks, at, want)
        gaps.append(np.asarray(g)[:n])
        ctrls.append(np.asarray(c)[:n])
    out = {"tokens": int(sum(len(g) for g in gaps))}
    for key, parts in (("", gaps), ("ctrl_", ctrls if control else [])):
        v = np.concatenate(parts) if parts else np.zeros(0)
        out[key + "max_gap"] = float(v.max()) if v.size else 0.0
        out[key + "mean_gap"] = float(v.mean()) if v.size else 0.0
        out[key + "mismatch"] = float((v > 0).mean()) if v.size else 0.0
    return out
