"""Pieces the plain references share: weights made from the seed, float32
building blocks at `highest` matmul precision, and the float8 rounding the
control computes in.

Nothing here imports the program. The weights are laid out as the
program's parameter tree (the benchmark hands the same tree to the engine),
and every leaf is drawn from its own key: `fold_in(seed key, leaf index)`
over the sorted leaf paths.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed (the 64-bit value is folded in
    whole: `jax.random.key` alone keeps only the low 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_params(specs: dict, seed: int, dtype=jnp.bfloat16):
    """The parameter tree of `specs` ({path: (shape, scale)}; scale None
    means ones) made on the device in one jitted call."""
    paths = sorted(specs)

    def build(key):
        out = {}
        for i, path in enumerate(paths):
            shape, scale = specs[path]
            if scale is None:
                leaf = jnp.ones(shape, dtype)
            else:
                leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * scale).astype(dtype)
            node = out
            *head, last = path.split("/")
            for part in head:
                node = node.setdefault(part, {})
            node[last] = leaf
        return out

    return jax.jit(build)(seed_key(seed))


def attention_specs(m: dict, L: int) -> dict:
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {
        "blocks/ln1/scale": ((L, d), None),
        "blocks/attn/wq": ((L, d, H * hd), 1 / math.sqrt(d)),
        "blocks/attn/wk": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "blocks/attn/wv": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "blocks/attn/wo": ((L, H * hd, d), 1 / math.sqrt(H * hd)),
        "blocks/ln2/scale": ((L, d), None),
    }


def lm_specs(m: dict) -> dict:
    d, V = m["d_model"], m["vocab"]
    return {
        "embed/table": ((V, d), 0.02),
        "ln_f/scale": ((d,), None),
        "head/w": ((d, V), 1 / math.sqrt(d)),
    }


def f8_round(x: jax.Array, axis=None) -> jax.Array:
    """`x` rounded through float8 e4m3 with a scale per slice along `axis`
    (None: one scale for the tensor), returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(x: jax.Array, w: jax.Array, f8: bool) -> jax.Array:
    """x @ w in float32 at highest precision; with `f8`, both operands are
    first rounded to float8 (a scale per row of `x`, one for `w`)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if f8:
        x, w = f8_round(x, axis=-1), f8_round(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on (S, H, hd), halves rotated (not interleaved)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


Q_BLOCK = 512


def causal_attention(q, k, v) -> jax.Array:
    """Exact causal GQA attention. q: (S, H, hd); k, v: (S, KV, hd).
    Query blocks of Q_BLOCK keep the scores to (H, Q_BLOCK, S)."""
    S, H, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    nb = S // Q_BLOCK if S % Q_BLOCK == 0 and S > Q_BLOCK else 1
    qb = q.reshape(nb, S // nb, KV, rep, hd)

    def block(args):
        qi, i = args
        s = jnp.einsum("qgrh,kgh->grqk", qi, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        qpos = i * (S // nb) + jnp.arange(S // nb)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqk,kgh->qgrh", w, v, precision=HIGHEST)

    out = jax.lax.map(block, (qb, jnp.arange(nb)))
    return out.reshape(S, H * hd)


def attention_block(x, p, m: dict, pos, f8: bool):
    """Pre-norm attention sub-block; returns the residual stream after it."""
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    S = x.shape[0]
    h = rmsnorm(x, p["ln1"]["scale"], m["norm_eps"])
    q = mm(h, p["attn"]["wq"], f8).reshape(S, H, hd)
    k = mm(h, p["attn"]["wk"], f8).reshape(S, KV, hd)
    v = mm(h, p["attn"]["wv"], f8).reshape(S, KV, hd)
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    return x + mm(causal_attention(q, k, v), p["attn"]["wo"], f8)


def lm_forward(params, tokens, m: dict, ffn, f8: bool) -> jax.Array:
    """Final-norm hidden states (S, d) in float32 of a decoder over one
    sequence at positions 0..S-1; `ffn(h, block_params, f8)` is the
    family's feed-forward on the normed stream. Layers run one at a time
    (a scan), each upcast to float32 inside its step."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(x, p):
        x = attention_block(x, p, m, pos, f8)
        h = rmsnorm(x, p["ln2"]["scale"], m["norm_eps"])
        return x + ffn(h, p, f8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rmsnorm(x, params["ln_f"]["scale"], m["norm_eps"])


def head_logits(params, h: jax.Array, f8: bool) -> jax.Array:
    """Logits of final-normed hidden states through the untied LM head:
    what a family that defines no `logits` of its own computes."""
    return mm(h, params["head"]["w"], f8)


def gaps(params, tokens, at, served, m: dict, forward, logits,
         control: bool):
    """For each scored position `at[j]` (the position whose next token is
    `served[j]`): how far the served token's float32 reference logit lies
    below the reference's best. With `control`, also the same gap for the
    token that the float8 computation puts first. `forward(params, tokens,
    m, f8)` gives the family's final-normed hidden states and
    `logits(params, h, f8)` its logits. Returns (gap, ctrl_gap), ctrl_gap
    zeros without `control`."""
    with jax.default_matmul_precision("highest"):
        h = forward(params, tokens, m, False)[at]
        ref = logits(params, h, False)
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        hc = forward(params, tokens, m, True)[at]
        top = logits(params, hc, True).argmax(-1)
        ctrl = best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
        return gap, ctrl
