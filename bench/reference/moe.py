"""Plain float32 reference of the mixture-of-experts decoder (OLMoE as
the repository runs it): the dense block's attention, then a softmax
router whose top-k gates are renormalised to sum to 1, and SwiGLU
experts. Every expert is computed over every token and weighted by its
gate (0 for experts outside a token's top k): no capacity, nothing
dropped."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common


def specs(m: dict) -> dict:
    """{leaf path: (shape, scale)} of the parameter tree."""
    L, d, E, f = m["n_layers"], m["d_model"], m["n_experts"], m["d_ff_expert"]
    return {**common.lm_specs(m), **common.attention_specs(m, L),
            "blocks/moe/router/w": ((L, d, E), 1 / math.sqrt(d)),
            "blocks/moe/experts/w_gate": ((L, E, d, f), 1 / math.sqrt(d)),
            "blocks/moe/experts/w_up": ((L, E, d, f), 1 / math.sqrt(d)),
            "blocks/moe/experts/w_down": ((L, E, f, d), 1 / math.sqrt(f))}


def make_ffn(m: dict):
    K = m["top_k"]

    def ffn(h, p, f8: bool):
        moe = p["moe"]
        probs = jax.nn.softmax(common.mm(h, moe["router"]["w"], f8), -1)
        top, idx = jax.lax.top_k(probs, K)
        top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
        S, E = probs.shape
        gate = jnp.zeros((S, E), jnp.float32).at[
            jnp.arange(S)[:, None], idx].set(top)
        w = moe["experts"]

        def expert(acc, e):
            g = common.mm(h, w["w_gate"][e], f8)
            u = common.mm(h, w["w_up"][e], f8)
            y = common.mm(jax.nn.silu(g) * u, w["w_down"][e], f8)
            return acc + gate[:, e][:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
        return out

    return ffn
