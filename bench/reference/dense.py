"""Plain float32 reference of the dense decoder (starcoder2 as the
repository runs it): pre-norm RMSNorm blocks, GQA attention with rotary
positions, and a two-matrix tanh-GELU feed-forward (SwiGLU when the
configuration sets `gated_mlp`)."""

from __future__ import annotations

import math

import jax

from . import common


def specs(m: dict) -> dict:
    """{leaf path: (shape, scale)} of the parameter tree."""
    L, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    s = {**common.lm_specs(m), **common.attention_specs(m, L),
         "blocks/mlp/w_up": ((L, d, f), 1 / math.sqrt(d)),
         "blocks/mlp/w_down": ((L, f, d), 1 / math.sqrt(f))}
    if m["gated_mlp"]:
        s["blocks/mlp/w_gate"] = ((L, d, f), 1 / math.sqrt(d))
    return s


def make_ffn(m: dict):
    return ffn


def ffn(h, p, f8: bool):
    mlp = p["mlp"]
    u = common.mm(h, mlp["w_up"], f8)
    if "w_gate" in mlp:
        a = jax.nn.silu(common.mm(h, mlp["w_gate"], f8)) * u
    else:
        a = jax.nn.gelu(u, approximate=True)
    return common.mm(a, mlp["w_down"], f8)
