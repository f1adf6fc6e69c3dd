"""Work of the mixture-of-experts decoder: the dense attention, a router
and top-k SwiGLU experts. Only the routed work counts: each token runs
`top_k` experts, whatever a dispatch computes besides."""

from __future__ import annotations

from . import common


def layer_gemms(m: dict) -> list[tuple[int, int, int]]:
    """(N, K, count) of one layer's GEMMs per row: attention projections,
    the router and the token's `top_k` experts (gate, up, down)."""
    d, f, K = m["d_model"], m["d_ff_expert"], m["top_k"]
    return common.attention_proj(m) + [(m["n_experts"], d, 1),
                                       (f, d, 2 * K), (d, f, K)]


def kernel_gemms(m: dict) -> list[tuple[int, int, int]]:
    """(N, K, count) per row and layer of the GEMMs the program runs
    through its tiled kernel: the attention projections and the router
    (the experts run as XLA einsums)."""
    return common.attention_proj(m) + [(m["n_experts"], m["d_model"], 1)]


def row_flops(m: dict) -> float:
    """GEMM operations of one token row through every layer (no head)."""
    return m["n_layers"] * sum(common.gemm_flops(1, n, k) * c
                               for n, k, c in layer_gemms(m))


def weight_bytes(m: dict, rows: int) -> float:
    """Weight bytes a step over `rows` token rows must read: attention,
    router and head once, the embedding for its rows, and the experts
    that `rows` tokens reach, taken at their expected number under
    uniform routing: E * (1 - (1 - K/E)^rows) distinct experts a layer."""
    d, f, E, K = (m["d_model"], m["d_ff_expert"], m["n_experts"],
                  m["top_k"])
    attn = sum(n * k * c for n, k, c in common.attention_proj(m)) + d * E
    experts = E * (1.0 - (1.0 - K / E) ** max(rows, 0)) * 3 * d * f
    return common.BF16 * (m["n_layers"] * (attn + experts)
                          + d * m["vocab"] + rows * d)
