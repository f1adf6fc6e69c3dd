"""Work of the dense decoder (GQA attention, two-matrix or gated FFN)."""

from __future__ import annotations

from . import common


def layer_gemms(m: dict) -> list[tuple[int, int, int]]:
    """(N, K, count) of one layer's GEMMs per row: attention projections
    and the feed-forward."""
    d, f = m["d_model"], m["d_ff"]
    up = 2 if m["gated_mlp"] else 1
    return common.attention_proj(m) + [(f, d, up), (d, f, 1)]


def kernel_gemms(m: dict) -> list[tuple[int, int, int]]:
    """(N, K, count) per row and layer of the GEMMs the program runs
    through its tiled kernel: every projection and the feed-forward."""
    return layer_gemms(m)


def row_flops(m: dict) -> float:
    """GEMM operations of one token row through every layer (no head)."""
    return m["n_layers"] * sum(common.gemm_flops(1, n, k) * c
                               for n, k, c in layer_gemms(m))


def weight_bytes(m: dict, rows: int) -> float:
    """Weight bytes a step over `rows` token rows must read: every layer's
    matrices and the head once (the embedding only for its rows)."""
    per_layer = sum(n * k * c for n, k, c in layer_gemms(m))
    return common.BF16 * (m["n_layers"] * per_layer
                          + m["d_model"] * m["vocab"]
                          + rows * m["d_model"])
