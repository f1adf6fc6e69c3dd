"""Operations and bytes that a step of a decoder must spend, from the
model's own equations at the configuration's widths (never from the
program's HLO or its own shape tables). So a roofline share or an MFU
reads the same work whatever implements it.

A GEMM is (M, N, K): M rows, N outputs, K inputs; it needs 2*M*N*K
operations.
"""

from __future__ import annotations

BF16 = 2


def gemm_flops(M: int, N: int, K: int) -> float:
    return 2.0 * M * N * K


def gemm_bytes(M: int, N: int, K: int, weight_reads: int = 1,
               out_bytes: int = BF16) -> float:
    """Least HBM bytes of a bf16 GEMM whose (K, N) weight is read
    `weight_reads` times: the weight, the (M, K) input once and the
    (M, N) result once, `out_bytes` an element."""
    return BF16 * (weight_reads * K * N + M * K) + out_bytes * M * N


def attention_proj(m: dict) -> list[tuple[int, int, int]]:
    """(N, K, count) of one layer's attention projections per row."""
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return [(H * hd, d, 1), (KV * hd, d, 2), (d, H * hd, 1)]


def attention_flops(m: dict, ctx: int) -> float:
    """Scores and weighted values of one query row over `ctx` keys, one
    layer: 2 * 2 * H * hd * ctx."""
    return 4.0 * m["n_heads"] * m["head_dim"] * ctx


def kv_bytes_per_token(m: dict) -> float:
    """Bytes of K and V one cached position holds over all layers."""
    return (2.0 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"]
            * BF16)


def prompt_attention_flops(m: dict, P: int) -> float:
    """Causal attention over a P-token prompt, all layers: row p attends
    to p + 1 keys."""
    return m["n_layers"] * attention_flops(m, 1) * P * (P + 1) / 2.0
