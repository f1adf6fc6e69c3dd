"""Mixture-of-experts blocks: OLMoE-style (top-8 of 64) and DeepSeek-V2
(MLA attention + 2 shared + 160 routed top-6 experts).

Routing is dense-dispatch (token x expert one-hot einsum) with a capacity
factor — the production-standard formulation that keeps shapes static for
XLA SPMD and shards cleanly: experts over the "model" axis (EP), tokens over
"data". An auxiliary load-balancing loss (Switch-style) is returned in
metrics and added to the train loss.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.distributed import tp
from repro.distributed.sharding import shard_activation
from repro.kernels import ops
from repro.models import layers as L
from repro.models.config import ModelConfig

Params = dict[str, Any]


# ---------------- experts ----------------

def experts_init(key, cfg: ModelConfig) -> Params:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)

    def init(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    return {
        "w_gate": init(k1, (E, d, f), s_in),
        "w_up": init(k2, (E, d, f), s_in),
        "w_down": init(k3, (E, f, d), s_out),
    }


def moe_ffn_init(key, cfg: ModelConfig) -> Params:
    kr, ke, ks = jax.random.split(key, 3)
    p: Params = {
        "router": {"w": L.dense_init(kr, cfg.d_model, cfg.n_experts, cfg)},
        "experts": experts_init(ke, cfg),
    }
    if cfg.n_shared_experts:
        p["shared_mlp"] = L.swiglu_init(
            ks, cfg, d_ff=cfg.d_ff_expert * cfg.n_shared_experts)
    return p


def moe_ffn_apply(p: Params, x: jax.Array, cfg: ModelConfig
                  ) -> tuple[jax.Array, jax.Array]:
    """Returns (out, aux_loss). x: (B, S, d).

    Sort/scatter dispatch: tokens are ranked within their (row, expert)
    group via a stable argsort (first-come-first-served, identical
    semantics to the textbook cumsum-one-hot dispatch) and scattered into
    a static (E, B*capacity, d) buffer. Capacity is PER ROW — derived from
    S, not the flattened T = B*S — so whether a row's tokens reach their
    experts never depends on which other rows share the batch: a request
    served alone and the same request served in a full continuous-batching
    wave take bit-identical expert paths, and decode steps (S=1, distinct
    top-k experts) can never drop a token. Memory is O(T*K*d) — no
    (T, E, C) dispatch tensor — which is what keeps the 1M-token x
    160-expert DeepSeek-V2 train step compilable. Under EP sharding
    (experts on "model") XLA lowers the scatter/gather to the expected
    all-to-all pattern.
    """
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    xt = x.reshape(T, d)

    logits = ops.matmul(xt, p["router"]["w"], out_dtype=jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)                     # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = max(1, int(cfg.capacity_factor * S * K / E))           # per row
    TK = T * K
    idx_flat = gate_idx.reshape(TK)                                   # expert id
    row_flat = jnp.arange(TK, dtype=jnp.int32) // (S * K)             # batch row
    grp = row_flat * E + idx_flat                                     # (row, e)
    order = jnp.argsort(grp, stable=True)
    sorted_grp = grp[order]
    group_start = jnp.searchsorted(sorted_grp, jnp.arange(B * E),
                                   side="left")                       # (B*E,)
    pos_sorted = jnp.arange(TK, dtype=jnp.int32) - group_start[sorted_grp]
    pos_flat = jnp.zeros((TK,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    keep = pos_flat < capacity
    slot = row_flat * capacity + jnp.where(keep, pos_flat, 0)

    gate_flat = (gate_vals.reshape(TK) * keep.astype(gate_vals.dtype))
    x_rep = jnp.repeat(xt, K, axis=0)                                 # (TK, d)
    contrib = jnp.where(keep[:, None], x_rep.astype(jnp.float32), 0.0)
    xe = jnp.zeros((E, B * capacity, d), jnp.float32).at[
        idx_flat, slot].add(contrib)
    # NOTE: sharding the capacity dim over "batch" here looks like it should
    # data-parallelize the expert GEMM, but SPMD then lowers the token
    # scatter as a giant cross-shard exchange (measured 14x collective blowup
    # — EXPERIMENTS.md §Perf iteration log). The production layout is
    # expert_axis="data" (tokens all-to-all over data, stationary experts,
    # TP over d_ff), selected per-config via cfg.moe_expert_axis.
    xe = shard_activation(xe.astype(x.dtype), "expert", None, None)

    w = p["experts"]
    g = jnp.einsum("ecd,edf->ecf", xe, w["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, w["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", h, w["w_down"])                   # (E,C,d)
    ye = shard_activation(ye, "expert", None, None)

    y_tok = ye[idx_flat, slot].astype(jnp.float32)                    # (TK, d)
    y_tok = y_tok * gate_flat[:, None]
    out = y_tok.reshape(T, K, d).sum(axis=1).astype(x.dtype)

    if cfg.n_shared_experts:
        out = out + L.swiglu_apply(p["shared_mlp"], xt, cfg)

    # Switch-transformer load-balancing loss (density normalized by top-k so
    # the balanced floor is exactly router_aux_coef per layer)
    density = (jnp.zeros((E,), jnp.float32).at[idx_flat].add(1.0) / TK)
    router_prob = probs.mean(0)
    aux = cfg.router_aux_coef * E * jnp.sum(density * router_prob)
    return out.reshape(B, S, d), aux


# ---------------- explicit shard_map MoE (production EP path) ----------------


def _local_dispatch(xt, logits, cfg: ModelConfig, capacity: int,
                    n_rows: int):
    """Per-shard top-k dispatch into (E, n_rows*capacity, d) — same math
    as the SPMD path but over this shard's tokens only. `capacity` is per
    batch row (this shard holds ``n_rows`` rows of S = T/n_rows tokens),
    so expert admission is independent of batch composition."""
    T, d = xt.shape
    E, K = cfg.n_experts, cfg.top_k
    S = T // n_rows
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)
    TK = T * K
    idx_flat = gate_idx.reshape(TK)
    row_flat = jnp.arange(TK, dtype=jnp.int32) // (S * K)
    grp = row_flat * E + idx_flat
    order = jnp.argsort(grp, stable=True)
    sorted_grp = grp[order]
    group_start = jnp.searchsorted(sorted_grp, jnp.arange(n_rows * E),
                                   side="left")
    pos_sorted = jnp.arange(TK, dtype=jnp.int32) - group_start[sorted_grp]
    pos_flat = jnp.zeros((TK,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    keep = pos_flat < capacity
    slot = row_flat * capacity + jnp.where(keep, pos_flat, 0)
    gate_flat = gate_vals.reshape(TK) * keep.astype(gate_vals.dtype)
    x_rep = jnp.repeat(xt, K, axis=0)
    contrib = jnp.where(keep[:, None], x_rep.astype(jnp.float32), 0.0)
    xe = jnp.zeros((E, n_rows * capacity, d), jnp.float32).at[
        idx_flat, slot].add(contrib)
    density = jnp.zeros((E,), jnp.float32).at[idx_flat].add(1.0) / TK
    aux = cfg.router_aux_coef * E * jnp.sum(density * probs.mean(0))
    return xe.astype(xt.dtype), idx_flat, slot, gate_flat, aux


def moe_ffn_shard_map(p: Params, x: jax.Array, cfg: ModelConfig
                      ) -> tuple[jax.Array, jax.Array]:
    """Explicit-collective MoE over mesh ("data", "model"):

      * tokens: sharded over "data", replicated over "model";
      * experts: sharded over "model" (EP); their d-dim fsdp shards are
        all-gathered over "data" *inside* (one small gather per layer:
        the E/tp factor already divided the weights);
      * each model shard computes only its experts' slots and contributes a
        partial per-token output; one psum over "model" combines.

    Backward collectives are the AD transposes of these — no SPMD-inferred
    full-buffer reductions (the baseline's dominant cost, §Perf).
    """
    from jax import shard_map
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    d, E, K = cfg.d_model, cfg.n_experts, cfg.top_k
    w = p["experts"]
    fsdp_axis = "data" if cfg.fsdp else None

    B, S, _ = x.shape
    dp_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in dp_ax:
        n_data *= mesh.shape[a]
    n_model = mesh.shape["model"]
    capacity = max(1, int(cfg.capacity_factor * S * K / E))  # per row
    E_loc = E // n_model

    def local_fn(x_loc, router_w, w_gate, w_up, w_down, shared):
        Bl, Sl, _ = x_loc.shape
        xt = x_loc.reshape(Bl * Sl, d)
        if fsdp_axis:
            router_w = jax.lax.all_gather(router_w, fsdp_axis, axis=0,
                                          tiled=True)
            w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)
        logits = (xt.astype(jnp.float32) @ router_w.astype(jnp.float32))
        xe, idx_flat, slot, gate_flat, aux = _local_dispatch(
            xt, logits, cfg, capacity, Bl)
        # my expert block
        j = jax.lax.axis_index("model")
        xe_my = jax.lax.dynamic_slice_in_dim(xe, j * E_loc, E_loc, axis=0)
        g = jnp.einsum("ecd,edf->ecf", xe_my, w_gate)
        u = jnp.einsum("ecd,edf->ecf", xe_my, w_up)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
        ye_my = jnp.einsum("ecf,efd->ecd", h, w_down)          # (E_loc, C, d)
        # partial per-token combine: only slots routed to my experts
        rel = idx_flat - j * E_loc
        mine = (rel >= 0) & (rel < E_loc)
        rel_c = jnp.clip(rel, 0, E_loc - 1)
        y_tok = ye_my[rel_c, slot].astype(jnp.float32)
        y_tok = jnp.where(mine[:, None], y_tok, 0.0) * gate_flat[:, None]
        partial = y_tok.reshape(Bl * Sl, K, d).sum(axis=1)
        if cfg.n_shared_experts and shared is not None:
            sg, su, sd = shared
            if fsdp_axis:
                sg = jax.lax.all_gather(sg, fsdp_axis, axis=0, tiled=True)
                su = jax.lax.all_gather(su, fsdp_axis, axis=0, tiled=True)
                sd = jax.lax.all_gather(sd, fsdp_axis, axis=1, tiled=True)
            hh = jax.nn.silu((xt @ sg).astype(jnp.float32)).astype(
                xt.dtype) * (xt @ su)
            partial = partial + (hh @ sd).astype(jnp.float32)
        out = jax.lax.psum(partial.astype(jnp.float32), "model")
        for ax in dp_ax:
            aux = jax.lax.pmean(aux, ax)
        aux = jax.lax.pmean(aux, "model")  # identical; enforce replication
        return out.astype(x_loc.dtype).reshape(Bl, Sl, d), aux

    P_ = PartitionSpec
    fa = fsdp_axis
    shared_specs = (P_(fa, "model"), P_(fa, "model"), P_("model", fa))
    shared_args = None
    if cfg.n_shared_experts:
        sm = p["shared_mlp"]
        shared_args = tuple(ops.resolve(sm[k])
                            for k in ("w_gate", "w_up", "w_down"))
    batch_ax = dp_ax if len(dp_ax) > 1 else dp_ax[0]
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P_(batch_ax, None, None),    # x
                  P_(fa, None),                # router w (d, E)
                  P_("model", fa, None),       # experts w_gate (E, d, f)
                  P_("model", fa, None),       # experts w_up
                  P_("model", None, fa),       # experts w_down (E, f, d)
                  shared_specs if shared_args is not None else None),
        out_specs=(P_(batch_ax, None, None), P_()),
        check_vma=False,
    )
    return fn(x, ops.resolve(p["router"]["w"]), w["w_gate"], w["w_up"],
              w["w_down"], shared_args)


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig):
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    # gather-mode serving must not take the shard_map path: its per-token
    # combine is a psum over "model", which re-associates the fp32 sum
    # (the SPMD dense-dispatch path keeps E-sharded experts bit-exact —
    # dispatch/combine are gathers/scatters, contractions stay local)
    if (cfg.moe_impl == "shard_map" and mesh is not None
            and getattr(cfg, "tp_reduce", "psum") != "gather"
            and {"data", "model"}.issubset(set(mesh.axis_names))):
        return moe_ffn_shard_map(p, x, cfg)
    return moe_ffn_apply(p, x, cfg)


# ---------------- OLMoE block: GQA attention + MoE FFN ----------------

def moe_block_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg),
        "attn": L.attention_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg),
        "moe": moe_ffn_init(k2, cfg),
    }


def moe_block_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                    positions, cache=None, cache_index=None,
                    seq_lens=None):
    # seq_lens masks the chunked KV write to valid rows (clamp-proof
    # cache_update); MoE routing itself is per-token
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_out, new_cache = L.attention_apply(
        p["attn"], h, cfg, positions=positions, kv_cache=cache,
        cache_index=cache_index, seq_lens=seq_lens)
    x = x + attn_out
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    moe_out, aux = moe_ffn(p["moe"], h, cfg)
    x = x + moe_out
    from repro.models.transformer import residual_spec
    x = shard_activation(x, *residual_spec(cfg, x))
    return x, new_cache, aux


# ---------------- DeepSeek-V2 MLA attention ----------------

def mla_init(key, cfg: ModelConfig) -> Params:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    r, rq, pe = cfg.kv_lora_rank, cfg.q_lora_rank, cfg.rope_head_dim
    ks = jax.random.split(key, 8)
    p: Params = {
        "w_dkv": L.dense_init(ks[0], d, r, cfg),       # latent KV compress
        "w_kpe": L.dense_init(ks[1], d, pe, cfg),      # decoupled RoPE key
        "w_uk": L.dense_init(ks[2], r, H * hd, cfg),   # K decompress
        "w_uv": L.dense_init(ks[3], r, H * hd, cfg),   # V decompress
        "wo": L.dense_init(ks[4], H * hd, d, cfg,
                           scale=1.0 / math.sqrt(H * hd)),
    }
    if rq:
        p["w_dq"] = L.dense_init(ks[5], d, rq, cfg)
        p["w_uq"] = L.dense_init(ks[6], rq, H * (hd + pe), cfg)
    else:
        p["w_uq"] = L.dense_init(ks[7], d, H * (hd + pe), cfg)
    return p


def mla_apply(p: Params, x: jax.Array, cfg: ModelConfig, *, positions,
              kv_cache: dict | None = None, cache_index=None,
              seq_lens=None) -> tuple[jax.Array, dict | None]:
    """Multi-head latent attention. The cache stores the *latent* c_kv
    (rank r) and the shared RoPE key (rank pe) — the MLA memory win."""
    B, S, d = x.shape
    H, hd, pe = cfg.n_heads, cfg.hd, cfg.rope_head_dim

    if cfg.q_lora_rank:
        q = tp.tp_column(ops.matmul(x, p["w_dq"]), p["w_uq"], cfg)
    else:
        q = tp.tp_column(x, p["w_uq"], cfg)
    q = q.reshape(B, S, H, hd + pe)
    q_c, q_pe = q[..., :hd], q[..., hd:]
    q_pe = L.apply_rope(q_pe, positions, cfg.rope_theta)

    c_kv = ops.matmul(x, p["w_dkv"])                    # (B, S, r)
    k_pe = ops.matmul(x, p["w_kpe"]).reshape(B, S, 1, pe)
    k_pe = L.apply_rope(k_pe, positions, cfg.rope_theta)

    new_cache = None
    kv_valid = None
    if kv_cache is not None and "c_kv_pages" in kv_cache:
        # paged latent cache: scatter c_kv/k_pe through the page table,
        # gather the dense per-row view back (same bit-parity contract as
        # layers.attention_apply's paged branch)
        table = kv_cache["table"]
        cc = L.paged_cache_update(kv_cache["c_kv_pages"],
                                  c_kv.astype(kv_cache["c_kv_pages"].dtype),
                                  table, cache_index, update_lens=seq_lens)
        cp = L.paged_cache_update(kv_cache["k_pe_pages"],
                                  k_pe[:, :, 0].astype(
                                      kv_cache["k_pe_pages"].dtype),
                                  table, cache_index, update_lens=seq_lens)
        new_cache = {"c_kv_pages": cc, "k_pe_pages": cp, "table": table}
        c_kv_full = L.paged_gather(cc, table)
        k_pe_full = L.paged_gather(cp, table)[:, :, None]
        kv_valid = L.page_valid_mask(table, c_kv_full.shape[1])
        kv_len = cache_index + S
        q_offset = cache_index
    elif kv_cache is not None:
        # cache_index: scalar (wave serving) or (B,) per-slot positions
        # (continuous batching) — L.cache_update handles both
        cc = L.cache_update(kv_cache["c_kv"],
                            c_kv.astype(kv_cache["c_kv"].dtype), cache_index,
                            update_lens=seq_lens)
        cp = L.cache_update(kv_cache["k_pe"],
                            k_pe[:, :, 0].astype(kv_cache["k_pe"].dtype),
                            cache_index, update_lens=seq_lens)
        new_cache = {"c_kv": cc, "k_pe": cp}
        c_kv_full, k_pe_full = cc, cp[:, :, None]
        kv_len = cache_index + S
        q_offset = cache_index
    else:
        c_kv_full, k_pe_full = c_kv, k_pe
        kv_len = None
        q_offset = 0

    Sk = c_kv_full.shape[1]
    k_c = tp.tp_column(c_kv_full, p["w_uk"], cfg).reshape(B, Sk, H, hd)
    v = tp.tp_column(c_kv_full, p["w_uv"], cfg).reshape(B, Sk, H, hd)

    scale = 1.0 / math.sqrt(hd + pe)

    def attend_block(q_c_b, q_pe_b, off):
        """Query block attention (fp32 accum, bf16 matmul)."""
        Sq = q_c_b.shape[1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_c_b, k_c,
                            preferred_element_type=jnp.float32)
        scores += jnp.einsum("bqhp,bkgp->bhqk", q_pe_b, k_pe_full,
                             preferred_element_type=jnp.float32)
        scores *= scale
        mask = L.attention_mask(Sq, Sk, causal=True, q_offset=off,
                                kv_len=kv_len)
        if kv_valid is not None:
            mask = mask & kv_valid[:, None, :]
        scores = jnp.where(mask[:, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(x.dtype), v,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    qc = L.Q_CHUNK
    if S <= qc or S % qc != 0:
        out = attend_block(q_c, q_pe, q_offset)
    else:
        nb = S // qc
        qcb = q_c.reshape(B, nb, qc, H, hd).swapaxes(0, 1)
        qpb = q_pe.reshape(B, nb, qc, H, pe).swapaxes(0, 1)

        def body(_, xs):
            cb, pb, i = xs
            return None, attend_block(cb, pb, q_offset + i * qc)

        _, outs = jax.lax.scan(body, None, (qcb, qpb, jnp.arange(nb)))
        out = outs.swapaxes(0, 1).reshape(B, nb * qc, H, hd)
    out = out.reshape(B, S, H * hd)
    return tp.tp_row(out, p["wo"], cfg), new_cache


def mla_moe_block_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg),
        "attn": mla_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg),
        "moe": moe_ffn_init(k2, cfg),
    }


def mla_moe_block_apply(p, x, cfg, *, positions, cache=None,
                        cache_index=None, seq_lens=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_out, new_cache = mla_apply(p["attn"], h, cfg, positions=positions,
                                    kv_cache=cache, cache_index=cache_index,
                                    seq_lens=seq_lens)
    x = x + attn_out
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    moe_out, aux = moe_ffn(p["moe"], h, cfg)
    x = x + moe_out
    from repro.models.transformer import residual_spec
    x = shard_activation(x, *residual_spec(cfg, x))
    return x, new_cache, aux


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jnp.zeros((cfg.n_layers, batch, max_len, cfg.kv_lora_rank),
                          dtype),
        "k_pe": jnp.zeros((cfg.n_layers, batch, max_len, cfg.rope_head_dim),
                          dtype),
    }


def init_mla_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                       dtype=jnp.bfloat16) -> dict:
    """Paged MLA latent pool (the rank-r/pe analogue of
    `transformer.init_kv_page_pool`; page 0 reserved as the null page)."""
    return {
        "c_kv_pages": jnp.zeros(
            (cfg.n_layers, num_pages, page_size, cfg.kv_lora_rank), dtype),
        "k_pe_pages": jnp.zeros(
            (cfg.n_layers, num_pages, page_size, cfg.rope_head_dim), dtype),
    }
