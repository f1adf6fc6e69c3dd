"""Decoder-only transformer scaffolding (dense blocks; scan over layers).

Generic over the block functions so MoE/VLM/hybrid families reuse the same
embedding / scan / head / cache plumbing. Layers are stacked along a leading
axis and driven by `lax.scan` to keep the HLO size O(1) in depth (critical
for the 512-device dry-run compiles).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_activation
from repro.distributed.tp import tp_column
from repro.kernels import ops
from repro.models import layers as L
from repro.models.config import ModelConfig

Params = dict[str, Any]

# ---------------- dense block ----------------


def dense_block_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg),
        "attn": L.attention_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg),
        "mlp": L.swiglu_init(k2, cfg),
    }


def residual_spec(cfg: ModelConfig, x: jax.Array) -> tuple:
    """Sharding names for the residual stream. With sequence parallelism the
    seq dim additionally shards over the TP axis between blocks (Megatron
    SP): the surrounding all-reduces become reduce-scatter + all-gather
    (half the wire bytes) and norms/residual math run on 1/TP of the
    activations."""
    if cfg.sequence_parallel and x.ndim >= 3 and x.shape[1] > 1:
        return ("batch", "seq_tp", None)
    return ("batch", None, None)


def dense_block_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                      positions: jax.Array, cache: dict | None = None,
                      cache_index=None, seq_lens=None):
    """Uniform block API across families: returns (x, cache, aux_loss).

    `seq_lens` (the per-row valid-token counts of a right-padded prefill
    chunk) masks the chunked KV write to valid rows (`cache_update`
    clamp-proofing); masking beyond that is unnecessary here — with
    causal attention + per-row cache indices, right-pad rows are already
    invisible to every real query.

    With sequence parallelism the canonical Megatron-SP structure applies:
    the residual stream and norms stay seq-sharded over TP; activations are
    all-gathered only at the qkv/gate matmul inputs, and the wo/w_down
    partial sums are constrained seq-sharded *before* the residual add so
    XLA lowers them as reduce-scatter (half the all-reduce wire bytes)."""
    sp = cfg.sequence_parallel and x.ndim == 3 and x.shape[1] > 1
    rs = residual_spec(cfg, x)
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if sp:
        h = shard_activation(h, "batch", None, None)   # all-gather point
    attn_out, new_cache = L.attention_apply(
        p["attn"], h, cfg, positions=positions, kv_cache=cache,
        cache_index=cache_index, seq_lens=seq_lens)
    if sp:
        attn_out = shard_activation(attn_out, *rs)     # reduce-scatter point
    x = x + attn_out
    x = shard_activation(x, *rs)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if sp:
        h = shard_activation(h, "batch", None, None)   # all-gather point
    mlp_out = L.swiglu_apply(p["mlp"], h, cfg)
    if sp:
        mlp_out = shard_activation(mlp_out, *rs)       # reduce-scatter point
    x = x + mlp_out
    x = shard_activation(x, *rs)
    return x, new_cache, jnp.zeros((), jnp.float32)


# ---------------- generic LM over any block ----------------


def lm_init(key, cfg: ModelConfig,
            block_init: Callable = dense_block_init) -> Params:
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    blocks = jax.vmap(lambda k: block_init(k, cfg))(layer_keys)
    params: Params = {
        "embed": {"table": L.embed_init(ke, cfg.vocab, cfg.d_model, cfg)},
        "blocks": blocks,
        "ln_f": L.rmsnorm_init(cfg.d_model, cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": L.dense_init(kh, cfg.d_model, cfg.vocab, cfg)}
    return params


def _embed(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = params["embed"]["table"][tokens]
    return shard_activation(x.astype(jnp.dtype(cfg.activation_dtype)),
                            "batch", None, None)


def _unembed(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return ops.matmul(x, ops.WeightRef(params["embed"]["table"]),
                          transpose_b=True, out_dtype=jnp.float32)
    # vocab-parallel under explicit TP (the head is sharded on vocab)
    return tp_column(x, ops.WeightRef(params["head"]["w"]), cfg,
                     out_dtype=jnp.float32)


def _scan_blocks(params: Params, x: jax.Array, cfg: ModelConfig,
                 block_apply: Callable, *, positions, cache=None,
                 cache_index=None):
    """Run stacked blocks via lax.scan; threads per-layer cache if given.

    Without a cache (the training loss) the scan slices every stacked
    leaf, the plain path its gradient takes. With one (the serving
    programs) it scans the layer index with the blocks closed over: each
    GEMM weight, a (K, N) matrix per layer, reaches the block as an
    `ops.WeightRef` that the tiled GEMM reads in place, and every other
    leaf (norm scales, biases, expert stacks) is sliced as the scan would.

    Returns (x, new_caches, total_aux_loss)."""
    blocks = params["blocks"]

    def layer(i):
        return jax.tree.map(
            lambda w: (ops.WeightRef(w, i) if w.ndim == 3 else
                       jax.lax.dynamic_index_in_dim(w, i, keepdims=False)),
            blocks)

    def body(carry, inp):
        h, aux_acc = carry
        if cache is None:
            blk = inp
            h, _, aux = block_apply(blk, h, cfg, positions=positions)
            return (h, aux_acc + aux), None
        i, layer_cache = inp
        h, new_cache, aux = block_apply(layer(i), h, cfg,
                                        positions=positions,
                                        cache=layer_cache,
                                        cache_index=cache_index)
        return (h, aux_acc + aux), new_cache

    if cfg.remat:
        body = jax.checkpoint(body)
    if cache is None:
        xs = blocks
    else:
        n = jax.tree.leaves(blocks)[0].shape[0]
        xs = (jnp.arange(n, dtype=jnp.int32), cache)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, caches, aux


def lm_loss(params: Params, batch: dict, cfg: ModelConfig,
            block_apply: Callable = dense_block_apply) -> tuple[jax.Array, dict]:
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _embed(params, tokens, cfg)
    x, _, aux = _scan_blocks(params, x, cfg, block_apply, positions=positions)
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)
    loss, metrics = L.cross_entropy(logits, batch["labels"],
                                    batch.get("loss_mask"))
    loss = loss + aux
    metrics["aux_loss"] = aux
    metrics["loss"] = loss
    return loss, metrics


# ---------------- serving (prefill / decode) ----------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> dict:
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def init_kv_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                      dtype=jnp.bfloat16) -> dict:
    """Paged KV pool: `num_pages` shared pages of `page_size` tokens per
    layer (page 0 reserved as the null page — see `repro.serving.paging`).
    The engine pairs this with a per-row page table to form the paged
    decode cache `{"k_pages", "v_pages", "table"}`."""
    shape = (cfg.n_layers, num_pages, page_size, cfg.kv_heads, cfg.hd)
    return {
        "k_pages": jnp.zeros(shape, dtype),
        "v_pages": jnp.zeros(shape, dtype),
    }


def lm_prefill(params: Params, batch: dict, cfg: ModelConfig,
               block_apply: Callable = dense_block_apply,
               max_len: int | None = None) -> tuple[jax.Array, dict]:
    """Full-sequence forward filling the KV cache; returns last logits.

    Two decode-state contracts, selected by ``batch["lengths"]``:

    * absent (legacy/wave): every row is exactly S tokens; returns the
      logits at position S-1 and a shared scalar ``index = S``.
    * present, a (B,) int32 of true prompt lengths over *right-padded*
      rows: returns each row's logits at ``lengths[b] - 1`` and a per-row
      ``index = lengths``. Right-padding is causal-safe — pad keys sit
      after every valid query, so no real token ever attends to padding,
      and decode overwrites pad cache rows before its per-row ``kv_len``
      mask can reach them. SSM blocks additionally receive the lengths as
      `seq_lens`, so conv/scan state stops exactly at each row's last
      valid token. A padded row is therefore bit-identical to the same
      prompt served unpadded (the continuous-batching slot-prefill
      contract), for attention and recurrent families alike.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_len = max_len or S
    lengths = batch.get("lengths")
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = batch.get("cache")
    if cache is None:
        cache = init_kv_cache(cfg, B, max_len)
    # constrain only the batch dim; per-family inner-dim shardings are set by
    # the launcher's explicit in_shardings (see launch/dryrun.py). Gather-
    # mode serving keeps its head-axis cache sharding (sharding.
    # serving_state_pspecs) — a batch-only constraint would all-gather it.
    if getattr(cfg, "tp_reduce", "psum") != "gather":
        cache = jax.tree.map(lambda c: shard_activation(c, None, "batch"),
                             cache)
    x = _embed(params, tokens, cfg)
    if lengths is not None:
        lens32 = jnp.asarray(lengths, jnp.int32)

        def ba(bp, h, c, **kw):
            return block_apply(bp, h, c, seq_lens=lens32, **kw)
    else:
        ba = block_apply

    x, cache, _ = _scan_blocks(params, x, cfg, ba,
                               positions=positions, cache=cache,
                               cache_index=jnp.int32(0))
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    if lengths is None:
        logits = _unembed(params, x[:, -1:], cfg)
        return logits[:, 0], {"kv": cache, "index": jnp.int32(S)}
    lengths = jnp.asarray(lengths, jnp.int32)
    last = jnp.take_along_axis(
        x, jnp.broadcast_to((lengths - 1)[:, None, None],
                            (B, 1, x.shape[-1])), axis=1)
    logits = _unembed(params, last, cfg)
    return logits[:, 0], {"kv": cache, "index": lengths}


def lm_prefill_chunk(params: Params, tokens: jax.Array, lengths: jax.Array,
                     state: dict, cfg: ModelConfig,
                     block_apply: Callable = dense_block_apply,
                     positions: jax.Array | None = None
                     ) -> tuple[jax.Array, dict]:
    """One admission-prefill chunk, fused into the serving loop.

    tokens: (B, S) — each row's next `lengths[b]` prompt tokens, right-
    padded to the shared chunk bucket S; state: {"kv", "index"} with a
    per-row ``index`` holding each row's chunk base offset (tokens already
    written; 0 on the first chunk). KV rows are written at
    ``index[b] .. index[b]+S`` (`layers.cache_update` per-row contract),
    attention masks use the per-row base as ``q_offset``, and SSM blocks
    receive `seq_lens` so conv/scan state advances only over valid
    positions. Returns each row's logits at its last valid position
    (meaningful on a row's final chunk) and the advanced state
    (``index + lengths``).

    A prompt prefilled in chunks is bit-identical to `lm_prefill` over the
    whole (bucketed) prompt: attention reads the same cache with the same
    masks, and the SSM serve-scan block size divides every chunk bucket
    (see `ssm.SERVE_CHUNK`).

    `positions` overrides the default per-row ``base + arange(S)`` rotary
    positions (families whose position ids are not the cache index — e.g.
    the VLM's mRoPE text offsets — pass their own; cache writes still land
    at the per-row cache index).
    """
    B, S = tokens.shape
    base = jnp.asarray(state["index"], jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if positions is None:
        positions = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x = _embed(params, tokens, cfg)

    def chunk_block(bp, h, c, **kw):
        return block_apply(bp, h, c, seq_lens=lengths, **kw)

    x, cache, _ = _scan_blocks(params, x, cfg, chunk_block,
                               positions=positions, cache=state["kv"],
                               cache_index=base)
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.broadcast_to(jnp.maximum(lengths - 1, 0)[:, None, None],
                            (B, 1, x.shape[-1])), axis=1)
    logits = _unembed(params, last, cfg)
    return logits[:, 0], {"kv": cache, "index": base + lengths}


def lm_decode_step(params: Params, token: jax.Array, state: dict,
                   cfg: ModelConfig,
                   block_apply: Callable = dense_block_apply,
                   positions: jax.Array | None = None
                   ) -> tuple[jax.Array, dict]:
    """One-token decode. token: (B,) int32. state: {"kv", "index"}.

    ``index`` is either a scalar (all rows at the same position — the wave
    contract) or (B,) (each slot at its own position — the continuous-
    batching contract; see `lm_prefill`). `positions` overrides the rotary
    position ids (defaults to the cache index)."""
    B = token.shape[0]
    idx = state["index"]
    if positions is not None:
        pass
    elif jnp.ndim(idx) == 0:
        positions = jnp.broadcast_to(idx, (B, 1)).astype(jnp.int32)
    else:
        positions = idx[:, None].astype(jnp.int32)
    x = _embed(params, token[:, None], cfg)
    x, cache, _ = _scan_blocks(params, x, cfg, block_apply,
                               positions=positions, cache=state["kv"],
                               cache_index=idx)
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)
    return logits[:, 0], {"kv": cache, "index": idx + 1}
