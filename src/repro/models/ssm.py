"""State-space blocks: Mamba1 (Falcon-Mamba) and Mamba2/SSD (Zamba2 backbone).

TPU adaptation notes (DESIGN.md §2): the CUDA selective-scan kernel is a
fused sequential scan with shared-memory staging; on TPU we use
  * Mamba1: chunked first-order recurrence — `lax.associative_scan` inside a
    chunk (parallel, VPU-friendly), `lax.scan` across chunks (O(S/Q) sequential
    steps, bounded VMEM working set per chunk).
  * Mamba2: the SSD block decomposition — intra-chunk attention-like matmuls
    (MXU work) + inter-chunk state recurrence. This is the TPU-native
    formulation of the paper's "adapt the insight, don't port the kernel".

States: mamba1 h is (B, d_inner, d_state) per layer; mamba2 h is
(B, H_ssm, d_state, headdim). Decode is O(1) per token for both.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed import tp
from repro.distributed.sharding import shard_activation
from repro.kernels import ops
from repro.models import layers as L
from repro.models.config import ModelConfig

Params = dict[str, Any]

CHUNK = 128

# Serving-prefill scan block. Chunked admission prefill feeds a prompt
# through the decode loop in pow2-bucket chunks and must carry the SSM
# state across chunk boundaries *bit-exactly* (the engine's parity
# contract). A first-order/SSD scan split at a multiple of its inner block
# size (with the carried state threaded through) executes the identical
# op sequence, and identity-padded tails (decay=1 / input=0) are
# bit-transparent — so every serving-path scan uses this block size, which
# divides every chunk bucket (`ops.prefill_buckets(min_bucket=8)`), and an
# unchunked serve prefill is bit-identical to any chunking of it. Training
# (no cache) keeps the wide CHUNK blocks.
SERVE_CHUNK = 8


def serve_chunk(cfg: ModelConfig) -> int:
    """Serving-scan block size: `cfg.ssm_serve_grain` when set (wider
    grains amortize the O(S/Q) sequential scan steps over long prompts),
    else the module default. The engine validates `chunk_tokens` is a
    multiple so the bit-parity argument above still applies."""
    return int(getattr(cfg, "ssm_serve_grain", 0) or 0) or SERVE_CHUNK


# ---------------- causal depthwise conv ----------------

def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                history: jax.Array | None = None) -> jax.Array:
    """x: (B, S, C); w: (C, K); history: (B, K-1, C) carried state."""
    w = ops.resolve(w)
    B, S, C = x.shape
    K = w.shape[1]
    if history is None:
        history = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([history, x], axis=1)          # (B, S+K-1, C)
    out = jnp.zeros((B, S, C), jnp.float32)
    for i in range(K):
        out = out + xp[:, i:i + S].astype(jnp.float32) * w[:, i].astype(
            jnp.float32)
    out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def conv_history(history: jax.Array, x: jax.Array,
                 seq_lens: jax.Array) -> jax.Array:
    """Carried conv state over a right-padded chunk: the last K-1 *valid*
    inputs per row (pad positions must not enter the next chunk's
    receptive field). `seq_lens`: (B,) valid token count per row; a row
    with 0 valid tokens keeps its history unchanged."""
    Km1 = history.shape[1]
    xp = jnp.concatenate([history, x], axis=1)          # (B, Km1+S, C)
    lens = jnp.asarray(seq_lens, jnp.int32)

    def row(xp_b, n):
        return jax.lax.dynamic_slice_in_dim(xp_b, n, Km1, axis=0)

    return jax.vmap(row)(xp, lens)


def _seq_mask(seq_lens, S: int) -> jax.Array:
    """(B, S) validity mask for right-padded chunk rows."""
    lens = jnp.asarray(seq_lens, jnp.int32)
    return jnp.arange(S, dtype=jnp.int32)[None, :] < lens[:, None]


# ---------------- first-order recurrence (chunked) ----------------

def _chunk_recurrence(decay_c, inp_c, h0):
    """Within-chunk h_t = decay_t*h_{t-1} + inp_t via associative scan.
    decay_c/inp_c: (Q, ...) leading time axis. h0: (...). Returns h for all
    t in chunk and the final state."""

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    A, Bc = jax.lax.associative_scan(combine, (decay_c, inp_c), axis=0)
    h_all = Bc + A * h0[None]
    return h_all, h_all[-1]


def mamba1_scan(decay: jax.Array, inp: jax.Array, C: jax.Array,
                h0: jax.Array, chunk: int = CHUNK
                ) -> tuple[jax.Array, jax.Array]:
    """decay/inp: (B, S, di, ds); C: (B, S, ds); h0: (B, di, ds).
    Returns y: (B, S, di) = C_t . h_t, and final state.

    A non-divisible tail is identity-padded (decay=1, input=0) to a
    multiple of the block size — bit-transparent to the recurrence, so
    arbitrary lengths scan without changing any real position's value."""
    B, S, di, ds = decay.shape
    q = min(chunk, S)
    pad = (-S) % q
    if pad:
        decay = jnp.concatenate(
            [decay, jnp.ones((B, pad, di, ds), decay.dtype)], axis=1)
        inp = jnp.concatenate(
            [inp, jnp.zeros((B, pad, di, ds), inp.dtype)], axis=1)
        C = jnp.concatenate([C, jnp.zeros((B, pad, ds), C.dtype)], axis=1)
    nc = (S + pad) // q
    dec = decay.reshape(B, nc, q, di, ds).swapaxes(0, 1)   # (nc,B,q,di,ds)
    ip = inp.reshape(B, nc, q, di, ds).swapaxes(0, 1)
    Cm = C.reshape(B, nc, q, ds).swapaxes(0, 1)            # (nc,B,q,ds)

    def body(h, xs):
        d_c, i_c, c_c = xs                                  # (B,q,di,ds), (B,q,ds)
        # time axis first for the associative scan
        h_all, h_last = _chunk_recurrence(
            d_c.swapaxes(0, 1), i_c.swapaxes(0, 1), h)      # (q,B,di,ds)
        y = jnp.einsum("qbds,bqs->bqd", h_all, c_c)
        return h_last, y

    h_final, ys = jax.lax.scan(body, h0, (dec, ip, Cm))
    y = ys.swapaxes(0, 1).reshape(B, S + pad, di)
    return y[:, :S], h_final


# ---------------- Mamba1 block ----------------

def mamba1_block_init(key, cfg: ModelConfig) -> Params:
    d, di, ds, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.d_conv
    r = max(1, cfg.d_model // 16)  # dt_rank
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    # S4D-real initialization for A
    A = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))
    return {
        "ln": L.rmsnorm_init(d, cfg),
        "ssm": {
            "in_proj": L.dense_init(ks[0], d, 2 * di, cfg),
            "conv_w": (jax.random.normal(ks[1], (di, K), jnp.float32)
                       / math.sqrt(K)).astype(dt),
            "conv_b": jnp.zeros((di,), dt),
            "x_proj": L.dense_init(ks[2], di, r + 2 * ds, cfg),
            "dt_proj": L.dense_init(ks[3], r, di, cfg),
            "dt_bias": jnp.full((di,), -4.6, dt),  # softplus^-1(0.01)
            "A_log": jnp.log(A),
            "D": jnp.ones((di,), jnp.float32),
            "out_proj": L.dense_init(ks[4], di, d, cfg),
        },
    }


def _mamba1_core(p: Params, x_conv: jax.Array, cfg: ModelConfig,
                 h0: jax.Array, *, single_step: bool = False,
                 seq_mask: jax.Array | None = None, chunk: int = CHUNK):
    """x_conv: post-conv activations (B, S, di). Returns (y, h_final).

    `seq_mask` (B, S) marks valid positions of a right-padded chunk: pad
    steps become the identity update (decay=1, input=0), so the carried
    state stops exactly at each row's last valid token. `chunk` sets the
    scan block size (serving paths use SERVE_CHUNK so chunked prefill is
    bit-identical to an unchunked serve — see SERVE_CHUNK)."""
    s = p["ssm"]
    di, ds = cfg.d_inner, cfg.ssm_state
    r = max(1, cfg.d_model // 16)
    # x_proj contracts di — re-replicate in gather mode so the sharded
    # channel axis never enters a plain dot (bit-parity contract)
    x_conv = tp.replicate_for_parity(x_conv, cfg)
    proj = ops.matmul(x_conv, s["x_proj"])
    dt_low, Bm, Cm = jnp.split(proj, [r, r + ds], axis=-1)
    dtv = ops.matmul(dt_low, s["dt_proj"]).astype(jnp.float32)
    dtv = jax.nn.softplus(dtv + s["dt_bias"].astype(jnp.float32))  # (B,S,di)
    A = -jnp.exp(ops.resolve(s["A_log"]).astype(jnp.float32))      # (di,ds)
    decay = jnp.exp(dtv[..., None] * A)                            # (B,S,di,ds)
    xf = x_conv.astype(jnp.float32)
    inp = (dtv * xf)[..., None] * Bm.astype(jnp.float32)[:, :, None, :]
    if seq_mask is not None:
        decay = jnp.where(seq_mask[..., None, None], decay, 1.0)
        inp = jnp.where(seq_mask[..., None, None], inp, 0.0)
    if single_step:
        h = decay[:, 0] * h0 + inp[:, 0]                           # (B,di,ds)
        y = jnp.einsum("bds,bs->bd", h, Cm[:, 0].astype(jnp.float32))[:, None]
        h_final = h
    else:
        y, h_final = mamba1_scan(decay, inp, Cm.astype(jnp.float32), h0,
                                 chunk=chunk)
    y = y + s["D"].astype(jnp.float32) * xf
    return y, h_final


def mamba1_block_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                       positions=None, cache: dict | None = None,
                       cache_index=None, seq_lens=None):
    """cache: {"conv": (B, K-1, di), "ssm": (B, di, ds)} or None.

    `seq_lens` (B,) marks each row's valid token count in a right-padded
    prefill chunk: conv history and SSM state advance only over valid
    positions (the chunked-admission contract)."""
    B, S, d = x.shape
    di = cfg.d_inner
    s = p["ssm"]
    h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    xz = tp.tp_column(h, s["in_proj"], cfg)
    x_, z = jnp.split(xz, 2, axis=-1)
    x_ = shard_activation(x_, "batch", None, "model")

    new_cache = None
    if cache is not None:
        x_conv = causal_conv(x_, s["conv_w"], s["conv_b"], cache["conv"])
        if seq_lens is None:
            hist = jnp.concatenate([cache["conv"], x_],
                                   axis=1)[:, -(cfg.d_conv - 1):]
        else:
            hist = conv_history(cache["conv"], x_, seq_lens)
        x_conv = jax.nn.silu(x_conv.astype(jnp.float32)).astype(x.dtype)
        y, h_final = _mamba1_core(
            p, x_conv, cfg, cache["ssm"].astype(jnp.float32),
            single_step=(S == 1),
            seq_mask=None if seq_lens is None else _seq_mask(seq_lens, S),
            chunk=serve_chunk(cfg))
        new_cache = {"conv": hist.astype(cache["conv"].dtype),
                     "ssm": h_final.astype(cache["ssm"].dtype)}
    else:
        x_conv = causal_conv(x_, s["conv_w"], s["conv_b"])
        x_conv = jax.nn.silu(x_conv.astype(jnp.float32)).astype(x.dtype)
        h0 = jnp.zeros((B, di, cfg.ssm_state), jnp.float32)
        y, _ = _mamba1_core(p, x_conv, cfg, h0)

    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = tp.tp_row(y.astype(x.dtype), s["out_proj"], cfg)
    x = x + out
    x = shard_activation(x, "batch", None, None)
    return x, new_cache, jnp.zeros((), jnp.float32)


def init_mamba1_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    return {
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.d_conv - 1, cfg.d_inner),
                          dtype),
        "ssm": jnp.zeros((cfg.n_layers, batch, cfg.d_inner, cfg.ssm_state),
                         dtype),
    }


# ---------------- Mamba2 (SSD) block ----------------

def mamba2_block_init(key, cfg: ModelConfig) -> Params:
    d, di, ds = cfg.d_model, cfg.d_inner, cfg.ssm_state
    P_, G = cfg.ssm_headdim, cfg.ssm_ngroups
    H = di // P_
    K = cfg.d_conv
    conv_ch = di + 2 * G * ds
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "ln": L.rmsnorm_init(d, cfg),
        "ssm": {
            "in_proj": L.dense_init(ks[0], d, 2 * di + 2 * G * ds + H, cfg),
            "conv_w": (jax.random.normal(ks[1], (conv_ch, K), jnp.float32)
                       / math.sqrt(K)).astype(dt),
            "conv_b": jnp.zeros((conv_ch,), dt),
            "A_log": jnp.zeros((H,), jnp.float32),
            "D": jnp.ones((H,), jnp.float32),
            "dt_bias": jnp.full((H,), -4.6, dt),
            "norm_scale": jnp.ones((di,), dt),
            "out_proj": L.dense_init(ks[2], di, d, cfg),
        },
    }


def ssd_scan(x: jax.Array, a_log: jax.Array, Bm: jax.Array, Cm: jax.Array,
             h0: jax.Array, chunk: int = CHUNK
             ) -> tuple[jax.Array, jax.Array]:
    """SSD chunked recurrence.

    x: (B, S, H, P) inputs already scaled by dt;
    a_log: (B, S, H) per-step log decay (<= 0);
    Bm/Cm: (B, S, N) state in/out projections (ngroups=1 broadcast);
    h0: (B, H, N, P). Returns y (B, S, H, P), h_final.
    """
    Bsz, S, H, P_ = x.shape
    N = Bm.shape[-1]
    q = min(chunk, S)
    pad = (-S) % q
    if pad:
        # identity tail: zero input/B kills state updates, zero log-decay
        # keeps the carried state — bit-transparent to real positions
        x = jnp.concatenate(
            [x, jnp.zeros((Bsz, pad, H, P_), x.dtype)], axis=1)
        a_log = jnp.concatenate(
            [a_log, jnp.zeros((Bsz, pad, H), a_log.dtype)], axis=1)
        Bm = jnp.concatenate(
            [Bm, jnp.zeros((Bsz, pad, N), Bm.dtype)], axis=1)
        Cm = jnp.concatenate(
            [Cm, jnp.zeros((Bsz, pad, N), Cm.dtype)], axis=1)
    nc = (S + pad) // q
    xr = x.reshape(Bsz, nc, q, H, P_).swapaxes(0, 1)
    ar = a_log.reshape(Bsz, nc, q, H).swapaxes(0, 1)
    Br = Bm.reshape(Bsz, nc, q, N).swapaxes(0, 1)
    Cr = Cm.reshape(Bsz, nc, q, N).swapaxes(0, 1)

    def body(h, xs):
        xc, ac, bc, cc = xs          # (B,q,H,P), (B,q,H), (B,q,N), (B,q,N)
        la = jnp.cumsum(ac, axis=1)                    # (B,q,H)
        # intra-chunk: attention-like causal matmul with decay weights
        scores = jnp.einsum("bqn,bkn->bqk", cc, bc)    # (B,q,q)
        decay_qk = jnp.exp(la[:, :, None, :] - la[:, None, :, :])  # (B,q,k,H)
        causal = jnp.tril(jnp.ones((q, q), bool))
        w = jnp.where(causal[None, :, :, None],
                      scores[..., None] * decay_qk, 0.0)
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", w, xc)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bqn,bhnp,bqh->bqhp", cc, h, jnp.exp(la))
        # next chunk state
        rem = jnp.exp(la[:, -1:, :] - la)              # (B,q,H)
        s_c = jnp.einsum("bkn,bkhp,bkh->bhnp", bc, xc, rem)
        h_next = jnp.exp(la[:, -1])[:, :, None, None] * h + s_c
        return h_next, y_intra + y_inter

    h_final, ys = jax.lax.scan(body, h0, (xr, ar, Br, Cr))
    y = ys.swapaxes(0, 1).reshape(Bsz, S + pad, H, P_)
    return y[:, :S], h_final


def _mamba2_split(cfg: ModelConfig, proj: jax.Array):
    di, ds, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
    z, rest = jnp.split(proj, [di], axis=-1)
    xBC, dt = jnp.split(rest, [di + 2 * G * ds], axis=-1)
    return z, xBC, dt  # dt: (..., H)


def mamba2_block_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                       positions=None, cache: dict | None = None,
                       cache_index=None, seq_lens=None):
    """cache: {"conv": (B, K-1, conv_ch), "ssm": (B, H, N, P)}.

    `seq_lens` (B,) marks each row's valid token count in a right-padded
    prefill chunk: conv history and SSD state advance only over valid
    positions (pad steps carry zero input/B and zero log-decay — the
    identity update)."""
    B, S, d = x.shape
    di, ds, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
    P_ = cfg.ssm_headdim
    H = di // P_
    s = p["ssm"]
    hin = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    proj = tp.tp_column(hin, s["in_proj"], cfg)
    z, xBC, dt_raw = _mamba2_split(cfg, proj)
    # z feeds the gated-norm mean (an axis reduction) and dt_raw the decay
    # path — neither may carry a sharded axis in gather mode
    z = tp.replicate_for_parity(z, cfg)
    dt_raw = tp.replicate_for_parity(dt_raw, cfg)
    xBC = shard_activation(xBC, "batch", None, "model")

    new_cache = None
    if cache is not None:
        conv_hist = cache["conv"]
        xBC_c = causal_conv(xBC, s["conv_w"], s["conv_b"], conv_hist)
        if seq_lens is None:
            hist = jnp.concatenate([conv_hist, xBC],
                                   axis=1)[:, -(cfg.d_conv - 1):]
        else:
            hist = conv_history(conv_hist, xBC, seq_lens)
    else:
        xBC_c = causal_conv(xBC, s["conv_w"], s["conv_b"])
        hist = None
    # the SSD einsums contract the state dim of Bm/Cm — re-replicate the
    # conv output in gather mode before anything reaches a contraction
    xBC_c = tp.replicate_for_parity(xBC_c, cfg)
    xBC_c = jax.nn.silu(xBC_c.astype(jnp.float32)).astype(x.dtype)
    xs, Bm, Cm = jnp.split(xBC_c, [di, di + G * ds], axis=-1)
    xs = xs.reshape(B, S, H, P_)
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32)
                          + s["dt_bias"].astype(jnp.float32))    # (B,S,H)
    a_log = -jnp.exp(s["A_log"].astype(jnp.float32)) * dtv        # (B,S,H)
    x_dt = xs.astype(jnp.float32) * dtv[..., None]
    Bm_f = Bm.astype(jnp.float32)
    if seq_lens is not None:
        mask = _seq_mask(seq_lens, S)
        a_log = jnp.where(mask[..., None], a_log, 0.0)
        x_dt = jnp.where(mask[..., None, None], x_dt, 0.0)
        Bm_f = jnp.where(mask[..., None], Bm_f, 0.0)

    h0 = (cache["ssm"].astype(jnp.float32) if cache is not None
          else jnp.zeros((B, H, ds, P_), jnp.float32))
    if cache is not None and S == 1:
        decay = jnp.exp(a_log[:, 0])                              # (B,H)
        upd = jnp.einsum("bn,bhp->bhnp", Bm_f[:, 0], x_dt[:, 0])
        h1 = decay[:, :, None, None] * h0 + upd
        y = jnp.einsum("bn,bhnp->bhp", Cm[:, 0].astype(jnp.float32), h1)
        y = y[:, None]                                            # (B,1,H,P)
        h_final = h1
    else:
        y, h_final = ssd_scan(x_dt, a_log, Bm_f,
                              Cm.astype(jnp.float32), h0,
                              chunk=serve_chunk(cfg) if cache is not None
                              else CHUNK)
    if cache is not None:
        new_cache = {"conv": hist.astype(cache["conv"].dtype),
                     "ssm": h_final.astype(cache["ssm"].dtype)}

    y = y + s["D"].astype(jnp.float32)[None, None, :, None] * xs.astype(
        jnp.float32)
    y = y.reshape(B, S, di)
    # gated RMSNorm (mamba2)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + cfg.norm_eps)
    y = y * s["norm_scale"].astype(jnp.float32)
    out = tp.tp_row(y.astype(x.dtype), s["out_proj"], cfg)
    x = x + out
    x = shard_activation(x, "batch", None, None)
    return x, new_cache, jnp.zeros((), jnp.float32)


def init_mamba2_cache(cfg: ModelConfig, batch: int, n_layers: int | None = None,
                      dtype=jnp.float32) -> dict:
    di, ds, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
    H = di // cfg.ssm_headdim
    Lc = n_layers if n_layers is not None else cfg.n_layers
    conv_ch = di + 2 * G * ds
    return {
        "conv": jnp.zeros((Lc, batch, cfg.d_conv - 1, conv_ch), dtype),
        "ssm": jnp.zeros((Lc, batch, H, ds, cfg.ssm_headdim), dtype),
    }
