"""Public GEMM ops — the framework's single entry point for matmuls.

Every matmul site in the model zoo calls `ops.matmul` / `ops.linear`. The op
dispatches per backend:

  * TPU: the Pallas tiled kernel with a block config chosen by the
    performance-predictor autotuner (the paper's technique, applied at every
    call site). Shapes are static at trace time, so tuning happens in Python
    during tracing and is cached process-wide.
  * CPU/GPU (tests, dry-run lowering): `lax.dot_general` — the Pallas kernel
    is TPU-target-only and is validated separately in interpret mode.

Set `force_mode("pallas_interpret")` in tests to route through the kernel.

A weight may be given as a `WeightRef`: one layer of a weight stacked over
layers, named by a traced index. The Pallas kernel reads that layer where
it lives in the stack; the XLA path slices it out first.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.tiled_matmul import BlockConfig, DEFAULT_CONFIG, tiled_matmul

_MODE: Literal["auto", "pallas", "pallas_interpret", "xla"] = "auto"
_CHIP: str = "tpu_v5e"
# (m, n, k, dtype) -> BlockConfig of every kernel call traced so far
_TRACED: dict[tuple[int, int, int, str], BlockConfig] = {}
# where `weight_reads` counts the Pallas GEMM calls being traced
_READS: list[dict[str, int]] = []


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WeightRef:
    """A GEMM weight read where it lives: the parameter `array` itself, or
    with `index` (an integer scalar, usually traced) layer `index` of a
    weight stacked over layers, `array` (L, K, N) ((L, N, K) for
    `transpose_b`). `matmul` hands the Pallas kernel the whole stack and
    the index, so the layer is never copied out of the stack first;
    every other reader takes `resolve(ref)`."""

    array: jax.Array
    index: jax.Array | None = None

    @property
    def shape(self) -> tuple[int, ...]:
        return self.array.shape if self.index is None else self.array.shape[1:]

    @property
    def dtype(self):
        return self.array.dtype


def resolve(w):
    """The array a weight stands for: a `WeightRef`'s parameter, or its
    layer sliced out of the stack; anything else as it is."""
    if not isinstance(w, WeightRef):
        return w
    if w.index is None:
        return w.array
    return jax.lax.dynamic_index_in_dim(w.array, w.index, keepdims=False)


@contextlib.contextmanager
def weight_reads(counts: dict[str, int]):
    """Count into `counts` each Pallas GEMM call traced inside the block
    by how its weight was read: ``in_place`` (a `WeightRef`) or
    ``staged`` (a plain array: a slice the caller made of a parameter, or
    any other array computed before the call)."""
    counts.setdefault("in_place", 0)
    counts.setdefault("staged", 0)
    _READS.append(counts)
    try:
        yield counts
    finally:
        _READS.pop()


def force_mode(mode: Literal["auto", "pallas", "pallas_interpret", "xla"]):
    """Override dispatch (tests use 'pallas_interpret'; dry-run uses 'xla')."""
    global _MODE
    _MODE = mode


def force_chip(chip: str) -> None:
    """Select the chip registry entry the trace-time autotuner targets."""
    global _CHIP
    from repro.core.chips import get_chip

    _CHIP = get_chip(chip).name


def traced_configs() -> dict[tuple[int, int, int, str], BlockConfig]:
    """The block config of every Pallas GEMM traced in this process,
    keyed by (m, n, k, dtype) — what the compiled programs run."""
    return dict(_TRACED)


def _resolve_mode() -> str:
    if _MODE != "auto":
        return _MODE
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@functools.lru_cache(maxsize=None)
def _tuned_config(m: int, n: int, k: int, dtype: str,
                  objective: str, chip: str) -> BlockConfig:
    """The predictor's block config for one GEMM call. A tuner failure
    raises: a kernel silently run at an untuned default would hide that
    the paper's technique never ran."""
    # Late import: autotuner depends on the trained predictor artifacts.
    from repro.core.autotuner import get_tuner

    return get_tuner(chip=chip).best_config(m, n, k, dtype=dtype,
                                            objective=objective)


def warm_gemm_cache(shapes, *, dtype: str = "bfloat16",
                    objective: str = "runtime",
                    chip: str | None = None,
                    rank_mode: str = "auto") -> dict[tuple, BlockConfig]:
    """Pre-tune a fleet of (m, n, k) GEMM shapes in one batched
    `tune_many` pass and prime the trace-time config cache, so the first
    jit trace of a model pays zero per-shape tuning latency.

    `dtype` uses trace-time spelling (str(a.dtype), e.g. "bfloat16") —
    the tuner canonicalizes. Trace-time lookups consult the *active*
    chip only (`force_chip`), so pass `chip=None` to warm the chip the
    traces will actually run against; warming an explicit other chip
    fills that chip's tuner/winner caches but cannot serve traces until
    `force_chip` selects it. `rank_mode` selects the candidate-ranking
    path ("auto" ranks fully in-graph on accelerator backends — see
    `GemmAutotuner.rank_in_graph` — and at trace time on CPU; "graph" /
    "trace" force one). Returns {shape: BlockConfig}. Tuner failures
    raise (the serving engine's `retune` catches a corrupt predictor
    artifact, `core.predictor.ArtifactError`, to flag degraded-mode
    tuning).
    """
    from repro.core.autotuner import get_tuner
    from repro.core.chips import get_chip

    shapes = [tuple(int(x) for x in s) for s in shapes]
    chip_name = get_chip(chip).name if chip else _CHIP
    best = get_tuner(chip=chip_name).tune_many(
        shapes, dtype=dtype, objective=objective, rank_mode=rank_mode)
    for m, n, k in shapes:
        # the tuner cache is hot now, so this just fills the lru wrapper
        _tuned_config(m, n, k, dtype, objective, chip_name)
    return dict(zip(shapes, best))


SSM_SERVE_GRAIN = 8  # min prefill bucket == SSM serve-scan block (ssm.SERVE_CHUNK)


@functools.lru_cache(maxsize=None)
def prefill_buckets(max_len: int, min_bucket: int = SSM_SERVE_GRAIN
                    ) -> tuple[int, ...]:
    """Power-of-two row buckets the serving engine pads prefill chunks to,
    so distinct prompt/chunk lengths share jit traces and tuned GEMM
    shapes. Memoized per (max_len, min_bucket): the engine's per-admission
    bucket lookup bisects this tuple instead of rebuilding a list."""
    buckets, b = [], min_bucket
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


def chunk_buckets(max_len: int, chunk_tokens: int,
                  grain: int = SSM_SERVE_GRAIN) -> tuple[int, ...]:
    """The chunk sizes an engine's chunked-admission prefill may trace:
    the prefill buckets capped at `chunk_tokens` (a prompt longer than the
    cap is fed through the decode loop `chunk_tokens` tokens per step).
    `grain` sets the bucket floor (the engine's SSM serve-scan block)."""
    caps = [b for b in prefill_buckets(max_len, grain) if b <= chunk_tokens]
    return tuple(caps) if caps else prefill_buckets(max_len, grain)[:1]


def serving_gemm_fleet(cfg, *, max_batch: int, max_len: int,
                       include_slot_prefill: bool = True,
                       chunk_tokens: int | None = None,
                       lane_width: int | None = None,
                       kv_cap: int | None = None,
                       tp: int = 1,
                       grain: int = SSM_SERVE_GRAIN
                       ) -> list[tuple[int, int, int]]:
    """Every GEMM shape a serving engine will trace: the batched prefill
    (max_batch * max_len rows, LM head over max_batch last positions), the
    lockstep decode step (max_batch rows), and — for continuous batching —
    the chunked-admission prefill grid: each (admission-width, chunk-
    bucket) pair the chunk scheduler can issue (pow2 widths up to
    max_batch x pow2 chunk buckets, LM head over the admission rows), plus
    the legacy single-slot buckets for `admission="serial"`. Feed to
    `warm_gemm_cache` so neither the first wave nor the first fused
    chunk+decode step pays per-shape tuning latency.

    `kv_cap` overrides the per-row cached-token capacity that sizes MLA's
    whole-cache `w_uk`/`w_uv` decompression rows (default `max_len`): the
    paged engine's gathered view spans `n_row_pages * page_size` logical
    positions per row, which is what the decompress GEMMs actually run
    over there.

    With `tp > 1` the fleet is the *per-shard* extents — gather-mode TP
    leaves every projection an (M, N/tp, K) GEMM per chip (see
    `gemm_shape_counts(..., tp=)`), so the autotuner tunes exactly the
    shapes a sharded engine step runs. `grain` is the engine's SSM
    serve-scan block (the prefill-bucket floor).
    """
    from repro.models.config import gemm_shape_counts

    cap_len = kv_cap if kv_cap is not None else max_len
    fleet = set(gemm_shape_counts(cfg, max_batch * max_len,
                                  head_tokens=max_batch,
                                  kv_rows=max_batch * cap_len, tp=tp))
    fleet |= set(gemm_shape_counts(cfg, max_batch,
                                   kv_rows=max_batch * cap_len, tp=tp))
    if include_slot_prefill:
        if chunk_tokens is None:
            # serial admission / legacy callers: single-shot slot prefills
            # only ever trace width 1
            widths = {1}
            chunks = prefill_buckets(max_len, grain)
        else:
            # chunked admission rounds the lane up to the next pow2, so
            # pre-tune the full pow2 ladder through the lane cap
            cap = lane_width if lane_width is not None else max_batch
            widths = {1}
            a = 1
            while a < cap:
                a *= 2
                widths.add(a)
            chunks = chunk_buckets(max_len, chunk_tokens, grain)
        for b in set(chunks) | set(prefill_buckets(max_len, grain)):
            # buckets past the chunk cap are only ever traced by width-1
            # serial slot prefills — don't pre-tune wide variants of them
            ws = sorted(widths) if b in chunks else [1]
            for w in ws:
                fleet |= set(gemm_shape_counts(cfg, w * b, head_tokens=w,
                                               kv_rows=w * cap_len, tp=tp))
    if getattr(cfg, "kind", None) in ("encdec", "vlm"):
        # prefill-once admission grid: encoder + cross-KV (encdec) or the
        # patch-prefix decoder pass (vlm) runs once per request over the
        # source/patch rows, bucketed by the full prefill ladder (admission
        # is not capped at chunk_tokens) at pow2 widths plus the full batch
        cap = lane_width if lane_width is not None else max_batch
        widths = {1, max_batch}
        a = 1
        while a < cap:
            a *= 2
            widths.add(a)
        for b in prefill_buckets(max_len, grain):
            for w in sorted(widths):
                if cfg.kind == "encdec":
                    fleet |= set(gemm_shape_counts(
                        cfg, 0, head_tokens=0, src_tokens=w * b, tp=tp))
                else:
                    fleet |= set(gemm_shape_counts(
                        cfg, w * b, head_tokens=0, tp=tp))
    return sorted(fleet)


def warm_fleet_gemm_cache(specs, *, objective: str = "runtime",
                          rank_mode: str = "auto"
                          ) -> list[dict[tuple, BlockConfig]]:
    """Cross-engine fleet pre-tuning: warm a *heterogeneous* fleet of
    serving engines in one batched tuning pass per chip.

    `specs` is a list of dicts, one per engine: a ``cfg`` (ModelConfig)
    plus `serving_gemm_fleet` keyword args (``max_batch``, ``max_len``,
    ``chunk_tokens``, ``lane_width``, ``tp``, ``grain``, ...) and
    optionally ``chip`` / ``dtype``. Engines sharing a (chip, dtype) are
    unioned into one shape fleet and tuned together — N engines on the
    same chip pay one `tune_many` pass, not N — while engines on
    different chips each warm their own chip's tuner/winner caches.
    Returns one ``{shape: BlockConfig}`` dict per input spec (the
    engine's own shapes only), suitable for `ServingEngine.pretuned`;
    tuner failures raise like `warm_gemm_cache`."""
    specs = [dict(sp) for sp in specs]
    fleets: list[list[tuple[int, int, int]]] = []
    groups: dict[tuple, set] = {}
    for sp in specs:
        kw = {k: v for k, v in sp.items()
              if k not in ("cfg", "chip", "dtype")}
        fleet = serving_gemm_fleet(sp["cfg"], **kw)
        fleets.append(fleet)
        groups.setdefault((sp.get("chip"), sp.get("dtype", "bfloat16")),
                          set()).update(fleet)
    tuned = {
        (chip, dtype): warm_gemm_cache(sorted(shapes), dtype=dtype,
                                       objective=objective, chip=chip,
                                       rank_mode=rank_mode)
        for (chip, dtype), shapes in groups.items()}
    return [
        {s: grp[s] for s in fleet if s in grp}
        for sp, fleet in zip(specs, fleets)
        for grp in [tuned[(sp.get("chip"), sp.get("dtype", "bfloat16"))]]]


def matmul(
    a: jax.Array,
    b: jax.Array | WeightRef,
    *,
    config: BlockConfig | None = None,
    objective: str = "runtime",
    transpose_b: bool = False,
    out_dtype=None,
) -> jax.Array:
    """out = a @ op(b) over the last axis of `a`; leading dims are batch.
    `b` may be a `WeightRef`, which the Pallas kernel reads in place."""
    *lead, k = a.shape
    if transpose_b:
        n, kb = b.shape
    else:
        kb, n = b.shape
    if kb != k:
        raise ValueError(f"contraction mismatch {k} vs {kb}")
    m = 1
    for d in lead:
        m *= d
    mode = _resolve_mode()
    out_dtype = out_dtype or a.dtype
    if mode == "xla":
        dn = (((1,), (1 if transpose_b else 0,)), ((), ()))
        out = jax.lax.dot_general(
            a.reshape(m, k), resolve(b), dn,
            preferred_element_type=jnp.float32
        ).astype(out_dtype)
    else:
        cfg = config or _tuned_config(m, n, k, str(a.dtype), objective, _CHIP)
        _TRACED[(m, n, k, str(a.dtype))] = cfg
        ref = isinstance(b, WeightRef)
        if _READS:
            _READS[-1]["in_place" if ref else "staged"] += 1
        out = tiled_matmul(
            a.reshape(m, k), b.array if ref else b,
            layer=b.index if ref else None,
            config=cfg,
            transpose_b=transpose_b,
            out_dtype=out_dtype,
            interpret=(mode == "pallas_interpret"),
        )
    return out.reshape(*lead, n)


def linear(x: jax.Array, w: jax.Array | WeightRef,
           b: jax.Array | None = None, **kw) -> jax.Array:
    """y = x @ w (+ b). w: (K, N), or a `WeightRef` to one."""
    y = matmul(x, w, **kw)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def gemm(a, b, c=None, *, alpha=1.0, beta=0.0, transpose_a=False,
         transpose_b=False, config: BlockConfig | None = None,
         out_dtype=None, interpret: bool | None = None) -> jax.Array:
    """Full BLAS-3 surface (rank-2 only) — used by benchmarks/tests."""
    mode = _resolve_mode()
    use_interpret = (mode == "pallas_interpret") if interpret is None else interpret
    if mode == "xla" and interpret is None:
        from repro.kernels.ref import matmul_ref

        return matmul_ref(a, b, c, transpose_a=transpose_a,
                          transpose_b=transpose_b, alpha=alpha, beta=beta,
                          out_dtype=out_dtype)
    return tiled_matmul(
        a, b, c, config=config or DEFAULT_CONFIG, transpose_a=transpose_a,
        transpose_b=transpose_b, alpha=alpha, beta=beta, out_dtype=out_dtype,
        interpret=use_interpret,
    )
