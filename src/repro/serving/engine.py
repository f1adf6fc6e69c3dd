"""Continuous-batching serving engine with chunked admission prefill and
per-request energy accounting.

The engine keeps one batched decode state of ``max_batch`` fixed slots.
Admission is **chunked and fused into the decode loop**: queued prompts
are split into power-of-two chunk buckets (`ops.chunk_buckets`) and every
engine step processes one chunk call over the whole *admission lane* — a
compact pow2-width batch of all in-flight admissions — alongside the
lockstep decode step of the resident slots. A long prompt therefore never
stops the world (resident slots keep generating between its chunks), and
queued short prompts prefill together in one bucketed call instead of N
serial traces — the TTFT stall under load that serialized slot prefill
produced. KV rows are written at per-row cache offsets (chunk base +
row index — `layers.cache_update` / `attention_mask` per-row contract),
and SSM/SSD conv+scan state is carried across chunk boundaries
bit-exactly (`ssm.SERVE_CHUNK`), which promotes mamba1/mamba2/hybrid out
of the wave-mode fallback. A finished admission row is spliced into its
reserved decode slot (`layers.take_slot_state` + `insert_slot_state`).

Bit parity is the hard contract: a prompt prefilled in chunks produces
the identical greedy stream to a single-shot prefill
(``admission="serial"``, the PR 4 path, kept as a baseline) and to the
wave loop. Each request carries telemetry (queue time, TTFT, resident
decode steps, tokens/s) and an energy estimate: the engine prices each
chunk call and each decode step via `core.energy.gemm_fleet_energy` (a
fused engine step is decode rows + chunk rows —
`core.energy.fused_step_energy` combines the fleets) and attributes each
call's per-row share to the occupying request. `report()` aggregates
tokens/s, J/token and slot occupancy for benchmarks to regress.

The legacy wave API (`run_wave`) remains as a compatibility shim: one
batched right-padded prefill, lockstep decode until every request in the
wave finishes. Finished rows keep executing until the wave drains — which
is exactly the waste continuous mode exists to remove — but EOS / budget
termination (including on the *first* sampled token) is honored in both
modes.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.config import ModelConfig

# families whose decode state supports per-row indices + slot surgery and
# whose prefill honors the right-padded `lengths` contract (attention KV
# caches via per-row cache_update/attention_mask; SSM/SSD state via
# seq_lens pad-skipping). encdec/vlm are *admit families*: their modality
# inputs (source embeddings / patch prefix) run through a prefill-once
# admission call (`ModelApi.admit`) whose outputs live in the decode state
# like any other cache leaf, after which the text prompt chunks through
# the same right-pad path as everyone else. MoE expert capacity is per
# row (`moe.moe_ffn_apply`), so rows are batch-independent at any
# capacity factor.
CONTINUOUS_KINDS = ("dense", "moe", "mla_moe", "mamba1", "mamba2",
                    "hybrid", "encdec", "vlm")


@dataclasses.dataclass
class Request:
    """One generation request: a prompt, a budget, an optional EOS id."""

    uid: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 32
    eos_id: int | None = None
    submit_s: float = 0.0       # stamped by ServingEngine.submit
    submit_model_s: float = 0.0  # engine model-clock at submission
    sla: str | None = None      # SLA-class name (FleetScheduler telemetry)
    # tokens a failed attempt already emitted (fault recovery): the
    # request prefills over prompt + replay[:-1] and decodes from the
    # last emitted token, so the client-visible stream stays an
    # append-only continuation and the final Result carries the full
    # stream exactly once. Chunked-admission path only.
    replay: list[int] | None = None
    # modality inputs consumed by the family's prefill-once admission:
    # encdec {"src_embeds": (T, d)}, vlm {"patch_embeds": (P, d),
    # "grid_hw": (gh, gw)}; None for text-only requests
    extras: dict | None = None


@dataclasses.dataclass
class Result:
    """A finished request's tokens plus latency/energy telemetry."""

    uid: int
    tokens: np.ndarray          # generated ids (includes EOS if emitted)
    prompt_len: int
    steps: int                  # decode iterations the request was resident
    n_tokens: int = 0           # generated-token count (energy denominator)
    queue_s: float = 0.0        # submit -> prefill start
    ttft_s: float = 0.0         # submit -> first token
    ttft_model_s: float = 0.0   # submit -> first token, model clock
    decode_s: float = 0.0       # first token -> last token
    tokens_per_s: float = 0.0
    energy_j: float = 0.0       # attributed prefill + resident-step energy
    energy_per_token_j: float = 0.0


@dataclasses.dataclass
class _Slot:
    req: Request
    tokens: list[int]
    prefill_energy_j: float
    t_start: float              # prefill start (wall)
    t_first: float              # first-token time (wall)
    t_first_model: float = 0.0  # first-token time (model clock)
    steps: int = 0              # resident decode iterations so far
    rng: np.random.Generator | None = None   # per-request sampling stream
    pages: list[int] | None = None  # paged layout: owned/shared page ids
    index: int = 0              # paged layout: host-tracked cache position
    # paged admit families: per-request dense admission leaves (encdec
    # cross-KV + src_len, vlm pos_off) concatenated into each call's state
    extra_top: dict | None = None
    extra_kv: dict | None = None


@dataclasses.dataclass
class _Admission:
    """A request mid-chunked-prefill: `row` in the admission-lane state,
    `base` prompt tokens written. Admission is decoupled from decode-slot
    availability: the first token is sampled when the last chunk lands
    (TTFT is lane-bound, not slot-bound), after which the finished row
    *parks* in the lane (`ready`/`first_tok`) until a decode slot frees
    and it is spliced in."""
    req: Request
    row: int = -1
    base: int = 0
    chunk_energy_j: float = 0.0
    t_start: float = 0.0        # first chunk dispatch (wall)
    rng: np.random.Generator | None = None
    ready: "_Slot | None" = None  # prefilled + first token sampled
    first_tok: int = 0
    pages: list[int] | None = None  # paged layout: reserved page ids
    prefix: int = 0             # admission-prefix cache rows (vlm patches)
    extra_top: dict | None = None   # paged admit families (see _Slot)
    extra_kv: dict | None = None
    # effective prefill token sequence: the prompt, extended by the
    # already-emitted replay prefix for fault-recovery requests (the
    # last replay token is decoded, not prefilled)
    eff: np.ndarray | None = None


class _LiveState:
    """The chunked stepper's cross-yield mutable state, held on the
    engine (not in generator locals) so `checkpoint_inflight` can
    surgically extract in-flight rows when the fleet scheduler declares
    this member crashed or evicted."""

    __slots__ = ("slots", "batch_state", "token_buf", "adm", "adm_state",
                 "adm_w", "lane_free", "lane_dirty", "zero_src")

    def __init__(self, max_batch: int):
        self.slots: list[_Slot | None] = [None] * max_batch
        self.batch_state = None
        self.token_buf = np.zeros(max_batch, np.int32)
        self.adm: list[_Admission] = []
        self.adm_state = None
        self.adm_w = 0
        self.lane_free: list[int] = []
        self.lane_dirty: set[int] = set()
        self.zero_src = None


# families whose cache the paged layout supports: per-token KV (or MLA
# latent) rows that page cleanly. SSM/hybrid state is O(1)-per-row (or
# mixed) and stays dense. encdec/vlm page their decoder self-attention
# KV; encdec's cross-KV stays dense per-request (read-only after
# admission, never grows).
PAGED_KINDS = ("dense", "moe", "mla_moe", "encdec", "vlm")


class ServingEngine:
    """Continuous-batching serving engine (see the module docstring for
    the serving model; `docs/serving.md` for the full guide)."""

    def __init__(self, model, params, cfg: ModelConfig, *,
                 max_batch: int = 8, max_len: int = 512,
                 greedy: bool = True, seed: int = 0,
                 mode: str = "auto",
                 admission: str = "chunked", chunk_tokens: int = 64,
                 kv_layout: str = "dense", page_size: int = 64,
                 num_pages: int | None = None, prefix_cache: bool = True,
                 pretune: bool = False, tune_objective: str = "runtime",
                 tune_rank_mode: str = "auto",
                 chip: str | None = None,
                 tp: int = 1, mesh=None, tp_overlap_chunks: int = 4,
                 ssm_serve_grain: int | None = None):
        """`mode` picks the serving loop: "continuous" (slot table with
        mid-decode retire/refill), "wave" (legacy batch-of-waves), or
        "auto" (continuous for the families that support per-slot decode
        state — see CONTINUOUS_KINDS — wave otherwise).

        `kv_layout` picks the KV-cache layout: "dense" (one max_len
        buffer per decode slot and lane row) or "paged" (a shared pool of
        `page_size`-token pages with per-row page tables, host-side
        free-list allocator, and — with `prefix_cache` — shared-prefix
        page reuse across requests; see `repro.serving.paging`).
        `num_pages` sizes the pool (default: full capacity for every slot
        and lane row, i.e. no HBM saving until callers lower it). Paged
        serving requires chunked admission, a PAGED_KINDS family, and
        `page_size` dividing `max_len`; token streams are bit-identical
        to the dense layout.

        `admission` picks how continuous mode prefills: "chunked"
        (default — prompts feed through the decode loop `chunk_tokens`
        tokens per engine step, queued admissions batched into one
        bucketed call) or "serial" (the PR 4 baseline: each request
        prefills alone in one single-shot call, stalling the loop for the
        whole prompt). Both produce bit-identical token streams.

        `pretune=True` batch-tunes the engine's GEMM fleet up front:
        every projection/FFN/head shape the batched prefill (max_batch *
        max_len rows), the decode step (max_batch rows), and each
        (admission-width x chunk-bucket) chunk call will trace goes
        through one `ops.warm_gemm_cache` pass (predictor-ranked,
        substrate-verified, cached per chip + artifact version), so the
        first request pays no per-shape autotuning. `tune_objective`
        picks the paper's serving objective ("runtime", "energy",
        "power", "edp"); `tune_rank_mode` picks the candidate-ranking
        path ("auto" ranks fully in-graph on accelerator backends, at
        trace time on CPU).

        `tp > 1` serves tensor-parallel over a (1, tp) device mesh
        (`mesh` overrides the default `launch.mesh.make_serving_mesh`):
        the config is flipped to explicit gather-mode TP collectives
        (`tp_reduce="gather"` — bit-identical streams to tp=1, see
        `docs/serving.md`), params and decode caches are sharded along
        the head/expert axes, row-parallel all-gathers are interleaved
        with the GEMM in `tp_overlap_chunks` column chunks, and the
        energy model prices the per-shard fleet plus the ring traffic.

        `ssm_serve_grain` widens the SSM serve-scan block (default
        `ops.SSM_SERVE_GRAIN`) — a pow2 multiple of it; chunk boundaries
        and prefill buckets align to the grain, so long SSM prompts scan
        in fewer, larger blocks per chunk call.
        """
        from repro.kernels import ops

        self.tp = max(int(tp), 1)
        self.mesh = None
        grain = int(ssm_serve_grain) if ssm_serve_grain else 0
        if grain and (grain < ops.SSM_SERVE_GRAIN
                      or grain % ops.SSM_SERVE_GRAIN
                      or grain & (grain - 1)):
            raise ValueError(
                f"ssm_serve_grain={grain} must be a power-of-two "
                f"multiple of {ops.SSM_SERVE_GRAIN}")
        self.ssm_grain = grain or ops.SSM_SERVE_GRAIN
        overrides: dict = {}
        if self.tp > 1:
            # gather-mode explicit collectives: the one TP strategy that
            # keeps greedy streams bit-identical to tp=1 (no psum/split-k
            # fp32 re-association anywhere in the layer graph)
            overrides.update(tp_collectives="explicit", tp_reduce="gather",
                             tp_overlap_chunks=max(int(tp_overlap_chunks),
                                                   1))
        if grain:
            overrides["ssm_serve_grain"] = grain
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        if self.tp > 1:
            from repro.distributed.sharding import param_shardings
            from repro.launch.mesh import make_serving_mesh

            self.mesh = mesh if mesh is not None else make_serving_mesh(
                self.tp)
            if ("model" not in self.mesh.axis_names
                    or self.mesh.shape["model"] != self.tp):
                raise ValueError(
                    f"mesh {dict(self.mesh.shape)} must carry a 'model' "
                    f"axis of size tp={self.tp}")
            params = jax.device_put(
                params, param_shardings(params, self.mesh,
                                        tp_reduce="gather"))

        self.model = model
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.greedy = greedy
        if mode not in ("auto", "continuous", "wave"):
            raise ValueError(f"unknown serving mode {mode!r}")
        if admission not in ("chunked", "serial"):
            raise ValueError(f"unknown admission mode {admission!r}")
        self.mode = mode
        self.admission = admission
        if (admission == "chunked" and chunk_tokens < max_len
                and chunk_tokens % self.ssm_grain):
            # chunk boundaries must stay multiples of the SSM serve-scan
            # block or chunked prefill loses bit parity for SSM families
            raise ValueError(
                f"chunk_tokens={chunk_tokens} must be a multiple of "
                f"{self.ssm_grain} (or >= max_len)")
        if (admission == "chunked" and cfg.sub_quadratic
                and cfg.attention_free and max_len < self.ssm_grain):
            # attention-free prompts may exceed max_len (multi-chunk), and
            # non-final chunk boundaries then need an SSM-grain-aligned
            # bucket, which a sub-grain bucket ladder cannot provide
            raise ValueError(
                f"max_len={max_len} < {self.ssm_grain} cannot serve "
                f"chunked SSM prefill; raise max_len or use wave mode")
        self.chunk_tokens = chunk_tokens
        # admission-lane capacity: prefill (and first-token sampling) for
        # up to this many in-flight requests is decoupled from decode-slot
        # availability — finished admissions park in the lane until a slot
        # frees, so TTFT under a burst is lane-bound, not retirement-bound
        self.lane_width = 2 * max_batch
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        self.kv_layout = kv_layout
        self.page_size = page_size
        self._allocator = None
        self._pool = None           # device page pool, built on first run
        self._copy_pages = None
        if kv_layout == "paged":
            from repro.serving.paging import PageAllocator

            if cfg.kind not in PAGED_KINDS:
                raise ValueError(
                    f"kv_layout='paged' unsupported for kind="
                    f"{cfg.kind!r} (SSM/hybrid state is O(1) per row, "
                    f"not per token); use dense")
            if mode == "wave" or admission != "chunked":
                raise ValueError(
                    "kv_layout='paged' requires continuous serving with "
                    "admission='chunked'")
            if max_len % page_size:
                raise ValueError(
                    f"page_size={page_size} must divide "
                    f"max_len={max_len} (page tables span max_len)")
            self._n_row_pages = max_len // page_size
            if num_pages is None:
                # full capacity for every slot and lane row + null page:
                # parity-safe default; benches shrink it to realize the
                # fixed-HBM concurrency win
                num_pages = ((max_batch + self.lane_width)
                             * self._n_row_pages + 1)
            self._allocator = PageAllocator(num_pages, page_size,
                                            prefix_cache=prefix_cache)
        self.queue: deque[Request] = deque()
        self.seed = seed
        if chip is not None:
            # validate eagerly: a chip typo must raise here, not silently
            # zero every energy estimate later
            from repro.core.chips import get_chip

            chip = get_chip(chip).name
        self.chip = chip
        self.pretuned: dict[tuple, object] = {}
        if pretune:
            fleet = ops.serving_gemm_fleet(
                cfg, max_batch=max_batch, max_len=max_len,
                include_slot_prefill=self._continuous_supported(),
                chunk_tokens=(chunk_tokens if admission == "chunked"
                              else None),
                lane_width=(self.lane_width if admission == "chunked"
                            else None),
                tp=self.tp, grain=self.ssm_grain)
            self.pretuned = ops.warm_gemm_cache(
                fleet, dtype=cfg.activation_dtype,
                objective=tune_objective, chip=chip,
                rank_mode=tune_rank_mode)
        # the engine's programs are named functions, so a device trace
        # names their executions `jit_engine_<call>`; tracing one counts
        # its Pallas GEMM calls by how each reads its weight
        self._weight_reads = {"in_place": 0, "staged": 0}
        reads = functools.partial(ops.weight_reads, self._weight_reads)

        def engine_prefill(p, b):
            with reads():
                return model.prefill(p, b, cfg, max_len=max_len)

        def engine_decode(p, t, s):
            with reads():
                return model.decode_step(p, t, s, cfg)

        def engine_chunk(p, t, ln, s):
            with reads():
                return model.prefill_chunk(p, t, ln, s, cfg)

        def engine_admit(p, pk, s):
            with reads():
                return model.admit(p, pk, s, cfg)

        self._prefill = jax.jit(engine_prefill)
        # decode/chunk/splice rebind their state output over the input:
        # donating the input state lets XLA update the KV caches in place
        # instead of copying the whole decode state every step
        self._decode = jax.jit(engine_decode, donate_argnums=(2,))
        self._chunk = (jax.jit(engine_chunk, donate_argnums=(3,))
                       if model.prefill_chunk is not None else None)
        # prefill-once admission call for admit families (encdec source
        # encoding + cross-KV, vlm patch prefix); batch-generic, donates
        # the state it writes into
        self._admit_fn = (jax.jit(engine_admit, donate_argnums=(2,))
                          if getattr(model, "admit", None) is not None
                          else None)
        self._splice_fn = None          # built lazily with the axes spec
        self._state_axes = None
        # model clock: predicted seconds of dispatched engine calls (the
        # analytical GEMM model's step_s), advanced per prefill/chunk/
        # decode call. TTFT measured against it is deterministic and
        # hardware-independent — the regression surface CI gates on.
        self._clock = 0.0
        self._step_energy_cache: dict[tuple | str | int, object] = {}
        # scheduler hooks (repro.serving.scheduler): `chunk_policy` is an
        # optional callable `(engine, pending) -> int | None` consulted by
        # the chunk stage — `pending` is a list of (Request,
        # remaining_prompt_tokens) for the rows still prefilling; a
        # returned token count is snapped up to the chunk-bucket ladder
        # (SSM-grain alignment still applies), None keeps the default SJF
        # sizing. `_stepper` holds the resumable chunked-serving generator
        # behind `serve_step`; `_lane_view` is the host-visible admission
        # snapshot refreshed after every step (routing reads it).
        self.chunk_policy = None
        self._stepper = None
        self._live: _LiveState | None = None
        self._lane_view = {"pending": 0, "pending_tokens": 0,
                           "parked": 0, "resident": 0, "in_flight": 0}
        # fault recovery: decode-state rows checkpointed off a failed
        # fleet member, waiting for a free decode slot here (`adopt`);
        # degraded-mode tuning flag set by `retune` on ArtifactError
        self._adopted: deque[dict] = deque()
        self.tuning_degraded = False
        self._degraded_reason: str | None = None
        # engine-level counters (reset per run_* call family, reported
        # cumulatively)
        self._stats = {
            "decode_steps": 0, "chunk_steps": 0,
            "resident_slot_steps": 0.0,
            "slot_steps": 0.0, "generated_tokens": 0, "energy_j": 0.0,
            "idle_energy_j": 0.0, "requests": 0, "wall_s": 0.0,
            # model-clock seconds of dispatched calls, collective wire
            # time on the links, and the share hidden behind GEMM compute
            # (tp=1 leaves the wire terms at zero); lane_rebuilds counts
            # admission-lane reallocations (free-list reuse keeps it at
            # width growths only)
            "model_s": 0.0, "wire_s": 0.0, "hidden_wire_s": 0.0,
            "lane_rebuilds": 0,
            # fault-recovery ledger: energy a failed attempt spent on
            # work that had to be replayed (charged here, to the failed
            # member, never to the request's final Result)
            "lost_energy_j": 0.0,
            # chunk calls: prompt tokens they prefilled (sum of lens) and
            # token positions they computed (sum of width x bucket)
            "chunk_prompt_tokens": 0, "chunk_token_slots": 0,
        }

    # ------------------------------------------------------------------
    # mesh / clock
    # ------------------------------------------------------------------
    def _activate(self) -> None:
        """Install this engine's mesh rules on the thread (clearing them
        for tp=1 engines). Jitted calls trace lazily, so the rules must
        be the engine's own at dispatch time — engines of different tp
        degrees can interleave in one process."""
        from repro.distributed.sharding import set_mesh_rules

        set_mesh_rules(self.mesh)

    def _tick(self, step_s: float, est=None) -> None:
        """Advance the model clock by one dispatched call's predicted
        time and fold its collective wire telemetry into the counters
        (`report()`'s model_tokens_per_s / overlap_factor surface)."""
        self._clock += step_s
        self._stats["model_s"] += step_s
        if est is not None and getattr(est, "collective_s", 0.0) > 0.0:
            self._stats["wire_s"] += est.collective_s
            self._stats["hidden_wire_s"] += (est.overlap_factor
                                             * est.collective_s)

    @property
    def model_clock_s(self) -> float:
        """Current model-clock reading (predicted seconds of every call
        this engine has dispatched, monotone across runs). The fleet
        scheduler orders engine steps by it."""
        return self._clock

    @property
    def chip_spec(self):
        """The `ChipSpec` this engine prices energy on (`tpu_v5e` when
        no chip was named at construction)."""
        from repro.core.chips import get_chip

        return get_chip(self.chip or "tpu_v5e")

    @property
    def idle_power_w(self) -> float:
        """Idle-floor power of this engine's whole chip fleet (per-chip
        `ChipSpec.idle_power_w` x tp chips) — what a parked engine burns
        per model-clock second in the fleet scheduler's ledger."""
        return self.chip_spec.idle_power_w * self.tp

    @property
    def has_work(self) -> bool:
        """True while the engine holds queued or in-flight requests
        (adopted rows included). May stay True for one extra
        `serve_step()` after the last retirement (the step that observes
        the drained loop returns `[]`)."""
        return (bool(self.queue) or bool(self._adopted)
                or self._stepper is not None)

    @property
    def lane_view(self) -> dict:
        """Host-visible admission-lane snapshot, refreshed after every
        `serve_step`: rows still prefilling (`pending` /
        `pending_tokens`), parked rows awaiting a decode slot, resident
        decode slots, and total in-flight admissions."""
        return dict(self._lane_view)

    @property
    def backlog_tokens(self) -> int:
        """Prompt tokens this engine still has to prefill: queued prompts
        plus the unwritten remainder of in-flight admissions. The fleet
        scheduler's TTFT predictor divides this by chunk throughput."""
        return (sum(len(r.prompt) for r in self.queue)
                + int(self._lane_view["pending_tokens"]))

    # ------------------------------------------------------------------
    # queue
    # ------------------------------------------------------------------
    def _row_capacity(self) -> int | None:
        """Per-row cache capacity in tokens, or None when unbounded —
        the ONE length bound `submit`/`_budget` apply, uniformly per row.
        Attention KV caches (and encdec's cross-KV leaves) hold `max_len`
        rows; attention-free SSM state is O(1) per row, so its capacity
        is unbounded (long prompts scan through in multiple chunks)."""
        return None if self.cfg.attention_free else self.max_len

    def _admit_dims(self, req: Request) -> tuple[int, int]:
        """(cache-prefix rows, side source rows) this request's admission
        consumes ahead of its prompt — (0, 0) for families without
        admission hooks. Validates the request's `extras` as a side
        effect (encdec requires source embeddings)."""
        if self.model.admit_dims is None:
            return (0, 0)
        return self.model.admit_dims(self.cfg, req.extras)

    def submit(self, req: Request) -> None:
        """Queue a request (stamps submit wall/model-clock times).

        One uniform per-row bound across every family: the row's
        admission prefix + prompt must fit its cache capacity with at
        least one decode position to spare, and an encdec source must fit
        the row's cross-KV capacity. Unbounded-capacity (attention-free)
        rows skip the bound entirely."""
        prefix, src = self._admit_dims(req)
        cap = self._row_capacity()
        if cap is not None:
            if prefix + len(req.prompt) >= cap:
                raise ValueError(
                    f"admission prefix {prefix} + prompt of "
                    f"{len(req.prompt)} tokens does not fit "
                    f"max_len={cap} (need >= 1 decode position)")
            if src > cap:
                raise ValueError(
                    f"source of {src} rows does not fit the per-row "
                    f"cross-KV capacity max_len={cap}")
        if req.submit_s == 0.0:
            req.submit_s = time.perf_counter()
        req.submit_model_s = self._clock
        self.queue.append(req)

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def _req_rng(self, uid: int) -> np.random.Generator:
        """Each request samples from its own (engine seed, uid) stream, so
        its tokens can never depend on which neighbors share the batch or
        when they retire."""
        return np.random.default_rng((self.seed, uid))

    def _sample(self, logits: np.ndarray,
                rngs: list[np.random.Generator | None] | None = None
                ) -> np.ndarray:
        """Next token per row. Greedy is a single vectorized argmax.
        Non-greedy draws a per-request Gumbel-max (`_req_rng` streams;
        `rngs[b] is None` marks a finished/dead row) — dead slots neither
        advance any RNG nor influence live rows."""
        if self.greedy:
            return logits.argmax(-1).astype(np.int32)
        out = np.zeros(logits.shape[0], np.int32)
        for b, rng in enumerate(rngs or []):
            if rng is None:
                continue
            z = logits[b]
            out[b] = np.int32((z + rng.gumbel(size=z.shape)).argmax())
        return out

    # ------------------------------------------------------------------
    # energy model
    # ------------------------------------------------------------------
    def _kv_gather_bytes(self, batch_rows: int) -> float:
        """Non-GEMM KV-cache HBM traffic one call issues: attention reads
        each row's cached keys/values once under the dense layout; the
        paged layout additionally materializes the gathered per-row view
        through the page table before reading it — 2x the cache bytes.
        Pricing both layouts keeps the bench's J/token comparison
        apples-to-apples (zero for attention-free families either way).
        Sharded engines read 1/tp of the cache per chip (head-sharded
        K/V); MLA's latent cache is replicated, so it is not divided."""
        from repro.models.config import kv_cache_bytes

        scale = 2.0 if self.kv_layout == "paged" else 1.0
        if self.cfg.kind == "encdec":
            # decode reads each row's dense cross-KV leaves (max_len
            # source-row capacity) alongside the self-attention cache
            scale += 1.0
        shard = 1 if self.cfg.kind == "mla_moe" else self.tp
        return (scale * kv_cache_bytes(self.cfg, batch_rows * self.max_len)
                / max(shard, 1))

    def _step_energy(self, key, n_rows: int, head_rows: int | None = None,
                     batch_rows: int | None = None,
                     src_rows: int | None = None):
        """Predicted StepEnergyEstimate for a step over `n_rows` GEMM rows
        (decode: max_batch; prefill/chunk: padded token count, with the LM
        head sized to the rows actually unembedded and MLA's cache-wide
        K/V decompression sized to batch_rows * max_len), cached per key.
        Under the paged layout the per-call page-gather traffic is charged
        as extra HBM bytes. A failure of the energy model raises."""
        hit = self._step_energy_cache.get(key)
        if hit is not None:
            return hit
        from repro.core.energy import gemm_fleet_energy
        from repro.models.config import (collective_wire_bytes,
                                         gemm_shape_counts)

        kv_rows = (batch_rows * self.max_len
                   if batch_rows is not None else None)
        wire_b, n_coll = collective_wire_bytes(
            self.cfg, n_rows, self.tp, head_tokens=head_rows,
            src_tokens=src_rows)
        est = gemm_fleet_energy(
            gemm_shape_counts(self.cfg, n_rows, head_tokens=head_rows,
                              kv_rows=kv_rows, tp=self.tp,
                              src_tokens=src_rows),
            chip=self.chip or "tpu_v5e",
            dtype=self.cfg.activation_dtype,
            configs=self.pretuned or None,
            extra_hbm_bytes=self._kv_gather_bytes(batch_rows or 0),
            tp=self.tp, collective_bytes=wire_b,
            n_collectives=n_coll,
            overlap_chunks=getattr(self.cfg, "tp_overlap_chunks", 1),
            name=f"{self.cfg.name}:{key}")
        self._step_energy_cache[key] = est
        return est

    @staticmethod
    def _cost(est) -> tuple[float, float, object]:
        """(energy_j, step_s, estimate) of a priced step."""
        return (est.energy_j, est.step_s, est)

    def _decode_cost(self) -> tuple[float, float, object]:
        """(energy_j, predicted step_s, est) of one lockstep decode
        step."""
        return self._cost(self._step_energy(
            ("decode", self.max_batch), self.max_batch,
            batch_rows=self.max_batch))

    def _prefill_cost(self, n_tokens: int, head_rows: int
                      ) -> tuple[float, float, object]:
        """(energy_j, step_s, est) of one prefill over `n_tokens` padded
        rows unembedding `head_rows` last positions (1 for slot prefill,
        B for a wave). `head_rows` is also the prefill's batch-row count,
        which sizes MLA's cache-wide decompression."""
        return self._cost(self._step_energy(
            ("prefill", int(n_tokens), int(head_rows)),
            int(n_tokens), int(head_rows), batch_rows=int(head_rows)))

    def _chunk_cost(self, width: int, chunk: int
                    ) -> tuple[float, float, object]:
        """(energy_j, step_s, est) of one admission chunk call: `width`
        lane rows of `chunk` tokens, LM head over last-valid positions."""
        return self._cost(self._step_energy(
            ("chunk", int(width), int(chunk)),
            int(width * chunk), int(width), batch_rows=int(width)))

    def _admit_cost(self, width: int, bucket: int
                    ) -> tuple[float, float, object]:
        """(energy_j, step_s, est) of one prefill-once admission call:
        encdec prices the encoder stack + per-decoder-layer cross-KV
        projections over `width * bucket` source rows (no decoder-token
        rows); vlm prices the patch prefix through the decoder. Neither
        runs the LM head."""
        if self.cfg.kind == "encdec":
            return self._cost(self._step_energy(
                ("admit", int(width), int(bucket)), 0, 0,
                batch_rows=int(width), src_rows=int(width * bucket)))
        return self._cost(self._step_energy(
            ("admit", int(width), int(bucket)), int(width * bucket), 0,
            batch_rows=int(width)))

    def decode_step_estimate(self):
        """Predicted `StepEnergyEstimate` of one lockstep decode step
        over the full slot table — the public handle the fleet
        scheduler's marginal-cost pricing divides per slot."""
        return self._decode_cost()[2]

    def fused_step_estimate(self, width: int, chunk: int):
        """Predicted cost of one *fused* engine step — the decode fleet
        (max_batch rows) plus one chunk call's fleet (`width` x `chunk`
        rows) priced through a single duty-cycle power model
        (`core.energy.fused_step_energy`). Cached per (width, chunk):
        the fleet scheduler prices every candidate placement through
        this, so repeat lookups must be dict-cheap."""
        key = ("fused", int(width), int(chunk))
        hit = self._step_energy_cache.get(key)
        if hit is not None:
            return hit
        from repro.core.energy import fused_step_energy
        from repro.models.config import (collective_wire_bytes,
                                         gemm_shape_counts)

        decode = gemm_shape_counts(self.cfg, self.max_batch,
                                   kv_rows=self.max_batch * self.max_len,
                                   tp=self.tp)
        ch = gemm_shape_counts(self.cfg, width * chunk, head_tokens=width,
                               kv_rows=width * self.max_len, tp=self.tp)
        wb_d, nc_d = collective_wire_bytes(self.cfg, self.max_batch,
                                           self.tp)
        wb_c, nc_c = collective_wire_bytes(self.cfg, width * chunk,
                                           self.tp, head_tokens=width)
        est = fused_step_energy(
            decode, ch, chip=self.chip or "tpu_v5e",
            dtype=self.cfg.activation_dtype,
            configs=self.pretuned or None,
            extra_hbm_bytes=(self._kv_gather_bytes(self.max_batch)
                             + self._kv_gather_bytes(width)),
            tp=self.tp, collective_bytes=wb_d + wb_c,
            n_collectives=nc_d + nc_c,
            overlap_chunks=getattr(self.cfg, "tp_overlap_chunks", 1),
            name=f"{self.cfg.name}:fused:{width}x{chunk}")
        self._step_energy_cache[key] = est
        return est

    # ------------------------------------------------------------------
    # continuous batching
    # ------------------------------------------------------------------
    def _continuous_supported(self) -> bool:
        if self.cfg.kind not in CONTINUOUS_KINDS:
            return False
        if (self.model.admit_dims is not None
                and (self.model.admit is None
                     or self.model.pack_admit is None)):
            return False
        if self.kv_layout == "paged":
            return (self.model.prefill_chunk is not None
                    and self.model.init_page_pool is not None)
        if self.admission == "chunked" or self.model.admit is not None:
            # admit families run serial admission through the same
            # admit + full-prompt-chunk path chunked admission uses
            return (self.model.prefill_chunk is not None
                    and self.model.init_state is not None)
        return (self.model.init_cache is not None
                and self.model.init_state is not None)

    def _bucket(self, n: int) -> int:
        """Smallest prefill bucket holding `n` prompt tokens — a bisect
        over the memoized `ops.prefill_buckets` tuple (the same list
        `serving_gemm_fleet` pre-tunes, so prefills only ever trace
        pre-warmed shapes). Attention-free prompts may exceed max_len;
        the bucket ladder keeps doubling past it."""
        from repro.kernels import ops

        buckets = ops.prefill_buckets(self.max_len, self.ssm_grain)
        i = bisect.bisect_left(buckets, n)
        if i < len(buckets):
            return buckets[i]
        b = buckets[-1]
        while b < n:
            b *= 2
        return b

    def _chunk_bucket(self, n: int) -> int:
        """Smallest chunk bucket holding `n` remaining prompt tokens,
        capped at `chunk_tokens` (longer remainders feed through the
        decode loop one chunk per step)."""
        from repro.kernels import ops

        buckets = ops.chunk_buckets(self.max_len, self.chunk_tokens,
                                    self.ssm_grain)
        i = bisect.bisect_left(buckets, n)
        return buckets[min(i, len(buckets) - 1)]

    def _budget(self, req: Request) -> int:
        """Effective token budget: >= 1, bounded by the row's remaining
        cache room — capacity minus its admission prefix and its own
        prompt length. This is the uniform per-row `lengths` bound; no row
        is ever clamped by another row's padded length."""
        cap = self._row_capacity()
        if cap is None:
            return max(1, req.max_new_tokens)
        prefix, _ = self._admit_dims(req)
        return max(1, min(req.max_new_tokens,
                          cap - prefix - len(req.prompt)))

    def _init_state(self, batch: int):
        """Zeroed decode-state pytree of `batch` rows (head-axis-sharded
        under tp — `sharding.SERVING_STATE_AXES`). Not cached: the jitted
        consumers donate their state argument, so a shared zero state
        would be consumed by its first use."""
        state = self.model.init_state(self.cfg, batch, self.max_len)
        if self.mesh is not None:
            from repro.distributed.sharding import serving_state_shardings

            state = jax.device_put(
                state, serving_state_shardings(state, self.mesh))
        return state

    def _ensure_splice(self) -> None:
        """Discover the decode-state batch-axis spec (state shapes at
        batch 1 vs 2, via eval_shape — no allocation) and jit the row
        splice: take row `i` of `src`, insert as row `j` of `dst`."""
        if self._splice_fn is not None:
            return
        from repro.models import layers as L

        # bypass the zero-state cache: eval_shape traces, and caching a
        # traced pytree would leak tracers into later real calls
        s1 = jax.eval_shape(
            lambda: self.model.init_state(self.cfg, 1, self.max_len))
        s2 = jax.eval_shape(
            lambda: self.model.init_state(self.cfg, 2, self.max_len))
        axes = L.state_batch_axes(s1, s2)
        self._state_axes = axes

        def engine_splice(dst, src, i, j):
            return L.insert_slot_state(dst, L.take_slot_state(src, axes, i),
                                       axes, j)

        self._splice_fn = jax.jit(engine_splice, donate_argnums=(0,))

    def _admit_rows(self, reqs: list[Request], width: int
                    ) -> tuple[dict, float]:
        """Prefill-once admission of `reqs` into a fresh `width`-row zero
        state, one batched call (the wave path admits a whole batch at
        once; chunked admission packs the step's fresh admissions).
        Returns (admitted state, total admission energy)."""
        dims = [self._admit_dims(r) for r in reqs]
        bucket = self._bucket(max(max(p, s) for p, s in dims) or 1)
        packed = self.model.pack_admit(
            self.cfg, [r.extras for r in reqs], width, bucket)
        state = self._admit_fn(self.params, packed,
                               self._init_state(width))
        adm_j, adm_s, adm_est = self._admit_cost(width, bucket)
        self._tick(adm_s, adm_est)
        return state, adm_j

    def _prefill_slot(self, req: Request, rng) -> tuple[int, dict, float]:
        """Single-shot slot prefill (`admission="serial"`): one request
        alone, right-padded to a pow2 bucket; samples its first token.
        Admit families run their admission call plus one full-prompt
        chunk — the exact path chunked admission takes, so serial/chunked
        parity holds by construction. Returns (first_token, slot_state,
        prefill_energy_j)."""
        if req.replay:
            raise ValueError(
                "replay requests require chunked admission (serve_step)")
        n = len(req.prompt)
        bucket = self._bucket(n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = req.prompt
        if self._admit_fn is not None:
            state, adm_j = self._admit_rows([req], 1)
            logits, state = self._chunk(
                self.params, jnp.asarray(toks),
                jnp.asarray([n], np.int32), state)
            pre_j, pre_s, pre_est = self._chunk_cost(1, bucket)
            self._tick(pre_s, pre_est)
            logits = np.asarray(logits, np.float32)
            tok = int(self._sample(logits, [rng])[0])
            return tok, state, adm_j + pre_j
        logits, state = self._prefill(
            self.params, {"tokens": jnp.asarray(toks),
                          "lengths": jnp.asarray([n], np.int32)})
        logits = np.asarray(logits, np.float32)
        tok = int(self._sample(logits, [rng])[0])
        pre_j, pre_s, pre_est = self._prefill_cost(bucket, head_rows=1)
        self._tick(pre_s, pre_est)
        return tok, state, pre_j

    def _finish(self, slot: _Slot, now: float, decode_energy_j: float,
                results: list[Result]) -> None:
        req = slot.req
        n_tok = len(slot.tokens)
        decode_s = max(now - slot.t_first, 0.0)
        energy = (slot.prefill_energy_j
                  + slot.steps * decode_energy_j / self.max_batch)
        self._stats["generated_tokens"] += n_tok
        self._stats["energy_j"] += energy
        self._stats["requests"] += 1
        results.append(Result(
            uid=req.uid, tokens=np.array(slot.tokens, np.int32),
            prompt_len=len(req.prompt), steps=slot.steps,
            n_tokens=n_tok,
            queue_s=max(slot.t_start - req.submit_s, 0.0),
            ttft_s=max(slot.t_first - req.submit_s, 0.0),
            ttft_model_s=max(slot.t_first_model - req.submit_model_s, 0.0),
            decode_s=decode_s,
            tokens_per_s=(n_tok / decode_s if decode_s > 0 else 0.0),
            energy_j=energy,
            energy_per_token_j=energy / max(n_tok, 1)))

    def _decode_step(self, slots, batch_state, token_buf, decode_cost,
                     results):
        """One lockstep decode step over the slot table; retires finished
        slots in place. Returns the new batch state."""
        decode_energy_j, decode_step_s, decode_est = decode_cost
        B = self.max_batch
        active = np.array([s is not None for s in slots])
        if not active.any():
            return batch_state
        n_active = int(active.sum())
        with TraceAnnotation("engine.decode", rows=n_active):
            self._tick(decode_step_s, decode_est)
            logits, batch_state = self._decode(
                self.params, jnp.asarray(token_buf), batch_state)
            with TraceAnnotation("engine.fetch"):
                logits = np.asarray(logits, np.float32)
            with TraceAnnotation("engine.sample"):
                cur = self._sample(
                    logits, [s.rng if s is not None else None
                             for s in slots])
                now = time.perf_counter()
                self._stats["decode_steps"] += 1
                self._stats["slot_steps"] += B
                self._stats["resident_slot_steps"] += n_active
                # dead slots still execute: their energy share is real
                # spend, charged to the engine (idle) rather than to any
                # request, so report()'s J/token stays comparable with
                # wave mode
                self._stats["idle_energy_j"] += (
                    (B - n_active) * decode_energy_j / B)
                for b in range(B):
                    slot = slots[b]
                    if slot is None:
                        continue
                    tok = int(cur[b])
                    slot.tokens.append(tok)
                    slot.steps += 1
                    token_buf[b] = tok
                    req = slot.req
                    if (req.eos_id is not None and tok == req.eos_id) or (
                            len(slot.tokens) >= self._budget(req)):
                        self._finish(slot, now, decode_energy_j, results)
                        slots[b] = None      # retired mid-decode; refilled
                        token_buf[b] = 0     # next loop iteration
        return batch_state

    def run_continuous(self) -> list[Result]:
        """Drain the queue with true continuous batching: retire finished
        slots mid-decode and refill them immediately."""
        self._activate()
        if not self._continuous_supported():
            raise ValueError(
                f"continuous batching unsupported for kind="
                f"{self.cfg.kind!r} (needs the per-row decode-state "
                f"contract); use wave mode")
        if self.kv_layout == "paged":
            return self._run_paged()
        if self.admission == "serial":
            return self._run_serial()
        return self._run_chunked()

    def _run_chunked(self) -> list[Result]:
        """Chunked admission fused into the decode loop, driven through
        the resumable stepper (`serve_step`) to exhaustion — token
        streams and telemetry are identical to running the loop
        inline."""
        out: list[Result] = []
        while self.has_work:
            out.extend(self.serve_step())
        return out

    def serve_step(self) -> list[Result]:
        """Advance chunked continuous serving by exactly one fused engine
        step — admit from the queue, one bucketed chunk call over the
        admission lane, one lockstep decode step over the residents — and
        return the requests that finished during it.

        This is the fleet scheduler's handle on the engine: between
        steps the caller may submit more requests, install or retarget
        `chunk_policy`, and interleave steps of other engines (each
        engine advances its own model clock). Requires continuous mode
        with ``admission="chunked"`` and the dense KV layout — the
        paged/serial/wave loops are not steppable. Returns ``[]`` on the
        final call that observes the drained loop; poll `has_work` to
        drive to exhaustion."""
        self._activate()
        if self._stepper is None:
            if not self.queue and not self._adopted:
                return []
            if (self.mode == "wave" or self.admission != "chunked"
                    or self.kv_layout != "dense"
                    or not self._continuous_supported()):
                raise ValueError(
                    f"serve_step requires chunked continuous serving on "
                    f"the dense KV layout (kind={self.cfg.kind!r}, "
                    f"mode={self.mode!r}, admission={self.admission!r}, "
                    f"kv_layout={self.kv_layout!r})")
            self._ensure_splice()
            self._stepper = self._chunked_stepper()
        try:
            return next(self._stepper)
        except StopIteration:
            self._stepper = None
            self._live = None
            self._lane_view = dict.fromkeys(self._lane_view, 0)
            return []

    def _chunked_stepper(self):
        """Generator behind `serve_step`: owns the admission lane, slot
        table and decode state across yields, emitting each step's newly
        finished `Result`s. Created lazily on the first `serve_step` with
        a non-empty queue; exhausts (StopIteration) when queue, lane and
        slots all drain."""
        B = self.max_batch
        results: list[Result] = []
        # cross-yield mutable state lives on the engine (`_LiveState`)
        # so `checkpoint_inflight` can extract in-flight rows when the
        # fleet scheduler declares this member crashed or evicted
        lv = self._live = _LiveState(B)
        decode_cost = self._decode_cost()
        decode_energy_j = decode_cost[0]
        # lane-row free list (lv.lane_free): vacated rows (spliced-out,
        # or finished on their first token) are reused in place by later
        # admissions — the device lane state reallocates only when the
        # pow2 width must *grow* past its high-water mark (satellite of
        # the stall fix: steady-state churn costs zero lane rebuilds). A
        # vacated row still holds its old occupant's state (cache write
        # index, SSM scan carry), so reused rows are zeroed by a one-row
        # splice before the new admission's first chunk.

        def zero_lane_row(r: int) -> None:
            """Overwrite lane row `r` with zeros (row 0 of a cached
            1-row zero state — the splice jit donates only dst, so the
            source survives reuse)."""
            if lv.zero_src is None:
                lv.zero_src = self._init_state(1)
            lv.adm_state = self._splice_fn(lv.adm_state, lv.zero_src,
                                           jnp.int32(0), jnp.int32(r))

        def adopt_ready() -> None:
            """Splice adopted decode-state rows (checkpointed off a
            failed fleet member) into free decode slots. The row's
            accumulated energy rides in as its prefill energy, so the
            final Result's attribution covers both attempts; already
            terminal rows retire immediately (defensive — the scheduler
            migrates only live requests)."""
            free = [b for b in range(B) if lv.slots[b] is None]
            now = time.perf_counter()
            while self._adopted and free:
                rec = self._adopted.popleft()
                b = free.pop(0)
                if lv.batch_state is None:
                    lv.batch_state = self._init_state(B)
                lv.batch_state = self._splice_fn(
                    lv.batch_state, rec["state"], jnp.int32(0),
                    jnp.int32(b))
                req = rec["req"]
                slot = _Slot(req=req,
                             tokens=[int(t) for t in rec["tokens"]],
                             prefill_energy_j=float(rec["energy_j"]),
                             t_start=now, t_first=now,
                             t_first_model=self._clock,
                             rng=rec.get("rng"))
                tok = slot.tokens[-1]
                if (req.eos_id is not None and tok == req.eos_id) or (
                        len(slot.tokens) >= self._budget(req)):
                    self._finish(slot, now, decode_energy_j, results)
                    continue
                lv.slots[b] = slot
                lv.token_buf[b] = tok

        def splice_ready() -> None:
            """Move parked (prefilled) admissions into free decode slots,
            FIFO by first-token time; their lane rows return to the free
            list."""
            free = [b for b in range(B) if lv.slots[b] is None]
            if not free:
                return
            keep: list[_Admission] = []
            for a in lv.adm:
                if a.ready is None or not free:
                    keep.append(a)
                    continue
                b = free.pop(0)
                if lv.batch_state is None:
                    lv.batch_state = self._init_state(B)
                lv.batch_state = self._splice_fn(
                    lv.batch_state, lv.adm_state, jnp.int32(a.row),
                    jnp.int32(b))
                lv.lane_free.append(a.row)
                lv.lane_dirty.add(a.row)
                lv.slots[b] = a.ready
                lv.token_buf[b] = a.first_tok
            lv.adm = keep

        def lane_stage() -> None:
            """Give every new admission a lane row: grow the lane when
            more rows are in flight than it holds, zero reused rows, and
            run the prefill-once admission call of admit families."""
            W = lv.adm_w or 1
            while W < len(lv.adm):
                W *= 2
            if lv.adm_state is None or W > lv.adm_w:
                # width growth (or first build): reallocate, carrying
                # every in-progress row across *at its own index* — row
                # assignments are sticky so no repacking splices happen
                with TraceAnnotation("engine.lane_grow"):
                    new_state = self._init_state(W)
                    held = set()
                    for a in lv.adm:
                        if a.row >= 0:
                            held.add(a.row)
                            if a.base > 0:
                                new_state = self._splice_fn(
                                    new_state, lv.adm_state,
                                    jnp.int32(a.row), jnp.int32(a.row))
                    lv.adm_state, lv.adm_w = new_state, W
                    lv.lane_free = [r for r in range(W) if r not in held]
                    lv.lane_dirty.clear()
                    self._stats["lane_rebuilds"] += 1
            lv.lane_free.sort()
            fresh: list[_Admission] = []
            for a in lv.adm:
                if a.row < 0:
                    a.row = lv.lane_free.pop(0)
                    if self._admit_fn is not None:
                        # admit families: the admission splice below
                        # overwrites the whole row (a complete batch-1
                        # state), so no zeroing splice is needed
                        lv.lane_dirty.discard(a.row)
                        fresh.append(a)
                    elif a.row in lv.lane_dirty:
                        lv.lane_dirty.discard(a.row)
                        zero_lane_row(a.row)
            if fresh:
                # prefill-once admission: one packed call over this
                # step's fresh admissions, each row spliced into its lane
                # slot (encoder + cross-KV for encdec, patch prefix for
                # vlm — their outputs are decode-state leaves)
                Wb = 1
                while Wb < len(fresh):
                    Wb *= 2
                t_adm = time.perf_counter()
                src_state, adm_j = self._admit_rows(
                    [a.req for a in fresh], Wb)
                for i, a in enumerate(fresh):
                    lv.adm_state = self._splice_fn(lv.adm_state, src_state,
                                                   jnp.int32(i),
                                                   jnp.int32(a.row))
                    a.chunk_energy_j += adm_j / Wb
                    a.prefix = self._admit_dims(a.req)[0]
                    if a.t_start == 0.0:
                        a.t_start = t_adm

        def chunk_stage() -> bool:
            """Run one chunk call over the rows still prefilling (parked
            and vacant rows ride along as zero-length identity rows).
            Samples first tokens for rows whose last chunk landed.
            Returns True when a request finished outright on its first
            sampled token (a lane row freed — the caller re-admits in
            the same pass)."""
            W = lv.adm_w
            pending = [a for a in lv.adm if a.ready is None]
            rem = [len(a.eff) - a.base for a in pending]
            # shortest-remainder-first bucket: short admissions finish in
            # cheap narrow calls (their TTFT is the point); long prompts
            # still progress min(C, rem) tokens per step and get full
            # chunks once the lane holds only longs
            C = self._chunk_bucket(min(rem))
            if self.chunk_policy is not None:
                # scheduler override: an SLO-aware policy may widen (or
                # narrow) the chunk against the SJF default; any request
                # still progresses min(C, rem) tokens per step, so every
                # ladder bucket is functionally valid — parity holds
                # because chunk boundaries stay bucket/grain aligned
                want = self.chunk_policy(
                    self, [(a.req, len(a.eff) - a.base)
                           for a in pending])
                if want:
                    C = self._chunk_bucket(int(want))
            if self.cfg.sub_quadratic and any(r > C for r in rem):
                # a *non-final* chunk boundary must stay a multiple of the
                # SSM serve-scan block or the carried scan state loses bit
                # parity with the unchunked prefill; the only unaligned
                # bucket is a non-multiple max_len, so drop to the widest
                # aligned one (validated to exist at construction)
                while C % self.ssm_grain:
                    C = self._chunk_bucket(C // 2)
            n_tok = sum(min(C, r) for r in rem)
            with TraceAnnotation("engine.chunk", width=W, bucket=C,
                                 rows=len(pending), tokens=n_tok):
                return chunk_call(W, C, pending, n_tok)

        def chunk_call(W: int, C: int, pending: list[_Admission],
                       n_tok: int) -> bool:
            """`chunk_stage`'s call of width `W` and bucket `C` over the
            `pending` rows (`n_tok` prompt tokens) and its first-token
            bookkeeping."""
            toks = np.zeros((W, C), np.int32)
            lens = np.zeros(W, np.int32)
            t_disp = time.perf_counter()
            for a in pending:
                n = min(C, len(a.eff) - a.base)
                toks[a.row, :n] = a.eff[a.base:a.base + n]
                lens[a.row] = n
                if a.t_start == 0.0:
                    a.t_start = t_disp
            logits, lv.adm_state = self._chunk(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                lv.adm_state)
            with TraceAnnotation("engine.fetch"):
                logits = np.asarray(logits, np.float32)
            now = time.perf_counter()
            est_j, est_s, est = self._chunk_cost(W, C)
            self._tick(est_s, est)
            self._stats["chunk_steps"] += 1
            self._stats["chunk_prompt_tokens"] += n_tok
            self._stats["chunk_token_slots"] += W * C
            # lane pad/parked rows are executed spend with no owner
            self._stats["idle_energy_j"] += (W - len(pending)) * est_j / W
            keep: list[_Admission] = []
            freed = False
            with TraceAnnotation("engine.sample"):
                for a in lv.adm:
                    if a.ready is not None:
                        keep.append(a)
                        continue
                    a.base += int(lens[a.row])
                    a.chunk_energy_j += est_j / W
                    if a.base < len(a.eff):
                        keep.append(a)
                        continue
                    replay = a.req.replay
                    if replay:
                        # fault replay: the emitted prefix is forced, not
                        # resampled — the stream stays an exact
                        # append-only continuation. Non-greedy rows burn
                        # the failed attempt's Gumbel draws so later
                        # tokens keep bit parity with the no-fault run.
                        if a.rng is not None:
                            for _ in replay:
                                a.rng.gumbel(size=logits.shape[-1])
                        tok = int(replay[-1])
                        toks0 = [int(t) for t in replay]
                    else:
                        tok = int(self._sample(logits[a.row:a.row + 1],
                                               [a.rng])[0])
                        toks0 = [tok]
                    srec = _Slot(req=a.req, tokens=toks0,
                                 prefill_energy_j=a.chunk_energy_j,
                                 t_start=a.t_start, t_first=now,
                                 t_first_model=self._clock, rng=a.rng)
                    # EOS or an exhausted budget on the first (or last
                    # replayed) token: finished before occupying a decode
                    # slot
                    if (a.req.eos_id is not None
                            and tok == a.req.eos_id) or (
                            len(toks0) >= self._budget(a.req)):
                        self._finish(srec, now, decode_energy_j, results)
                        lv.lane_free.append(a.row)
                        lv.lane_dirty.add(a.row)
                        freed = True
                        continue
                    a.ready = srec
                    a.first_tok = tok
                    keep.append(a)
            lv.adm = keep
            if not lv.adm:
                lv.adm_state, lv.adm_w = None, 0
                lv.lane_free = []
                lv.lane_dirty.clear()
            return freed

        emitted = 0
        while (self.queue or self._adopted or lv.adm
               or any(s is not None for s in lv.slots)):
            t_it0 = time.perf_counter()
            with TraceAnnotation("engine.step"):
                # ---- adopt + admit + chunk: splice adopted rows and
                # fill free lane rows from the queue, then run one chunk
                # call; a request finishing on its first sampled token
                # frees its lane row again, so keep admitting until the
                # lane is full of live work or the queue drains ----
                with TraceAnnotation("engine.splice"):
                    adopt_ready()
                    splice_ready()
                while True:
                    with TraceAnnotation("engine.admit"):
                        while self.queue and len(lv.adm) < self.lane_width:
                            req = self.queue.popleft()
                            rng = (None if self.greedy
                                   else self._req_rng(req.uid))
                            eff = np.asarray(req.prompt, np.int32)
                            if req.replay and len(req.replay) > 1:
                                eff = np.concatenate(
                                    [eff, np.asarray(req.replay[:-1],
                                                     np.int32)])
                            lv.adm.append(
                                _Admission(req=req, rng=rng, eff=eff))
                        if not any(a.ready is None for a in lv.adm):
                            break
                        lane_stage()
                    freed = chunk_stage()
                    if not (freed and self.queue):
                        break
                with TraceAnnotation("engine.splice"):
                    adopt_ready()
                    splice_ready()
                # ---- one lockstep decode step over the residents ----
                lv.batch_state = self._decode_step(
                    lv.slots, lv.batch_state, lv.token_buf, decode_cost,
                    results)
                self._stats["wall_s"] += time.perf_counter() - t_it0
                pending_n = sum(a.ready is None for a in lv.adm)
                self._lane_view = {
                    "pending": pending_n,
                    "pending_tokens": sum(len(a.eff) - a.base
                                          for a in lv.adm
                                          if a.ready is None),
                    "parked": len(lv.adm) - pending_n,
                    "resident": sum(s is not None for s in lv.slots),
                    "in_flight": len(lv.adm),
                }
            new, emitted = results[emitted:], len(results)
            yield new
        self._live = None

    # ------------------------------------------------------------------
    # fault recovery (repro.serving.faults / scheduler)
    # ------------------------------------------------------------------
    def state_compatible(self, other: "ServingEngine") -> bool:
        """True when a decode-state row checkpointed from `other` can be
        spliced into this engine with a bit-identical continuation:
        same model/params objects, config, cache geometry, layout, and
        sampling contract (seed + greedy). The scheduler consults this
        to choose migration over replay."""
        return (self.model is other.model
                and self.params is other.params
                and self.cfg == other.cfg
                and self.max_len == other.max_len
                and self.tp == other.tp
                and self.kv_layout == "dense"
                and other.kv_layout == "dense"
                and self.seed == other.seed
                and self.greedy == other.greedy)

    def checkpoint_inflight(self, *, state_lost: bool = False
                            ) -> list[dict]:
        """Surgically extract every in-flight request for recovery on
        another member, then clear this engine (crash semantics: the
        queue, lane, and slot table are gone afterwards).

        Each record carries the request, its emitted tokens, its
        accumulated attributable energy, its sampling stream, the
        engine-relative TTFT if the first token was already emitted, and
        — for rows whose device state survives (resident decode slots
        and parked admissions, unless ``state_lost``) — the batch-1
        decode-state pytree `layers.take_slot_state` carves out, ready
        for `adopt` on a compatible member. Mid-prefill admissions and
        queued requests always restart from scratch (their partial chunk
        energy is the failed attempt's lost spend — the scheduler
        charges it back via `charge_lost_energy`)."""
        from repro.models import layers as L

        records: list[dict] = []
        lv = self._live
        decode_j = self._decode_cost()[0]

        def rec(req, tokens, state, energy, rng, ttft, lost=0.0):
            records.append({
                "req": req, "tokens": list(tokens), "state": state,
                "energy_j": float(energy), "rng": rng,
                "ttft_model_s": ttft, "lost_energy_j": float(lost)})

        if lv is not None:
            for b, slot in enumerate(lv.slots):
                if slot is None:
                    continue
                state = None
                if not state_lost and lv.batch_state is not None:
                    state = L.take_slot_state(lv.batch_state,
                                              self._state_axes, b)
                energy = (slot.prefill_energy_j
                          + slot.steps * decode_j / self.max_batch)
                rec(slot.req, slot.tokens, state, energy, slot.rng,
                    max(slot.t_first_model - slot.req.submit_model_s,
                        0.0),
                    lost=0.0 if state is not None else energy)
            for a in lv.adm:
                if a.ready is not None:
                    state = None
                    if not state_lost and lv.adm_state is not None:
                        state = L.take_slot_state(lv.adm_state,
                                                  self._state_axes, a.row)
                    energy = a.ready.prefill_energy_j
                    rec(a.req, a.ready.tokens, state, energy, a.rng,
                        max(a.ready.t_first_model
                            - a.req.submit_model_s, 0.0),
                        lost=0.0 if state is not None else energy)
                else:
                    # mid-prefill: partial chunks cannot migrate — the
                    # spend so far is lost to the failed attempt
                    rec(a.req, [], None, 0.0, None, None,
                        lost=a.chunk_energy_j)
        for r in self._adopted:
            records.append(dict(r) if state_lost is False
                           else {**r, "state": None,
                                 "lost_energy_j": r["energy_j"],
                                 "energy_j": 0.0})
        for req in self.queue:
            rec(req, req.replay or [], None, 0.0, None, None)
        self.queue.clear()
        self._adopted.clear()
        self._stepper = None
        self._live = None
        self._lane_view = dict.fromkeys(self._lane_view, 0)
        return records

    def adopt(self, record: dict) -> None:
        """Accept a checkpointed decode-state row from a failed member
        (migration). The row waits in the adoption queue until a decode
        slot frees; `has_work` counts it. Raises when the record carries
        no state or a structurally incompatible one — the scheduler
        falls back to replay."""
        from repro.models import layers as L

        if record.get("state") is None:
            raise ValueError(
                "adopt needs a checkpointed state row; replay lost-state "
                "requests instead")
        if self.kv_layout != "dense" or self.admission != "chunked":
            raise ValueError(
                "adoption requires chunked continuous serving on the "
                "dense KV layout")
        self._ensure_splice()
        spec = jax.eval_shape(
            lambda: self.model.init_state(self.cfg, 1, self.max_len))
        if not L.state_structures_match(record["state"], spec):
            raise ValueError(
                "checkpointed state row is structurally incompatible "
                "with this engine's decode state")
        self._adopted.append(record)

    def charge_lost_energy(self, j: float) -> None:
        """Charge energy a failed attempt spent on work that must be
        replayed: real spend with no surviving owner, folded into this
        engine's idle share (so fleet ledgers still sum) and tracked in
        `lost_energy_j` for the robustness report."""
        self._stats["idle_energy_j"] += float(j)
        self._stats["lost_energy_j"] += float(j)

    def retune(self, *, objective: str = "runtime",
               rank_mode: str = "auto", _inject=None) -> bool:
        """Re-tune the engine's GEMM fleet mid-run (e.g. after a chip or
        artifact change). On `ArtifactError` — a corrupt or missing
        predictor artifact, or the injected fault ``_inject`` — tuning
        degrades to the paper's BASELINE block configs instead of
        raising: serving continues, pricing uses BASELINE everywhere,
        and `report()` carries ``tuning_degraded`` plus the reason.
        Token streams are unaffected either way (block configs change
        cost predictions, never semantics). Returns True when tuning
        succeeded, False when it degraded."""
        from repro.core.predictor import ArtifactError
        from repro.kernels import ops

        fleet = ops.serving_gemm_fleet(
            self.cfg, max_batch=self.max_batch, max_len=self.max_len,
            include_slot_prefill=self._continuous_supported(),
            chunk_tokens=(self.chunk_tokens
                          if self.admission == "chunked" else None),
            lane_width=(self.lane_width
                        if self.admission == "chunked" else None),
            tp=self.tp, grain=self.ssm_grain)
        try:
            if _inject is not None:
                raise _inject
            self.pretuned = ops.warm_gemm_cache(
                fleet, dtype=self.cfg.activation_dtype,
                objective=objective, chip=self.chip,
                rank_mode=rank_mode)
            self.tuning_degraded = False
            self._degraded_reason = None
        except ArtifactError as e:
            from repro.core.autotuner import baseline_configs

            self.pretuned = baseline_configs(fleet)
            self.tuning_degraded = True
            self._degraded_reason = str(e)
        # step-energy estimates were priced under the old configs
        self._step_energy_cache.clear()
        return not self.tuning_degraded

    # ------------------------------------------------------------------
    # paged layout: pool pressure (fault injection / degraded mode)
    # ------------------------------------------------------------------
    def inject_page_pressure(self, pages: int) -> int:
        """Squeeze `pages` pages out of the paged KV pool (an external
        tenant, a chaos fault). Returns how many were actually taken;
        `release_page_pressure` gives them back."""
        if self._allocator is None:
            raise ValueError("page pressure requires kv_layout='paged'")
        return self._allocator.squeeze(pages)

    def release_page_pressure(self) -> int:
        """Return every squeezed page to the paged KV pool."""
        if self._allocator is None:
            raise ValueError("page pressure requires kv_layout='paged'")
        return self._allocator.unsqueeze()

    def _ensure_pool(self) -> None:
        """Build the device page pool and the jitted page-copy call on
        first use (the pool is the engine's single biggest allocation —
        engines constructed but never run shouldn't pay it)."""
        if self._pool is not None:
            return
        from repro.models import layers as L

        self._pool = self.model.init_page_pool(
            self.cfg, self._allocator.num_pages, self.page_size)
        if self.mesh is not None:
            from repro.distributed.sharding import serving_state_shardings

            self._pool = jax.device_put(
                self._pool,
                serving_state_shardings(self._pool, self.mesh))

        def engine_copy_pages(pool, src, dst):
            return L.copy_pool_pages(pool, src, dst)

        self._copy_pages = jax.jit(engine_copy_pages, donate_argnums=(0,))

    def _run_paged(self) -> list[Result]:
        """Chunked-admission continuous batching over the paged KV layout.

        Structure mirrors `_run_chunked`, but all per-row cache state
        lives in one shared device page pool addressed through host-built
        page tables (`repro.serving.paging.PageAllocator` owns the
        bookkeeping), which changes three things:

        * the pool threads *sequentially* through the donated chunk and
          decode calls (one device state, not a lane state + a slot
          state), with each call's page table and cache positions rebuilt
          from host records — so parking a finished admission and
          splicing it into a decode slot are pure host moves of a page
          list, zero device copies;
        * admission reserves a request's full page capacity up front
          (`PageAllocator.admit`) and reuses registered shared-prefix
          pages, skipping their prefill chunks entirely (`base` starts
          past the matched tokens) — the TTFT win prefix reuse exists
          for. Pool exhaustion defers admission until a retirement frees
          pages (deadlock-free: the failure surfaces only at admission);
        * a finished prompt registers its pages in the prefix registry
          (plus a frozen snapshot of a partial last page) for later
          requests to map copy-on-write.

        Token streams are bit-identical to the dense layout: the gathered
        per-row view spans the same max_len positions with the same
        masks, and every unmasked position holds the same written values.
        """
        self._ensure_pool()
        if any(r.replay for r in self.queue):
            raise ValueError(
                "replay requests require the dense KV layout")
        t_run0 = time.perf_counter()
        from repro.serving.paging import PageCacheFull

        B = self.max_batch
        n_pg = self._n_row_pages
        n_layers = self.cfg.n_layers
        results: list[Result] = []
        slots: list[_Slot | None] = [None] * B
        token_buf = np.zeros(B, np.int32)
        decode_cost = self._decode_cost()
        decode_energy_j = decode_cost[0]
        adm: list[_Admission] = []
        alloc = self._allocator
        pool = self._pool
        pool_keys = set(pool)
        admit_family = self._admit_fn is not None
        extra_top_spec: dict = {}
        extra_kv_spec: dict = {}
        if admit_family:
            # admit families carry dense per-request leaves alongside the
            # page pool (encdec cross-KV + src_len, vlm pos_off): discover
            # them — and their batch axes — from the dense state spec. A
            # dense leaf is "extra" iff the pool holds no paged twin.
            self._ensure_splice()
            spec1 = jax.eval_shape(lambda: self.model.init_state(
                self.cfg, 1, self.max_len))
            extra_top_spec = {k: v for k, v in spec1.items()
                              if k not in ("kv", "index")}
            extra_kv_spec = {k: v for k, v in spec1["kv"].items()
                             if f"{k}_pages" not in pool_keys
                             and k not in pool_keys}

        def _zero_leaf(spec, axis: int, width: int):
            shape = list(spec.shape)
            shape[axis] = width
            return jnp.zeros(tuple(shape), spec.dtype)

        def zero_extras(width: int) -> tuple[dict, dict]:
            top = {k: _zero_leaf(v, self._state_axes[k], width)
                   for k, v in extra_top_spec.items()}
            kvx = {k: _zero_leaf(v, self._state_axes["kv"][k], width)
                   for k, v in extra_kv_spec.items()}
            return top, kvx

        def gather_extras(recs: list, width: int) -> tuple[dict, dict]:
            """Per-call extra state: concatenate each record's batch-1
            admission leaves along the leaf's batch axis (zero rows for
            empty slots). Records are _Admission or _Slot objects. The
            result feeds a buffer-donating jit, so a width-1 gather must
            COPY — returning the record's stored leaf would let donation
            delete it out from under the next step."""
            rows = list(recs[:width]) + [None] * (width - len(recs[:width]))
            ztop, zkv = zero_extras(1)

            def cat(parts, axis):
                if len(parts) == 1:
                    return jnp.copy(parts[0])
                return jnp.concatenate(parts, axis=axis)

            top = {}
            kvx = {}
            for k in extra_top_spec:
                parts = [(r.extra_top[k] if r is not None and r.extra_top
                          else ztop[k]) for r in rows]
                top[k] = cat(parts, self._state_axes[k])
            for k in extra_kv_spec:
                parts = [(r.extra_kv[k] if r is not None and r.extra_kv
                          else zkv[k]) for r in rows]
                kvx[k] = cat(parts, self._state_axes["kv"][k])
            return top, kvx

        def dev_table(rows: list[list[int] | None], width: int):
            """(L, width, n_pg) device table from per-row page lists
            (missing/short rows padded with the null page)."""
            tbl = np.zeros((width, n_pg), np.int32)
            for i, pgs in enumerate(rows):
                if pgs:
                    tbl[i, :len(pgs)] = pgs
            return jnp.broadcast_to(jnp.asarray(tbl)[None],
                                    (n_layers, width, n_pg))

        def apply_copies(copies: list[tuple[int, int]]) -> None:
            """Run the allocator's pending (src, dst) page copies on the
            pool — COW forks and prefix snapshots. Copy batches pad to a
            pow2 bucket with null-page self-copies to bound jit traces."""
            nonlocal pool
            if not copies:
                return
            n = 1
            while n < len(copies):
                n *= 2
            src = np.zeros(n, np.int32)
            dst = np.zeros(n, np.int32)
            for i, (s, d) in enumerate(copies):
                src[i], dst[i] = s, d
            pool = self._copy_pages(pool, jnp.asarray(src),
                                    jnp.asarray(dst))

        def admit_from_queue() -> None:
            """Admit queued requests while the lane has room and the pool
            can cover their full reservation; on exhaustion the request
            waits at the head of the queue for a retirement — unless
            nothing is in flight to retire, which is a hard failure.
            Admit families run their prefill-once admission call here:
            the patch prefix writes through the reserved pages (vlm), the
            cross-KV lands in per-request dense leaves (encdec); prefix
            reuse is disabled for them — their self-attention KV depends
            on the modality input, not the token prefix alone."""
            nonlocal pool
            while self.queue and len(adm) < self.lane_width:
                req = self.queue[0]
                prefix, src = self._admit_dims(req)
                try:
                    a = alloc.admit(np.asarray(req.prompt, np.int32),
                                    self._budget(req),
                                    prefix_rows=prefix,
                                    reuse=not admit_family)
                except PageCacheFull:
                    # degraded mode: under pool pressure the shared-
                    # prefix registry is a cache, not a promise — shed
                    # it (dropping the registry's references frees
                    # sole-owner pages now, shared ones at their last
                    # reader) and retry before deferring the admission
                    if alloc.shed_registry():
                        continue
                    if not adm and not any(s is not None for s in slots):
                        raise
                    break
                self.queue.popleft()
                apply_copies(a.copies)
                rng = None if self.greedy else self._req_rng(req.uid)
                rec = _Admission(req=req, rng=rng, base=a.base,
                                 pages=a.pages, prefix=prefix)
                if admit_family and (prefix or src):
                    bucket = self._bucket(max(prefix, src))
                    packed = self.model.pack_admit(
                        self.cfg, [req.extras], 1, bucket)
                    top0, kv0 = zero_extras(1)
                    st = {"kv": {**pool, "table": dev_table([a.pages], 1),
                                 **kv0},
                          "index": jnp.zeros((1,), jnp.int32), **top0}
                    st = self._admit_fn(self.params, packed, st)
                    pool = {k: st["kv"][k] for k in pool_keys}
                    rec.extra_top = {k: st[k] for k in extra_top_spec}
                    rec.extra_kv = {k: st["kv"][k]
                                    for k in extra_kv_spec}
                    adm_j, adm_s, adm_est = self._admit_cost(1, bucket)
                    self._tick(adm_s, adm_est)
                    rec.chunk_energy_j += adm_j
                    rec.t_start = time.perf_counter()
                adm.append(rec)

        def splice_ready() -> None:
            """Move parked admissions into free decode slots — a pure
            host transfer of the page list (the row's KV already lives in
            the shared pool)."""
            nonlocal adm
            free = [b for b in range(B) if slots[b] is None]
            if not free:
                return
            keep: list[_Admission] = []
            for a in adm:
                if a.ready is None or not free:
                    keep.append(a)
                    continue
                b = free.pop(0)
                slots[b] = a.ready
                token_buf[b] = a.first_tok
            adm = keep

        def chunk_stage() -> bool:
            """One bucketed chunk call over the rows still prefilling
            (parked rows hold no lane state here, so the call width
            covers only pending rows). Returns True when a request
            finished outright on its first sampled token (lane row and
            pages freed — the caller re-admits in the same pass)."""
            nonlocal adm, pool
            pending = [a for a in adm if a.ready is None]
            W = 1
            while W < len(pending):
                W *= 2
            for i, a in enumerate(pending):
                a.row = i
            rem = [len(a.req.prompt) - a.base for a in pending]
            C = self._chunk_bucket(min(rem))
            toks = np.zeros((W, C), np.int32)
            lens = np.zeros(W, np.int32)
            base = np.zeros(W, np.int32)
            rows: list[list[int] | None] = [None] * W
            t_disp = time.perf_counter()
            recs: list[_Admission | None] = [None] * W
            for a in pending:
                n = min(C, len(a.req.prompt) - a.base)
                toks[a.row, :n] = a.req.prompt[a.base:a.base + n]
                lens[a.row] = n
                # cache positions sit past the admission prefix (vlm
                # patch rows occupy [0, prefix) of the row's pages)
                base[a.row] = a.prefix + a.base
                rows[a.row] = a.pages
                recs[a.row] = a
                if a.t_start == 0.0:
                    a.t_start = t_disp
            extra_top, extra_kv = (gather_extras(recs, W)
                                   if admit_family else ({}, {}))
            state = {"kv": {**pool, "table": dev_table(rows, W),
                            **extra_kv},
                     "index": jnp.asarray(base), **extra_top}
            logits, state = self._chunk(
                self.params, jnp.asarray(toks), jnp.asarray(lens), state)
            pool = {k: v for k, v in state["kv"].items()
                    if k in pool_keys}
            logits = np.asarray(logits, np.float32)
            now = time.perf_counter()
            est_j, est_s, est = self._chunk_cost(W, C)
            self._tick(est_s, est)
            self._stats["chunk_steps"] += 1
            self._stats["chunk_prompt_tokens"] += int(lens.sum())
            self._stats["chunk_token_slots"] += W * C
            self._stats["idle_energy_j"] += (W - len(pending)) * est_j / W
            keep: list[_Admission] = []
            freed = False
            for a in adm:
                if a.ready is not None:
                    keep.append(a)
                    continue
                a.base += int(lens[a.row])
                a.chunk_energy_j += est_j / W
                plen = len(a.req.prompt)
                if a.base < plen:
                    keep.append(a)
                    continue
                if not admit_family:
                    # prompt fully cached: publish its pages to the
                    # prefix registry (may snapshot a partial last page).
                    # Admit families never register — their KV depends on
                    # the modality input, so token-prefix reuse is unsound
                    apply_copies(alloc.register(
                        np.asarray(a.req.prompt, np.int32), a.pages,
                        a.base))
                tok = int(self._sample(logits[a.row:a.row + 1],
                                       [a.rng])[0])
                srec = _Slot(req=a.req, tokens=[tok],
                             prefill_energy_j=a.chunk_energy_j,
                             t_start=a.t_start, t_first=now,
                             t_first_model=self._clock, rng=a.rng,
                             pages=a.pages, index=a.prefix + plen,
                             extra_top=a.extra_top, extra_kv=a.extra_kv)
                if (a.req.eos_id is not None and tok == a.req.eos_id) or (
                        self._budget(a.req) <= 1):
                    self._finish(srec, now, decode_energy_j, results)
                    alloc.release(a.pages)
                    freed = True
                    continue
                a.ready = srec
                a.first_tok = tok
                keep.append(a)
            adm = keep
            return freed

        def decode_step() -> None:
            """One lockstep decode step: page tables and per-slot cache
            positions rebuilt from host records, pool threaded through
            the donated call; finished slots release their pages (shared
            prefix pages drop a reference, freeing only with the last
            reader)."""
            nonlocal pool
            if not any(s is not None for s in slots):
                return
            self._tick(decode_cost[1], decode_cost[2])
            extra_top, extra_kv = (gather_extras(slots, B)
                                   if admit_family else ({}, {}))
            state = {"kv": {**pool,
                            "table": dev_table(
                                [s.pages if s else None for s in slots],
                                B),
                            **extra_kv},
                     "index": jnp.asarray(np.array(
                         [s.index if s else 0 for s in slots], np.int32)),
                     **extra_top}
            logits, state = self._decode(
                self.params, jnp.asarray(token_buf), state)
            pool = {k: v for k, v in state["kv"].items()
                    if k in pool_keys}
            logits = np.asarray(logits, np.float32)
            cur = self._sample(
                logits, [s.rng if s is not None else None for s in slots])
            now = time.perf_counter()
            n_active = sum(s is not None for s in slots)
            self._stats["decode_steps"] += 1
            self._stats["slot_steps"] += B
            self._stats["resident_slot_steps"] += n_active
            self._stats["idle_energy_j"] += (
                (B - n_active) * decode_energy_j / B)
            for b in range(B):
                slot = slots[b]
                if slot is None:
                    continue
                tok = int(cur[b])
                slot.tokens.append(tok)
                slot.steps += 1
                slot.index += 1
                token_buf[b] = tok
                req = slot.req
                if (req.eos_id is not None and tok == req.eos_id) or (
                        len(slot.tokens) >= self._budget(req)):
                    self._finish(slot, now, decode_energy_j, results)
                    alloc.release(slot.pages)
                    slots[b] = None
                    token_buf[b] = 0

        while self.queue or adm or any(s is not None for s in slots):
            splice_ready()
            while True:
                admit_from_queue()
                if not any(a.ready is None for a in adm):
                    break
                freed = chunk_stage()
                if not (freed and self.queue):
                    break
            splice_ready()
            decode_step()
        self._pool = pool
        self._stats["wall_s"] += time.perf_counter() - t_run0
        return results

    def _run_serial(self) -> list[Result]:
        """PR 4-style admission: each request prefills alone (single-shot
        bucketed call) and is spliced into a free slot — kept as the
        stall-prone baseline `benchmarks/bench_serving.py` regresses
        chunked admission against."""
        self._ensure_splice()
        t_run0 = time.perf_counter()
        B = self.max_batch
        results: list[Result] = []
        slots: list[_Slot | None] = [None] * B
        batch_state = None
        token_buf = np.zeros(B, np.int32)
        decode_cost = self._decode_cost()
        decode_energy_j = decode_cost[0]

        while self.queue or any(s is not None for s in slots):
            # ---- refill free slots from the queue (a request finishing
            # on its very first token frees the slot again, so keep
            # admitting until the slot holds a live request or the queue
            # drains — no decode step runs with a needlessly dead slot) --
            for b in range(B):
                while slots[b] is None and self.queue:
                    req = self.queue.popleft()
                    rng = (None if self.greedy
                           else self._req_rng(req.uid))
                    t0 = time.perf_counter()
                    tok, slot_state, pre_j = self._prefill_slot(req, rng)
                    t1 = time.perf_counter()
                    slot = _Slot(req=req, tokens=[tok],
                                 prefill_energy_j=pre_j,
                                 t_start=t0, t_first=t1,
                                 t_first_model=self._clock, rng=rng)
                    # EOS or a 1-token budget on the *first* sampled
                    # token: finished before ever occupying a decode slot
                    if (req.eos_id is not None and tok == req.eos_id) or (
                            self._budget(req) <= 1):
                        self._finish(slot, t1, decode_energy_j, results)
                        continue
                    if batch_state is None:
                        batch_state = self._init_state(B)
                    batch_state = self._splice_fn(
                        batch_state, slot_state, jnp.int32(0),
                        jnp.int32(b))
                    slots[b] = slot
                    token_buf[b] = tok
            if not any(s is not None for s in slots):
                break                  # queue drained, no live slots
            batch_state = self._decode_step(
                slots, batch_state, token_buf, decode_cost, results)
        self._stats["wall_s"] += time.perf_counter() - t_run0
        return results

    # ------------------------------------------------------------------
    # wave mode (compatibility shim)
    # ------------------------------------------------------------------
    def run_wave(self) -> list[Result]:
        """Serve one wave: take up to max_batch queued requests, prefill
        (one batched right-padded call), decode until all finish. Finished
        rows stay resident to the end of the wave (counted in `steps` so
        energy attribution reflects the waste)."""
        if not self.queue:
            return []
        if any(r.replay for r in self.queue):
            raise ValueError(
                "replay requests require chunked admission (serve_step)")
        self._activate()
        t_run0 = time.perf_counter()
        batch_reqs = [self.queue.popleft()
                      for _ in range(min(self.max_batch, len(self.queue)))]
        B = len(batch_reqs)
        lens = np.array([len(r.prompt) for r in batch_reqs], np.int32)
        S = int(lens.max())
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(batch_reqs):
            toks[i, :lens[i]] = r.prompt           # right-pad + lengths
        t0 = time.perf_counter()
        if self._admit_fn is not None:
            # admit families: one batched prefill-once admission (all B
            # rows in a single call against a width-B zero state) + one
            # full-width chunk over the right-padded prompts — the same
            # path chunked admission runs, so wave/chunked parity holds
            # by construction
            state, adm_j = self._admit_rows(batch_reqs, B)
            Sb = self._bucket(S)
            wt = np.zeros((B, Sb), np.int32)
            wt[:, :S] = toks
            logits, state = self._chunk(
                self.params, jnp.asarray(wt), jnp.asarray(lens), state)
            logits = np.asarray(logits, np.float32)
            t_first = time.perf_counter()
            prefill_j, prefill_s, pre_est = self._chunk_cost(B, Sb)
            prefill_j += adm_j
        else:
            batch = {"tokens": jnp.asarray(toks),
                     "lengths": jnp.asarray(lens)}
            logits, state = self._prefill(self.params, batch)
            logits = np.asarray(logits, np.float32)
            t_first = time.perf_counter()
            prefill_j, prefill_s, pre_est = self._prefill_cost(
                B * S, head_rows=B)
        self._tick(prefill_s, pre_est)
        t_first_model = self._clock
        est = self._step_energy(("decode", B), B, batch_rows=B)
        decode_energy_j, decode_step_s, _ = self._cost(est)

        # per-row budgets: the uniform `lengths` bound (each row clamps by
        # its own prefix + prompt, never by the wave's shared padded
        # length)
        budgets = np.array([self._budget(r) for r in batch_reqs])
        out: list[list[int]] = [[] for _ in range(B)]
        done = np.zeros(B, bool)
        steps = 0
        rngs = [None if self.greedy else self._req_rng(r.uid)
                for r in batch_reqs]
        cur = self._sample(logits, rngs)
        for i, r in enumerate(batch_reqs):
            tok = int(cur[i])
            out[i].append(tok)
            # honor EOS / a 1-token budget on the first sampled token
            if (r.eos_id is not None and tok == r.eos_id) or (
                    budgets[i] <= 1):
                done[i] = True
        while not done.all():
            self._tick(decode_step_s, est)
            logits, state = self._decode(self.params, jnp.asarray(cur), state)
            logits = np.asarray(logits, np.float32)
            cur = self._sample(
                logits, [None if done[i] else rngs[i] for i in range(B)])
            steps += 1
            for i, r in enumerate(batch_reqs):
                if done[i]:
                    continue
                tok = int(cur[i])
                out[i].append(tok)
                if (r.eos_id is not None and tok == r.eos_id) or (
                        len(out[i]) >= budgets[i]):
                    done[i] = True
        t_end = time.perf_counter()
        self._stats["decode_steps"] += steps
        self._stats["slot_steps"] += steps * B
        self._stats["resident_slot_steps"] += steps * B
        results = []
        for i, r in enumerate(batch_reqs):
            n_tok = len(out[i])
            # resident until the wave drains — the Racing-to-Idle cost
            energy = prefill_j / B + steps * decode_energy_j / B
            decode_s = max(t_end - t_first, 0.0)
            self._stats["generated_tokens"] += n_tok
            self._stats["energy_j"] += energy
            self._stats["requests"] += 1
            results.append(Result(
                uid=r.uid, tokens=np.array(out[i], np.int32),
                prompt_len=len(r.prompt), steps=steps, n_tokens=n_tok,
                queue_s=max(t0 - r.submit_s, 0.0),
                ttft_s=max(t_first - r.submit_s, 0.0),
                ttft_model_s=max(t_first_model - r.submit_model_s, 0.0),
                decode_s=decode_s,
                tokens_per_s=n_tok / decode_s if decode_s > 0 else 0.0,
                energy_j=energy,
                energy_per_token_j=energy / max(n_tok, 1)))
        self._stats["wall_s"] += time.perf_counter() - t_run0
        return results

    # ------------------------------------------------------------------
    def reset_stats(self) -> None:
        """Zero the cumulative counters behind `report()` — e.g. after a
        warm-up pass, so throughput excludes jit compilation time."""
        for k, v in self._stats.items():
            self._stats[k] = type(v)(0)

    def run_until_empty(self) -> list[Result]:
        """Serve every queued request to completion in the engine's mode
        (``mode="auto"`` picks continuous batching when the family
        supports it, else the wave loop)."""
        self._activate()
        mode = self.mode
        if mode == "auto":
            mode = ("continuous" if self._continuous_supported()
                    else "wave")
        if mode == "continuous":
            return self.run_continuous()
        results = []
        while self.queue:
            results.extend(self.run_wave())
        return results

    def report(self) -> dict:
        """Engine-level serving report: throughput, energy, occupancy.

        `energy_j` / `j_per_token` count *total* spend — per-request
        attributed energy plus the idle share of decode steps executed
        with dead slots (and of chunk-call pad rows) — so continuous and
        wave modes compare like-for-like."""
        s = self._stats
        toks = s["generated_tokens"]
        slot_steps = s["slot_steps"]
        total_j = s["energy_j"] + s["idle_energy_j"]
        paging = ({"paging": self._allocator.report()}
                  if self._allocator is not None else {})
        return {
            **paging,
            "tp": self.tp,
            # model-clock throughput: tokens over the analytical model's
            # predicted seconds of dispatched calls — deterministic and
            # host-independent, the surface the sharded bench gates on
            # (wall_s on a host-platform mesh measures emulation, not tp)
            "model_s": s["model_s"],
            "model_tokens_per_s": (toks / s["model_s"]
                                   if s["model_s"] > 0 else 0.0),
            "collective_wire_s": s["wire_s"],
            "overlap_factor": (s["hidden_wire_s"] / s["wire_s"]
                               if s["wire_s"] > 0 else 0.0),
            "lane_rebuilds": s["lane_rebuilds"],
            # Pallas GEMM calls in the programs traced so far, by how each
            # read its weight: in place, or from a copy made before it
            "weight_reads": dict(self._weight_reads),
            "requests": s["requests"],
            "generated_tokens": toks,
            "decode_steps": s["decode_steps"],
            "chunk_steps": s["chunk_steps"],
            "chunk_prompt_tokens": s["chunk_prompt_tokens"],
            "chunk_token_slots": s["chunk_token_slots"],
            "slot_steps": slot_steps,
            "resident_slot_steps": s["resident_slot_steps"],
            "slot_occupancy": (s["resident_slot_steps"] / slot_steps
                               if slot_steps else 0.0),
            "wall_s": s["wall_s"],
            "tokens_per_s": toks / s["wall_s"] if s["wall_s"] > 0 else 0.0,
            "energy_j": total_j,
            "attributed_energy_j": s["energy_j"],
            "idle_energy_j": s["idle_energy_j"],
            "j_per_token": total_j / toks if toks else 0.0,
            # robustness surface: replayed work charged to this engine
            # as the failed attempt, and whether tuning fell back to
            # BASELINE configs
            "lost_energy_j": s["lost_energy_j"],
            "tuning_degraded": self.tuning_degraded,
            "tuning_degraded_reason": self._degraded_reason,
        }
