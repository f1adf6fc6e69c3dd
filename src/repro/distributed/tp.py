"""Explicit-collective tensor-parallel linear layers.

Under plain SPMD, TP matmuls accumulate in fp32 and GSPMD inserts the
partial-sum all-reduce at the dot output — *before* the bf16 cast — so every
TP collective (forward and its AD transposes) moves fp32 bytes: 2x the wire
traffic the math needs. The dry-run measured this as the dominant term on
every dense train cell (EXPERIMENTS.md §Perf).

These wrappers take manual control with shard_map + custom_vjp:

  column_parallel:  y_loc = x @ w_loc          (w col-sharded over "model")
      fwd: no collective;  bwd dx: psum over "model" in bf16.
  row_parallel:     y = psum(x_loc @ w_loc)    (w row-sharded over "model")
      fwd: psum (or psum_scatter under SP) in bf16;  bwd: NO collective
      (the upstream cotangent is already replicated).
  row_parallel_gather:  y = reassemble(all_gather(all_gather(x) @ w_loc))
      (w COLUMN-sharded) — the serving engine's bit-stable mode: every
      output element is one full-contraction dot, so the result is
      bit-identical to the unsharded matmul (a psum re-associates the
      fp32 accumulation across shards; a gather never does).

Both row-parallel forms split the projection into `tp_overlap_chunks`
interleaved column chunks: chunk c's collective (psum / all-gather) has no
consumer until the final concat, so XLA's latency-hiding scheduler runs it
on the wire while chunk c+1's GEMM occupies the MXU — the double-buffered
SUMMA-pipelining idea, with identical numerics (per-chunk reductions touch
disjoint output columns).

Forward per-shard GEMMs run through `kernels.ops.matmul` inside the
shard_map — the predictor-tuned Pallas kernel on TPU, on each chip's own
(M, N/tp, K) shard (a Mosaic kernel cannot be partitioned by GSPMD, so it
only ever runs on shards). They keep fp32 accumulation — only the wire
format changes. Weight grads stay sharded like the weights; the data-
axis gradient reduction stays with SPMD (bf16 cotangents).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, dp_axes


def _dp(mesh):
    ax = dp_axes(mesh)
    return ax if len(ax) > 1 else (ax[0] if ax else None)


def _dot(x, w, out_dtype=None):
    """One shard's GEMM, fp32-accumulated, cast to `out_dtype` (default:
    x's dtype) before anything goes on the wire."""
    from repro.kernels import ops

    return ops.matmul(x, w, out_dtype=out_dtype)


def _replicated_dot(x, w, mesh, out_dtype=None):
    """`_dot` on fully replicated operands, one kernel per chip: the
    fallback for projections whose extents the mesh does not divide
    (the contraction stays unsharded, so numerics match tp=1)."""
    return shard_map(functools.partial(_dot, out_dtype=out_dtype),
                     mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                     check_vma=False)(x, w)


def _ctx(ctx):
    """Unpack a row-parallel (mesh,) or (mesh, chunks) context tuple."""
    mesh = ctx[0]
    chunks = int(ctx[1]) if len(ctx) > 1 else 1
    return mesh, max(chunks, 1)


def _n_chunks(n_cols: int, chunks: int) -> int:
    """Largest chunk count <= `chunks` that divides the column extent."""
    c = max(min(chunks, n_cols), 1)
    while n_cols % c:
        c -= 1
    return c


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def column_parallel(x: jax.Array, w: jax.Array, ctx: tuple) -> jax.Array:
    """x: (..., d) replicated over model; w: (d, F) col-sharded on F.
    Returns y: (..., F) col-sharded. `ctx` is (mesh,) or (mesh,
    out_dtype) — the LM head keeps fp32 logits."""
    return _col_fwd(x, w, ctx)[0]


def _col_fwd(x, w, ctx):
    mesh = ctx[0]
    dp = _dp(mesh)

    def local(xl, wl):
        return _dot(xl, wl, ctx[1] if len(ctx) > 1 else None)

    y = shard_map(local, mesh=mesh,
                  in_specs=(P(dp), P(None, "model")),
                  out_specs=P(dp, *([None] * (x.ndim - 2)), "model"),
                  check_vma=False)(x, w)
    return y, (x, w)


def _col_bwd(ctx, res, g):
    mesh = ctx[0]
    x, w = res
    dp = _dp(mesh)
    dp_names = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    lead = x.ndim - 1
    g = g.astype(x.dtype)

    def local(gl, wl, xl):
        # dx: partial over the model axis; cast BEFORE the psum -> bf16 wire
        dxl = jax.lax.dot_general(
            gl, wl, (((gl.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        dx = jax.lax.psum(dxl, "model")
        gf = gl.reshape(-1, gl.shape[-1])
        xf = xl.reshape(-1, xl.shape[-1])
        dwl = jax.lax.dot_general(
            xf, gf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(w.dtype)
        # data-axis gradient reduction (bf16 wire), explicit under shard_map
        for ax in dp_names:
            dwl = jax.lax.psum(dwl, ax)
        return dx, dwl

    dx, dw = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, *([None] * (lead - 1)), "model"),
                  P(None, "model"), P(dp)),
        out_specs=(P(dp), P(None, "model")),
        check_vma=False)(g, w, x)
    return dx, dw


column_parallel.defvjp(_col_fwd, _col_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def row_parallel(x: jax.Array, w: jax.Array, ctx: tuple) -> jax.Array:
    """x: (..., F) col-sharded on F over model; w: (F, d) row-sharded.
    Returns y: (..., d) replicated over model (psum in bf16)."""
    return _row_fwd(x, w, ctx)[0]


def _row_fwd(x, w, ctx):
    mesh, chunks = _ctx(ctx)
    dp = _dp(mesh)
    c = _n_chunks(w.shape[-1], chunks)

    def local(xl, wl):
        if c == 1:
            yl = _dot(xl, wl)   # cast before the wire
            return jax.lax.psum(yl, "model")
        # interleaved chunks: psum(chunk i) rides the wire while the MXU
        # computes chunk i+1 (disjoint columns -> identical numerics)
        width = wl.shape[-1] // c
        outs = [jax.lax.psum(
            _dot(xl, jax.lax.slice_in_dim(wl, i * width, (i + 1) * width,
                                          axis=1)),
            "model") for i in range(c)]
        return jnp.concatenate(outs, axis=-1)

    y = shard_map(local, mesh=mesh,
                  in_specs=(P(dp, *([None] * (x.ndim - 2)), "model"),
                            P("model", None)),
                  out_specs=P(dp),
                  check_vma=False)(x, w)
    return y, (x, w)


def _row_bwd(ctx, res, g):
    mesh, _ = _ctx(ctx)
    x, w = res
    dp = _dp(mesh)
    dp_names = dp if isinstance(dp, tuple) else ((dp,) if dp else ())

    def local(gl, wl, xl):
        # g is replicated over model: dx needs NO collective
        dxl = jax.lax.dot_general(
            gl, wl, (((gl.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        gf = gl.reshape(-1, gl.shape[-1])
        xf = xl.reshape(-1, xl.shape[-1])
        dwl = jax.lax.dot_general(
            xf, gf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(w.dtype)
        for ax in dp_names:
            dwl = jax.lax.psum(dwl, ax)
        return dxl, dwl

    dx, dw = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp), P("model", None),
                  P(dp, *([None] * (x.ndim - 2)), "model")),
        out_specs=(P(dp, *([None] * (x.ndim - 2)), "model"),
                   P("model", None)),
        check_vma=False)(g, w, x)
    return dx, dw


row_parallel.defvjp(_row_fwd, _row_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def row_parallel_gather(x: jax.Array, w: jax.Array, ctx: tuple) -> jax.Array:
    """x: (..., F) col-sharded on F over model; w: (F, d) COLUMN-sharded.
    Returns y: (..., d) replicated, bit-identical to the unsharded matmul:
    x is all-gathered once, then every shard computes its d/tp output
    columns with the full-F contraction — no cross-shard reduction ever
    re-associates the fp32 accumulation. Output chunks are all-gathered
    interleaved with the next chunk's GEMM (double-buffered)."""
    return _row_gather_fwd(x, w, ctx)[0]


def _row_gather_fwd(x, w, ctx):
    mesh, chunks = _ctx(ctx)
    dp = _dp(mesh)
    tp = mesh.shape["model"]

    def local(xl, wl):
        xf = jax.lax.all_gather(xl, "model", axis=xl.ndim - 1, tiled=True)
        n_loc = wl.shape[-1]
        c = _n_chunks(n_loc, chunks)
        width = n_loc // c
        outs = []
        for i in range(c):
            yl = _dot(xf, jax.lax.slice_in_dim(
                wl, i * width, (i + 1) * width, axis=1))
            # gather of chunk i overlaps chunk i+1's GEMM in the schedule
            outs.append(jax.lax.all_gather(yl, "model", axis=yl.ndim - 1,
                                           tiled=True))
        if c == 1:
            return outs[0]
        # gathered chunk i holds columns [shard j, chunk i] interleaved;
        # restore the global shard-major column order (pure layout ops)
        g = jnp.stack(outs, axis=-2)             # (..., c, tp*width)
        lead = g.shape[:-2]
        g = g.reshape(*lead, c, tp, width)
        g = jnp.swapaxes(g, -3, -2)
        return g.reshape(*lead, tp * n_loc)

    y = shard_map(local, mesh=mesh,
                  in_specs=(P(dp, *([None] * (x.ndim - 2)), "model"),
                            P(None, "model")),
                  out_specs=P(dp),
                  check_vma=False)(x, w)
    return y, (x, w)


def _row_gather_bwd(ctx, res, g):
    mesh, _ = _ctx(ctx)
    x, w = res
    dp = _dp(mesh)
    dp_names = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    f_loc = x.shape[-1] // mesh.shape["model"]

    def local(gl, wl, xl):
        # my slice of the (replicated) cotangent columns
        j = jax.lax.axis_index("model")
        n_loc = wl.shape[-1]
        g_my = jax.lax.dynamic_slice_in_dim(gl, j * n_loc, n_loc,
                                            axis=gl.ndim - 1)
        # dx = g @ w.T: partial over my output columns, psum, slice my F rows
        dxf = jax.lax.dot_general(
            g_my, wl, (((g_my.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        dxf = jax.lax.psum(dxf, "model")
        dxl = jax.lax.dynamic_slice_in_dim(dxf, j * f_loc, f_loc,
                                           axis=dxf.ndim - 1)
        xf = jax.lax.all_gather(xl, "model", axis=xl.ndim - 1, tiled=True)
        xflat = xf.reshape(-1, xf.shape[-1])
        gflat = g_my.reshape(-1, g_my.shape[-1])
        dwl = jax.lax.dot_general(
            xflat, gflat, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(w.dtype)
        for ax in dp_names:
            dwl = jax.lax.psum(dwl, ax)
        return dxl, dwl

    dx, dw = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp), P(None, "model"),
                  P(dp, *([None] * (x.ndim - 2)), "model")),
        out_specs=(P(dp, *([None] * (x.ndim - 2)), "model"),
                   P(None, "model")),
        check_vma=False)(g, w, x)
    return dx, dw


row_parallel_gather.defvjp(_row_gather_fwd, _row_gather_bwd)


def tp_enabled(cfg) -> bool:
    mesh = current_mesh()
    return (getattr(cfg, "tp_collectives", "auto") == "explicit"
            and mesh is not None and "model" in mesh.axis_names)


def replicate(x: jax.Array) -> jax.Array:
    """Force `x` fully replicated under the active mesh (no-op without one).

    The parity escape hatch: a plain dot whose *contracting* dim is sharded
    lets GSPMD pick a split-k partial-sum strategy, re-associating the fp32
    accumulation. Re-replicating first costs one all-gather and keeps the
    contraction bit-identical to the unsharded path.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def replicate_for_parity(x: jax.Array, cfg) -> jax.Array:
    """`replicate(x)` only in bit-stable gather mode — activations headed
    into a plain contraction or axis reduction (x_proj, gated-norm mean)
    must not carry a sharded axis there, or GSPMD may re-associate the
    fp32 sum. psum-mode training keeps its sharding (perf over bits)."""
    if tp_enabled(cfg) and getattr(cfg, "tp_reduce", "psum") == "gather":
        return replicate(x)
    return x


def tp_column(x, w, cfg, out_dtype=None):
    """x @ w, column-parallel under explicit TP (output cast to
    `out_dtype`, default x's dtype). `w` may be an `ops.WeightRef`: read
    in place without TP, sliced out of its stack before the shard_map
    with it."""
    from repro.kernels import ops

    if not tp_enabled(cfg):
        return ops.matmul(x, w, out_dtype=out_dtype)
    w = ops.resolve(w)
    mesh = current_mesh()
    if w.shape[-1] % mesh.shape["model"] == 0:
        ctx = (mesh,) if out_dtype is None else (mesh, jnp.dtype(out_dtype))
        return column_parallel(x, w, ctx)
    return _replicated_dot(x, w, mesh, out_dtype)


def tp_row(x, w, cfg):
    """x @ w, row-parallel under explicit TP; `w` as in `tp_column`."""
    from repro.kernels import ops

    if tp_enabled(cfg):
        w = ops.resolve(w)
        mesh = current_mesh()
        tp = mesh.shape["model"]
        chunks = max(int(getattr(cfg, "tp_overlap_chunks", 1)), 1)
        if (getattr(cfg, "tp_reduce", "psum") == "gather"
                and w.shape[-1] % tp == 0 and x.shape[-1] % tp == 0):
            return row_parallel_gather(x, w, (mesh, chunks))
        if getattr(cfg, "tp_reduce", "psum") != "gather" \
                and w.shape[0] % tp == 0:
            return row_parallel(x, w, (mesh, chunks))
        return _replicated_dot(x, w, mesh)
    return ops.matmul(x, w)
