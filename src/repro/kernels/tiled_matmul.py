"""Pallas TPU tiled GEMM — the paper's custom kernel, rebuilt TPU-native.

The CUDA original stages (tile x tile) squares of A and B through shared
memory with one thread per output element. The TPU version stages
(block_m x block_k) / (block_k x block_n) slabs through VMEM with an fp32
accumulator held in VMEM scratch across the contraction grid dimension, and
feeds the MXU via `lax.dot_general`:

  grid = (cdiv(M, bm), cdiv(N, bn), cdiv(K, bk)); k is the innermost
  ("arbitrary") dimension so the accumulator tile lives across k-steps and
  is flushed once at k == last.

Every weight is read where it lives in HBM; none is copied before the
call:

  * Ragged edges are handled in the kernel, never by padding a weight
    (only A's rows, an activation's few, go up to the tiling). A partial
    M or N block reads values past the edge that only reach output rows
    or columns Pallas does not write back. On the last K block the
    contraction is cut to the positions that exist (a static slice of
    both blocks, and an iota mask where the edge is not lane-aligned),
    so what lies past the edge never enters the sum.
  * A weight stacked over layers, (L, K, N), is read in place at a layer
    index given as a scalar-prefetch operand: the B block index map picks
    the layer, so no slice of the stack is staged ahead of the kernel.

Supports alpha/beta scaling (the paper's CUTLASS sweep axis), all four
nn/nt/tn/tt layouts (transposes happen on the VMEM-resident block, feeding
the MXU directly), bf16/f32 inputs with fp32 accumulation. The C operand
is streamed only when beta != 0.

TARGET is TPU (compiled path); correctness is validated on CPU with
`interpret=True` against `ref.matmul_ref` in tests, and every block shape
of the tuner's candidate grid is compiled for a described v5e in
`tests/test_tpu_compile.py`. The kernel asks Mosaic for exactly the VMEM
its blocks need (`BlockConfig.vmem_bytes`): the default scoped limit is
smaller than the tuner's largest admitted blocks.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's own scratch on top of `BlockConfig.vmem_bytes`: compiling for
# v5e, 2 MiB is too little for a 1024x512x2048 bf16 block and 4 MiB fits
# every candidate of the tuner's grid; 8 MiB keeps a margin
VMEM_HEADROOM_BYTES = 8 * 2**20


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """VMEM tiling for one GEMM call — the TPU analogue of 'tile size'."""

    block_m: int = 256
    block_n: int = 256
    block_k: int = 512

    def vmem_bytes(self, in_bytes: int = 2, out_bytes: int = 2,
                   with_c: bool = False) -> int:
        """VMEM the pipelined kernel holds: double-buffered A and B
        blocks, the double-buffered output block (and C input block when
        beta != 0), the fp32 accumulator and the fp32 dot result that is
        added into it."""
        bm, bn, bk = self.block_m, self.block_n, self.block_k
        out_blocks = 2 if with_c else 1
        return (2 * (bm * bk + bk * bn) * in_bytes
                + 2 * out_blocks * bm * bn * out_bytes + 2 * bm * bn * 4)

    def as_tuple(self) -> tuple[int, int, int]:
        return (self.block_m, self.block_n, self.block_k)


DEFAULT_CONFIG = BlockConfig()


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _accumulate(a_ref, b_ref, acc_ref, *, valid: int, transpose_a: bool,
                transpose_b: bool):
    """acc += A_blk @ B_blk over the blocks' first `valid` contraction
    positions: all of them, except on a last K block that runs past K.
    The blocks are cut to the lane-aligned span that holds those
    positions, and where `valid` ends inside that span, the positions
    past it are zeroed in both operands, so whatever lies past the edge
    (unspecified values) never meets a zero in the product."""
    bk = b_ref.shape[1] if transpose_b else b_ref.shape[0]
    span = min(_round_up(valid, 128), bk)
    a = a_ref[:span, :] if transpose_a else a_ref[:, :span]
    b = b_ref[:, :span] if transpose_b else b_ref[:span, :]
    if span != valid:
        ka, kb = (0 if transpose_a else 1), (1 if transpose_b else 0)
        a = jnp.where(jax.lax.broadcasted_iota(jnp.int32, a.shape, ka)
                      < valid, a, jnp.zeros_like(a))
        b = jnp.where(jax.lax.broadcasted_iota(jnp.int32, b.shape, kb)
                      < valid, b, jnp.zeros_like(b))
    if transpose_a:
        a = a.T  # block was loaded as (bk, bm)
    if transpose_b:
        b = b.T  # block was loaded as (bn, bk)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _matmul_kernel(*refs, n_prefetch: int, alpha: float, beta: float,
                   n_k_steps: int, k_last: int, transpose_a: bool,
                   transpose_b: bool):
    """One (i, j, k) grid step: acc += A_blk @ B_blk, flush at last k.
    `refs` is (layer index if stacked, a, b, c_in if beta != 0, out,
    acc); the layer index is read by the B index map only. `k_last` is
    the number of contraction positions the last K block holds."""
    a_ref, b_ref, *refs = refs[n_prefetch:]
    c_in_ref, c_ref, acc_ref = refs if beta != 0.0 else (None, *refs)
    kk = pl.program_id(2)
    bk = b_ref.shape[1] if transpose_b else b_ref.shape[0]
    acc = functools.partial(_accumulate, a_ref, b_ref, acc_ref,
                            transpose_a=transpose_a, transpose_b=transpose_b)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if k_last == bk:
        acc(valid=bk)
    else:
        pl.when(kk < n_k_steps - 1)(lambda: acc(valid=bk))
        pl.when(kk == n_k_steps - 1)(lambda: acc(valid=k_last))

    @pl.when(kk == n_k_steps - 1)
    def _flush():
        out = alpha * acc_ref[...]
        if beta != 0.0:
            out = out + beta * c_in_ref[...].astype(jnp.float32)
        c_ref[...] = out.astype(c_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("config", "transpose_a", "transpose_b", "alpha", "beta",
                     "out_dtype", "interpret"),
)
def tiled_matmul(
    a: jax.Array,
    b: jax.Array,
    c: jax.Array | None = None,
    *,
    layer: jax.Array | None = None,
    config: BlockConfig = DEFAULT_CONFIG,
    transpose_a: bool = False,
    transpose_b: bool = False,
    alpha: float = 1.0,
    beta: float = 0.0,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = alpha * op(A) @ op(B) + beta * C  (paper's GEMM surface).

    a: (M, K) or (K, M) if transpose_a; b: (K, N) or (N, K) if
    transpose_b. With `layer` (an integer scalar, traced or not), b is a
    stack (L, K, N) or (L, N, K) and the kernel reads b[layer] in place.
    Shapes need not divide the block config: the grid covers each
    dimension with partial last blocks, and no weight is padded (A's
    rows only, up to the tiling).
    """
    stacked = layer is not None
    b_mat = b.shape[1:] if stacked else b.shape
    if a.ndim != 2 or len(b_mat) != 2 or b.ndim != 2 + stacked:
        raise ValueError("tiled_matmul expects rank-2 operands "
                         "(a rank-3 stack for b with `layer`)")
    m = a.shape[1] if transpose_a else a.shape[0]
    ka = a.shape[0] if transpose_a else a.shape[1]
    kb = b_mat[1] if transpose_b else b_mat[0]
    n = b_mat[0] if transpose_b else b_mat[1]
    if ka != kb:
        raise ValueError(f"contraction mismatch: {ka} vs {kb}")
    k = ka
    if beta != 0.0 and c is None:
        raise ValueError("beta != 0 requires c")
    out_dtype = out_dtype or a.dtype

    # A's rows (an activation's few rows) go up to the tiling: 8
    # sublanes, or 128 lanes for a transposed A block (bk, bm). The
    # output then has at least 8 rows, and is cut back to M.
    mp = _round_up(m, 128 if transpose_a else 8)
    if mp != m:
        a = jnp.pad(a, ((0, 0), (0, mp - m)) if transpose_a
                    else ((0, mp - m), (0, 0)))
    # clamp blocks to the (aligned) problem so tiny problems stay
    # single-block
    bm = min(config.block_m, mp)
    if transpose_a:
        bm = _round_up(bm, 128)
    bn = min(config.block_n, _round_up(n, 128))
    bk = min(config.block_k, _round_up(k, 128))
    grid = (pl.cdiv(mp, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))

    # index maps take (i, j, kk) and, for a stack, the prefetched layer
    if transpose_a:
        a_spec = pl.BlockSpec((bk, bm), lambda i, j, kk, *_: (kk, i))
    else:
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk, *_: (i, kk))
    b_block = (bn, bk) if transpose_b else (bk, bn)

    def b_index(j, kk):
        return (j, kk) if transpose_b else (kk, j)

    if stacked:
        b_spec = pl.BlockSpec((None, *b_block),
                              lambda i, j, kk, l: (l[0], *b_index(j, kk)))
        prefetch = [jnp.reshape(layer, (1,)).astype(jnp.int32)]
    else:
        b_spec = pl.BlockSpec(b_block, lambda i, j, kk: b_index(j, kk))
        prefetch = []
    c_spec = pl.BlockSpec((bm, bn), lambda i, j, kk, *_: (i, j))
    in_specs, operands = [a_spec, b_spec], [a, b]
    if beta != 0.0:
        in_specs.append(c_spec)
        operands.append(jnp.pad(c.astype(out_dtype), ((0, mp - m), (0, 0))))
    vmem = BlockConfig(bm, bn, bk).vmem_bytes(
        in_bytes=jnp.dtype(a.dtype).itemsize,
        out_bytes=jnp.dtype(out_dtype).itemsize, with_c=beta != 0.0)

    kernel = functools.partial(
        _matmul_kernel,
        n_prefetch=len(prefetch),
        alpha=alpha,
        beta=beta,
        n_k_steps=grid[2],
        k_last=k - (grid[2] - 1) * bk,
        transpose_a=transpose_a,
        transpose_b=transpose_b,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=c_spec,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + VMEM_HEADROOM_BYTES,
        ),
        interpret=interpret,
        name=f"tiled_matmul_{bm}x{bn}x{bk}",
    )(*prefetch, *operands)
    return out[:m] if mp != m else out
