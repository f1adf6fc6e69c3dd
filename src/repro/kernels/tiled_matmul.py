"""Pallas TPU tiled GEMM — the paper's custom kernel, rebuilt TPU-native.

The CUDA original stages (tile x tile) squares of A and B through shared
memory with one thread per output element. The TPU version stages
(block_m x block_k) / (block_k x block_n) slabs through VMEM with an fp32
accumulator held in VMEM scratch across the contraction grid dimension, and
feeds the MXU via `lax.dot_general`:

  grid = (M/bm, N/bn, K/bk); k is the innermost ("arbitrary") dimension so
  the accumulator tile lives across k-steps and is flushed once at k == last.

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
import math

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


def _matmul_kernel(a_ref, b_ref, *refs, alpha: float, beta: float,
                   n_k_steps: int, transpose_a: bool, transpose_b: bool):
    """One (i, j, k) grid step: acc += A_blk @ B_blk, flush at last k.
    `refs` is (c_in, out, acc) when beta != 0, else (out, acc)."""
    c_in_ref, c_ref, acc_ref = refs if beta != 0.0 else (None, *refs)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if transpose_a:
        a = a.T  # block was loaded as (bk, bm)
    if transpose_b:
        b = b.T  # block was loaded as (bn, bk)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        out = alpha * acc_ref[...]
        if beta != 0.0:
            out = out + beta * c_in_ref[...].astype(jnp.float32)
        c_ref[...] = out.astype(c_ref.dtype)


def _pad_to(x: jax.Array, multiples: tuple[int, ...]) -> jax.Array:
    pads = []
    needs = False
    for dim, mult in zip(x.shape, multiples):
        target = math.ceil(dim / mult) * mult
        pads.append((0, target - dim))
        needs = needs or target != dim
    return jnp.pad(x, pads) if needs else x


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
    config: BlockConfig = DEFAULT_CONFIG,
    transpose_a: bool = False,
    transpose_b: bool = False,
    alpha: float = 1.0,
    beta: float = 0.0,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = alpha * op(A) @ op(B) + beta * C  (paper's GEMM surface).

    a: (M, K) or (K, M) if transpose_a; b: (K, N) or (N, K) if transpose_b.
    Shapes need not divide the block config; inputs are zero-padded and the
    output is sliced back (TPU-style explicit padding).
    """
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("tiled_matmul expects rank-2 operands")
    m = a.shape[1] if transpose_a else a.shape[0]
    ka = a.shape[0] if transpose_a else a.shape[1]
    kb = b.shape[1] if transpose_b else b.shape[0]
    n = b.shape[0] if transpose_b else b.shape[1]
    if ka != kb:
        raise ValueError(f"contraction mismatch: {ka} vs {kb}")
    k = ka
    if beta != 0.0 and c is None:
        raise ValueError("beta != 0 requires c")
    out_dtype = out_dtype or a.dtype

    bm, bn, bk = config.block_m, config.block_n, config.block_k
    # clamp blocks to (padded) problem so tiny problems stay single-block
    bm = min(bm, math.ceil(m / 8) * 8)
    if transpose_a:
        # a transposed A block is (bk, bm): bm is its lane dimension
        bm = math.ceil(bm / 128) * 128
    bn = min(bn, math.ceil(n / 128) * 128)
    bk = min(bk, math.ceil(k / 128) * 128)

    a = _pad_to(a, (bk, bm) if transpose_a else (bm, bk))
    b = _pad_to(b, (bn, bk) if transpose_b else (bk, bn))
    mp = a.shape[1] if transpose_a else a.shape[0]
    kp = a.shape[0] if transpose_a else a.shape[1]
    np_ = b.shape[0] if transpose_b else b.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    if transpose_a:
        a_spec = pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i))
    else:
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    if transpose_b:
        b_spec = pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk))
    else:
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    c_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    in_specs, operands = [a_spec, b_spec], [a, b]
    if beta != 0.0:
        in_specs.append(c_spec)
        operands.append(_pad_to(c.astype(out_dtype), (bm, bn)))
    vmem = BlockConfig(bm, bn, bk).vmem_bytes(
        in_bytes=jnp.dtype(a.dtype).itemsize,
        out_bytes=jnp.dtype(out_dtype).itemsize, with_c=beta != 0.0)

    kernel = functools.partial(
        _matmul_kernel,
        alpha=alpha,
        beta=beta,
        n_k_steps=grid[2],
        transpose_a=transpose_a,
        transpose_b=transpose_b,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=c_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + VMEM_HEADROOM_BYTES,
        ),
        interpret=interpret,
        name=f"tiled_matmul_{bm}x{bn}x{bk}",
    )(*operands)
    return out[:m, :n]
