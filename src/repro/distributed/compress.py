"""Gradient compression for cross-pod data parallelism.

At 1000+ nodes the scarce resource is the inter-pod (DCN/ICI-bridge) link,
not in-pod ICI. Two mechanisms:

  * bf16 gradient all-reduce (default): params are bf16, so backward
    cotangents — and therefore the SPMD-inserted all-reduce — are bf16,
    halving DP collective bytes vs fp32. Visible directly in the dry-run HLO.
  * int8 error-feedback all-reduce (`compressed_allreduce`): explicit
    shard_map collective for the pod axis. Per-tensor max-abs scale,
    stochastic rounding, residual carried by the caller (error feedback
    keeps the compression unbiased over steps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _quantize(x: jax.Array, key: jax.Array, bits: int = 8
              ) -> tuple[jax.Array, jax.Array]:
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x)) / qmax + 1e-12
    scaled = x / scale
    noise = jax.random.uniform(key, x.shape, x.dtype, -0.5, 0.5)
    q = jnp.clip(jnp.round(scaled + noise), -qmax, qmax).astype(jnp.int8)
    return q, scale


def compressed_psum(x: jax.Array, key: jax.Array, axis_name: str,
                    residual: jax.Array | None = None, bits: int = 8
                    ) -> tuple[jax.Array, jax.Array]:
    """Inside shard_map: int8-quantized psum over `axis_name` with error
    feedback. Returns (mean_gradient, new_residual)."""
    if residual is not None:
        x = x + residual
    q, scale = _quantize(x.astype(jnp.float32), key, bits)
    # int8 wire format; accumulate in int32 (worlds <= 2^23 summands safe)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    scale_sum = jax.lax.psum(scale, axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    # each shard contributed ~q*scale; approximate with mean scale
    mean = total.astype(jnp.float32) * (scale_sum / n) / n
    new_residual = x - (q.astype(jnp.float32) * scale)
    return mean, new_residual


def compressed_allreduce_tree(grads, key: jax.Array, mesh, axis: str = "pod",
                              residuals=None, bits: int = 8):
    """Apply compressed_psum leaf-wise over `axis` via shard_map. Grads must
    already be reduced over other axes. Residual tree is threaded through."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if axis not in mesh.axis_names:
        return grads, residuals
    leaves, treedef = jax.tree.flatten(grads)
    res_leaves = (jax.tree.leaves(residuals) if residuals is not None
                  else [jnp.zeros_like(l, jnp.float32) for l in leaves])
    keys = jax.random.split(key, len(leaves))

    outs = []
    for leaf, res, k in zip(leaves, res_leaves, keys):
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        def _one(x, r, kk):
            return compressed_psum(x, kk, axis, residual=r, bits=bits)

        outs.append(_one(leaf, res, k))
    new_grads = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_res = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return new_grads, new_res


def wire_bytes_saved(grads, bits: int = 8, from_bits: int = 16) -> float:
    total = sum(x.size for x in jax.tree.leaves(grads))
    return total * (from_bits - bits) / 8.0
