"""Lowering registry: any fitted estimator -> one jit-compiled scorer.

Every estimator family in the mlperf zoo registers a *lowering* here — a
function that exports the fitted model to flat numpy arrays (the same
global-id layout the state contract uses) plus a pure jax `apply(params, X)`
that reproduces the numpy `predict` inside `jax.jit`:

  * tree / forest / GBDT — one stacked level-synchronous descent over the
    concatenated ensemble (leaves self-loop, `max_depth` gather steps),
    combined per family: mean over trees (forest), ``base + lr * sum``
    (GBDT, weighted-sum flat descent).
  * linreg / ridge — a single affine map. Accumulation runs feature-by-
    feature (`lax.fori_loop`), mirroring `linreg.ordered_affine`, because
    BLAS/XLA matmuls don't guarantee a summation order and the x64 contract
    below is *bit*-exactness.
  * stacking — every base model's descent runs in the same graph, the
    meta-ridge combine is one fixed-order affine over the stacked
    predictions.

Two precisions, same contract as the forest predictor always had:

  * ``float64=False`` — float32 arrays for embedding in fp32 jitted
    programs; thresholds are nudged one fp32 ulp (see
    `tree.cast_flat_ensemble`) so fp64-trained splits survive rounding.
  * ``float64=True`` — arrays stay float64 (build and call under a scoped
    ``jax.enable_x64``); every gather, comparison, and
    accumulation happens in the same order as the numpy reference, so the
    compiled scorer is bit-identical to `est.predict`.

`lower_estimator` dispatches on the estimator class through the registry;
`JaxEstimator` (jaxpredict.py) wraps the result in a ready-to-call object.
"""

from __future__ import annotations

import contextlib
from typing import Callable, NamedTuple

import numpy as np


class Lowered(NamedTuple):
    """Flat-array params + a pure `apply(params, X) -> (N, K)` jax fn."""

    params: dict
    apply: Callable
    n_targets: int


_LOWERINGS: dict[str, Callable] = {}


def register_lowering(cls_name: str):
    """Decorator: register `fn(est, float64) -> Lowered` for a class name."""

    def deco(fn):
        _LOWERINGS[cls_name] = fn
        return fn

    return deco


def compilable_families() -> list[str]:
    """Estimator class names that can serve through the compiled scorer."""
    return sorted(_LOWERINGS)


def supports_compile(est) -> bool:
    return type(est).__name__ in _LOWERINGS


def lower_estimator(est, *, float64: bool = False) -> Lowered:
    """Export any registered fitted estimator for jit-compiled prediction."""
    name = type(est).__name__
    try:
        fn = _LOWERINGS[name]
    except KeyError:
        raise TypeError(
            f"no compiled lowering for estimator {name!r}; "
            f"known: {compilable_families()}"
        ) from None
    return fn(est, float64)


def precision_scope(x64: bool):
    """Scoped x64 so float64 arrays survive asarray/tracing; the default
    fp32 path is a no-op context."""
    if x64:
        import jax

        return jax.enable_x64(True)
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# shared jax building blocks (imported lazily inside apply fns is not needed:
# this module is only imported from jax-aware call sites)
# ---------------------------------------------------------------------------


def _descend(p: dict, X, *, max_depth: int, n_trees: int):
    """Stacked flat-array descent: leaf values for every (tree, sample)
    pair, shape (T, N, K). All cursors advance together, one gather per
    node array per level; leaves self-loop so a fixed `max_depth` step
    count lands every cursor on its leaf (mirror of
    `tree.predict_stacked`)."""
    import jax
    import jax.numpy as jnp

    N, F = X.shape
    Xr = X.reshape(-1)
    roots = p["roots"]
    node = jnp.repeat(roots, N)                          # (T*N,)
    row = jnp.tile(jnp.arange(N, dtype=roots.dtype) * F, n_trees)
    feature, threshold = p["feature"], p["threshold"]
    left, right = p["left"], p["right"]

    def step(_, node):
        x = Xr[row + feature[node]]
        return jnp.where(x <= threshold[node], left[node], right[node])

    node = jax.lax.fori_loop(0, max_depth, step, node)
    return p["value"][node].reshape(n_trees, N, -1)      # (T, N, K)


def _sum_trees(leaves):
    """Sequential sum over the tree axis. numpy's `leaves.sum(axis=0)`
    accumulates slice-by-slice in order; an XLA `reduce` may reassociate,
    so the x64 bit-exact contract needs this explicit fori accumulation.
    The while-loop body is a separate XLA computation, so the adds can't
    be FMA-contracted with whatever produced `leaves`."""
    import jax
    import jax.numpy as jnp

    T = leaves.shape[0]
    return jax.lax.fori_loop(
        0, T, lambda i, acc: acc + leaves[i], jnp.zeros_like(leaves[0]))


def _ordered_affine(X, coef, intercept):
    """X @ coef + intercept — the jax mirror of `linreg.ordered_affine`.

    The product tensor is materialized *before* the accumulation loop:
    LLVM contracts a `mul` feeding an `add` in the same fused loop into an
    FMA (different rounding than numpy, and no XLA flag disables it), but
    a while-loop body is a separate computation, so products land in
    memory first and the loop runs pure adds — same ops, same order, same
    bits as the numpy reference. coef: (F, K); intercept: (K,)."""
    import jax
    import jax.numpy as jnp

    F = coef.shape[0]
    P = X[:, :, None] * coef[None, :, :]                 # (N, F, K)
    acc0 = jnp.zeros((X.shape[0], coef.shape[1]), dtype=X.dtype)

    def step(f, acc):
        return acc + P[:, f, :]

    return jax.lax.fori_loop(0, F, step, acc0) + intercept[None, :]


# ---------------------------------------------------------------------------
# per-family lowerings
# ---------------------------------------------------------------------------


def _tree_params(flat: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    return {k: flat[k] for k in
            ("feature", "threshold", "left", "right", "value", "roots")}


@register_lowering("RandomForestRegressor")
def _lower_forest(est, float64: bool) -> Lowered:
    flat = est.to_flat_arrays(float64=float64)
    max_depth = int(flat["max_depth"])
    n_trees = len(flat["roots"])
    params = _tree_params(flat)
    # divisor as a *traced* param: a literal constant would let XLA rewrite
    # the division into a reciprocal multiply (last-ulp drift vs numpy).
    params["count"] = np.asarray(float(n_trees),
                                 dtype=flat["value"].dtype)

    def apply(p, X):
        leaves = _descend(p, X, max_depth=max_depth, n_trees=n_trees)
        return _sum_trees(leaves) / p["count"]

    return Lowered(params, apply, int(est.n_targets_))


@register_lowering("GradientBoostedTreesRegressor")
def _lower_gbdt(est, float64: bool) -> Lowered:
    flat = est.to_flat_arrays(float64=float64)
    max_depth = int(flat["max_depth"])
    n_trees = len(flat["roots"])
    # Pre-scale leaf values by the learning rate HERE, in numpy: the numpy
    # `predict` multiplies leaves elementwise by lr before summing, so
    # gathering pre-scaled values gives bit-identical addends while keeping
    # the jitted combine add-only (no mul feeding an add => no FMA drift).
    value = flat["value"]
    params = {**_tree_params(flat),
              "value": value.dtype.type(est.learning_rate) * value,
              "base": flat["base"]}

    def apply(p, X):
        import jax.numpy as jnp

        base = jnp.broadcast_to(p["base"][None, :],
                                (X.shape[0], p["base"].shape[0]))
        if n_trees == 0:
            return base
        leaves = _descend(p, X, max_depth=max_depth, n_trees=n_trees)
        return base + _sum_trees(leaves)

    return Lowered(params, apply, int(est.n_targets_))


@register_lowering("DecisionTreeRegressor")
def _lower_tree(est, float64: bool) -> Lowered:
    from repro.core.mlperf.tree import cast_flat_ensemble, flatten_ensemble

    flat = cast_flat_ensemble(flatten_ensemble([est.tree_]), float64=float64)
    max_depth = int(est.max_depth)

    def apply(p, X):
        leaves = _descend(p, X, max_depth=max_depth, n_trees=1)
        return leaves[0]

    return Lowered(_tree_params(flat), apply, int(est.n_targets_))


def _affine_params(coef, intercept, float64: bool) -> dict[str, np.ndarray]:
    coef = np.asarray(coef, dtype=np.float64)
    if coef.ndim == 1:
        coef = coef[:, None]
    intercept = np.atleast_1d(np.asarray(intercept, dtype=np.float64))
    intercept = np.broadcast_to(intercept, (coef.shape[1],)).copy()
    if not float64:
        coef = coef.astype(np.float32)
        intercept = intercept.astype(np.float32)
    return {"coef": coef, "intercept": intercept}


@register_lowering("LinearRegression")
def _lower_linear(est, float64: bool) -> Lowered:
    params = _affine_params(est.coef_, est.intercept_, float64)

    def apply(p, X):
        return _ordered_affine(X, p["coef"], p["intercept"])

    return Lowered(params, apply, params["coef"].shape[1])


# Ridge shares LinearRegression's prediction surface exactly.
register_lowering("Ridge")(_lower_linear)


@register_lowering("StackingRegressor")
def _lower_stacking(est, float64: bool) -> Lowered:
    lowered = [lower_estimator(b, float64=float64)
               for b in est.fitted_bases_]
    base_applies = [low.apply for low in lowered]
    # meta ridges are per-target with 1-d coefs over Z; stack to (Z, T) so
    # one fori over Z-columns reproduces every per-target ordered dot.
    meta_coef = np.stack(
        [np.asarray(m.coef_, dtype=np.float64) for m in est.meta_],
        axis=1)                                           # (Z, T)
    meta_intercept = np.array(
        [float(np.ravel(m.intercept_)[0]) for m in est.meta_],
        dtype=np.float64)
    if not float64:
        meta_coef = meta_coef.astype(np.float32)
        meta_intercept = meta_intercept.astype(np.float32)
    params = {"bases": [low.params for low in lowered],
              "meta_coef": meta_coef, "meta_intercept": meta_intercept}
    passthrough = bool(est.passthrough)

    def apply(p, X):
        import jax.numpy as jnp

        preds = [ap(bp, X).reshape(X.shape[0], -1)
                 for ap, bp in zip(base_applies, p["bases"])]
        Z = jnp.concatenate(preds + ([X] if passthrough else []), axis=1)
        return _ordered_affine(Z, p["meta_coef"], p["meta_intercept"])

    return Lowered(params, apply, int(est.n_targets_))
