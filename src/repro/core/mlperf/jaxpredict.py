"""Jit-compiled estimator inference for the whole mlperf zoo.

The sklearn-style originals can only predict in Python. `JaxEstimator`
wraps any fitted estimator that has a registered lowering (see
`compiled.py`): the model is exported to flat arrays (tree ensembles in the
global-id layout — concatenated node arrays, children rebased to global
ids, leaves self-looping — linear models as coefficient matrices, stacking
as the composition of its bases) and evaluated as ONE jitted computation.
That keeps the entire model in a single XLA program, so the performance
predictor can run *inside* jitted code — e.g. ranking thousands of
candidate GEMM block configs in one call during autotuning, or fully
in-graph via `GemmAutotuner.rank_in_graph`.

Two precisions:

  * default (float32) — for embedding inside fp32 jitted programs.
    Tree thresholds are nudged one ulp so most fp64-trained splits survive
    fp32 rounding, but near-threshold samples can still flip branches.
  * ``x64=True`` — arrays stay float64 (built and called under a scoped
    ``jax.enable_x64``), and every accumulation runs in the
    numpy reference's order, so predictions are bit-identical to
    `est.predict`. This is what the autotuner's serving scorer uses: XLA
    speed with exact-parity predictions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mlperf.compiled import lower_estimator, precision_scope


class JaxEstimator:
    """Wraps any lowered mlperf estimator for jitted inference."""

    def __init__(self, est, *, x64: bool = False):
        self.x64 = x64
        lowered = lower_estimator(est, float64=x64)
        with self._precision():
            self.params = jax.tree.map(jnp.asarray, lowered.params)
        self._apply = jax.jit(lowered.apply)
        self.n_targets = int(lowered.n_targets)

    def _precision(self):
        """Scoped x64 so float64 arrays survive asarray/tracing; the
        default fp32 path is a no-op context."""
        return precision_scope(self.x64)

    def __call__(self, X) -> jax.Array:
        with self._precision():
            X = jnp.asarray(X, dtype=jnp.float64 if self.x64 else jnp.float32)
            if X.ndim == 1:
                X = X[None]
            return self._apply(self.params, X)

    def predict(self, X) -> np.ndarray:
        return np.asarray(self(X))


class JaxForestPredictor(JaxEstimator):
    """Back-compat name from when only forests could serve compiled."""
