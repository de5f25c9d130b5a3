"""Preprocessing steps as pure, mask-weighted JAX functions.

The reference runs sklearn transformers unchanged inside each Spark task
(e.g. BASELINE config #5: Pipeline(StandardScaler + MLPClassifier) —
reference: grid_search.py fits the whole pipeline per task).  Under vmap a
transformer is a pair of pure functions with the fold expressed as a weight
mask — `fit_transform` statistics must be *weighted* statistics so each fold
sees only its training rows while shapes stay fixed:

    fit(static, X, w)          -> state pytree  (weighted stats)
    apply(static, state, X)    -> X'            (full-length transform)

These are deliberately tiny: XLA fuses them into the downstream matmuls, so
a pipeline costs nothing extra on TPU (no materialised intermediate the way
Spark materialises RDDs between stages).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import NotCompiledError

EPS = 1e-12


class StandardScalerStep:
    name = "standard_scaler"
    dynamic_params: dict = {}
    #: pure function of (static, X, fold mask): safe to hoist into a
    #: shared-prefix stage and reuse across suffix candidates
    prefix_safe = True
    #: strictly monotone per-feature map: quantile binning (and therefore
    #: histogram-tree fits) is provably invariant under this step
    monotone_per_feature = True

    @staticmethod
    def fit(static, X, w):
        wsum = jnp.sum(w) + EPS
        with_mean = bool(static.get("with_mean", True))
        with_std = bool(static.get("with_std", True))
        mean = (w @ X) / wsum
        # variance is always about the true mean (sklearn computes var_
        # even when with_mean=False); only the shift is disabled
        var = (w @ ((X - mean) ** 2)) / wsum
        scale = jnp.where(var > 0, jnp.sqrt(var), 1.0)
        if not with_std:
            scale = jnp.ones_like(scale)
        if not with_mean:
            mean = jnp.zeros_like(mean)
        return {"mean": mean, "scale": scale}

    @staticmethod
    def apply(static, state, X):
        return (X - state["mean"]) / state["scale"]


class MinMaxScalerStep:
    name = "minmax_scaler"
    dynamic_params: dict = {}
    #: pure function of (static, X, fold mask): safe to hoist into a
    #: shared-prefix stage and reuse across suffix candidates
    prefix_safe = True
    monotone_per_feature = True

    @staticmethod
    def fit(static, X, w):
        big = jnp.asarray(jnp.finfo(X.dtype).max, X.dtype)
        masked_min = jnp.min(jnp.where(w[:, None] > 0, X, big), axis=0)
        masked_max = jnp.max(jnp.where(w[:, None] > 0, X, -big), axis=0)
        lo, hi = static.get("feature_range", (0.0, 1.0))
        span = masked_max - masked_min
        scale = jnp.where(span > 0, (hi - lo) / span, 1.0)
        return {"min": masked_min, "scale": scale, "lo": lo}

    @staticmethod
    def apply(static, state, X):
        out = (X - state["min"]) * state["scale"] + state["lo"]
        if static.get("clip", False):
            lo, hi = static.get("feature_range", (0.0, 1.0))
            out = jnp.clip(out, lo, hi)
        return out


class MaxAbsScalerStep:
    name = "maxabs_scaler"
    dynamic_params: dict = {}
    #: pure function of (static, X, fold mask): safe to hoist into a
    #: shared-prefix stage and reuse across suffix candidates
    prefix_safe = True
    # |x|-scaling by a positive constant: monotone per feature
    monotone_per_feature = True

    @staticmethod
    def fit(static, X, w):
        m = jnp.max(jnp.abs(X) * (w[:, None] > 0), axis=0)
        return {"scale": jnp.where(m > 0, m, 1.0)}

    @staticmethod
    def apply(static, state, X):
        return X / state["scale"]


class NormalizerStep:
    """Stateless per-row normalisation (norm in l1/l2/max)."""

    name = "normalizer"
    dynamic_params: dict = {}
    #: pure function of (static, X, fold mask): safe to hoist into a
    #: shared-prefix stage and reuse across suffix candidates
    prefix_safe = True
    monotone_per_feature = False   # row-wise, mixes features

    @staticmethod
    def fit(static, X, w):
        return {}

    @staticmethod
    def apply(static, state, X):
        norm = static.get("norm", "l2")
        if norm == "l1":
            d = jnp.sum(jnp.abs(X), axis=1, keepdims=True)
        elif norm == "max":
            d = jnp.max(jnp.abs(X), axis=1, keepdims=True)
        else:
            d = jnp.linalg.norm(X, axis=1, keepdims=True)
        return X / jnp.maximum(d, EPS)


class PCAStep:
    """Weighted PCA via eigendecomposition of the fold-weighted covariance
    (n_components is static — it changes the transformed width).

    Matches sklearn's PCA(svd_solver='full') up to component sign on the
    training fold; whitening supported.  Randomized/arpack solvers and
    n_components='mle' are not compiled (fit raises -> host fallback).
    """

    name = "pca"
    dynamic_params: dict = {}
    #: pure function of (static, X, fold mask): safe to hoist into a
    #: shared-prefix stage and reuse across suffix candidates
    prefix_safe = True
    monotone_per_feature = False   # rotation, mixes features

    @staticmethod
    def min_group_size(static) -> int:
        """A PCA fit needs at least n_components rows (keyed-fleet
        eligibility hook, mirroring Family.min_group_size)."""
        nc = static.get("n_components")
        if isinstance(nc, (int, np.integer)) and not isinstance(nc, bool):
            return max(1, int(nc))
        return 1

    @staticmethod
    def check_static(static, n_features=None):
        """Raise ValueError for configs the compiled path cannot serve
        (callers probe this BEFORE launching so designed host fallbacks
        stay silent; fit also calls it so trace-time misuse still fails).

        sklearn raises for n_components outside [0, min(n_samples,
        n_features)]; a silent evecs[:, :nc] truncation would diverge
        from the host-fitted keys in a hybrid fleet.
        """
        nc = static.get("n_components")
        if nc is None or isinstance(nc, bool) or \
                not isinstance(nc, (int, np.integer)):
            raise NotCompiledError(
                "PCA without an integer n_components is not compiled; "
                "use backend='host'")
        if nc < 0:
            raise ValueError(f"n_components={nc} must be >= 0")
        if n_features is not None and nc > n_features:
            raise ValueError(
                f"n_components={nc} must be <= n_features={n_features}")
        if static.get("svd_solver", "auto") not in ("auto", "full",
                                                    "covariance_eigh"):
            raise NotCompiledError(
                "only full-SVD PCA is compiled; use backend='host'")

    @staticmethod
    def fit(static, X, w):
        PCAStep.check_static(static, X.shape[1])
        nc = int(static["n_components"])
        wsum = jnp.sum(w) + EPS
        mean = (w @ X) / wsum
        Xc = X - mean
        cov = (Xc * w[:, None]).T @ Xc / wsum          # (d, d)
        evals, evecs = jnp.linalg.eigh(cov)            # ascending
        # top-nc components, descending eigenvalue order
        comps = evecs[:, ::-1][:, :nc].T               # (nc, d)
        var = jnp.maximum(evals[::-1][:nc], 0.0)
        return {"mean": mean, "components": comps, "var": var}

    @staticmethod
    def apply(static, state, X):
        Z = (X - state["mean"]) @ state["components"].T
        if static.get("whiten", False):
            Z = Z / jnp.sqrt(state["var"] + EPS)[None, :]
        return Z


#: sklearn transformer class name -> step implementation
STEP_REGISTRY = {
    "StandardScaler": StandardScalerStep,
    "MinMaxScaler": MinMaxScalerStep,
    "MaxAbsScaler": MaxAbsScalerStep,
    "Normalizer": NormalizerStep,
    "PCA": PCAStep,
}


def resolve_step(transformer) -> object | None:
    # sklearn classes only — a third-party class merely NAMED StandardScaler
    # must not silently get the compiled transform (same guard as
    # base.resolve_family)
    if not type(transformer).__module__.startswith("sklearn."):
        return None
    return STEP_REGISTRY.get(type(transformer).__name__)
