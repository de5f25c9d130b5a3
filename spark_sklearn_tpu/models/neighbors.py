"""Compiled k-nearest-neighbors families — a TPU-first redesign.

Reference behavior: KNeighborsClassifier/Regressor run as arbitrary
sklearn estimators inside Spark tasks (reference: grid_search.py ->
sklearn _fit_and_score), so every (candidate, fold) task recomputes the
FULL pairwise-distance problem from scratch on a CPU executor.

The TPU-first shape inverts that cost model completely:

  - ONE squared-distance Gram `||xi||^2 + ||xj||^2 - 2 X X^T` for the
    whole search — a single (n, d) @ (d, n) MXU matmul shared by every
    candidate and every fold.
  - Per FOLD (not per task): mask non-train columns to +inf, one
    `lax.top_k` of the grid-wide max n_neighbors, then a cumulative
    weighted one-hot vote over the sorted neighbors.
  - Per CANDIDATE: k is just an INDEX into the cumulative votes — O(1)
    per (candidate, fold) task after the shared preamble.

A 20-candidate x 5-fold KNN grid therefore costs ~one matmul + 5 top_k
calls total, where the reference pays 100 full distance computations.

sklearn-semantics notes:
  - brute-force euclidean only (metric minkowski with p=2 / euclidean);
    other metrics raise -> Tier B host path.
  - weights in {"uniform", "distance"}; distance weights use 1/d with
    d clamped at 1e-12, so an exact-duplicate neighbor dominates the
    vote (sklearn's exact rule: zero-distance neighbors take the whole
    vote; the clamp reproduces it to float precision).
  - predict on rows that belong to the train fold sees the row itself
    as a zero-distance neighbor, exactly like sklearn's
    `KNeighborsClassifier.fit(Xtr).predict(Xtr)`.
  - KNN fit takes no sample_weight in sklearn -> weighted searches take
    the host tier (accepts_sample_weight = False).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, encode_labels, register_family)

_EPS_DIST = 1e-12


def _check_metric(static):
    metric = static.get("metric", "minkowski")
    p = static.get("p", 2)
    if metric not in ("minkowski", "euclidean") or \
            (metric == "minkowski" and p not in (2, 2.0)):
        raise NotCompiledError(
            f"metric={metric!r}/p={p!r} is not compiled (brute euclidean "
            "only); use backend='host'")
    weights = static.get("weights", "uniform")
    if weights not in ("uniform", "distance") and not callable(weights):
        raise NotCompiledError(
            f"weights={weights!r} is not compiled; use backend='host'")
    if callable(weights):
        raise NotCompiledError("callable weights are not compiled; use "
                               "backend='host'")


def _sq_dists(X):
    """Squared euclidean Gram via ONE wide matmul."""
    sq = jnp.sum(X * X, axis=1)
    D = sq[:, None] + sq[None, :] - 2.0 * (X @ X.T)
    return jnp.maximum(D, 0.0)


def _fold_neighbors(D, train_ind, maxk):
    """Per-fold sorted neighbors: (vals, idx) of the maxk nearest TRAIN
    columns for every row; excluded columns sit at +inf so `valid`
    masks lanes beyond the fold's train count."""
    Dm = jnp.where(train_ind[None, :] > 0, D, jnp.inf)
    negv, idx = lax.top_k(-Dm, maxk)            # (n, maxk)
    d2 = -negv
    valid = jnp.isfinite(d2)
    return d2, idx, valid


def _neighbor_weights(d2, valid, weights, dtype):
    if weights == "distance":
        w = 1.0 / jnp.maximum(jnp.sqrt(d2), _EPS_DIST)
    else:
        w = jnp.ones_like(d2)
    return jnp.where(valid, w, jnp.zeros((), dtype))


class KNeighborsClassifierFamily(Family):
    name = "kneighbors_classifier"
    is_classifier = True
    dynamic_params = {"n_neighbors": np.int32}
    #: sklearn's vote tables are float64 regardless of X
    proba_dtype_rule = "float64"
    #: sklearn's KNeighbors fit has no sample_weight parameter
    accepts_sample_weight = False
    keyed_compatible = False

    @classmethod
    def extract_params(cls, estimator):
        return dict(estimator.get_params(deep=False))

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        classes, y_enc = encode_labels(y)
        data = {"X": np.ascontiguousarray(X, dtype=dtype), "y": y_enc}
        meta = {"n_classes": int(len(classes)), "classes": classes,
                "n_features": int(X.shape[1])}
        return data, meta

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        ks = [int(c.get("n_neighbors",
                        base_params.get("n_neighbors", 5)))
              for c in candidates] or [int(base_params.get("n_neighbors",
                                                           5))]
        meta["max_k"] = max(ks)
        # sklearn raises at kneighbors() when a fold's train count is
        # smaller than n_neighbors; the compiled vote table would
        # silently clip to k=n_train instead — refuse host-side so both
        # backends agree on such grids (ADVICE r3)
        mft = meta.get("min_fold_train_count")
        if mft is not None and meta["max_k"] > mft:
            raise ValueError(
                f"Expected n_neighbors <= n_samples_fit, but "
                f"n_neighbors = {meta['max_k']}, n_samples_fit = {mft} "
                f"(smallest CV train fold) — sklearn raises when "
                f"scoring such a fold")

    # the per-task cache is (n, n_classes) float votes
    @staticmethod
    def max_tasks_hint(n_samples: int, meta) -> int:
        kc = meta.get("n_classes", 2)
        budget = 1 << 30
        return max(1, budget // max(1, n_samples * kc * 4))

    @classmethod
    def _cum_votes(cls, data, static, train_w, meta, n_folds, val_fn):
        """Shared preamble: distance Gram + per-fold cumulative weighted
        votes.  `val_fn(idx) -> (n, maxk, V)` supplies what gets voted
        (one-hot labels for the classifier, y values for the
        regressor)."""
        _check_metric(static)
        X = data["X"]
        B = train_w.shape[0]
        nc = B // n_folds
        maxk = int(meta.get("max_k",
                            static.get("n_neighbors", 5)))
        maxk = min(maxk, X.shape[0])
        weights = static.get("weights", "uniform")
        D = _sq_dists(X)                         # ONE matmul, whole search
        fold_w = train_w.reshape(nc, n_folds, -1)[0]      # (F, n)

        def per_fold(wf):
            d2, idx, valid = _fold_neighbors(D, wf, maxk)
            wkn = _neighbor_weights(d2, valid, weights, X.dtype)
            vals = val_fn(idx)                   # (n, maxk, V)
            cum = jnp.cumsum(vals * wkn[:, :, None], axis=1)
            cumw = jnp.cumsum(wkn, axis=1)       # (n, maxk)
            return cum, cumw

        return jax.vmap(per_fold)(fold_w)        # (F, n, maxk, V), (F,n,maxk)

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        n_folds = int(static.get("__n_folds__", 0))
        if n_folds <= 0:
            raise ValueError("engine must pass __n_folds__ for KNN")
        X, y = data["X"], data["y"]
        B = train_w.shape[0]
        kc = meta["n_classes"]
        maxk = min(int(meta.get("max_k", static.get("n_neighbors", 5))),
                   X.shape[0])

        def one_hot_labels(idx):
            return jax.nn.one_hot(y[idx], kc, dtype=X.dtype)

        cum, _cumw = cls._cum_votes(
            data, static, train_w, meta, n_folds, one_hot_labels)

        k_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get("n_neighbors", static.get("n_neighbors", 5)),
            jnp.int32), (B,))
        kk = jnp.clip(k_task - 1, 0, maxk - 1)
        f_idx = jnp.arange(B, dtype=jnp.int32) % n_folds

        def per_task(f_i, k_i):
            votes = cum[f_i][:, k_i, :]                   # (n, kc)
            return votes / jnp.maximum(
                jnp.sum(votes, axis=1, keepdims=True), _EPS_DIST)

        proba = jax.vmap(per_task)(f_idx, kk)             # (B, n, kc)
        return {"proba": proba}

    # -- prediction from cached votes (search-internal) -------------------
    @classmethod
    def predict(cls, model, static, X, meta):
        return jnp.argmax(model["proba"], axis=-1).astype(jnp.int32)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        return model["proba"]

    @classmethod
    def decision(cls, model, static, X, meta):
        if meta["n_classes"] == 2:
            # ranking twin of sklearn's predict_proba[:, 1] for AUC
            return model["proba"][:, 1]
        return model["proba"]

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"classes_": meta["classes"],
                "n_features_in_": meta["n_features"]}


class KNeighborsRegressorFamily(KNeighborsClassifierFamily):
    name = "kneighbors_regressor"
    is_classifier = False

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        data = {"X": np.ascontiguousarray(X, dtype=dtype),
                "y": np.ascontiguousarray(y, dtype=dtype)}
        meta = {"n_features": int(X.shape[1])}
        return data, meta

    @staticmethod
    def max_tasks_hint(n_samples: int, meta) -> int:
        budget = 1 << 30
        return max(1, budget // max(1, n_samples * 4))

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        n_folds = int(static.get("__n_folds__", 0))
        if n_folds <= 0:
            raise ValueError("engine must pass __n_folds__ for KNN")
        X, y = data["X"], data["y"]
        B = train_w.shape[0]
        maxk = min(int(meta.get("max_k", static.get("n_neighbors", 5))),
                   X.shape[0])

        def y_vals(idx):
            return y[idx][:, :, None]                     # (n, maxk, 1)

        cum, cumw = cls._cum_votes(
            data, static, train_w, meta, n_folds, y_vals)

        k_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get("n_neighbors", static.get("n_neighbors", 5)),
            jnp.int32), (B,))
        kk = jnp.clip(k_task - 1, 0, maxk - 1)
        f_idx = jnp.arange(B, dtype=jnp.int32) % n_folds

        def per_task(f_i, k_i):
            s = cum[f_i][:, k_i, 0]
            w = cumw[f_i][:, k_i]
            return s / jnp.maximum(w, _EPS_DIST)

        pred = jax.vmap(per_task)(f_idx, kk)              # (B, n)
        return {"pred": pred}

    @classmethod
    def predict(cls, model, static, X, meta):
        return model["pred"]

    @classmethod
    def decision(cls, model, static, X, meta):
        raise NotImplementedError("KNeighborsRegressor has no decision")

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        raise NotImplementedError("KNeighborsRegressor has no proba")

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"n_features_in_": meta["n_features"]}


register_family(
    KNeighborsClassifierFamily,
    "sklearn.neighbors._classification.KNeighborsClassifier",
    "sklearn.neighbors.KNeighborsClassifier",
)
register_family(
    KNeighborsRegressorFamily,
    "sklearn.neighbors._regression.KNeighborsRegressor",
    "sklearn.neighbors.KNeighborsRegressor",
)
