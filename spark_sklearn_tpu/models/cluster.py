"""KMeans family — Lloyd's algorithm as matmuls.

Reference counterpart: sklearn KMeans running whole inside Spark tasks
(and as a KeyedEstimator clusterer — reference: keyed_models.py
estimatorType="clusterer").  Lloyd maps perfectly to the MXU:

  - distances: ||x - c||^2 = ||x||^2 + ||c||^2 - 2 x.c — one (n, d)x(d, k)
    matmul per iteration;
  - center update: one-hot(assignments)^T @ X — one (k, n)x(n, d) matmul
    (no scatter);
  - k-means++ seeding: a `fori_loop` over k centers, each step one
    distance update + a Gumbel-max categorical draw over the weighted
    min-distances (sklearn's D^2 sampling, minus its local-trial
    refinement — accuracy-level parity, oracle-tested).

Fold masks enter as sample weights in both the sampling probabilities and
the center updates, like every other family.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, register_family)


def _sq_dists(X, C):
    """(n, k) squared distances via the matmul identity."""
    xx = jnp.sum(X * X, axis=1, keepdims=True)
    cc = jnp.sum(C * C, axis=1)
    return jnp.maximum(xx - 2.0 * (X @ C.T) + cc[None, :], 0.0)


def _neg_inertia(family, model, static, data, meta, w):
    """Default scorer: sklearn's KMeans.score = -inertia on the fold."""
    d2 = _sq_dists(data["X"], model["centers"])
    return -jnp.sum(w * jnp.min(d2, axis=1))


class KMeansFamily(Family):
    name = "kmeans"
    is_classifier = False
    dynamic_params = {"tol": np.float32}
    default_scorer = staticmethod(_neg_inertia)

    @classmethod
    def min_group_size(cls, static) -> int:
        # a fit needs at least n_clusters real samples (sklearn raises on
        # fewer; padded fleet groups must fall back instead of silently
        # seeding centers from zero-padding)
        return int(static.get("n_clusters", 8))

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        data = {"X": np.ascontiguousarray(X, dtype=dtype)}
        if y is not None:
            y_arr = np.asarray(y)
            if np.issubdtype(y_arr.dtype, np.number):
                data["y"] = y_arr   # object labels never reach the device
        meta = {"n_features": int(X.shape[1])}
        return data, meta

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        X = data["X"]
        n, d = X.shape
        k = int(static.get("n_clusters", 8))
        max_iter = int(static.get("max_iter", 300))
        # sklearn scales tol by the mean feature variance of the FIT-TIME
        # X (_kmeans.py _tolerance) — weighted, so zero-weight padding rows
        # (keyed fleets) don't deflate a key's own variance scale
        w0 = train_w
        wsum0 = jnp.sum(w0) + 1e-12
        xbar = (w0 @ X) / wsum0
        wvar = (w0 @ ((X - xbar) ** 2)) / wsum0
        tol = jnp.asarray(dynamic.get("tol", static.get("tol", 1e-4)),
                          X.dtype) * jnp.mean(wvar)
        seed = static.get("random_state")
        base_key = jax.random.PRNGKey(0 if seed is None else int(seed))
        init = static.get("init", "k-means++")
        if not isinstance(init, str) or init not in ("k-means++", "random"):
            raise NotCompiledError(
                f"init={init!r} is not compiled; use backend='host'")
        n_init = static.get("n_init", "auto")
        if n_init == "auto":
            n_init = 1 if init == "k-means++" else 10
        n_init = int(n_init)
        w = train_w

        def seed_centers(key):
            if init == "random":
                idx = jax.random.choice(
                    key, n, (k,), replace=False,
                    p=w / (jnp.sum(w) + 1e-12))
                return X[idx]
            # k-means++ D^2 sampling
            k0, key = jax.random.split(key)
            logw = jnp.where(w > 0, jnp.log(w + 1e-12), -jnp.inf)
            first = jnp.argmax(logw + jax.random.gumbel(k0, (n,)))
            C0 = jnp.zeros((k, d), X.dtype).at[0].set(X[first])
            min_d2 = jnp.sum((X - X[first]) ** 2, axis=1)

            def place(i, carry):
                C, min_d2, key = carry
                key, kk = jax.random.split(key)
                logits = jnp.where(
                    (w > 0) & (min_d2 > 0),
                    jnp.log(w * min_d2 + 1e-30), -jnp.inf)
                nxt = jnp.argmax(logits + jax.random.gumbel(kk, (n,)))
                C = C.at[i].set(X[nxt])
                min_d2 = jnp.minimum(
                    min_d2, jnp.sum((X - X[nxt]) ** 2, axis=1))
                return C, min_d2, key

            C0, _, _ = jax.lax.fori_loop(1, k, place, (C0, min_d2, key))
            return C0

        def lloyd(C0):
            def cond(carry):
                C, prev_shift, it = carry
                return jnp.logical_and(it < max_iter, prev_shift > tol)

            def body(carry):
                C, _, it = carry
                d2 = _sq_dists(X, C)
                assign = jnp.argmin(d2, axis=1)
                oh = jax.nn.one_hot(assign, k, dtype=X.dtype) * w[:, None]
                counts = jnp.sum(oh, axis=0)                   # (k,)
                sums = oh.T @ X                                # (k, d)
                C_new = jnp.where(
                    counts[:, None] > 0,
                    sums / jnp.maximum(counts[:, None], 1e-12),
                    C)                                         # keep empties
                shift = jnp.sum((C_new - C) ** 2)
                return C_new, shift, it + 1

            C, _, n_iter = jax.lax.while_loop(
                cond, body,
                (C0, jnp.asarray(jnp.inf, X.dtype),
                 jnp.asarray(0, jnp.int32)))
            d2 = _sq_dists(X, C)
            return C, jnp.sum(w * jnp.min(d2, axis=1)), n_iter

        def one_init(t, best):
            bC, b_inertia, b_iter = best
            C, inertia, n_iter = lloyd(
                seed_centers(jax.random.fold_in(base_key, t)))
            better = inertia < b_inertia
            return (jnp.where(better, C, bC),
                    jnp.where(better, inertia, b_inertia),
                    jnp.where(better, n_iter, b_iter))

        best = (jnp.zeros((k, d), X.dtype),
                jnp.asarray(jnp.inf, X.dtype), jnp.asarray(0, jnp.int32))
        C, inertia, n_iter = jax.lax.fori_loop(0, n_init, one_init, best)
        return {"centers": C, "inertia": inertia, "n_iter": n_iter}

    @classmethod
    def predict(cls, model, static, X, meta):
        return jnp.argmin(_sq_dists(X, model["centers"]),
                          axis=1).astype(jnp.int32)

    @classmethod
    def decision(cls, model, static, X, meta):
        return -_sq_dists(X, model["centers"])

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {
            "cluster_centers_": np.asarray(model["centers"]),
            "inertia_": float(model["inertia"]),
            "n_iter_": int(model["n_iter"]),
            "n_features_in_": meta["n_features"],
        }


register_family(
    KMeansFamily,
    "sklearn.cluster._kmeans.KMeans",
    "sklearn.cluster.KMeans",
)
