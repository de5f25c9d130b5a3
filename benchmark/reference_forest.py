"""The plain reference of the forest cell: what one (candidate, fold) fit of
``RandomForestClassifier`` has to answer under the configuration's written
rule, from nothing of the program under test.

It imports nothing of ``spark_sklearn_tpu`` and takes nothing the program
made.  Straightforward numpy in float32, one fold and one tree at a time,
a level's histograms by ``numpy.bincount`` over the rows that count; the
random draws are ``jax.random``'s under the rule below.  A node's gain is
only ever read at the features of its own subset, so the reference builds
the histograms of those ``max_features`` features and no others.

The forest, as the configuration states it (``guarantees``, ``assumed``):

- **bins**: a feature's 255 edges are its ``k / 256`` quantiles over ALL
  rows of the data set (``numpy.quantile(..., method="lower")``), a row's
  code is ``searchsorted(edges, x, side="right")``, 0..255;
- **bootstrap**: tree t of every fit draws ``jax.random.poisson(k_t, 1.0,
  (n,))`` over all n rows, ``k_t = jax.random.split(PRNGKey(random_state),
  T)[t]`` (the value does not depend on T >= t + 1 under jax's default
  partitionable threefry); a row's weight is that count where the row is
  one of the fold's training rows and 0 elsewhere.  Every candidate and
  fold follows the same keys;
- **growth**: level by level to ``max_depth``.  A node at level l takes
  the ``max_features`` = floor(sqrt(d)) features with the smallest of
  ``jax.random.uniform(fold_in(fold_in(k_t, 7), l), (2^l, d))[node]``.
  For a feature f of the subset and a bin b < 255, left = the rows of the
  node with code <= b, right the others; with L_c, R_c, T_c the weighted
  counts of class c and L, R, T their sums over classes,
  ``gain = sum_c (L_c^2 / (L + 1e-9) + R_c^2 / (R + 1e-9) - T_c^2 / (T +
  1e-9))`` (the variance of the one-hot target, gini up to scaling), only
  where L >= 1 and R >= 1 (``min_samples_leaf``).  The split is the first
  largest gain in (feature, bin) order; a node splits where that gain
  exceeds 1e-7 and is a leaf otherwise; a row goes right where its code
  exceeds b;
- **leaf**: the weighted class distribution of the training rows in it
  (over their weight + 1e-9); **forest**: the mean of the trees' leaf
  distributions (soft vote), the predicted class its first largest entry.

The control of the comparison is this same code with ``dtype=bfloat16``:
the histograms' cumulative sums and the gains rounded to bfloat16 after
every operation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

N_BINS = 256
LAMBDA = np.float32(1e-9)
MIN_GAIN = np.float32(1e-7)
DEFAULTS = {"n_estimators": 100, "max_depth": None, "max_features": "sqrt",
            "bootstrap": True, "min_samples_leaf": 1, "random_state": None,
            "criterion": "gini"}


def settings_of(config, candidate):
    out = dict(DEFAULTS)
    out.update(config["estimator"]["params"])
    out.update(candidate)
    if (out["max_features"], out["criterion"], out["min_samples_leaf"]) != (
            "sqrt", "gini", 1) or out["max_depth"] is None:
        raise ValueError(f"the reference states no rule for {out}")
    return out


def bin_features(X):
    """``(n, d)`` uint8 codes under the 255 lower quantiles a feature."""
    X = np.asarray(X, np.float32)
    qs = np.linspace(0, 1, N_BINS + 1)[1:-1]
    edges = np.quantile(X, qs, axis=0, method="lower").T.astype(np.float32)
    codes = np.empty(X.shape, np.uint8)
    for f in range(X.shape[1]):
        codes[:, f] = np.searchsorted(edges[f], X[:, f], side="right")
    return codes


def tree_keys(random_state, n_trees):
    key = jax.random.PRNGKey(0 if random_state is None else int(random_state))
    return jax.random.split(key, n_trees)


def bootstrap_counts(key, n):
    return np.asarray(jax.random.poisson(key, 1.0, (n,)), np.float32)


def feature_subsets(key, level, n_nodes, d, max_features):
    """``(n_nodes, max_features)`` feature ids, ascending a node."""
    k_lvl = jax.random.fold_in(jax.random.fold_in(key, 7), level)
    scores = np.asarray(jax.random.uniform(k_lvl, (n_nodes, d)))
    kth = np.sort(scores, axis=1)[:, max_features - 1][:, None]
    chosen = scores <= kth
    return np.argsort(~chosen, axis=1, kind="stable")[:, :max_features]


def _rounder(dtype):
    if dtype is None or np.dtype(dtype) == np.float32:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(np.float32)


def best_splits(hist, rnd):
    """``hist (nodes, features, bins, classes)`` float32 weighted counts ->
    each node's (position in its subset, bin, gain) of the first largest
    gain."""
    left_c = rnd(np.cumsum(hist, axis=2))
    left = rnd(left_c.sum(axis=3))
    tot_c, tot = left_c[:, :, -1:, :], left[:, :, -1:]
    right = rnd(tot - left)
    gain = np.zeros_like(left)
    for c in range(hist.shape[3]):
        lc, tc = left_c[..., c], tot_c[..., c]
        rc = rnd(tc - lc)
        term = rnd(rnd(rnd(rnd(lc * lc) / rnd(left + LAMBDA))
                       + rnd(rnd(rc * rc) / rnd(right + LAMBDA)))
                   - rnd(rnd(tc * tc) / rnd(tot + LAMBDA)))
        gain = rnd(gain + term)
    gain[~((left >= 1.0) & (right >= 1.0))] = -np.inf
    gain[:, :, -1] = -np.inf
    flat = gain.reshape(gain.shape[0], -1)
    best = flat.argmax(axis=1)
    return (best // N_BINS, best % N_BINS,
            flat[np.arange(len(best)), best])


def grow_tree(codes, y, w, key, depth, n_classes, max_features, dtype=None):
    """One tree on the rows of weight > 0; returns each row's leaf
    distribution ``(n, n_classes)`` (rows of weight 0 are routed too)."""
    n, d = codes.shape
    rnd = _rounder(dtype)
    node = np.zeros(n, np.int64)               # heap ids: children 2i+1, 2i+2
    frozen = np.zeros(n, bool)
    counted = w > 0
    for level in range(depth):
        n_nodes, offset = 2 ** level, 2 ** level - 1
        subsets = feature_subsets(key, level, n_nodes, d, max_features)
        rows = np.flatnonzero(counted & ~frozen)
        local = node[rows] - offset
        at = codes[rows[:, None], subsets[local]].astype(np.int64)
        ids = ((local[:, None] * max_features + np.arange(max_features))
               * N_BINS + at) * n_classes + y[rows, None]
        hist = np.bincount(
            ids.ravel(), weights=np.repeat(w[rows], max_features),
            minlength=n_nodes * max_features * N_BINS * n_classes,
        ).astype(np.float32).reshape(n_nodes, max_features, N_BINS,
                                     n_classes)
        which, bins, gains = best_splits(hist, rnd)
        feature = subsets[np.arange(n_nodes), which]
        splits = gains > MIN_GAIN
        live = np.flatnonzero(~frozen)
        mine = node[live] - offset
        goes_right = codes[live, feature[mine]] > bins[mine]
        node[live] = np.where(splits[mine], 2 * node[live] + 1 + goes_right,
                              node[live])
        frozen[live] = ~splits[mine]
    n_all = 2 ** (depth + 1) - 1
    sums = np.bincount(node[counted] * n_classes + y[counted],
                       weights=w[counted], minlength=n_all * n_classes
                       ).astype(np.float32).reshape(n_all, n_classes)
    value = sums / (sums.sum(axis=1, keepdims=True) + LAMBDA)
    return value[node]


def forest_cv_scores(X, y, splits, candidates, config, dtype=None):
    """``(len(candidates), n_folds)`` test accuracies and the trees grown.
    Candidates that differ in ``n_estimators`` only read one forest of the
    largest at their own counts: tree t does not depend on the count."""
    y = np.asarray(y)
    classes, y_enc = np.unique(y, return_inverse=True)
    codes = bin_features(X)
    n, d = codes.shape
    max_features = max(1, int(np.sqrt(d)))
    settings = [settings_of(config, c) for c in candidates]
    forests = {}
    for j, s in enumerate(settings):
        rest = tuple(sorted((k, v) for k, v in s.items()
                            if k != "n_estimators"))
        forests.setdefault(rest, []).append(j)
    scores = np.empty((len(candidates), len(splits)))
    trees = 0
    for rest, members in forests.items():
        s = dict(rest)
        counts = {int(settings[j]["n_estimators"]) for j in members}
        keys = tree_keys(s["random_state"], max(counts))
        for f, (train, test) in enumerate(splits):
            in_fold = np.zeros(n, np.float32)
            in_fold[train] = 1.0
            votes = np.zeros((len(test), len(classes)), np.float32)
            for t in range(max(counts)):
                w = in_fold * (bootstrap_counts(keys[t], n)
                               if s["bootstrap"] else 1.0)
                votes += grow_tree(codes, y_enc, w, keys[t],
                                   int(s["max_depth"]), len(classes),
                                   max_features, dtype)[test]
                trees += 1
                if t + 1 in counts:
                    acc = np.mean((votes / np.float32(t + 1)).argmax(axis=1)
                                  == y_enc[test])
                    for j in members:
                        if settings[j]["n_estimators"] == t + 1:
                            scores[j, f] = acc
    return scores, trees
