"""The plain reference of the k-NN cell: what one (candidate, fold) fit and
score of ``KNeighborsClassifier`` (brute force, euclidean) has to answer,
from nothing of the program under test.

It imports nothing of ``spark_sklearn_tpu``.  Fold by fold, the fold's test
rows go in blocks against that fold's training rows ONLY (the textbook form:
the program instead masks a row's own fold out of every row's distances):

- **distances**: ``|a|^2 - 2 a.b + |b|^2``, clamped at 0, in ``jax.numpy``
  float32 under ``jax.default_matmul_precision("highest")`` (on a TPU a
  float32 product is otherwise one bfloat16 pass);
- **neighbours**: a stable argsort of a test row's distances, the first
  ``max_k``: equal distances in the order of the training rows;
- **votes**, for each candidate's ``n_neighbors`` k: its k neighbours'
  labels, each with weight 1 (``uniform``) or 1 / distance (``distance``;
  scikit-learn's rule: where some of the k are at distance 0, those take
  the whole vote with weight 1 each); the prediction is the class with the
  most, the smallest class on a tie; the score is the accuracy.

The neighbour lists of a fold serve all candidates: k is only how many of
them vote.

The control of the comparison (``dtype=jax.numpy.bfloat16``): the same,
with the rows rounded to bfloat16 for the product, ONE pass accumulated in
float32, which is what a TPU does with a float32 product by default.  The
norms stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: test rows a product
BLOCK = 2048


@functools.partial(jax.jit, static_argnames=("max_k", "dtype"))
def _nearest(Xb, Xtr, sq_tr, max_k, dtype):
    """A block's ``max_k`` nearest training rows: (squared distances,
    indices), by a stable argsort."""
    with jax.default_matmul_precision("highest"):
        prod = jnp.dot(Xb.astype(dtype), Xtr.astype(dtype).T,
                       preferred_element_type=jnp.float32)
        sq_b = jnp.sum(Xb * Xb, axis=1)
        d2 = jnp.maximum(sq_b[:, None] - 2.0 * prod + sq_tr[None, :], 0.0)
        order = jnp.argsort(d2, axis=1, stable=True)[:, :max_k]
        return jnp.take_along_axis(d2, order, axis=1), order


def neighbours(X_train, X_test, max_k, dtype=jnp.float32, block=BLOCK):
    """``(len(X_test), max_k)`` squared distances and indices into
    ``X_train`` of each test row's nearest training rows."""
    Xtr = jnp.asarray(X_train, jnp.float32)
    sq_tr = jnp.sum(Xtr * Xtr, axis=1)
    d2, idx = [], []
    for i in range(0, len(X_test), block):
        Xb = np.asarray(X_test[i:i + block], np.float32)
        m = len(Xb)
        if m < block:        # one compiled shape a fold
            Xb = np.concatenate([Xb, np.zeros((block - m, Xb.shape[1]),
                                              np.float32)])
        d, j = _nearest(jnp.asarray(Xb), Xtr, sq_tr, int(max_k), dtype)
        d2.append(np.asarray(d)[:m])
        idx.append(np.asarray(j)[:m])
    return np.concatenate(d2), np.concatenate(idx)


def vote(d2k, labels, k, weights, n_classes):
    """Predictions from neighbour lists: ``d2k`` (m, >= k) squared
    distances and ``labels`` (m, >= k) the neighbours' classes."""
    lab = labels[:, :k]
    if weights == "uniform":
        w = np.ones(lab.shape)
    elif weights == "distance":
        dist = np.sqrt(d2k[:, :k].astype(np.float64))
        zero = dist == 0.0
        with np.errstate(divide="ignore"):
            w = np.where(zero.any(axis=1, keepdims=True),
                         zero.astype(np.float64), 1.0 / dist)
    else:
        raise ValueError(f"weights={weights!r} is not this reference's")
    votes = np.zeros((len(lab), n_classes))
    np.add.at(votes, (np.arange(len(lab))[:, None], lab), w)
    return np.argmax(votes, axis=1)


def predict(X_train, y_train, X_test, k, weights, n_classes,
            dtype=jnp.float32, block=BLOCK):
    """``KNeighborsClassifier(k, weights=weights).fit(X_train,
    y_train).predict(X_test)`` for labels 0 .. n_classes - 1."""
    d2k, idx = neighbours(X_train, X_test, k, dtype, block)
    return vote(d2k, np.asarray(y_train)[idx], k, weights, n_classes)


def knn_cv_scores(X, y, splits, candidates, config, dtype=jnp.float32):
    """``((len(candidates), len(splits)) accuracies,)`` for candidates
    ``{"n_neighbors", "weights"}`` (either may be left to the
    configuration's estimator parameters)."""
    base = config.get("estimator", {}).get("params", {})
    params = [(int(c.get("n_neighbors", base.get("n_neighbors", 5))),
               c.get("weights", base.get("weights", "uniform")))
              for c in candidates]
    classes, y_enc = np.unique(y, return_inverse=True)
    max_k = max(k for k, _ in params)
    scores = np.empty((len(candidates), len(splits)))
    for f, (train, test) in enumerate(splits):
        d2k, idx = neighbours(X[train], X[test], max_k, dtype)
        labels = y_enc[train][idx]
        for c, (k, weights) in enumerate(params):
            pred = vote(d2k, labels, k, weights, len(classes))
            scores[c, f] = float(np.mean(pred == y_enc[test]))
    return (scores,)
