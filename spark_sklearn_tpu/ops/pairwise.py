"""Squared euclidean distances between two sets of rows, by one matrix
product: ``|a|^2 - 2 a.b + |b|^2``.

One function for every caller, so that a change to it is measured on each:
the RBF kernel of the kernel duals (``models/svm.py::_kernel``, whole Gram
matrices), the neighbour search (``models/neighbors.py``, a block of
query rows against every row), and k-means' distances to its centers
(``models/cluster.py``, also the converted k-NN models').  The order of
the operations is the RBF kernel's as it has always been.  Rounding can leave a small negative
between near rows, so each caller clamps at 0 where it reads the result (the
RBF kernel after it negates gamma, as it always did).  A caller that holds
the norms already passes them, and one that needs a product more accurate
than the backend's default for float32 (one bfloat16 pass on a TPU) passes
its ``precision``.
"""

from __future__ import annotations

import jax.numpy as jnp


def sq_dists(X1, X2, sq1=None, sq2=None, precision=None):
    """``(len(X1), len(X2))`` squared distances, not clamped.  ``sq1`` and
    ``sq2``: the rows' squared norms where the caller has them
    (``sum(X * X, axis=1)``); ``precision``: the product's
    (``jax.lax.Precision``), None for the backend's default."""
    if sq1 is None:
        sq1 = jnp.sum(X1 * X1, axis=1)
    if sq2 is None:
        sq2 = jnp.sum(X2 * X2, axis=1)
    return sq1[:, None] - 2.0 * jnp.matmul(X1, X2.T, precision=precision) \
        + sq2[None, :]
