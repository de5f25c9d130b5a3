"""The k-NN cell's estimator, named through a factory (``generate.py`` builds
``load_object(class)(**params)``) so that the cell's set-up stands on what
its configuration states of the program: a neighbour search in row blocks
that the memory ledger prices.  A tree of this repository from before it
forms the whole 70 000 x 70 000 distance matrix (19.6 GB, more than the
chip holds), cannot compile the launch, and would bisect it down to
scikit-learn on the host for hours: it must fail here, at set-up, in
seconds, and never enter a search."""

from __future__ import annotations

from sklearn.neighbors import KNeighborsClassifier

from spark_sklearn_tpu.models.neighbors import (
    KNeighborsClassifierFamily, KNeighborsRegressorFamily)

# no such program, no run: the import is the check
if "launch_workspace" not in vars(KNeighborsClassifierFamily) \
        or "launch_tiles" not in vars(KNeighborsClassifierFamily) \
        or "max_tasks_hint" in vars(KNeighborsRegressorFamily):
    raise ImportError(
        "this spark_sklearn_tpu forms the whole (n, n) distance matrix and "
        "prices no neighbour search: the cell knn_mnist needs the program "
        "that searches in row blocks the memory ledger sizes")


def knn(**params):
    """``KNeighborsClassifier(**params)``: scikit-learn's defaults
    otherwise (``metric`` minkowski, ``p`` 2)."""
    return KNeighborsClassifier(**params)
