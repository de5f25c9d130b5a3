"""The forest cell's estimator, named through a factory (``generate.py``
builds ``load_object(class)(**params)``) so that the cell's set-up stands on
the program's histogram module: a tree of this repository from before
``spark_sklearn_tpu/ops/tree_hist.py`` grows a level's histograms as
scattered adds (hours a search at the cell's rows on a TPU), and must fail
here, at set-up, in seconds, and never enter a search."""

from __future__ import annotations

from sklearn.ensemble import RandomForestClassifier

# no program, no run: the import is the check
from spark_sklearn_tpu.ops import tree_hist  # noqa: F401


def forest(**params):
    """``RandomForestClassifier(**params)``: scikit-learn's defaults
    otherwise (``max_features="sqrt"``, ``bootstrap=True``, gini)."""
    return RandomForestClassifier(**params)
