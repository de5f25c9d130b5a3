"""The boosting cell's estimator, named through a factory (``generate.py``
builds ``load_object(class)(**params)``) so that the cell's set-up stands on
what its configuration states of the program: for two classes ONE tree a
stage on the log-odds, and a lane that the memory ledger prices.  A tree of
this repository from before both grows two softmax trees a stage (another
model, at twice the cost) in lanes of 107 MB that no plan sees, and must
fail here, at set-up, in seconds, and never enter a search."""

from __future__ import annotations

from sklearn.ensemble import GradientBoostingClassifier

from spark_sklearn_tpu.models.trees import (
    GradientBoostingClassifierFamily, GradientBoostingRegressorFamily)

# no such program, no run: the import is the check
if "launch_workspace" not in vars(GradientBoostingRegressorFamily) \
        or not hasattr(GradientBoostingClassifierFamily, "_trees_per_stage"):
    raise ImportError(
        "this spark_sklearn_tpu grows a tree a class for two classes and "
        "prices no boosting lane: the cell gbc_covtype145k needs the "
        "program that does both")


def boost(**params):
    """``GradientBoostingClassifier(**params)``: scikit-learn's defaults
    otherwise (``max_depth`` 3, ``subsample`` 1.0, log-loss)."""
    return GradientBoostingClassifier(**params)
