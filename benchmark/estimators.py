"""Estimators a configuration names that JSON cannot hold: a ``Pipeline``'s
steps are objects, so ``estimator.class`` names a factory here and
``estimator.params`` are the factory's arguments (``generate.py`` builds
``load_object(class)(**params)``)."""

from __future__ import annotations

from sklearn.neural_network import MLPClassifier
from sklearn.pipeline import Pipeline
from sklearn.preprocessing import StandardScaler


def scaled_mlp(**mlp_params):
    """scikit-learn's advice for its neural network ("Tips on practical
    use"): scale the data with ``StandardScaler`` in a ``Pipeline``.  The
    grid's keys address the steps as ``mlp__<parameter>``."""
    return Pipeline([("scale", StandardScaler()),
                     ("mlp", MLPClassifier(**mlp_params))])
