"""The k-NN cell's plain reference against scikit-learn, and the program
against the reference (XLA:CPU, small sizes).

``benchmark/reference_knn.py`` imports nothing of the program: a fold's
test rows against that fold's training rows only, a stable argsort, the
votes written out.  It predicts what ``KNeighborsClassifier`` predicts,
and the compiled search scores what it scores."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.model_selection import StratifiedKFold
from sklearn.neighbors import KNeighborsClassifier

import spark_sklearn_tpu as sst

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generate            # noqa: E402
import reference_knn       # noqa: E402


def make_data(n, d=40, k=5, seed=3, separation=0.6):
    return generate.make_data({
        "n_samples": n, "n_features": d, "n_classes": k, "latent": 8,
        "separation": separation, "pixel_noise": 1.0, "base_seed": seed})


@pytest.mark.parametrize("k", [1, 4, 15])
@pytest.mark.parametrize("weights", ["uniform", "distance"])
def test_reference_predicts_what_scikit_learn_predicts(k, weights):
    X, y = make_data(700)
    Xtr, ytr, Xte = X[:500], y[:500], X[500:]
    ours = reference_knn.predict(Xtr, ytr, Xte, k, weights, 5, block=64)
    theirs = KNeighborsClassifier(k, weights=weights).fit(
        Xtr, ytr).predict(Xte)
    np.testing.assert_array_equal(ours, theirs)


def test_zero_distance_neighbours_take_the_whole_vote():
    """scikit-learn's rule: where a neighbour is at distance 0, the
    neighbours at distance 0 vote and no other."""
    d2k = np.array([[0.0, 1.0, 1.0], [1.0, 1.0, 4.0]])
    labels = np.array([[2, 1, 1], [2, 1, 1]])
    assert list(reference_knn.vote(d2k, labels, 3, "distance", 3)) == [2, 1]
    assert list(reference_knn.vote(d2k, labels, 3, "uniform", 3)) == [1, 1]


def test_search_scores_what_the_reference_scores():
    """Every candidate and fold of the cell's grid at a small size: the
    compiled search's split scores are the reference's."""
    X, y = make_data(600)
    cv = StratifiedKFold(5)
    grid = {"n_neighbors": list(range(1, 31)),
            "weights": ["uniform", "distance"]}
    gs = sst.GridSearchCV(KNeighborsClassifier(), grid, cv=cv,
                          backend="tpu", refit=False).fit(X, y)
    cands = list(gs.cv_results_["params"])
    want = reference_knn.knn_cv_scores(X, y, list(cv.split(X, y)), cands,
                                       {})[0]
    got = np.stack([gs.cv_results_[f"split{i}_test_score"]
                    for i in range(5)], axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_control_rounds_the_product_to_bfloat16():
    """One bfloat16 pass moves the distances by more than float32's
    rounding: the control's lists are the reference's on rows
    rounded to bfloat16."""
    X, _ = make_data(300)
    d_ref, _ = reference_knn.neighbours(X[:200], X[200:], 5)
    d_ctl, _ = reference_knn.neighbours(X[:200], X[200:], 5,
                                        dtype=jnp.bfloat16)
    gap = np.abs(d_ctl - d_ref).max()
    assert 1e-4 < gap < 1.0
