"""The forest family against its plain reference, and the reference against
scikit-learn (XLA:CPU, small sizes).

``benchmark/reference_forest.py`` imports nothing of the program: its own
binning, its own histograms (``numpy.bincount``), the configuration's
written rule for the random draws.  Exact CART differs from a binned,
Poisson-bootstrapped forest by design, so the reference is tied to
scikit-learn's ``RandomForestClassifier`` at accuracy level; the program is
then held to the reference tree for tree: the same predictions, so the
same split scores to the last flipped row.
"""

import os
import sys

import numpy as np
import pytest
from sklearn.ensemble import RandomForestClassifier
from sklearn.model_selection import StratifiedKFold

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models import trees as tree_models
from spark_sklearn_tpu.utils.native import quantile_bin

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generate            # noqa: E402
import reference_forest    # noqa: E402

FOLDS = 3
CONFIG = {"estimator": {"params": {"random_state": 0}}}
COUNTERS = ("tree_slots_per_launch", "tree_levels_per_launch",
            "hist_bytes_per_lane", "trees_per_candidate")


def make_data(n, d, k, seed):
    return generate.make_data({
        "n_samples": n, "n_features": d, "n_classes": k, "latent": 8,
        "separation": 1.0, "pixel_noise": 1.0, "base_seed": seed})


def split_scores(gs, folds=FOLDS):
    return np.stack([gs.cv_results_[f"split{i}_test_score"]
                     for i in range(folds)], axis=1)


def program(X, y, grid, **params):
    return sst.GridSearchCV(
        RandomForestClassifier(random_state=0, **params), grid,
        cv=StratifiedKFold(FOLDS), backend="tpu", refit=False).fit(X, y)


# --- the reference against scikit-learn --------------------------------------

@pytest.mark.parametrize("k,band", [(3, 0.04), (7, 0.05)])
def test_reference_is_a_forest_at_scikit_learns_accuracy(k, band):
    """Mean test accuracy over the folds within ``band`` of scikit-learn's
    exact-CART forest of the same size and depth, and far above chance."""
    X, y = make_data(2100, 20, k, 7 + k)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    candidate = {"max_depth": 6, "n_estimators": 12}
    ours, trees = reference_forest.forest_cv_scores(
        X, y, splits, [candidate], CONFIG)
    assert trees == 12 * FOLDS
    theirs = [RandomForestClassifier(random_state=0, **candidate)
              .fit(X[tr], y[tr]).score(X[te], y[te]) for tr, te in splits]
    assert abs(ours.mean() - np.mean(theirs)) < band
    assert ours.mean() > 2.0 / k


def test_reference_binning_is_the_programs():
    X, _ = make_data(1500, 9, 3, 1)
    _, codes = quantile_bin(X, 256)
    assert np.array_equal(reference_forest.bin_features(X), codes)


def test_reference_reads_one_forest_at_every_count():
    """Candidates that differ in ``n_estimators`` only: tree t is the same
    tree, so 3 + 5 + 8 trees' answers come from 8 trees a fold."""
    X, y = make_data(600, 10, 3, 2)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    cands = [{"max_depth": 3, "n_estimators": m} for m in (5, 3, 8)]
    together, trees = reference_forest.forest_cv_scores(
        X, y, splits, cands, CONFIG)
    assert trees == 8 * FOLDS
    for j, c in enumerate(cands):
        alone, _ = reference_forest.forest_cv_scores(X, y, splits, [c],
                                                     CONFIG)
        assert np.array_equal(alone[0], together[j])


def test_control_in_bfloat16_is_another_forest():
    import jax.numpy as jnp
    X, y = make_data(1500, 20, 4, 5)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    cands = [{"max_depth": 5, "n_estimators": 6}]
    ref, _ = reference_forest.forest_cv_scores(X, y, splits, cands, CONFIG)
    ctl, _ = reference_forest.forest_cv_scores(X, y, splits, cands, CONFIG,
                                               dtype=jnp.bfloat16)
    assert np.abs(ref - ctl).max() > 1e-3


# --- the program against the reference ---------------------------------------

@pytest.mark.parametrize("k,d,grid", [
    (4, 20, {"max_depth": [3, 5], "n_estimators": [3, 6]}),
    (7, 54, {"max_depth": [4], "n_estimators": [5, 2]}),
    (3, 12, {"max_depth": [6], "n_estimators": [4]}),
])
def test_program_grows_the_references_trees(k, d, grid):
    X, y = make_data(1500, d, k, 5)
    gs = program(X, y, grid)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    ref, _ = reference_forest.forest_cv_scores(
        X, y, splits, list(gs.cv_results_["params"]), CONFIG)
    # the same predictions: scores equal to float32's writing of them
    assert np.abs(split_scores(gs) - ref).max() < 1e-6
    n_test = len(splits[0][1])
    assert np.array_equal(np.rint(split_scores(gs) * n_test),
                          np.rint(ref * n_test))


def test_program_without_bootstrap_grows_the_references_trees():
    X, y = make_data(900, 10, 3, 9)
    gs = program(X, y, {"n_estimators": [3]}, bootstrap=False, max_depth=4)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    ref, _ = reference_forest.forest_cv_scores(
        X, y, splits, [{"n_estimators": 3}],
        {"estimator": {"params": {"random_state": 0, "bootstrap": False,
                                  "max_depth": 4}}})
    assert np.abs(split_scores(gs) - ref).max() < 1e-6


def test_counters_of_a_forest_search():
    X, y = make_data(600, 20, 4, 5)
    gs = program(X, y, {"max_depth": [3, 5], "n_estimators": [3, 6, 4]})
    rep = gs.search_report
    # a launch a depth: its lanes (3 candidates x 3 folds, padded to the
    # mesh) carried to 6 trees
    lanes = rep["lanes_per_launch"]
    assert len(lanes) == 2 and min(lanes) >= 9
    assert rep["tree_slots_per_launch"] == [6 * n for n in lanes]
    assert rep["tree_levels_per_launch"] == [
        6 * n * depth for n, depth in zip(lanes, (3, 5))]
    assert rep["trees_per_candidate"] == [3, 6, 4, 3, 6, 4]
    assert rep["hist_bytes_per_lane"] == [
        2 ** (depth - 1) * 20 * 5 * 256 * 4 for depth in (3, 5)]
    groups = rep["memory"]["groups"]
    assert [g["per_candidate_bytes"] > 3 * 1.4 * h
            for g, h in zip(groups, rep["hist_bytes_per_lane"])] == [True] * 2
    assert all(g["capped"] is False for g in groups)


@pytest.mark.parametrize("counter", COUNTERS)
def test_counters_are_the_forests_own(counter):
    """Another family's search reports none of them."""
    from sklearn.linear_model import LogisticRegression
    X, y = make_data(300, 10, 3, 2)
    gs = sst.GridSearchCV(LogisticRegression(max_iter=20), {"C": [0.1, 1.0]},
                          cv=StratifiedKFold(FOLDS), backend="tpu",
                          refit=False).fit(X, y)
    assert not gs.search_report.get(counter)


def test_codes_go_to_the_device_as_bytes():
    X, y = make_data(300, 10, 3, 2)
    for family in (tree_models.RandomForestClassifierFamily,
                   tree_models.RandomForestRegressorFamily,
                   tree_models.GradientBoostingClassifierFamily,
                   tree_models.GradientBoostingRegressorFamily):
        data, _ = family.prepare_data(X, y)
        assert data["codes"].dtype == np.uint8
        assert data["codes"].flags["C_CONTIGUOUS"]
