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
            "hist_bytes_per_lane", "trees_per_candidate",
            "trees_grown_per_launch", "hist_features_per_node",
            "hist_lanes_per_mask")


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
    # mesh) read ONE forest a fold, grown to 6 trees
    lanes = rep["lanes_per_launch"]
    assert len(lanes) == 2 and min(lanes) >= 9
    assert rep["tree_slots_per_launch"] == [6 * FOLDS] * 2
    assert rep["trees_grown_per_launch"] == [6 * FOLDS] * 2
    assert rep["tree_levels_per_launch"] == [
        6 * FOLDS * depth for depth in (3, 5)]
    assert rep["trees_per_candidate"] == [3, 6, 4, 3, 6, 4]
    # a node's histograms hold its own sqrt(20) = 4 features of the 20
    assert rep["hist_features_per_node"] == [4, 4]
    # the plain form builds no mask (on a TPU a tree's own tiles: 1)
    assert rep["hist_lanes_per_mask"] == [0, 0]
    assert [(g["hist_features"], g["n_features"])
            for g in rep["per_group"].values()] == [(4, 20)] * 2
    assert rep["hist_bytes_per_lane"] == [
        2 ** (depth - 1) * 4 * 5 * 256 * 4 for depth in (3, 5)]
    # the ledger: the forests' histograms whatever the width, a
    # candidate's votes (four copies, by fold and row) on top of its masks
    groups = rep["memory"]["groups"]
    assert [g["fixed_bytes"] > FOLDS * 1.39 * h
            for g, h in zip(groups, rep["hist_bytes_per_lane"])] == [True] * 2
    assert [g["per_candidate_bytes"] > FOLDS * 600 * (4 + 4 * 4 * 4)
            for g in groups] == [True] * 2
    assert [g["per_candidate_bytes"] < g["fixed_bytes"]
            for g in groups] == [True] * 2
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


# --- one forest a fold, read at every count of the launch ---------------------

def search(estimator, X, y, grid, refit=False, one_device=False, **config):
    import jax
    from sklearn.model_selection import KFold
    if one_device:
        config["devices"] = jax.devices()[:1]
    cv = StratifiedKFold(FOLDS) if y.dtype.kind == "i" else KFold(FOLDS)
    return sst.GridSearchCV(
        estimator, grid, cv=cv, backend="tpu", refit=refit,
        config=sst.TpuConfig(**config) if config else None).fit(X, y)


def rows_by_params(gs):
    scores = split_scores(gs)
    return {tuple(sorted(p.items())): scores[i]
            for i, p in enumerate(gs.cv_results_["params"])}


def forest_problem(kind):
    from sklearn.ensemble import RandomForestRegressor
    X, y = make_data(500, 10, 3, 11)
    if kind == "classifier":
        return RandomForestClassifier, X, y
    return RandomForestRegressor, X, (X[:, 0] * 2 + X[:, 1] * X[:, 2]
                                      ).astype(np.float32)


@pytest.mark.parametrize("bootstrap", [True, False])
@pytest.mark.parametrize("kind", ["classifier", "regressor"])
def test_a_grid_scores_what_each_candidate_scores_alone(kind, bootstrap):
    """Tree ti of a fold is the same tree at every count, and a
    candidate's votes are added in the same order: a depth x count grid's
    split scores, and the refit it picks, are those of its candidates
    searched one at a time, to the bit."""
    cls, X, y = forest_problem(kind)
    est = cls(random_state=0, bootstrap=bootstrap)
    grid = search(est, X, y, {"max_depth": [2, 4], "n_estimators": [3, 5, 2]},
                  refit=True)
    together = rows_by_params(grid)
    assert len(grid.search_report["lanes_per_launch"]) == 2
    alone = [dict(grid.best_params_), {"max_depth": 4, "n_estimators": 2},
             {"max_depth": 2, "n_estimators": 5}]
    for i, params in enumerate(alone):
        solo = search(est, X, y, {k: [v] for k, v in params.items()},
                      refit=i == 0)
        assert np.array_equal(split_scores(solo)[0],
                              together[tuple(sorted(params.items()))])
        if i == 0:
            read = "predict_proba" if kind == "classifier" else "predict"
            assert np.array_equal(
                getattr(solo.best_estimator_, read)(X),
                getattr(grid.best_estimator_, read)(X))


@pytest.mark.parametrize("config,launches", [
    ({}, 1),                                   # padded to the mesh's width
    ({"one_device": True}, 1),                 # the group's own width
    ({"one_device": True, "max_tasks_per_batch": 2 * FOLDS}, 3),
])
def test_a_group_of_counts_is_one_launch_where_it_fits(config, launches):
    """Five counts (once enough for chunks graded by tree count): one
    launch that grows the largest count's trees once.  Where the width
    is forced below the group, each chunk grows its own largest, a padded
    lane raises none, and every score is what the whole group's launch
    gives."""
    counts = [4, 2, 6, 3, 5]
    X, y = make_data(400, 10, 3, 4)
    est = RandomForestClassifier(random_state=0, max_depth=3)
    gs = search(est, X, y, {"n_estimators": counts}, **config)
    rep = gs.search_report
    assert len(rep["lanes_per_launch"]) == launches
    assert rep["trees_per_candidate"] == counts
    chunks = [counts[i:i + 2] for i in range(0, 5, 2)] \
        if launches == 3 else [counts]
    assert rep["trees_grown_per_launch"] == [FOLDS * max(c) for c in chunks]
    assert rep["tree_slots_per_launch"] == rep["trees_grown_per_launch"]
    assert not any(g["sorted"] for g in rep["geometry"]["groups"])
    whole = search(est, X, y, {"n_estimators": counts}, one_device=True)
    assert np.array_equal(split_scores(gs), split_scores(whole))


@pytest.mark.parametrize("kind", ["classifier", "regressor"])
def test_fit_is_the_launchs_loop_with_one_forest(kind):
    """`fit` (a direct call) and `fit_task_batched` (a search's launch)
    run one loop: a task of the launch is the model `fit` returns for its
    candidate and fold."""
    import jax
    cls, X, y = forest_problem(kind)
    family = sst.models.base.resolve_family(cls())
    data, meta = family.prepare_data(X, y)
    family.observe_candidates([{"n_estimators": 3}, {"n_estimators": 5}],
                              {"random_state": 0, "max_depth": 3}, meta)
    static = {"random_state": 0, "max_depth": 3}
    rng = np.random.default_rng(0)
    masks = (rng.random((2, len(y))) < 0.7).astype(np.float32)
    counts = np.asarray([3, 5], np.int32)
    launch = jax.jit(lambda n, w: family.fit_task_batched(
        {"n_estimators": n}, {**static, "__n_folds__": 2}, data, w, meta))(
            np.repeat(counts, 2), np.tile(masks, (2, 1)))
    for c, count in enumerate(counts):
        for f in range(2):
            one = jax.jit(lambda n, w: family.fit(
                {"n_estimators": n}, static, data, w, meta))(count, masks[f])
            for leaf in one:
                assert np.array_equal(np.asarray(one[leaf]),
                                      np.asarray(launch[leaf][2 * c + f]))


def test_scalers_in_front_of_a_forest_share_its_launch():
    """Binning is invariant under the scalers, so the pipeline's launch
    is the bare forest's: the same scores, one launch for its counts, and
    nothing the shared-prefix stage could stage."""
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler
    X, y = make_data(400, 10, 3, 4)
    forest = RandomForestClassifier(random_state=0, max_depth=3)
    bare = search(forest, X, y, {"n_estimators": [4, 2, 5]})
    piped = search(Pipeline([("scale", StandardScaler()), ("rf", forest)]),
                   X, y, {"rf__n_estimators": [4, 2, 5]})
    assert np.array_equal(split_scores(piped), split_scores(bare))
    rep = piped.search_report
    assert len(rep["lanes_per_launch"]) == 1
    assert rep["prefix"]["fallbacks"] == ["not-a-compiled-pipeline"]
