"""The boosting families against their plain reference, the reference against
scikit-learn, and what this stage loop owes the engine (XLA:CPU, small sizes).

``benchmark/reference_boost.py`` imports nothing of the program: its own
binning, its own left-side sums (one product a level of the 0/1 masks ``code
<= bin`` with the statistics), its own routing.  A binned second-order tree
differs from scikit-learn's exact ``friedman_mse`` one by design, so the
reference is tied to ``GradientBoostingClassifier`` at accuracy level; the
program is then held to the reference stage for stage: the same (feature,
bin) at every node, F to float32 rounding, the same split scores.
"""

import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.ensemble import (GradientBoostingClassifier,
                              GradientBoostingRegressor)
from sklearn.model_selection import KFold, StratifiedKFold

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models import trees as tree_models
from spark_sklearn_tpu.models.base import CANDIDATE_AXIS, resolve_family
from spark_sklearn_tpu.ops import tree_hist
from spark_sklearn_tpu.ops.trees import grow_tree
from spark_sklearn_tpu.utils.native import quantile_bin

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generate            # noqa: E402
import reference_boost     # noqa: E402

FOLDS = 3
CONFIG = {"estimator": {"params": {"random_state": 0}}}
STATIC = {"random_state": 0}
GBC = tree_models.GradientBoostingClassifierFamily
GBR = tree_models.GradientBoostingRegressorFamily
BOOST_SCOPES = ("sst.boost.gradient", "sst.boost.update")


def make_data(n, d, k, seed, separation=0.5):
    return generate.make_data({
        "n_samples": n, "n_features": d, "n_classes": k, "latent": 8,
        "separation": separation, "pixel_noise": 1.0, "base_seed": seed})


def split_scores(gs, folds=FOLDS):
    return np.stack([gs.cv_results_[f"split{i}_test_score"]
                     for i in range(folds)], axis=1)


def search(estimator, X, y, grid, one_device=False, **config):
    if one_device:
        config["devices"] = jax.devices()[:1]
    cv = StratifiedKFold(FOLDS) if y.dtype.kind == "i" else KFold(FOLDS)
    return sst.GridSearchCV(
        estimator, grid, cv=cv, backend="tpu", refit=False,
        config=sst.TpuConfig(**config) if config else None).fit(X, y)


def fold_mask(n, seed=0, share=0.7):
    return (np.random.default_rng(seed).random(n) < share).astype(np.float32)


def fit_direct(family, X, y, counts, lr=0.2, t_max=None):
    """``family.fit`` on lanes of ``counts`` under the engine's two vmaps
    (one fold): the models' leaves, a lane a count."""
    data, meta = family.prepare_data(X, y)
    meta["max_estimators"] = int(t_max or max(counts))
    w = fold_mask(len(y))

    def launch(n_est):
        def one(c):
            return jax.vmap(lambda m: family.fit(
                {"learning_rate": jnp.float32(lr), "n_estimators": c},
                STATIC, data, m, meta))(w[None])
        return jax.vmap(one, axis_name=CANDIDATE_AXIS)(n_est)
    out = jax.jit(launch)(np.asarray(counts, np.int32))
    return {k: np.asarray(v)[:, 0] for k, v in out.items()}


# --- the reference against scikit-learn --------------------------------------

@pytest.mark.parametrize("seed,rate,count", [(7, 0.1, 30), (11, 0.4, 12)])
def test_reference_is_a_booster_at_scikit_learns_accuracy(seed, rate, count):
    X, y = make_data(1500, 12, 2, seed)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    candidate = {"learning_rate": rate, "n_estimators": count}
    ours, stages = reference_boost.boost_cv_scores(
        X, y, splits, [candidate], CONFIG)
    assert stages == count * FOLDS
    theirs = [GradientBoostingClassifier(random_state=0, **candidate)
              .fit(X[tr], y[tr]).score(X[te], y[te]) for tr, te in splits]
    assert abs(ours.mean() - np.mean(theirs)) < 0.03
    assert ours.mean() > 0.6


def test_reference_binning_is_the_programs():
    X, _ = make_data(1500, 9, 2, 1)
    _, codes = quantile_bin(X, 256)
    assert np.array_equal(reference_boost.bin_features(X), codes)


def test_reference_reads_one_run_at_every_count():
    """Candidates of one learning rate are one run a fold, read at their
    counts: 3 + 5 + 8 stages' answers come from 8 stages, and each is what
    the candidate scores alone."""
    X, y = make_data(600, 8, 2, 2)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    cands = [{"learning_rate": 0.2, "n_estimators": m} for m in (5, 3, 8)]
    together, stages = reference_boost.boost_cv_scores(
        X, y, splits, cands, CONFIG)
    assert stages == 8 * FOLDS
    for j, c in enumerate(cands):
        alone, _ = reference_boost.boost_cv_scores(X, y, splits, [c], CONFIG)
        assert np.array_equal(alone[0], together[j])


def test_reference_states_the_two_class_model_only():
    X, y = make_data(300, 6, 3, 2)
    with pytest.raises(ValueError, match="two-class"):
        reference_boost.boost_cv_scores(
            X, y, list(StratifiedKFold(FOLDS).split(X, y)),
            [{"n_estimators": 2}], CONFIG)


# --- the program against the reference ---------------------------------------

def kernels_form(codes, stats, n_bins, integer_stats=False, order=None):
    """The grower's rows in the kernels' form, interpreted: real-valued
    statistics as three bfloat16 parts, every feature of every node; in
    a launch's row order (`kernels_order`) where the caller has one."""
    return tree_hist.GroupedLevels(codes, stats, n_bins, integer_stats,
                                   tile=128, interpret=True, order=order)


def kernels_order(made=None):
    """`tree_hist.row_order` for `kernels_form`, whatever the platform;
    notes each order it makes in `made`."""
    def row_order(codes, w):
        if made is not None:
            made.append(codes.shape)
        return tree_hist.launch_order(codes, w, tile=128)
    return row_order


@pytest.mark.parametrize("n,d,seed,rate", [(900, 8, 3, 0.4),
                                           (700, 54, 4, 0.1)])
def test_kernels_grow_the_references_trees(n, d, seed, rate, monkeypatch):
    """Stage by stage on the kernels' form (what a TPU runs): the same
    (feature, bin) at every node of the first stages, and F within float32
    rounding of the reference's after them."""
    monkeypatch.setattr(tree_hist, "levels_of", kernels_form)
    X, y = make_data(n, d, 2, seed)
    codes = jnp.asarray(reference_boost.bin_features(X))
    w = jnp.asarray(fold_mask(n, seed))
    yf = jnp.asarray(y, jnp.float32)
    stages = 5
    _, F_ref, features, cuts = reference_boost.boost(
        codes, jnp.asarray(y, jnp.int32), w, np.float32(rate),
        np.float32(1.0), reference_boost.stage_keys(0, stages),
        jnp.asarray([stages], jnp.int32), depth=3, n_stages=stages)
    share = float((w * yf).sum() / w.sum())
    F = jnp.full((n,), np.log(share / (1 - share)), jnp.float32)
    for t in range(stages):
        p = jax.nn.sigmoid(F)
        tree = grow_tree(codes, (p - yf)[:, None], p * (1 - p), w, 3, 256,
                         min_child_weight=1.0, reg_lambda=1e-6)
        feature, cut = np.asarray(tree.feat[:7]), np.asarray(tree.thresh[:7])
        assert np.array_equal(feature, np.asarray(features[t])), t
        splits = feature >= 0
        assert np.array_equal(cut[splits], np.asarray(cuts[t])[splits]), t
        F = F + np.float32(rate) * tree.value[tree.leaf][:, 0]
    # five Newton steps of at most a few units each, float32 throughout
    assert np.abs(np.asarray(F) - np.asarray(F_ref)).max() < 2e-5


def test_search_scores_what_the_reference_scores():
    """3 learning rates x 3 counts x 3 folds through the normal path: the
    same predictions, so the same split scores to the last flipped row."""
    X, y = make_data(900, 8, 2, 3)
    grid = {"learning_rate": [0.1, 0.4, 0.2], "n_estimators": [4, 2, 6]}
    gs = search(GradientBoostingClassifier(random_state=0), X, y, grid)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    ref, _ = reference_boost.boost_cv_scores(
        X, y, splits, list(gs.cv_results_["params"]), CONFIG)
    assert np.abs(split_scores(gs) - ref).max() < 1e-6
    n_test = len(splits[0][1])
    assert np.array_equal(np.rint(split_scores(gs) * n_test),
                          np.rint(ref * n_test))


def test_a_count_is_a_prefix_to_the_bit():
    """Stage t depends on the stages before it and on nothing else: a lane
    of 3 stages beside lanes of 8 and 5, in a program built for 8, holds the
    F of a 3-stage fit alone in a program built for 3, and of the 8-stage
    lane cut... by construction, since the 8-stage lane IS that fit plus
    five stages."""
    X, y = make_data(500, 10, 2, 6)
    together = fit_direct(GBC, X, y, [3, 8, 5])
    assert together["n_iter"].tolist() == [3, 8, 5]
    for lane, count in enumerate([3, 8, 5]):
        alone = fit_direct(GBC, X, y, [count])
        assert np.array_equal(alone["logits"][0], together["logits"][lane])
        # ... and of a fit stopped at `count` by its own n_estimators in a
        # program built for a larger grid
        capped = fit_direct(GBC, X, y, [count], t_max=8)
        assert np.array_equal(capped["logits"][0], together["logits"][lane])


# --- one tree a stage for two classes ----------------------------------------

def test_two_classes_grow_one_tree_a_stage():
    X, y = make_data(600, 10, 2, 5)
    model = fit_direct(GBC, X, y, [4])
    # the raw score is the log-odds, a 1-D margin; class 1 where it is > 0
    assert model["logits"].shape == (1, 600)
    assert np.array_equal(model["pred"][0], (model["logits"][0] > 0))
    data, meta = GBC.prepare_data(X, y)
    lane = {k: v[0] for k, v in model.items()}
    proba = np.asarray(GBC.predict_proba(lane, STATIC, None, meta))
    assert proba.shape == (600, 2)
    assert np.allclose(proba.sum(axis=1), 1.0, atol=1e-6)
    assert np.array_equal(np.asarray(GBC.decision(lane, STATIC, None, meta)),
                          lane["logits"])
    assert GBC._trees_per_stage(meta) == 1
    assert GBC._trees_per_stage({"n_classes": 3}) == 3


def test_two_classes_agree_with_scikit_learn():
    X, y = make_data(900, 12, 2, 8)
    grid = {"learning_rate": [0.1, 0.3], "n_estimators": [10, 30]}
    est = GradientBoostingClassifier(max_depth=3, random_state=0)
    ours = search(est, X, y, grid)
    theirs = sst.GridSearchCV(est, grid, cv=StratifiedKFold(FOLDS),
                              backend="host", refit=False).fit(X, y)
    np.testing.assert_allclose(ours.cv_results_["mean_test_score"],
                               theirs.cv_results_["mean_test_score"],
                               atol=5e-2)


def parent_multiclass_fit(dynamic, static, data, train_w, meta):
    """``GradientBoostingClassifierFamily.fit`` as the commit before the
    binary path had it (PR 38, 86a106d), word for word but for the names it
    takes from its module."""
    codes, y1h = data["codes"], data["y1h"]
    n = codes.shape[0]
    k = meta["n_classes"]
    depth = 3
    t_max = int(meta.get("max_estimators")
                or static.get("n_estimators", 100))
    lr = jnp.asarray(dynamic.get(
        "learning_rate", static.get("learning_rate", 0.1)), jnp.float32)
    n_est = jnp.asarray(dynamic.get(
        "n_estimators", static.get("n_estimators", 100)), jnp.int32)
    subsample = jnp.asarray(dynamic.get(
        "subsample", static.get("subsample", 1.0)), jnp.float32)
    min_leaf = float(static.get("min_samples_leaf", 1))
    key = jax.random.PRNGKey(0)

    wsum = jnp.sum(train_w) + 1e-12
    prior = jnp.clip(
        (train_w[:, None] * y1h).sum(0) / wsum, 1e-6, 1 - 1e-6)
    F = jnp.broadcast_to(jnp.log(prior)[None, :], (n, k)).astype(
        jnp.float32) + jnp.zeros((n, k), jnp.float32)
    keys = jax.random.split(key, t_max)
    n_lim = jnp.minimum(n_est, t_max)

    def one_stage(carry):
        t, F = carry
        k_t = keys[t]
        P = jax.nn.softmax(F, axis=1)
        w_t = train_w * (
            jax.random.uniform(k_t, (n,)) < subsample).astype(jnp.float32)

        def per_class(g_c, h_c):
            return grow_tree(codes, g_c[:, None], h_c, w_t, depth,
                             256, min_child_weight=min_leaf,
                             reg_lambda=1e-6)

        G = (P - y1h)
        H = P * (1.0 - P)
        trees_k = jax.vmap(per_class, in_axes=(1, 1))(G, H)
        delta = jax.vmap(lambda tr: tree_models._own_rows(tr)[:, 0],
                         in_axes=0, out_axes=1)(trees_k)
        live = (t < n_est).astype(jnp.float32)
        return t + 1, F + lr * live * delta

    _, F = jax.lax.while_loop(
        lambda c: c[0] < n_lim, one_stage, (jnp.asarray(0, jnp.int32), F))
    return {"pred": jnp.argmax(F, axis=1).astype(jnp.int32), "logits": F}


@pytest.mark.parametrize("k,subsample", [(3, 1.0), (5, 1.0), (3, 0.6)])
def test_three_and_more_classes_are_the_parents_to_the_bit(k, subsample):
    """A tree a class on the softmax's gradients, as before the binary
    path: the same F, bit for bit, as the parent's stage written out."""
    X, y = make_data(300, 10, k, 21, separation=1.0)
    data, meta = GBC.prepare_data(X, y)
    meta["max_estimators"] = 7
    dyn = {"learning_rate": np.float32(0.2), "n_estimators": np.int32(7),
           "subsample": np.float32(subsample)}
    w = fold_mask(len(y))
    ours = jax.jit(lambda d, m: GBC.fit(d, STATIC, data, m, meta))(dyn, w)
    parents = jax.jit(lambda d, m: parent_multiclass_fit(
        d, STATIC, data, m, meta))(dyn, w)
    assert ours["logits"].shape == (300, k)
    assert np.array_equal(np.asarray(ours["logits"]),
                          np.asarray(parents["logits"]))
    assert np.array_equal(np.asarray(ours["pred"]),
                          np.asarray(parents["pred"]))


# --- three parts a statistic --------------------------------------------------

def test_one_part_is_another_histogram_on_real_gradients():
    """The limits' control is a real fault: float32 gradients in ONE
    bfloat16 part read another histogram, in three parts the plain form's
    to float32 rounding."""
    rng = np.random.default_rng(3)
    n, d = 512, 6
    codes = jnp.asarray(rng.integers(0, 256, (n, d)), jnp.uint8)
    p = rng.random(n).astype(np.float32)
    stats = jnp.asarray(np.stack([p * (1 - p), p - (rng.random(n) < 0.5)],
                                 axis=1), jnp.float32)
    plain = np.asarray(tree_hist.PlainLevels(codes, stats, 256).histograms(0))
    three = np.asarray(kernels_form(codes, stats, 256).histograms(0))
    one = np.asarray(kernels_form(codes, stats, 256, True).histograms(0))
    scale = np.abs(plain).max()
    off_three = np.abs(three[:, :d, :2] - plain).max()
    off_one = np.abs(one[:, :d, :2] - plain).max()
    assert off_three < 1e-5 * scale
    # 512 rounding errors of 2^-9 each, of either sign
    assert off_one > 1e-4 * scale and off_one > 50 * off_three


@pytest.mark.parametrize("parts", [1, 3])
def test_parts_are_rounded_by_an_operation_of_their_own(parts):
    """A convert to bfloat16 and back is one XLA:TPU leaves out: on the chip
    `rest - part` was zero and every part after the first with it (PERF.md
    section 6, PR 39).  The rounding is a ``reduce_precision``, which no
    compiler may drop; the parts add up to the float32 value to the bit."""
    x = np.random.default_rng(1).standard_normal((257, 2)).astype(np.float32)
    text = jax.jit(lambda s: tree_hist.split_parts(s, 8, parts)).lower(
        x).as_text()
    assert text.count("stablehlo.reduce_precision") == parts
    got = np.asarray(tree_hist.split_parts(jnp.asarray(x), 8, parts)
                     .astype(jnp.float32)).reshape(257, parts, 8)[:, :, :2]
    first = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(
        jnp.float32))
    assert np.array_equal(got[:, 0], first)
    if parts == 3:
        assert np.array_equal(got.sum(axis=1), x)
        assert np.abs(got[:, 1]).max() > 0 and np.abs(got[:, 2]).max() > 0


# --- the ledger and the plan --------------------------------------------------

@pytest.mark.parametrize("family,k,trees", [(GBR, None, 1), (GBC, 2, 1),
                                            (GBC, 4, 4)])
def test_launch_workspace_prices_a_lane_by_its_rows(family, k, trees):
    meta = {"n_features": 54, "max_estimators": 100}
    if k:
        meta["n_classes"] = k
    # rows that change from stage to stage: a tree sorts its own
    sampled = {"subsample": 0.8}
    ws = family.launch_workspace(145_253, meta, 5, static=sampled)
    assert ws["fixed_bytes"] == 0
    row = 5 * tree_hist.row_bytes(54, 2, integer_stats=False) + 16
    assert row == 736
    assert ws["per_candidate_bytes"] == 5 * trees * 145_253 * (row + 24)
    # the fold's training rows at every stage: one copy of a row a tree
    # but its codes, which are its fold's order's, and a fold's order (its
    # codes twice, its place both ways)
    fixed = family.launch_workspace(145_253, meta, 5, static={})
    assert tree_hist.order_row_bytes(54) == 136
    assert tree_hist.row_bytes(54, 2, False, own_codes=False) == 80
    assert fixed["fixed_bytes"] == 5 * 145_253 * 136
    assert fixed["per_candidate_bytes"] == 5 * trees * 145_253 * (96 + 24)
    # ... unless the search names subsample
    assert family.launch_workspace(
        145_253, {**meta, "subsample_searched": True}, 5, static={}) == ws
    # deep trees on few rows: the deepest level's histograms decide
    deep = family.launch_workspace(1000, meta, 5, static={"max_depth": 10})
    assert deep["per_candidate_bytes"] > 5 * trees * 1.39 * (
        512 * 54 * 2 * 256 * 4)
    # laid out in runs of 3: a third of a lane and three copies of its F
    run = family.launch_workspace(
        145_253, meta, 5, static={**sampled, tree_models._STAGE_RUN: 3})
    assert run["fixed_bytes"] == 0
    assert run["per_candidate_bytes"] == 5 * (
        trees * 145_253 * (row + 24) // 3 + 3 * 4 * 145_253 * trees)


def test_width_cap_binds_under_a_small_budget():
    """The ledger sees a boosting lane: under a budget that holds two
    candidates' lanes the group is planned narrower than its run of five,
    and scores what it scores uncapped."""
    X, y = make_data(600, 10, 2, 5)
    grid = {"learning_rate": [0.05, 0.1, 0.2, 0.4, 0.8],
            "n_estimators": [3]}
    est = GradientBoostingClassifier(random_state=0)
    free = search(est, X, y, grid, one_device=True)
    group = free.search_report["memory"]["groups"][0]
    # the launch's one thing whatever its width: a fold's row order
    assert group["workspace_bytes"] > 0
    assert group["fixed_bytes"] == FOLDS * 600 * tree_hist.order_row_bytes(10)
    lane = group["per_candidate_bytes"]
    # a tree of it holds a row's kernels' bytes (in the fold's one order,
    # whose codes it reads)
    assert lane > FOLDS * 600 * tree_hist.row_bytes(10, 2, False,
                                                    own_codes=False)
    budget = int(group["resident_bytes"] + group["fixed_bytes"]
                 + 2.5 * lane)
    capped = search(est, X, y, grid, one_device=True,
                    hbm_budget_bytes=budget)
    geometry = capped.search_report["geometry"]["groups"]
    assert [g["width"] for g in geometry] == [2]
    assert sum(g["capped"] for g in geometry) == 1
    assert all(g["chunk_bytes"] + g["resident_bytes"] <= budget
               for g in capped.search_report["memory"]["groups"])
    assert np.array_equal(split_scores(capped), split_scores(free))


@pytest.mark.parametrize("room,width,runs", [
    (4.5, 4, {2}),        # the widest whole runs under the cap
    (1.5, 1, {1}),        # narrower than a run: the plain program
])
def test_width_cap_below_a_run_runs_the_plain_program(room, width, runs,
                                                      monkeypatch):
    """A budget that holds `room` laid-out candidates: a width of whole
    runs keeps the layout, a width under a run gets the program without
    it (and is priced so), and both score what the uncapped search
    scores."""
    X, y = make_data(600, 10, 2, 5)
    grid = {"learning_rate": [0.05, 0.1, 0.2, 0.4, 0.8],
            "n_estimators": [2, 3]}
    est = GradientBoostingClassifier(random_state=0)
    free = search(est, X, y, grid, one_device=True)
    group = free.search_report["memory"]["groups"][0]
    assert group["width"] == 10 and not group["capped"]
    budget = int(group["resident_bytes"] + group["fixed_bytes"]
                 + room * group["per_candidate_bytes"])
    seen = []
    real = tree_models._stage_run
    monkeypatch.setattr(tree_models, "_stage_run", lambda static, n: (
        seen.append(real(static, n)) or seen[-1]))
    capped = search(est, X, y, grid, one_device=True,
                    hbm_budget_bytes=budget)
    geometry = capped.search_report["geometry"]["groups"]
    assert [(g["width"], g["capped"]) for g in geometry] == [(width, True)]
    assert set(seen) == runs
    rec = capped.search_report["memory"]["groups"][0]
    assert (rec["per_candidate_bytes"] > group["per_candidate_bytes"]) \
        == (runs == {1})
    assert np.array_equal(split_scores(capped), split_scores(free))


def empty_program_cache():
    from spark_sklearn_tpu.search import grid as engine
    engine._PROGRAM_CACHE.clear()
    engine._PROGRAM_CACHE_FAMILY_COUNTS.clear()


@pytest.fixture
def fresh_programs():
    """The in-memory program cache emptied on both sides, and given back:
    a search planted with the parent's launch must not meet this
    program's under the same key, nor leave its own behind."""
    from spark_sklearn_tpu.search import grid as engine
    saved = (dict(engine._PROGRAM_CACHE),
             dict(engine._PROGRAM_CACHE_FAMILY_COUNTS))
    empty_program_cache()
    yield
    empty_program_cache()
    engine._PROGRAM_CACHE.update(saved[0])
    engine._PROGRAM_CACHE_FAMILY_COUNTS.update(saved[1])


def as_on_the_parent(monkeypatch):
    """The boosters as PR 39 had them: no layout, a lane a (candidate,
    fold) under the engine's per-task vmaps, cut where the counts change;
    built anew (`fresh_programs`)."""
    monkeypatch.setattr(tree_models, "_stage_runs", lambda *a: None)
    monkeypatch.delattr(GBR, "fit_task_batched")
    empty_program_cache()


FIFTEEN = {"learning_rate": [0.025, 0.05, 0.1, 0.2, 0.4],
           "n_estimators": [2, 4, 3]}


def test_fifteen_candidates_of_three_counts_are_one_launch_of_five_runs(
        fresh_programs):
    """The cell's grid: 5 runs of 3 counts, one stage loop a (learning
    rate, fold) to 4 stages, read at 2, 3 and 4: 5 x 3 x 4 lane-stages
    where a launch a count grew 15 x (2 + 3 + 4)."""
    X, y = make_data(400, 8, 2, 4)
    gs = search(GradientBoostingClassifier(random_state=0), X, y, FIFTEEN,
                one_device=True)
    rep = gs.search_report
    assert [(g["width"], g["n_chunks"], g["sorted"])
            for g in rep["geometry"]["groups"]] == [(15, 1, False)]
    assert rep["lanes_per_launch"] == [15 * FOLDS]
    assert rep["solver_iters_per_launch"] == [4]
    assert rep["tree_steps_per_launch"] == [5 * FOLDS * 4]
    assert rep["trees_grown_per_launch"] == rep["tree_steps_per_launch"]
    # each candidate's own count, in cv_results_ order
    assert rep["trees_per_candidate"] == [
        p["n_estimators"] for p in gs.cv_results_["params"]]
    group = rep["memory"]["groups"][0]
    lane = GBC.launch_workspace(400, {"n_features": 8, "n_classes": 2},
                                FOLDS, static={})["per_candidate_bytes"]
    assert group["workspace_bytes"] < 15 * lane / 2


def test_fifteen_candidates_of_three_counts_were_three_launches_of_five(
        monkeypatch, fresh_programs):
    """The parent's plan of the same grid, and the same split scores: a
    candidate's stages are the same trees whichever launch grew them."""
    X, y = make_data(400, 8, 2, 4)
    est = GradientBoostingClassifier(random_state=0)
    runs = search(est, X, y, FIFTEEN, one_device=True)
    as_on_the_parent(monkeypatch)
    gs = search(est, X, y, FIFTEEN, one_device=True)
    rep = gs.search_report
    assert [(g["width"], g["n_chunks"], g["sorted"])
            for g in rep["geometry"]["groups"]] == [(5, 3, True)]
    assert rep["lanes_per_launch"] == [5 * FOLDS] * 3
    assert rep["solver_iters_per_launch"] == [2, 3, 4]
    # no lane is carried past its own count
    assert rep["tree_steps_per_launch"] == [15 * 2, 15 * 3, 15 * 4]
    assert sum(rep["trees_per_candidate"]) * FOLDS == sum(
        rep["tree_steps_per_launch"])
    assert np.array_equal(split_scores(runs), split_scores(gs))


def test_fifteen_candidates_on_the_mesh_launch_whole_runs(
        monkeypatch, fresh_programs):
    """On the suite's 8-device mesh the shard multiple (16) is no
    multiple of the run (3): the group's width is rounded to whole runs
    of shard stripes, 24, and its one launch grows 8 runs (5 and 3 of
    padding) a fold to 4 stages, fewer lane-stages than the parent's
    sorted launches of 8, and scores what they score."""
    X, y = make_data(400, 8, 2, 4)
    est = GradientBoostingClassifier(random_state=0)
    runs = search(est, X, y, FIFTEEN)
    rep = runs.search_report
    assert [(g["width"], g["n_chunks"]) for g in
            rep["geometry"]["groups"]] == [(24, 1)]
    assert rep["tree_steps_per_launch"] == [8 * FOLDS * 4]
    as_on_the_parent(monkeypatch)
    parent = search(est, X, y, FIFTEEN)
    assert sum(rep["tree_steps_per_launch"]) < sum(
        parent.search_report["tree_steps_per_launch"])
    assert np.array_equal(split_scores(runs), split_scores(parent))


@pytest.mark.parametrize("grid", [
    # runs of 3 and 2 counts: ragged, so cut where the counts change
    [{"learning_rate": [0.1], "n_estimators": [2, 4, 3]},
     {"learning_rate": [0.3], "n_estimators": [2, 3]}],
    # every candidate its own learning rate: runs of one, one launch
    {"learning_rate": [0.02, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.8]},
])
def test_grids_that_are_not_whole_runs_plan_and_score_as_on_the_parent(
        grid, monkeypatch, fresh_programs):
    X, y = make_data(300, 8, 2, 7)
    est = GradientBoostingClassifier(random_state=0, n_estimators=3)
    ours = search(est, X, y, grid, one_device=True)
    as_on_the_parent(monkeypatch)
    theirs = search(est, X, y, grid, one_device=True)
    for key in ("lanes_per_launch", "solver_iters_per_launch",
                "tree_steps_per_launch", "trees_per_candidate"):
        assert ours.search_report[key] == theirs.search_report[key], key
    assert [(g["width"], g["n_chunks"], g["sorted"])
            for g in ours.search_report["geometry"]["groups"]] == [
        (g["width"], g["n_chunks"], g["sorted"])
        for g in theirs.search_report["geometry"]["groups"]]
    assert np.array_equal(split_scores(ours), split_scores(theirs))


def launched(family, X, y, cands, t_max, shapes=None):
    """`fit_task_batched` over `cands` (dicts of learning_rate,
    n_estimators, subsample) in `launch_layout`'s order with its fact:
    the tasks' models, the candidates' parameters in that order, the run
    length, and what a candidate's one fit takes (data, meta, masks).
    With `shapes`, the output shapes of the launch's histogram kernel
    calls are appended to it."""
    data, meta = family.prepare_data(X, y)
    meta["max_estimators"] = t_max
    dyn = {k: np.asarray([c[k] for c in cands],
                         family.dynamic_params[k]) for k in cands[0]}
    order, facts, run = family.launch_layout(dyn, STATIC, meta, FOLDS)
    dyn = {k: v[order] for k, v in dyn.items()}
    masks = np.stack([fold_mask(len(y), seed) for seed in range(FOLDS)])

    def launch(d, w):
        return family.fit_task_batched(
            d, {**STATIC, **facts, "__n_folds__": FOLDS}, data, w, meta)

    args = ({k: np.repeat(v, FOLDS) for k, v in dyn.items()},
            np.tile(masks, (len(cands), 1)))
    if shapes is not None:
        text = str(jax.make_jaxpr(launch)(*args))
        shapes += [tuple(int(k) for k in s.split(",")) for s in
                   re.findall(r":f32\[([\d,]+)\] = pallas_call\[", text)]
    tasks = jax.jit(launch)(*args)
    return tasks, dyn, run, (data, meta, masks)


def laid_out(family, X, y, cands, t_max):
    """`launched`, beside each candidate's one-candidate `fit` on each
    fold."""
    tasks, dyn, run, (data, meta, masks) = launched(family, X, y, cands,
                                                    t_max)
    one = jax.jit(lambda d, m: family.fit(d, STATIC, data, m, meta))
    alone = [one({k: v[c] for k, v in dyn.items()}, masks[f])
             for c in range(len(cands)) for f in range(FOLDS)]
    return tasks, alone, dyn, run


@pytest.mark.parametrize("family,k,subsample", [
    (GBR, None, [1.0]), (GBC, 2, [1.0]), (GBC, 3, [1.0]),
    (GBC, 2, [0.5, 0.8])])
def test_a_laid_out_launch_is_each_candidates_own_fit(family, k, subsample):
    """One stage loop a (run, fold), read at each count, gives each
    candidate the raw scores of its own one-candidate `fit`, to the bit,
    and its own count."""
    X, y = make_data(300, 8, k or 2, 13, separation=1.0)
    if family is GBR:
        y = (X[:, 0] * 2 + X[:, 1] * X[:, 2]).astype(np.float32)
    cands = [{"learning_rate": lr, "n_estimators": m, "subsample": s}
             for lr in (0.3, 0.1) for s in subsample for m in (4, 2, 5)]
    tasks, alone, dyn, run = laid_out(family, X, y, cands, t_max=5)
    assert run == 3
    leaf = "logits" if k else "pred"
    assert tasks[leaf].shape[0] == len(cands) * FOLDS
    for t, model in enumerate(alone):
        assert np.array_equal(np.asarray(tasks[leaf][t]),
                              np.asarray(model[leaf])), t
        assert int(tasks["n_iter"][t]) == dyn["n_estimators"][t // FOLDS]
        if k:
            assert np.array_equal(np.asarray(tasks["pred"][t]),
                                  np.asarray(model["pred"]))


def per_stage_fit(dynamic, static, data, train_w, meta):
    """The two-class stage loop as the commit before the launch-wide row
    order had it (commit 405db69), written out for one count: every stage
    draws its row weights, every tree sorts its rows at level 0 and back.
    Returns F."""
    codes, y1h = data["codes"], data["y1h"]
    n = codes.shape[0]
    t_max = int(meta["max_estimators"])
    lr = jnp.asarray(dynamic["learning_rate"], jnp.float32)
    n_est = jnp.asarray(dynamic["n_estimators"], jnp.int32)
    subsample = jnp.asarray(dynamic.get(
        "subsample", static.get("subsample", 1.0)), jnp.float32)
    wsum = jnp.sum(train_w) + 1e-12
    prior = jnp.clip(
        (train_w[:, None] * y1h).sum(0) / wsum, 1e-6, 1 - 1e-6)
    F = jnp.full((n,), jnp.log(prior[1] / (1.0 - prior[1])), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), t_max)

    def one_stage(carry):
        t, F = carry
        p = jax.nn.sigmoid(F)
        w_t = train_w * (jax.random.uniform(keys[t], (n,)) < subsample
                         ).astype(jnp.float32)
        tree = grow_tree(codes, (p - y1h[:, 1])[:, None], p * (1.0 - p),
                         w_t, 3, 256, min_child_weight=1.0,
                         reg_lambda=1e-6)
        return t + 1, F + lr * tree_models._own_rows(tree)[:, 0]

    return jax.lax.while_loop(lambda c: c[0] < n_est, one_stage,
                              (jnp.asarray(0, jnp.int32), F))[1]


@pytest.mark.parametrize("subsample", [None, 0.6])
def test_a_launch_order_keeps_f_to_the_bit(subsample, monkeypatch):
    """2 learning rates x 3 counts x 3 folds on the kernels' form (what a
    TPU runs).  At subsample 1.0, not searched, a lane's stage loop runs
    in ONE row order made before it, and every candidate's F is bit for
    bit the per-stage path's written out (a sort at level 0 and one back
    in every tree); searched at 0.6, the loop makes no order and is that
    path."""
    made = []
    monkeypatch.setattr(tree_hist, "levels_of", kernels_form)
    monkeypatch.setattr(tree_hist, "row_order", kernels_order(made))
    X, y = make_data(300, 8, 2, 17, separation=1.0)
    cands = [{"learning_rate": lr, "n_estimators": m}
             for lr in (0.3, 0.1) for m in (3, 1, 4)]
    if subsample is not None:
        cands = [{**c, "subsample": subsample} for c in cands]
    tasks, dyn, run, (data, meta, masks) = launched(GBC, X, y, cands, 4)
    assert run == 3
    assert len(made) == (1 if subsample is None else 0)
    written = jax.jit(jax.vmap(lambda d, w: per_stage_fit(
        d, STATIC, data, w, meta)))(
        {k: np.repeat(v, FOLDS) for k, v in dyn.items()},
        np.tile(masks, (len(cands), 1)))
    assert np.array_equal(np.asarray(tasks["logits"]), np.asarray(written))
    assert tasks["n_iter"].tolist() == np.repeat(dyn["n_estimators"],
                                                 FOLDS).tolist()


@pytest.mark.parametrize("depth", [2, 6])
@pytest.mark.parametrize("searched", [False, True])
def test_row_sorts_count_a_lanes_order_once(depth, searched, monkeypatch,
                                            fresh_programs):
    """On the kernels' form: with subsample 1.0 and not searched, one sort
    a lane (its order) and one a tree at level 5; searched (even at 1.0),
    a tree sorts at level 0 and at level 5.  2 runs x 3 folds = 6 lanes of
    3 stages, 18 trees.  Built anew (`fresh_programs`): the programs of
    the kernels' form must not serve the plain form's searches."""
    monkeypatch.setattr(tree_hist, "on_tpu", lambda: True)
    monkeypatch.setattr(tree_hist, "levels_of", kernels_form)
    monkeypatch.setattr(tree_hist, "row_order", kernels_order())
    X, y = make_data(300, 8, 2, 9, separation=1.0)
    grid = {"learning_rate": [0.1, 0.3], "n_estimators": [2, 3]}
    if searched:
        grid["subsample"] = [1.0]
    gs = search(GradientBoostingClassifier(random_state=0, max_depth=depth),
                X, y, grid, one_device=True, sort_candidates=False)
    rep = gs.search_report
    lanes, trees = 2 * FOLDS, 3 * 2 * FOLDS
    assert rep["tree_steps_per_launch"] == [trees]
    later = trees if depth > 5 else 0
    assert rep["row_sorts_per_launch"] == [
        trees + later if searched else lanes + later]


@contextlib.contextmanager
def the_parents_kernel_rule():
    """The kernels' ``vmap`` rule as the commit before the shared masks
    had it (93609e2): every batched axis merged into the lanes, a fold's
    codes broadcast to each of its runs (no operand is a lane's own)."""
    saved = dict(tree_hist._OWN)
    tree_hist._OWN.update(dict.fromkeys(saved, ()))
    tree_hist._lanes.cache_clear()
    try:
        yield
    finally:
        tree_hist._OWN.update(saved)
        tree_hist._lanes.cache_clear()


@pytest.mark.parametrize("k", [2, 3])
def test_a_folds_runs_share_its_masks_to_the_bit(k, monkeypatch):
    """2 learning rates x 2 counts x 3 folds on the kernels' form (what a
    TPU runs), two classes (a tree a stage) and three (a tree a class):
    the histogram kernel takes a fold's code tiles once for its 2 runs
    (and 3 classes), ``(runs, folds, classes, ...)``, where the parent's
    rule broadcast the codes to ``runs x folds`` lanes of their own; every
    candidate's raw scores are the parent's to the bit."""
    monkeypatch.setattr(tree_hist, "levels_of", kernels_form)
    monkeypatch.setattr(tree_hist, "row_order", kernels_order())
    X, y = make_data(300, 8, k, 23, separation=1.0)
    cands = [{"learning_rate": lr, "n_estimators": m}
             for lr in (0.3, 0.1) for m in (3, 2)]
    classes = 1 if k == 2 else k
    shapes = []
    tasks, _, run, _ = launched(GBC, X, y, cands, 3, shapes)
    assert run == 2
    assert shapes == [(2, FOLDS, classes, 2 ** level, 32, 8, 256)
                      for level in range(3)]
    with the_parents_kernel_rule():
        parents = []
        planted, _, _, _ = launched(GBC, X, y, cands, 3, parents)
    assert parents == [(2 * FOLDS * classes, 2 ** level, 32, 8, 256)
                       for level in range(3)]
    assert np.array_equal(np.asarray(tasks["logits"]),
                          np.asarray(planted["logits"]))
    assert np.array_equal(np.asarray(tasks["pred"]),
                          np.asarray(planted["pred"]))


@pytest.mark.parametrize("estimator,k,lanes", [
    ("boost", 2, 2),        # a fold's 2 runs read its order
    ("boost", 3, 6),        # ... and a stage's 3 trees a class
    ("subsampled", 2, 1),   # subsample 0.5: every tree its own rows
    ("forest", 3, 1),       # every tree its own bootstrap rows
])
def test_hist_lanes_per_mask_counts_the_lanes_of_a_tile(
        estimator, k, lanes, monkeypatch, fresh_programs):
    """On the kernels' form: the lanes one mask serves.  A laid-out grid
    of 2 learning rates x 2 counts grows 2 runs a fold in its one order;
    a booster at subsample 0.5 and a forest grow each tree in an order of
    its own.  Built anew (`fresh_programs`)."""
    from sklearn.ensemble import RandomForestClassifier
    monkeypatch.setattr(tree_hist, "on_tpu", lambda: True)
    monkeypatch.setattr(tree_hist, "levels_of", kernels_form)
    monkeypatch.setattr(tree_hist, "row_order", kernels_order())
    X, y = make_data(300, 8, k, 9, separation=1.0)
    if estimator == "forest":
        est = RandomForestClassifier(random_state=0, max_depth=3)
        grid = {"n_estimators": [2, 3]}
    else:
        est = GradientBoostingClassifier(
            random_state=0, subsample=0.5 if estimator == "subsampled"
            else 1.0)
        grid = {"learning_rate": [0.1, 0.3], "n_estimators": [2, 3]}
    gs = search(est, X, y, grid, one_device=True, sort_candidates=False)
    assert gs.search_report["hist_lanes_per_mask"] == [lanes]


@pytest.mark.parametrize("dyn,runs", [
    ({"learning_rate": [0.1, 0.2, 0.1, 0.2], "n_estimators": [5, 5, 2, 2]},
     ([2, 0, 3, 1], 2)),
    # float32 values decide a run: 0.1 and 0.1 + 1e-9 are one
    ({"learning_rate": [0.1, 0.1 + 1e-9], "n_estimators": [4, 3]},
     ([1, 0], 2)),
    ({"learning_rate": [0.1, 0.1, 0.2], "n_estimators": [4, 3, 3]}, None),
    ({"learning_rate": [0.1, 0.2, 0.3]}, None),
    ({"learning_rate": [0.1, 0.1], "n_estimators": [4, 3],
      "subsample": [0.5, 0.6]}, None),
])
def test_layout_and_proxy_follow_the_grids_runs(dyn, runs):
    dyn = {k: np.asarray(v, GBC.dynamic_params[k]) for k, v in dyn.items()}
    layout = GBC.launch_layout(dyn, STATIC, {}, FOLDS)
    proxy = GBC.convergence_proxy(dyn, STATIC)
    if runs is None:
        assert layout is None
        assert (proxy is None) == ("n_estimators" not in dyn)
    else:
        order, facts, run = layout
        assert (order.tolist(), run) == runs
        assert facts == {tree_models._STAGE_RUN: run}
        assert proxy is None


def test_a_scaled_booster_gets_the_layout(monkeypatch):
    """Monotone scalers in front of a booster: the pipeline hands the
    final step's layout, its fact under the step's prefix, to the bare
    booster's launch, and scores what the bare booster scores."""
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler
    from spark_sklearn_tpu.models.pipeline import \
        BinnedInvariantPipelineFamily
    X, y = make_data(300, 8, 2, 11)
    pipe = Pipeline([("s", StandardScaler()),
                     ("g", GradientBoostingClassifier(random_state=0))])
    family = resolve_family(pipe)
    assert isinstance(family, BinnedInvariantPipelineFamily)
    dyn = {"g__learning_rate": np.float32([0.1, 0.2, 0.1, 0.2]),
           "g__n_estimators": np.int32([3, 3, 2, 2])}
    order, facts, run = family.launch_layout(dyn, {}, {}, FOLDS)
    assert (order.tolist(), run) == ([2, 0, 3, 1], 2)
    assert facts == {"g__" + tree_models._STAGE_RUN: 2}
    assert family.convergence_proxy(dyn, {}) is None
    assert family.convergence_proxy(
        {"g__learning_rate": np.float32([0.1, 0.2, 0.3, 0.4]),
         "g__n_estimators": np.int32([3, 2, 4, 5])}, {}).tolist() == [
        3, 2, 4, 5]
    runs = []
    real = tree_models._stage_run
    monkeypatch.setattr(tree_models, "_stage_run", lambda static, n: (
        runs.append(real(static, n)) or runs[-1]))
    grid = {"learning_rate": [0.1, 0.2], "n_estimators": [2, 3]}
    piped = search(pipe, X, y, {"g__" + k: v for k, v in grid.items()},
                   one_device=True)
    assert runs and set(runs) == {2}
    bare = search(GradientBoostingClassifier(random_state=0), X, y, grid,
                  one_device=True)
    assert piped.search_report["solver_iters_per_launch"] == [3]
    assert np.array_equal(split_scores(piped), split_scores(bare))


def test_a_scaled_booster_on_a_ragged_grid_is_sorted_as_the_bare_one():
    """Runs of 3 and 2 counts behind a monotone scaler: no layout, so the
    forwarded proxy sorts and cuts the group where the counts change, as
    for the bare booster, and the two score alike."""
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler
    X, y = make_data(300, 8, 2, 11)
    pipe = Pipeline([("s", StandardScaler()),
                     ("g", GradientBoostingClassifier(random_state=0))])
    grid = [{"learning_rate": [0.1], "n_estimators": [2, 4, 3]},
            {"learning_rate": [0.3], "n_estimators": [2, 3]}]
    piped = search(pipe, X, y, [{"g__" + k: v for k, v in g.items()}
                                for g in grid], one_device=True)
    bare = search(GradientBoostingClassifier(random_state=0), X, y, grid,
                  one_device=True)
    plans = [[(g["width"], g["n_chunks"], g["sorted"])
              for g in gs.search_report["geometry"]["groups"]]
             for gs in (piped, bare)]
    assert plans[0] == plans[1] and all(s for _, _, s in plans[0])
    for key in ("lanes_per_launch", "solver_iters_per_launch"):
        assert piped.search_report[key] == bare.search_report[key], key
    assert np.array_equal(split_scores(piped), split_scores(bare))


@pytest.mark.parametrize("proxy,graded,width", [
    (np.repeat([25, 50, 100], 5), 2, 5),        # the cell: a launch a count
    (np.repeat([25, 50, 100], 200), 75, 200),
    (np.repeat(np.arange(100), 10), 125, 125),  # short runs: graded as ever
    (np.logspace(-4, 3, 300), 38, 38),          # all differ: as the parent
    (np.logspace(-4, 3, 1000), 125, 125),
    (np.asarray([5, 5, 5, 9, 9]), 1, 1),        # runs of unequal length
])
def test_sorted_launches_are_cut_where_equal_proxies_end(proxy, graded,
                                                         width):
    from spark_sklearn_tpu.search.grid import (_SORTED_LAUNCHES,
                                               _sorted_launch_width)
    assert graded == -(-len(proxy) // _SORTED_LAUNCHES)
    assert _sorted_launch_width(np.sort(proxy), graded) == width


def test_a_grid_of_distinct_c_plans_as_on_the_parent():
    """Every proxy differs: the sorted group is cut into about eight graded
    launches, the width the parent's formula gives."""
    from sklearn.linear_model import LogisticRegression
    from spark_sklearn_tpu.search.grid import _SORTED_LAUNCHES
    X, y = make_data(300, 10, 3, 2, separation=1.0)
    gs = search(LogisticRegression(max_iter=10), X, y,
                {"C": np.logspace(-3, 2, 40).tolist()}, one_device=True)
    assert [(g["width"], g["n_chunks"], g["sorted"])
            for g in gs.search_report["geometry"]["groups"]] == [
        (-(-40 // _SORTED_LAUNCHES), 8, True)]


# --- scopes and counters ------------------------------------------------------

@pytest.mark.parametrize("family,k", [(GBC, 2), (GBC, 3), (GBR, None)])
@pytest.mark.parametrize("scope", BOOST_SCOPES)
def test_stage_scopes_name_the_lowered_programs_ops(scope, family, k):
    n, d = 64, 5
    S = jax.ShapeDtypeStruct
    meta = {"n_features": d, "max_estimators": 4}
    data = {"codes": S((n, d), jnp.uint8), "y": S((n,), jnp.float32)}
    if k:
        meta.update(n_classes=k, classes=np.arange(k))
        data.update(y=S((n,), jnp.int32), y1h=S((n, k), jnp.float32))
    text = jax.jit(lambda dyn, data, w: family.fit(
        dyn, STATIC, data, w, meta)).lower(
        {"learning_rate": S((), jnp.float32),
         "n_estimators": S((), jnp.int32)}, data,
        S((n,), jnp.float32)).as_text(debug_info=True)
    assert scope in text
    # the trees between the two keep their own names
    assert "sst.tree.histogram" in text and "sst.tree.bootstrap" not in text


@pytest.mark.parametrize("scope", BOOST_SCOPES)
def test_stage_scopes_are_declared(scope):
    from spark_sklearn_tpu.obs.spans import SPAN_VOCABULARY, \
        known_scope_names
    assert scope in known_scope_names()
    declared = {d.name: d for d in SPAN_VOCABULARY}[scope]
    assert declared.kind == "scope" and declared.layer == "solvers"
    assert declared.module == "models.trees"


@pytest.mark.parametrize("kind,trees", [("binary", 1), ("multiclass", 3),
                                        ("regressor", 1)])
def test_launch_counters_reach_the_report(kind, trees):
    k = {"binary": 2, "multiclass": 3}.get(kind)
    X, y = make_data(300, 8, k or 2, 9, separation=1.0)
    est = GradientBoostingClassifier(random_state=0, max_depth=2)
    if kind == "regressor":
        y = (X[:, 0] * 2 + X[:, 1] * X[:, 2]).astype(np.float32)
        est = GradientBoostingRegressor(random_state=0, max_depth=2)
    gs = search(est, X, y, {"learning_rate": [0.1, 0.3],
                            "n_estimators": [2, 3]}, one_device=True,
                sort_candidates=False)
    rep = gs.search_report
    # one launch of 4 candidates x folds, in 2 runs of one learning rate:
    # a lane a (run, fold) grown to 3 stages
    assert rep["lanes_per_launch"] == [4 * FOLDS]
    lanes = 2 * FOLDS
    assert rep["solver_iters_per_launch"] == [3]
    assert rep["tree_steps_per_launch"] == [3 * lanes]
    assert rep["tree_slots_per_launch"] == [3 * lanes * trees]
    assert rep["trees_grown_per_launch"] == rep["tree_slots_per_launch"]
    assert rep["tree_levels_per_launch"] == [3 * lanes * trees * 2]
    # the plain form sorts no rows and builds no mask
    assert rep["row_sorts_per_launch"] == [0]
    assert rep["hist_lanes_per_mask"] == [0]
    assert rep["trees_per_candidate"] == [2, 3, 2, 3]
    assert rep["hist_features_per_node"] == [8]
    assert rep["hist_bytes_per_lane"] == [2 * 8 * 2 * 256 * 4]
    assert [(g["hist_features"], g["n_features"])
            for g in rep["per_group"].values()] == [(8, 8)]


def test_counters_are_the_boosters_own():
    from sklearn.linear_model import LogisticRegression
    X, y = make_data(300, 10, 3, 2, separation=1.0)
    gs = search(LogisticRegression(max_iter=20), X, y, {"C": [0.1, 1.0]})
    assert not gs.search_report.get("tree_steps_per_launch")


def test_family_is_resolved_for_both_boosters():
    assert resolve_family(GradientBoostingClassifier()) is GBC
    assert resolve_family(GradientBoostingRegressor()) is GBR
