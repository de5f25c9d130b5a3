"""The minibatch perceptron against its plain reference, and the reference
against scikit-learn (XLA:CPU, small sizes).

``benchmark/reference_mlp.py`` imports nothing of the program.  It is tied
to ``sklearn.neural_network.MLPClassifier`` by a recipe that makes both take
the same steps: ``shuffle=False`` walks the rows in order, and under
``warm_start=True`` a fitted model's ``coefs_`` / ``intercepts_`` can be
overwritten in place before a second ``fit`` makes a fresh optimiser.  The
program is then held to the reference in its WEIGHTS, not only its scores.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.linear_model import LogisticRegression
from sklearn.model_selection import StratifiedKFold
from sklearn.neural_network import MLPClassifier

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models import mlp
from spark_sklearn_tpu.models.base import CANDIDATE_AXIS
from spark_sklearn_tpu.models.mlp import MLPClassifierFamily

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import estimators        # noqa: E402
import reference_mlp     # noqa: E402
import work_mlp          # noqa: E402

N, D, K, FOLDS, BATCH, EPOCHS = 300, 20, 3, 3, 32, 3
PARAMS = {"max_iter": EPOCHS, "random_state": 0, "batch_size": BATCH,
          "learning_rate_init": 0.01}
CONFIG = {"estimator": {"params": PARAMS}}
COUNTERS = ("minibatch_steps_per_launch", "minibatch_rows_per_launch",
            "mlp_params_per_lane", "epochs_per_candidate")


def make_data(n=N, k=K, seed=0):
    rng = np.random.default_rng(seed)
    centres = 1.5 * rng.standard_normal((k, D))
    y = rng.permutation(np.arange(n) % k)
    X = (centres[y] + rng.standard_normal((n, D))).astype(np.float32)
    return X, y.astype(np.int64)


def search(X, y, grid, params=PARAMS, folds=FOLDS):
    return sst.GridSearchCV(
        estimators.scaled_mlp(**params), grid, cv=StratifiedKFold(folds),
        backend="tpu", refit=False).fit(X, y)


def split_scores(gs, folds=FOLDS):
    return np.stack([gs.cv_results_[f"split{i}_test_score"]
                     for i in range(folds)], axis=1)


# --- the reference against scikit-learn --------------------------------------

def sklearn_from(init, X, y, epochs, sample_weight=None, **params):
    """scikit-learn's own fit from the weights `init`, its rows in order:
    one throw-away epoch makes the attributes, they are overwritten in
    place, and the second ``fit`` under ``warm_start`` starts a fresh
    optimiser from them."""
    sk = MLPClassifier(shuffle=False, max_iter=1, random_state=0, **params)
    with pytest.warns(Warning):          # max_iter reached, both fits
        sk.fit(X, y)
        for i, (W, b) in enumerate(init):
            sk.coefs_[i][...] = np.asarray(W)
            sk.intercepts_[i][...] = np.asarray(b)
        sk.set_params(warm_start=True, max_iter=epochs)
        sk.fit(X, y, sample_weight=sample_weight)
    return sk


@pytest.mark.parametrize("alpha", [0.01, 1.0])
@pytest.mark.parametrize("k, hidden", [(3, (16,)), (10, (12, 8))])
def test_reference_takes_sklearns_steps(k, hidden, alpha):
    """Same initial weights, same batches (the last one of an epoch
    smaller: 330 = 10 x 32 + 10): after four epochs of Adam the weights
    agree to float32 rounding."""
    n = 330
    rng = np.random.default_rng(1)
    X = rng.standard_normal((n, D)).astype(np.float32)
    y = rng.integers(0, k, n)
    init = reference_mlp.initial_weights(0, (D, *hidden, k))
    settings = reference_mlp.settings_of(
        {"estimator": {"params": {"max_iter": 4, "random_state": 0,
                                  "batch_size": BATCH, "shuffle": False}}},
        {"mlp__hidden_layer_sizes": list(hidden), "mlp__alpha": alpha})
    fitted = reference_mlp.fit_fold(jnp.asarray(X), y, n, k, settings,
                                    [alpha])
    sk = sklearn_from(init, X, y, epochs=4, hidden_layer_sizes=hidden,
                      alpha=alpha, batch_size=BATCH)
    assert sk.n_iter_ == 4
    for i, (W, b) in enumerate(fitted):
        assert np.abs(sk.coefs_[i] - np.asarray(W[0])).max() < 5e-6
        assert np.abs(sk.intercepts_[i] - np.asarray(b[0])).max() < 5e-6
        # they moved: the initial weights are 1e-2 and more away
        assert np.abs(sk.coefs_[i] - np.asarray(init[i][0])).max() > 1e-2


def test_reference_standardises_as_sklearn_does():
    from sklearn.preprocessing import StandardScaler
    X, _ = make_data()
    X[:, 3] = 0.25                       # a constant column: scaled by 1
    train, test = np.arange(0, 200), np.arange(200, 300)
    ours = reference_mlp.standardise(X[train], X[test])
    sk = StandardScaler().fit(X[train])
    assert np.abs(np.asarray(ours[0]) - sk.transform(X[train])).max() < 1e-6
    assert np.abs(np.asarray(ours[1]) - sk.transform(X[test])).max() < 1e-6


def test_bfloat16_control_is_another_result():
    X, y = make_data()
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    cands = [{"mlp__hidden_layer_sizes": [16], "mlp__alpha": a}
             for a in (1e-4, 1e-1)]
    f32 = reference_mlp.mlp_cv_scores(X, y, splits, cands, CONFIG,
                                      weights=True)[2]
    bf16 = reference_mlp.mlp_cv_scores(X, y, splits, cands, CONFIG,
                                       dtype=jnp.bfloat16, weights=True)[2]
    gap = np.abs(f32[0][0][0] - bf16[0][0][0]).max()
    assert 1e-3 < gap < 0.5


# --- the program against the reference ---------------------------------------

@pytest.mark.parametrize("hidden", [[16], [8, 4]])
def test_program_weights_agree_with_the_reference(hidden):
    """One fold's fit through the family's own ``fit`` on the
    reference's standardised rows: every layer to float32 rounding after
    3 epochs x 7 steps (200 training rows, batch 32: the last step of an
    epoch trains on 8)."""
    X, y = make_data()
    train, test = list(StratifiedKFold(FOLDS).split(X, y))[0]
    settings = reference_mlp.settings_of(
        CONFIG, {"mlp__hidden_layer_sizes": hidden, "mlp__alpha": 0.01})
    X_train, _ = reference_mlp.standardise(X[train], X[test])
    want = reference_mlp.fit_fold(X_train, y[train], N, K, settings, [0.01])
    mean, scale = X[train].mean(0), X[train].std(0)
    data, meta = MLPClassifierFamily.prepare_data((X - mean) / scale, y)
    train_w = np.zeros(N, np.float32)
    train_w[train] = 1.0
    got = MLPClassifierFamily.fit(
        {"alpha": jnp.float32(0.01)},
        {**PARAMS, "hidden_layer_sizes": hidden}, data, train_w, meta)
    assert int(got["n_iter"]) == EPOCHS
    assert int(got["n_steps"]) == EPOCHS * 7
    assert int(got["batch_rows"]) == BATCH
    for layer, (W, b) in zip(got["layers"], want):
        assert np.abs(np.asarray(layer["W"]) - np.asarray(W[0])).max() < 2e-5
        assert np.abs(np.asarray(layer["b"]) - np.asarray(b[0])).max() < 2e-5


@pytest.fixture(scope="module")
def small_search():
    X, y = make_data()
    grid = {"mlp__hidden_layer_sizes": [[16], [8, 4]],
            "mlp__alpha": [1e-4, 1e-2, 1.0]}
    return X, y, search(X, y, grid)


def test_search_scores_agree_with_the_reference(small_search):
    X, y, gs = small_search
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    want = reference_mlp.mlp_cv_scores(
        X, y, splits, list(gs.cv_results_["params"]), CONFIG)[0]
    got = split_scores(gs)
    assert got.min() > 0.5               # the nets learned something
    assert np.abs(got - want).max() < 1e-6


def test_search_reports_the_minibatch_counters(small_search):
    """Two compile groups (list-valued shapes), one launch each: 3 epochs
    x ceil(200 / 32) steps, 32 training rows in the fullest batch."""
    _, _, gs = small_search
    rep = gs.search_report
    assert rep["n_compile_groups"] == 2
    assert rep["minibatch_steps_per_launch"] == [21, 21]
    assert rep["minibatch_rows_per_launch"] == [BATCH, BATCH]
    assert rep["mlp_params_per_lane"] == [
        (D + 1) * 16 + 17 * K, (D + 1) * 8 + 9 * 4 + 5 * K]
    assert rep["epochs_per_candidate"] == [EPOCHS] * 6
    assert rep["prefix"]["mode"] == "shared"
    assert rep["prefix"]["n_prefixes_distinct"] == 1
    assert rep["prefix"]["fallbacks"] == []


def test_ledger_prices_a_lane_by_its_hidden_widths(small_search):
    _, _, gs = small_search
    groups = gs.search_report["memory"]["groups"]
    wide, narrow = groups[0], groups[1]
    # weights, two moments, gradients, the new weights: five copies a lane
    assert wide["per_candidate_bytes"] > 5 * 387 * 4 * FOLDS
    assert wide["per_candidate_bytes"] > narrow["per_candidate_bytes"]
    # the prefix stage's (folds, n, d) buffer is there whatever the width
    assert wide["fixed_bytes"] >= FOLDS * N * D * 4


def test_a_list_keys_a_compile_group_as_a_tuple_does(small_search):
    X, y, gs = small_search
    grid = {"mlp__hidden_layer_sizes": [(16,), (8, 4)],
            "mlp__alpha": [1e-4, 1e-2, 1.0]}
    tuples = search(X, y, grid)
    assert tuples.search_report["n_compile_groups"] == 2
    assert np.array_equal(split_scores(tuples), split_scores(gs))
    # the second search of the same Pipeline built no program
    assert tuples.search_report["pipeline"]["n_compiles"] == 0


def test_no_test_row_enters_a_minibatch():
    """The spy: every test row is NaN.  A gathered test row poisons the
    products whatever its weight (0 x NaN), so finite weights mean no
    step ever held one."""
    X, y = make_data()
    train, test = list(StratifiedKFold(FOLDS).split(X, y))[1]
    poisoned = X.copy()
    poisoned[test] = np.nan
    data, meta = MLPClassifierFamily.prepare_data(poisoned, y)
    train_w = np.zeros(N, np.float32)
    train_w[train] = 1.0
    got = MLPClassifierFamily.fit(
        {"alpha": jnp.float32(0.01)}, {**PARAMS, "hidden_layer_sizes": [16]},
        data, train_w, meta)
    assert all(np.isfinite(np.asarray(leaf)).all()
               for leaf in jax.tree_util.tree_leaves(got["layers"]))
    assert int(got["batch_rows"]) == BATCH


def test_the_parents_zero_weight_batches_read_different(monkeypatch):
    """Planted: every row stands in the epoch's order, as before PR 33
    (a permutation of ALL rows, the test fold's at weight 0).  The
    counters show it (10 steps an epoch and no batch of 32 training rows)
    and the weights leave the reference."""
    X, y = make_data()
    train, test = list(StratifiedKFold(FOLDS).split(X, y))[0]
    mean, scale = X[train].mean(0), X[train].std(0)
    data, meta = MLPClassifierFamily.prepare_data((X - mean) / scale, y)
    train_w = np.zeros(N, np.float32)
    train_w[train] = 1.0
    static = {**PARAMS, "hidden_layer_sizes": [16]}
    sound = MLPClassifierFamily.fit({"alpha": jnp.float32(0.01)}, static,
                                    data, train_w, meta)
    monkeypatch.setattr(mlp, "_training_rows", lambda w: (
        jnp.arange(w.shape[0]), jnp.asarray(w.shape[0], jnp.int32)))
    planted = MLPClassifierFamily.fit({"alpha": jnp.float32(0.01)}, static,
                                      data, train_w, meta)
    assert int(sound["n_steps"]) == EPOCHS * 7
    assert int(planted["n_steps"]) == EPOCHS * 10
    assert int(planted["batch_rows"]) < BATCH
    assert np.abs(np.asarray(planted["layers"][0]["W"])
                  - np.asarray(sound["layers"][0]["W"])).max() > 1e-3


def test_folds_whose_step_counts_differ():
    """289 rows in 3 folds: 192, 193 and 193 training rows, so 6, 7 and 7
    steps an epoch at batch 32 — the folds advance in one loop and the
    short one sits its last step out."""
    X, y = make_data(n=289)
    splits = list(StratifiedKFold(FOLDS).split(X, y))
    assert sorted(len(tr) for tr, _ in splits) == [192, 193, 193]
    gs = search(X, y, {"mlp__hidden_layer_sizes": [[16]],
                       "mlp__alpha": [1e-3, 1e-1]})
    want = reference_mlp.mlp_cv_scores(
        X, y, splits, list(gs.cv_results_["params"]), CONFIG)[0]
    assert np.abs(split_scores(gs) - want).max() < 1e-6
    assert gs.search_report["minibatch_steps_per_launch"] == [EPOCHS * 7]


# --- sample weights ----------------------------------------------------------

def weighted_fit(X, y, train, w, hidden, alpha=0.01, **params):
    """The family's own fit on the rows `train` at the weights `w`, as
    the engine hands them: the fold's mask times the sample weights."""
    data, meta = MLPClassifierFamily.prepare_data(X, y)
    train_w = np.zeros(len(y), np.float32)
    train_w[train] = w[train]
    return MLPClassifierFamily.fit(
        {"alpha": jnp.float32(alpha)},
        {**PARAMS, "hidden_layer_sizes": hidden, **params}, data, train_w,
        meta), meta


@pytest.mark.parametrize("k, hidden", [(3, (16,)), (10, (12, 8))])
def test_sample_weights_are_sklearns(k, hidden):
    """One batch an epoch holds every training row, so its order does not
    matter and sklearn can take the same steps from the same weights
    (``fit(X, y, sample_weight)``: the loss a weighted mean, regulariser
    and gradients over the weights' sum).  Five epochs agree to float32
    rounding, and are far from what unit weights give."""
    X, y = make_data(k=k)
    train, _ = list(StratifiedKFold(FOLDS).split(X, y))[0]
    w = np.random.default_rng(2).uniform(0.2, 3.0, N).astype(np.float32)
    static = {"batch_size": len(train), "max_iter": 5}
    got, _ = weighted_fit(X, y, train, w, hidden, **static)
    unit, _ = weighted_fit(X, y, train, np.ones(N, np.float32), hidden,
                           **static)
    sk = sklearn_from(
        reference_mlp.initial_weights(0, (D, *hidden, k)), X[train],
        y[train], epochs=5, sample_weight=w[train],
        hidden_layer_sizes=hidden, alpha=0.01, batch_size=len(train),
        learning_rate_init=0.01)
    assert int(got["batch_rows"]) == len(train)
    for i, layer in enumerate(got["layers"]):
        assert np.abs(sk.coefs_[i] - np.asarray(layer["W"])).max() < 5e-6
        assert np.abs(sk.intercepts_[i] - np.asarray(layer["b"])).max() < 5e-6
        assert np.abs(sk.coefs_[i]
                      - np.asarray(unit["layers"][i]["W"])).max() > 5e-3


def test_a_minibatch_divides_by_its_weights_sum():
    """Minibatches of 32 (the last of an epoch 8): weights and alpha
    both four times over are the same steps, since loss, regulariser and
    gradients are all over the batch's sum of weights; the slots a batch
    fills are counted whatever their weights."""
    X, y = make_data()
    train, _ = list(StratifiedKFold(FOLDS).split(X, y))[0]
    w = np.random.default_rng(3).uniform(0.2, 3.0, N).astype(np.float32)
    once, _ = weighted_fit(X, y, train, w, [16], alpha=0.05)
    four, _ = weighted_fit(X, y, train, 4 * w, [16], alpha=0.2)
    unit, _ = weighted_fit(X, y, train, np.ones(N, np.float32), [16],
                           alpha=0.05)
    assert int(once["batch_rows"]) == int(four["batch_rows"]) == BATCH
    assert int(once["n_steps"]) == EPOCHS * 7
    for a, b, c in zip(once["layers"], four["layers"], unit["layers"]):
        assert np.abs(np.asarray(a["W"]) - np.asarray(b["W"])).max() < 2e-5
    assert np.abs(np.asarray(once["layers"][0]["W"])
                  - np.asarray(unit["layers"][0]["W"])).max() > 5e-3


def test_search_hands_sample_weight_to_the_minibatches():
    """The public call on a bare MLPClassifier with ``sample_weight``:
    classes that overlap and one of them six times as heavy, so the
    weights move predictions.  Every split's (weighted) score is the one
    of that fold's own weighted fit, and not the one of a fit that only
    asks whether a row's weight is positive."""
    rng = np.random.default_rng(4)
    y = rng.permutation(np.arange(N) % K)
    X = (0.4 * rng.standard_normal((K, D))[y]
         + rng.standard_normal((N, D))).astype(np.float32)
    w = np.where(y == 0, 6.0, 1.0).astype(np.float32)
    params = {**PARAMS, "hidden_layer_sizes": (16,)}
    alphas = [1e-3, 1e-1]
    got = split_scores(sst.GridSearchCV(
        MLPClassifier(**params), {"alpha": alphas},
        cv=StratifiedKFold(FOLDS), backend="tpu",
        refit=False).fit(X, y, sample_weight=w))
    want, indicator = np.zeros_like(got), np.zeros_like(got)
    for f, (train, test) in enumerate(StratifiedKFold(FOLDS).split(X, y)):
        for c, alpha in enumerate(alphas):
            for out, fit_w in ((want, w), (indicator, np.sign(w))):
                model, meta = weighted_fit(X, y, train, fit_w, (16,),
                                           alpha=alpha)
                pred = MLPClassifierFamily.predict(
                    model, params, jnp.asarray(X[test]), meta)
                out[c, f] = np.average(np.asarray(pred) == y[test],
                                       weights=w[test])
    assert np.abs(got - want).max() < 1e-6
    assert np.abs(got - indicator).min() > 0.02


def test_another_familys_report_has_no_minibatch_counter():
    X, y = make_data()
    gs = sst.GridSearchCV(LogisticRegression(max_iter=20),
                          {"C": [0.1, 1.0]}, cv=3, backend="tpu",
                          refit=False).fit(X, y)
    assert gs.search_report["solver_iters_per_launch"]
    for name in COUNTERS:
        assert not gs.search_report.get(name)


# --- the compiled form -------------------------------------------------------

def _launch_jaxpr(early_stopping=False, cands=4):
    meta = {"n_classes": K, "classes": np.arange(K), "n_features": D}
    static = {**PARAMS, "hidden_layer_sizes": [16],
              "early_stopping": early_stopping}
    y = jnp.zeros((N,), jnp.int32)
    y1h = jnp.zeros((N, K), jnp.float32)

    def launch(alpha, X_folds, w):
        def one_cand(a):
            def one_fold(wf, Xf):
                return MLPClassifierFamily.fit(
                    {"alpha": a}, static, {"X": Xf, "y": y, "y1h": y1h},
                    wf, meta)
            return jax.vmap(one_fold)(w, X_folds)
        return jax.vmap(one_cand, axis_name=CANDIDATE_AXIS)(alpha)

    return jax.make_jaxpr(launch)(
        jnp.zeros((cands,)), jnp.zeros((FOLDS, N, D)),
        jnp.ones((FOLDS, N))).jaxpr


def _shapes(jaxpr, primitive=None):
    """Shapes of every value an equation (of ``primitive``) writes,
    through the loops' and conditionals' own jaxprs: one list an
    equation."""
    out = []
    for eqn in jaxpr.eqns:
        if primitive is None or eqn.primitive.name == primitive:
            out.append([tuple(v.aval.shape) for v in eqn.outvars])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_shapes(inner, primitive))
    return out


def test_one_gather_a_fold_serves_every_candidate():
    """Under the engine's named candidate axis the epoch loop's key,
    order and minibatch are one value for all candidates: the gathered
    rows are (folds, batch, d), and nothing anywhere is (candidates,
    folds, batch, d) or an order of the rows a candidate."""
    written = {shape for eqn in _shapes(_launch_jaxpr(cands=4))
               for shape in eqn}
    assert (FOLDS, BATCH, D) in written
    assert not [s for s in written
                if len(s) >= 3 and int(np.prod(s)) in (
                    4 * FOLDS * BATCH * D, 4 * FOLDS * N)]


@pytest.mark.parametrize("early_stopping, copies", [(False, 3), (True, 4)])
def test_best_weights_are_carried_only_under_early_stopping(
        early_stopping, copies):
    """The epoch loop carries the first layer's weights and two moments
    — and the best epoch's weights only where early_stopping reads
    them."""
    loops = _shapes(_launch_jaxpr(early_stopping), "while")
    first_layer = 4 * FOLDS * D * 16
    assert max(sum(1 for s in carried if int(np.prod(s)) == first_layer
                   and len(s) == 4) for carried in loops) == copies


def test_predicted_class_stays_out_of_the_products_fusion():
    """XLA:TPU fuses an argmax into the product that makes the logits
    and, for networks batched by vmap, reads class 0 on every row (8 of
    12 lanes at 10 % with logits worth 92 %: PERF.md, PR 33).  The
    prediction takes its argmax behind an optimization barrier."""
    meta = {"n_classes": K, "classes": np.arange(K), "n_features": D}
    model = {"layers": mlp._init_params(jax.random.PRNGKey(0), (D, 16, K),
                                        jnp.float32)}
    models = jax.tree_util.tree_map(lambda a: jnp.stack([a, 2 * a]), model)
    X = jnp.asarray(make_data()[0])

    def predict(ms):
        return jax.vmap(lambda m: MLPClassifierFamily.predict(
            m, {"hidden_layer_sizes": [16]}, X, meta))(ms)

    assert "optimization_barrier" in jax.jit(predict).lower(models).as_text()
    logits = MLPClassifierFamily.decision(model, {}, X, meta)
    assert np.array_equal(np.asarray(predict(models)[0]),
                          np.asarray(logits).argmax(axis=1))


@pytest.mark.parametrize("n", [2 * 4096, 2 * 4096 + 77])
def test_scoring_by_row_blocks_is_the_whole_forward_pass(n):
    """Nets of two and more hidden layers score 4 096 rows at a time
    (their hidden activations of all rows at once are gigabytes at the
    cell's size): the same numbers, row for row."""
    params = mlp._init_params(jax.random.PRNGKey(1), (D, 8, 4, K),
                              jnp.float32)
    X = jnp.asarray(np.random.default_rng(2).standard_normal(
        (n, D)).astype(np.float32))
    whole = mlp._forward(params, X, jax.nn.relu)
    blocks = jax.jit(lambda X: mlp._forward_by_blocks(
        params, X, jax.nn.relu))(X)
    assert blocks.shape == (n, K)
    assert np.abs(np.asarray(blocks) - np.asarray(whole)).max() < 1e-6


# --- the work model ----------------------------------------------------------

CELL = {"data": {"n_samples": 70000, "n_features": 784, "n_classes": 10,
                 "n_folds": 5},
        "estimator": {"params": {"max_iter": 8, "random_state": 0}}}


def _report(shapes, n_each, epochs=None):
    rep = {"geometry": {"groups": [
               {"group": g, "n_candidates": n_each}
               for g in range(len(shapes))]},
           "per_group": {g: {"static_params": repr(
               {"mlp__hidden_layer_sizes": shape})}
               for g, shape in enumerate(shapes)}}
    if epochs is not None:
        rep["epochs_per_candidate"] = epochs
    return rep


def test_work_against_a_hand_count():
    needs = work_mlp.mlp_adam_minibatch(
        CELL, 2, _report([[300]], 2, epochs=[8, 8]))
    weights, parameters = 784 * 300 + 300 * 10, 238510
    fits, steps = 2 * 5, 8 * 280
    assert needs["fit_flops"] == 6.0 * 56000 * weights * 8 * fits
    assert needs["fit_bytes"] == (24.0 * parameters * steps * fits
                                  + 5 * steps * 200 * 784 * 4)
    assert needs["flops"] == pytest.approx(
        needs["fit_flops"] + 2.0 * 14000 * weights * fits
        + 5.0 * 70000 * 784 * 5)


def test_work_of_the_whole_cell_is_the_issues_reckoning():
    shapes = [[300], [1000], [300, 100], [500, 300]]
    needs = work_mlp.mlp_adam_minibatch(CELL, 48, _report(shapes, 12))
    # 2 240 steps x 60 lanes x 1 845 940 parameters x 1 200 FLOP, less
    # the intercepts' share: 2.98e14; Adam's 24 B a parameter: 5.95 TB
    assert needs["fit_flops"] == pytest.approx(2.98e14, rel=0.01)
    assert needs["fit_bytes"] == pytest.approx(5.95e12, rel=0.01)


@pytest.mark.parametrize("report", [
    {}, _report([[300]], 3),
    {"geometry": {"groups": [{"group": 0, "n_candidates": 2}]},
     "per_group": {0: {"static_params": "{'mlp__alpha': 1.0}"}}},
    _report([[300]], 2, epochs=[8, -1])])
def test_work_is_none_without_every_candidates_shape_and_epochs(report):
    assert work_mlp.mlp_adam_minibatch(CELL, 2, report) is None
