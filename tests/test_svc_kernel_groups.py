"""The kernel-dual launch groups its candidates by kernel (XLA:CPU, small
sizes; nothing here is timed).

Where the candidates of a launch fall into runs of S that share ``gamma``
and differ in the primary scalar only (``C``; ``nu`` under NuSVC),
``SVCFamily.launch_layout`` orders them kernel-major on the host and hands
the launch the run length; ``fit_task_batched`` then builds ONE kernel
matrix a run and advances the S candidates' duals stacked on the duals'
leading axis.  Every candidate stays what it is alone: its alphas,
intercepts, decisions and its own iteration count, whatever its
neighbours need (``_stacked_tol``, ``_box_fista``).  Ragged runs, distinct
gammas, a launch that is not made of whole runs and a compiled Pipeline's
per-fold kernels run the ungrouped program, text for text.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.svm import SVC, NuSVC

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models import svm
from spark_sklearn_tpu.models.svm import (
    NuSVCFamily, SVCFamily, _BlockKernel, _block_rows, _class_sorted,
    _kernel, _kernel_run, _pairs, _power_step, fista_dual_ascent)

FOLDS = 3
GAMMAS = (0.05, 0.3)
PRIMARY = {"svc": (0.3, 1.0, 10.0, 30.0), "nu_svc": (0.1, 0.2, 0.3, 0.4)}
FAMILY = {"svc": SVCFamily, "nu_svc": NuSVCFamily}
#: class counts -> the duals' layout: balanced classes run block-compact,
#: skewed ones and binary problems keep dense rows
LAYOUT = {"block": (22, 21, 21), "dense": (48, 8, 8), "binary": (32, 32)}


def _problem(counts, d=6, seed=1):
    k, n = len(counts), sum(counts)
    rng = np.random.default_rng(seed)
    y = np.repeat(np.arange(k), counts)[rng.permutation(n)]
    X = (rng.standard_normal((n, d)) + 0.7 * y[:, None]).astype(np.float32)
    meta = {"n_classes": k, "classes": np.arange(k), "n_features": d,
            "x_var": 1.0, "pairs": _pairs(k), "class_counts": tuple(counts)}
    folds = np.arange(n) % FOLDS
    masks = np.stack([(folds != f).astype(np.float32) for f in range(FOLDS)])
    return X, y.astype(np.int32), meta, masks


def _launch(family, counts, candidates, run=None, **static):
    """One task-batched fit of `candidates` = [(primary, gamma), ...] in
    the order given, with the layout's fact `run` (None: without)."""
    X, y, meta, masks = _problem(counts)
    static = {"kernel": "rbf", "__n_folds__": FOLDS, **static}
    if run:
        static[svm._KERNEL_RUN] = run
    primary, gamma = (np.repeat(np.asarray(v, np.float32), FOLDS)
                      for v in zip(*candidates))
    model = jax.jit(lambda dyn, data, w: family.fit_task_batched(
        dyn, static, data, w, meta))(
        {family.primary_param: primary, "gamma": gamma},
        {"X": X, "y": y}, np.tile(masks, (len(candidates), 1)))
    votes = jax.vmap(lambda dec: family.predict(
        {"pair_dec": dec}, static, None, meta))(model["pair_dec"])
    return (np.asarray(model["pair_dec"]), np.asarray(votes),
            np.asarray(model["n_iter"])[::FOLDS])


def _kernel_major(name):
    return [(p, g) for g in GAMMAS for p in PRIMARY[name]]


# --- the launch: grouped against ungrouped -----------------------------------

@pytest.mark.parametrize("class_weight", [None, "balanced"])
@pytest.mark.parametrize("layout", sorted(LAYOUT))
@pytest.mark.parametrize("name", sorted(FAMILY))
def test_grouped_launch_equals_the_ungrouped(name, layout, class_weight):
    """Pair decisions to 1e-5, predictions and every candidate's count
    equal, in both layouts of a dual; the `tol` exit fires (exact float32
    here), so the candidates of one gamma end at DIFFERENT counts and each
    keeps the result and the count of its solve alone (a scan step of its
    own)."""
    static = {"class_weight": class_weight} if class_weight else {}
    cands = _kernel_major(name)
    alone = _launch(FAMILY[name], LAYOUT[layout], cands, **static)
    for run in (4, 2):
        dec, votes, iters = _launch(
            FAMILY[name], LAYOUT[layout], cands, run=run, **static)
        np.testing.assert_allclose(dec, alone[0], atol=1e-5, rtol=0,
                                   equal_nan=True)
        assert np.array_equal(votes, alone[1])
        assert np.array_equal(iters, alone[2])
    for g in range(len(GAMMAS)):
        of_gamma = alone[2][4 * g:4 * g + 4]
        assert len(set(of_gamma.tolist())) > 1 and of_gamma.min() < 300


@pytest.mark.parametrize("layout", ["block", "dense"])
def test_stacked_duals_equal_each_alone(layout):
    """``fista_dual_ascent`` with ``_stacked_tol``'s `tol` on S candidates'
    boxes against the S solves alone: alphas and intercepts to 1e-5, each candidate's own
    count, and the loop runs as long as the slowest needs."""
    X, y, meta, masks = _problem(LAYOUT["block"])
    n, pairs, k = len(y), meta["pairs"], meta["n_classes"]
    P = len(pairs)
    n_b = _block_rows(meta, n)
    if layout == "block":
        rows, valid, _ = _class_sorted(jnp.asarray(y), meta["class_counts"],
                                       n_b)
        valid = valid.astype(jnp.float32)
        K = _kernel(X[rows], X[rows], "rbf", 0.3, 3.0, 0.0)
        step = _power_step(
            K, n, jnp.float32, centred=True, valid=valid.reshape(-1),
            start=svm._power_start(n, jnp.float32)[rows] * valid.reshape(-1))
        w = jnp.take(jnp.asarray(masks), rows, axis=1).reshape(
            FOLDS, k, n_b) * valid
        base = w[:, pairs, :].reshape(-1, 2 * n_b)
        yb = jnp.broadcast_to(
            (valid[pairs] * jnp.asarray([1.0, -1.0])[None, :, None])[None],
            (FOLDS, P, 2, n_b)).reshape(-1, 2 * n_b)

        def product(folds):
            return _BlockKernel(K, pairs, folds, n_b)
    else:
        K = _kernel(X, X, "rbf", 0.3, 3.0, 0.0)
        step = _power_step(K, n, jnp.float32, centred=True)
        pos = (y[None, :] == pairs[:, 0][:, None])
        neg = (y[None, :] == pairs[:, 1][:, None])
        base = (masks[:, None, :] * (pos | neg)[None]).reshape(-1, n)
        yb = jnp.broadcast_to(
            (pos.astype(np.float32) - neg)[None],
            (FOLDS, P, n)).reshape(-1, n)

        def product(folds):
            return K

    Cs = (0.3, 3.0, 30.0)
    alone = [fista_dual_ascent(product(FOLDS), yb, C * base, step, 300, 1e-3)
             for C in Cs]
    A, b, iters = fista_dual_ascent(
        product(len(Cs) * FOLDS), jnp.tile(yb, (len(Cs), 1)),
        jnp.concatenate([C * base for C in Cs]), step, 300,
        svm._stacked_tol(1e-3, len(Cs), FOLDS * P, jnp.float32))
    m = FOLDS * P
    for s, (A_s, b_s, it_s) in enumerate(alone):
        np.testing.assert_allclose(A[s * m:(s + 1) * m], A_s, atol=1e-5)
        np.testing.assert_allclose(b[s * m:(s + 1) * m], b_s, atol=1e-5)
        assert int(iters[s]) == int(it_s)
    assert len({int(i) for i in iters}) == len(Cs)


# --- the host's layout -------------------------------------------------------

def _layout(family, dynamic, counts=LAYOUT["block"], n_folds=FOLDS):
    meta = _problem(counts)[2]
    dynamic = {k: np.asarray(v, np.float32) for k, v in dynamic.items()}
    return family.launch_layout(dynamic, {"kernel": "rbf"}, meta, n_folds)


def test_layout_orders_kernel_major_by_value():
    """ParameterGrid puts gamma last and the benchmark shuffles the value
    lists: the candidates of one gamma are strided, never adjacent."""
    C, gamma = zip(*[(c, g) for c in (10.0, 0.3, 3.0) for g in (0.03, 0.004)])
    order, facts, run = _layout(SVCFamily, {"C": C, "gamma": gamma})
    assert facts == {svm._KERNEL_RUN: 3} and run == 3
    assert order.tolist() == [3, 5, 1, 2, 4, 0]


@pytest.mark.parametrize("case", ["ragged", "distinct", "one_candidate"])
def test_layout_declines(case):
    dynamic = {
        "ragged": {"C": [1, 2, 3, 1], "gamma": [0.1, 0.1, 0.1, 0.2]},
        "distinct": {"C": [1, 1, 1, 1], "gamma": [0.1, 0.2, 0.3, 0.4]},
        "one_candidate": {"C": [1], "gamma": [0.1]},
    }[case]
    assert _layout(SVCFamily, dynamic) is None


def test_layout_without_a_dynamic_gamma_is_one_run():
    """A C-only grid (gamma static): every candidate shares the kernel;
    NuSVC's primary scalar is nu."""
    order, facts, run = _layout(NuSVCFamily, {"nu": [0.4, 0.1, 0.2]})
    assert facts == {svm._KERNEL_RUN: 3} and order.tolist() == [1, 2, 0]
    assert run == 3


@pytest.mark.parametrize("layout", sorted(LAYOUT))
def test_layout_run_is_the_run_of_a_gamma(layout):
    """S is the candidates of a gamma, in every layout of the duals: what
    they hold stacked is the ledger's to bound, not the layout's."""
    dynamic = {"C": np.arange(1, 17), "gamma": [0.1] * 8 + [0.2] * 8}
    _, facts, run = _layout(SVCFamily, dynamic, LAYOUT[layout])
    assert facts == {svm._KERNEL_RUN: 8} and run == 8


def test_other_families_have_no_layout():
    from spark_sklearn_tpu.models.base import Family
    from spark_sklearn_tpu.models.linear import LogisticRegressionFamily
    assert LogisticRegressionFamily.launch_layout.__func__ \
        is Family.launch_layout.__func__
    assert LogisticRegressionFamily.launch_layout(
        {"C": np.ones(4, np.float32)}, {}, {}, 3) is None


# --- who keeps the ungrouped program -----------------------------------------

def _lowered(counts, cands, fold_inputs=False, run=None, n=64, d=6):
    meta = _problem(counts)[2]
    static = {"kernel": "rbf", "__n_folds__": FOLDS}
    if run:
        static[svm._KERNEL_RUN] = run
    S = jax.ShapeDtypeStruct
    lanes = cands * FOLDS
    data = {"X": S((n, d), jnp.float32), "y": S((n,), jnp.int32)}
    if fold_inputs:
        data["X_folds"] = S((FOLDS, n, d), jnp.float32)
    return jax.jit(lambda dyn, data, w: SVCFamily.fit_task_batched(
        dyn, static, data, w, meta)).lower(
        {"C": S((lanes,), jnp.float32), "gamma": S((lanes,), jnp.float32)},
        data, S((lanes, n), jnp.float32)).as_text()


@pytest.mark.parametrize("case", ["width_not_a_multiple", "fold_inputs",
                                  "run_of_one"])
def test_launch_falls_back_to_a_kernel_a_candidate(case):
    """A chunk halved after an OOM (6 candidates under a fact of 4), a
    compiled Pipeline's per-fold rows and a fact of 1 lower to the text of
    the launch that was handed no fact."""
    cands, kw = {"width_not_a_multiple": (6, {}),
                 "fold_inputs": (8, {"fold_inputs": True}),
                 "run_of_one": (8, {})}[case]
    run = 1 if case == "run_of_one" else 4
    counts = LAYOUT["block"]
    assert _kernel_run({svm._KERNEL_RUN: run}, cands,
                       kw.get("fold_inputs", False)) == 1
    assert _lowered(counts, cands, run=run, **kw) == \
        _lowered(counts, cands, **kw)


def test_grouped_launch_is_another_program():
    counts = LAYOUT["block"]
    assert _kernel_run({svm._KERNEL_RUN: 4}, 8) == 4
    assert _lowered(counts, 8, run=4) != _lowered(counts, 8)


# --- through the search ------------------------------------------------------

def _search_data():
    rng = np.random.default_rng(0)
    y = np.arange(240) % 3
    X = (rng.standard_normal((240, 6)) + 0.8 * y[:, None]).astype(np.float32)
    return X, y


def _search(estimator, grid):
    X, y = _search_data()
    search = sst.GridSearchCV(estimator, grid, cv=FOLDS, refit=False,
                              backend="tpu").fit(X, y)
    return search.cv_results_, search.search_report


@pytest.mark.parametrize("seed", [5, 2147483999])
@pytest.mark.parametrize("name", sorted(FAMILY))
def test_cv_results_equal_the_search_without_the_layout(name, seed,
                                                        monkeypatch):
    """Value lists shuffled as the benchmark's ``--seed`` does: the same
    ``cv_results_``, in the grid's order, as the search whose layout hook
    declines; 4 kernels built where that one builds 16."""
    rng = np.random.default_rng(seed)
    estimator = {"svc": SVC(kernel="rbf"), "nu_svc": NuSVC(kernel="rbf")}[name]
    grid = {FAMILY[name].primary_param:
                rng.permutation(PRIMARY[name]).tolist(),
            "gamma": rng.permutation([0.004, 0.03, 0.1, 0.3]).tolist()}
    got, rep = _search(estimator, grid)
    monkeypatch.setattr(
        FAMILY[name], "launch_layout", classmethod(lambda cls, *a: None))
    want, rep0 = _search(estimator, grid)
    assert rep["gram_builds_per_launch"] == [4]
    assert rep0["gram_builds_per_launch"] == [16]
    assert rep["dual_iters_per_candidate"] == rep0["dual_iters_per_candidate"]
    assert len(set(rep["dual_iters_per_candidate"])) > 4
    assert [dict(p) for p in got["params"]] == [dict(p)
                                                 for p in want["params"]]
    for key in want:
        if "time" in key or key == "params":
            continue
        if np.asarray(want[key]).dtype == object:
            assert list(got[key]) == list(want[key]), key
        else:
            np.testing.assert_allclose(got[key], want[key], atol=1e-6,
                                       err_msg=key)


def test_distinct_gammas_build_a_kernel_a_candidate():
    grid = {"C": [1.0], "gamma": np.logspace(-3, 0, 16).tolist()}
    assert _search(SVC(kernel="rbf"), grid)[1][
        "gram_builds_per_launch"] == [16]


def test_ledger_prices_the_stacked_duals():
    """The workspace of a grouped launch: still ONE matrix, S times the
    duals' arrays and the product's result."""
    meta = _problem(LAYOUT["block"])[2]
    one = SVCFamily.launch_workspace(64, meta, FOLDS, static={})
    four = SVCFamily.launch_workspace(64, meta, FOLDS,
                                      static={svm._KERNEL_RUN: 4})
    n_p = 3 * 24
    matrix = n_p * n_p * 6
    assert four["per_candidate_bytes"] == one["per_candidate_bytes"]
    assert four["fixed_bytes"] - matrix == 4 * (one["fixed_bytes"] - matrix)
    # behind a Pipeline's transformers nothing is grouped
    assert SVCFamily.launch_workspace(
        64, meta, FOLDS, static={svm._KERNEL_RUN: 4},
        row_sets=FOLDS) == SVCFamily.launch_workspace(
        64, meta, FOLDS, static={}, row_sets=FOLDS)


# --- launches that are not made of whole runs --------------------------------

def _without_layout(monkeypatch, family=SVCFamily):
    monkeypatch.setattr(family, "launch_layout",
                        classmethod(lambda cls, *a: None))


@pytest.mark.parametrize("n_gamma, n_C", [(7, 4), (3, 2)])
def test_bisected_ranges_that_cut_a_run(n_gamma, n_C, monkeypatch):
    """An out-of-memory launch is halved down to ranges that start inside
    a run of one gamma ([3:7] of 7 x 4, [1:3] of 3 x 2): a range that is
    not whole runs runs the program without the layout's fact, and every
    candidate keeps its own gamma."""
    from spark_sklearn_tpu.parallel import faults
    grid = {"C": np.logspace(-0.5, 1.5, n_C).tolist(),
            "gamma": np.logspace(-2.5, -0.3, n_gamma).tolist()}
    X, y = _search_data()
    with monkeypatch.context() as m:
        _without_layout(m)
        want, _ = _search(SVC(kernel="rbf"), grid)

    ranges = []

    def inject(self, n_real):
        ranges.append(n_real)
        if n_real > n_C:
            raise faults.InjectedFault(
                faults.OOM, f"RESOURCE_EXHAUSTED: {n_real} candidates")
    monkeypatch.setattr(faults.LaunchSupervisor, "inject_subrange", inject)
    search = sst.GridSearchCV(
        SVC(kernel="rbf"), grid, cv=FOLDS, refit=False, backend="tpu",
        config=sst.TpuConfig(fault_plan="oom@0", retry_backoff_s=0.01,
                             partial_results="best_effort")).fit(X, y)
    report = search.search_report["faults"]
    assert report["bisections"] >= 3 and not report["host_fallbacks"]
    assert n_C in ranges and min(ranges) < n_C
    for key in ("mean_test_score", "std_test_score", "rank_test_score"):
        np.testing.assert_allclose(search.cv_results_[key], want[key],
                                   atol=1e-6, err_msg=key)


@pytest.mark.parametrize("first", ["with_layout", "without_layout"])
def test_checkpoint_does_not_resume_across_the_layout(first, tmp_path,
                                                      monkeypatch):
    """The layout permutes the candidates a chunk holds, so a chunk's id
    says so: a journal written in the grid's order (an older library, a
    hook that declined) is not replayed through the kernel-major indices,
    nor the other way round; in its own mode it resumes."""
    X, y = _search_data()
    grid = {"C": [3.0, 0.3, 30.0, 1.0], "gamma": [0.1, 0.004, 0.3, 0.03]}

    def fit():
        return sst.GridSearchCV(
            SVC(kernel="rbf"), grid, cv=FOLDS, refit=False, backend="tpu",
            config=sst.TpuConfig(checkpoint_dir=str(tmp_path))).fit(X, y)

    def fit_without():
        with monkeypatch.context() as m:
            _without_layout(m)
            return fit()

    one, other = (fit, fit_without) if first == "with_layout" \
        else (fit_without, fit)
    a = one()
    b = other()
    c = other()
    assert a.search_report["n_chunks_resumed"] == 0
    assert b.search_report["n_chunks_resumed"] == 0
    assert b.search_report["n_launches"] >= 1
    assert c.search_report["n_chunks_resumed"] >= 1
    assert c.search_report["n_launches"] == 0
    for got in (b, c):
        np.testing.assert_allclose(got.cv_results_["mean_test_score"],
                                   a.cv_results_["mean_test_score"],
                                   atol=1e-6)
    assert len(set(np.round(a.cv_results_["mean_test_score"], 6))) > 4


@pytest.mark.parametrize("n_C", [16, 2])
def test_cross_search_fuse_keeps_each_members_gamma(n_C):
    """Two searches over other gammas share launches (chunks of 8
    candidates, concatenated): with 16 C a gamma a member is half a run
    and the seam falls inside one, so the fused launch runs without the
    layout's fact; with 2 C a gamma the members are whole runs and it
    keeps it.  Either way every member's cells are its solo search's."""
    import time
    X, y = _search_data()
    gammas = np.logspace(-2.4, -0.3, 32 // n_C)
    grids = [{"C": np.logspace(-1, 1.5, n_C).tolist(),
              "gamma": (gammas * f).tolist()} for f in (1.0, 1.7)]

    def search(grid, **kw):
        return sst.GridSearchCV(
            SVC(kernel="rbf"), grid, cv=FOLDS, refit=False, backend="tpu",
            config=sst.TpuConfig(max_tasks_per_batch=8 * FOLDS,
                                 fusion_window_ms=200.0, **kw))

    alone = [search(grid).fit(X, y) for grid in grids]
    assert alone[0].search_report["lanes_per_launch"] == [8 * FOLDS] * 4
    assert alone[0].search_report["gram_builds_per_launch"] == [
        8 if n_C == 16 else 4] * 4
    session = sst.createLocalTpuSession(
        f"fuse-svc-{n_C}", config=sst.TpuConfig(
            max_tasks_per_batch=8 * FOLDS, fusion_window_ms=200.0))
    try:
        session.executor.pause()
        futures = [session.submit(search(grid, tenant=f"t{i}"), X, y)
                   for i, grid in enumerate(grids)]
        t0 = time.time()
        while session.executor.queued_count() < 2 and time.time() - t0 < 60:
            time.sleep(0.005)
        session.executor.resume()
        fused = [f.result(timeout=300) for f in futures]
    finally:
        session.stop()
    assert sum(s.search_report["scheduler"]["n_fused"] for s in fused) > 0
    for got, want in zip(fused, alone):
        np.testing.assert_allclose(got.cv_results_["mean_test_score"],
                                   want.cv_results_["mean_test_score"],
                                   atol=1e-6)
