"""The staged line search of ``glm_lbfgs_batched`` against its single pass.

Where a caller hands ``trial_data_loss`` the solver evaluates the first
``_LS_FIRST_STAGE`` of its ``ls_trials`` trial steps, and the others
(under ``lax.cond``) only in an iteration where some lane that is not done
passed none of them.  The steps offered, the Armijo rule and the pick are
the single pass's, so a lane that moves takes the step it took before;
``LBFGSResult.ls_second_pass`` counts the iterations that ran the second
stage.  The single pass is the same solver with the constant at
``ls_trials`` (the code path of the parent commit).  XLA:CPU, a 12-lane
multinomial fit through ``LogisticRegressionFamily.fit_task_batched``;
what the staged search compiles to on the chip is pinned in
``tests/test_scopes_tpu_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models import linear
from spark_sklearn_tpu.ops import solvers

LANES, T = 12, 16
K = solvers._LS_FIRST_STAGE


def _problem(scale=1.0):
    rng = np.random.default_rng(3)
    n, d, k = 600, 20, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    Wt = rng.normal(size=(d, k)).astype(np.float32)
    y = np.argmax(X @ Wt + rng.gumbel(size=(n, k)), axis=1)
    return (scale * X).astype(np.float32), y.astype(np.int32), k


def _fit(monkeypatch, first_stage, scale=1.0, fit_intercept=True, tol=None,
         poison=None):
    """One 12-lane fit with the solver's constant at ``first_stage``.
    ``poison = (lane, value, n_trials)`` overwrites that lane's first
    ``n_trials`` trial losses in every iteration.  Returns the model, each
    iteration's picks (iterations, lanes) and its logits as the line
    search saw them (iterations, n, lanes, k)."""
    X, y, k = _problem(scale)
    n, d = X.shape
    fam = linear.LogisticRegressionFamily
    data = {"X": jnp.asarray(X), "y": jnp.asarray(y),
            "y1h": jnp.asarray(np.eye(k, dtype=np.float32)[y])}
    meta = {"n_classes": k, "classes": np.arange(k), "n_features": d}
    static = {"max_iter": 100, "fit_intercept": fit_intercept}
    dyn = {"C": jnp.asarray(np.logspace(-3, 1, LANES), jnp.float32)}
    if tol is not None:
        dyn["tol"] = jnp.asarray(tol, jnp.float32)
    w = jnp.ones((LANES, n), jnp.float32)
    picks, logits = [], []
    real_pick = solvers._armijo_pick
    real_losses = linear._multinomial_trial_losses

    def spy_pick(armijo):
        pick = real_pick(armijo)
        jax.debug.callback(lambda p: picks.append(np.asarray(p)), pick,
                           ordered=True)
        return pick

    def spy_losses(Z, Zp, alphas, wT, y1h):
        losses = real_losses(Z, Zp, alphas, wT, y1h)
        # the second stage is handed the trials from first_stage on
        second = first_stage < T and alphas.shape[0] == T - first_stage
        if not second:
            jax.debug.callback(lambda z: logits.append(np.asarray(z)), Z,
                               ordered=True)
        if poison is not None:
            lane, value, n_trials = poison
            n_here = max(0, n_trials - (first_stage if second else 0))
            bad = jnp.zeros(losses.shape, bool).at[:n_here, lane].set(True)
            losses = jnp.where(bad, value, losses)
        return losses

    monkeypatch.setattr(solvers, "_LS_FIRST_STAGE", first_stage)
    monkeypatch.setattr(solvers, "_armijo_pick", spy_pick)
    monkeypatch.setattr(linear, "_multinomial_trial_losses", spy_losses)
    model = jax.jit(lambda: fam.fit_task_batched(
        dyn, static, data, w, meta))()
    model = {name: np.asarray(leaf) for name, leaf in model.items()}
    jax.effects_barrier()
    return model, np.stack(picks), np.stack(logits)


def _moved(logits):
    """(iterations - 1, lanes): the lane's logits changed in that
    iteration, so it was neither done nor frozen there."""
    return np.any(logits[1:] != logits[:-1], axis=(1, 3))


def _assert_same_fit(staged, single):
    assert "ls_second_pass" in staged and "ls_second_pass" not in single
    np.testing.assert_array_equal(staged["n_iter"], single["n_iter"])
    np.testing.assert_array_equal(staged["converged"], single["converged"])
    for leaf in ("coef", "intercept"):
        assert np.isfinite(staged[leaf]).all()
        np.testing.assert_allclose(staged[leaf], single[leaf],
                                   rtol=0, atol=1e-5)


@pytest.mark.parametrize("fit_intercept", [True, False],
                         ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("start", ["ordinary", "steep"])
def test_staged_search_picks_what_the_single_pass_picks(
        monkeypatch, start, fit_intercept):
    # steep: features x 100, so unit steps overshoot by orders of
    # magnitude and lanes that move need trials >= K
    scale = {"ordinary": 1.0, "steep": 100.0}[start]
    staged, picks, logits = _fit(monkeypatch, K, scale, fit_intercept)
    single, picks1, logits1 = _fit(monkeypatch, T, scale, fit_intercept)
    _assert_same_fit(staged, single)
    n_iter = int(single["n_iter"][0])
    assert picks.shape == picks1.shape == (n_iter, LANES)
    np.testing.assert_allclose(logits, logits1, rtol=0, atol=1e-4)
    # every lane that moved in an iteration took the single pass's step
    moved = _moved(logits1)
    np.testing.assert_array_equal(picks[:-1][moved], picks1[:-1][moved])
    # elsewhere the staged pick may be the filler over trials that were
    # not evaluated: only where the single pass picked one of those
    differ = picks != picks1
    assert (picks[differ] == T - 1).all() and (picks1[differ] >= K).all()
    # the counter: at least the iterations where a lane that moved needed
    # a trial >= K, at most those where any lane (done ones too) did
    second = int(staged["ls_second_pass"][0])
    assert (staged["ls_second_pass"] == second).all()
    needed = int(np.sum(np.any(moved & (picks1[:-1] >= K), axis=1)))
    offered = int(np.sum(np.any(picks1 >= K, axis=1)))
    assert needed <= second <= offered <= n_iter
    if start == "steep":
        # lanes that moved took late trials, the single pass's (above)
        assert needed >= 1 and second >= 1


@pytest.mark.parametrize("lane_state", ["done", "live"])
def test_only_a_lane_that_is_not_done_asks_for_the_rest(
        monkeypatch, lane_state):
    """The slowest lane (weakest regularisation) passes none of its first
    K + 1 trials in any iteration.  Live, it asks for the second stage in
    every iteration; done (a tol it meets at once retires it after
    iteration 0), it asks for nothing more."""
    lane = LANES - 1
    tol = np.full(LANES, 1e-4, np.float32)
    if lane_state == "done":
        tol[lane] = 1e9
    poison = (lane, np.inf, K + 1)
    staged, picks, logits = _fit(monkeypatch, K, tol=tol, poison=poison)
    single, picks1, _ = _fit(monkeypatch, T, tol=tol, poison=poison)
    _assert_same_fit(staged, single)
    n_iter = int(staged["n_iter"][0])
    second = int(staged["ls_second_pass"][0])
    if lane_state == "live":
        assert _moved(logits)[:, lane].all()
        assert second == n_iter
        np.testing.assert_array_equal(picks[:, lane], picks1[:, lane])
        assert (picks[:, lane] >= K + 1).all()
        return
    assert not _moved(logits)[1:, lane].any()       # done after iteration 0
    # without the poisoned lane: the other lanes' own second passes
    others, _, _ = _fit(monkeypatch, K, tol=tol)
    base = int(others["ls_second_pass"][0])
    assert n_iter == int(others["n_iter"][0]) > base + 1
    # iteration 0, while the lane was live, and never again for its sake
    assert base <= second <= base + 1
    # the single pass still picks a late trial for the done lane in every
    # iteration: the staged search did not evaluate it
    assert (picks1[:, lane] >= K + 1).all()
    assert int(np.sum(picks[:, lane] == T - 1)) >= n_iter - second - 1


@pytest.mark.parametrize("extent", ["first_trials", "every_trial"])
@pytest.mark.parametrize("value", [np.inf, np.nan], ids=["inf", "nan"])
def test_non_finite_first_trials_run_the_second_stage(
        monkeypatch, value, extent):
    lane = 4
    n_trials = {"first_trials": K, "every_trial": T}[extent]
    poison = (lane, value, n_trials)
    staged, picks, logits = _fit(monkeypatch, K, poison=poison)
    single, picks1, _ = _fit(monkeypatch, T, poison=poison)
    _assert_same_fit(staged, single)          # and every leaf is finite
    n_iter = int(staged["n_iter"][0])
    second = int(staged["ls_second_pass"][0])
    moved = _moved(logits)
    clean, _, _ = _fit(monkeypatch, K)
    if extent == "first_trials":
        # the lane takes its first finite trial for as long as it moves
        assert second >= int(np.sum(moved[:, lane])) >= 1
        assert (picks[:-1][moved[:, lane], lane] >= K).all()
        np.testing.assert_array_equal(picks[:-1][moved], picks1[:-1][moved])
    else:
        # no finite loss at all: the lane stays where it started and is
        # never done, so it asks in every iteration up to the cap
        assert not moved[:, lane].any()
        assert second == n_iter == 100
        assert (staged["coef"][lane] == 0).all()
        assert (picks[:, lane] == T - 1).all()
    # the other lanes' fits are their own: vmap lanes are independent
    # (n_iter is the launch's lockstep count, the poisoned lane's too)
    rest = np.arange(LANES) != lane
    np.testing.assert_allclose(staged["coef"][rest], clean["coef"][rest],
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("n_classes,penalty,chunk_loop,staged", [
    (10, "l2", "per_chunk", True), (10, "l2", "scan", True),
    (2, "l2", "per_chunk", False), (10, "elasticnet", "per_chunk", False)],
    ids=["multinomial", "multinomial_scan", "binary", "multinomial_fista"])
def test_report_counts_second_passes(n_classes, penalty, chunk_loop, staged):
    """``linesearch_second_pass_per_launch``: an entry a launch, between 0
    and the launch's iterations where the line search is staged, 0 where
    it is the generic one or the launch ran another solver; through the
    first chunk's fit launch, the later chunks' fused launches and the
    scanned segment."""
    from sklearn.linear_model import LogisticRegression
    X, y, _ = _problem()
    y = y % n_classes
    est_kw = {"max_iter": 29}
    if penalty == "elasticnet":
        est_kw.update(penalty="elasticnet", l1_ratio=0.5, solver="saga")
    gs = sst.GridSearchCV(
        LogisticRegression(**est_kw),
        {"C": list(np.logspace(-3, 1, 40))}, cv=3, backend="tpu",
        refit=False, config=sst.TpuConfig(chunk_loop=chunk_loop)).fit(X, y)
    rep = gs.search_report
    iters = rep["solver_iters_per_launch"]
    second = rep["linesearch_second_pass_per_launch"]
    assert len(second) == len(iters) == len(rep["lanes_per_launch"]) > 1
    assert all(isinstance(v, int) and 0 <= v <= i
               for v, i in zip(second, iters))
    assert rep["linesearch_one_pass_per_launch"] == [int(staged)] * len(iters)
    if staged:
        # a 600-row fit sits at its rounding floor for a few iterations
        # before the stall exit retires its lanes: some launch ran it
        assert sum(second) >= 1
    else:
        assert second == [0] * len(iters)


@pytest.mark.parametrize("report,expected", [
    ({"solver_iters_per_launch": [30, 50, 20],
      "linesearch_second_pass_per_launch": [2, 0, 1]}, 3.0),
    ({"solver_iters_per_launch": [30, 50, 20]}, None),      # the parent's
    ({"linesearch_second_pass_per_launch": []}, None),      # no solver ran
    ({"solver_iters_per_launch": [30, 50],
      "linesearch_second_pass_per_launch": [2]}, None)],
    ids=["share", "no_counter", "no_launch", "mismatched"])
def test_benchmark_reader_of_the_counter(report, expected):
    """``benchmark/layers/solver.linesearch_second_pass_share.py``: the
    share of iterations that ran the second stage, nothing (and no
    failure) on a report without the counter."""
    import importlib.util
    import os
    path = os.path.join(
        os.path.dirname(__file__), os.pardir, "benchmark", "layers",
        "solver.linesearch_second_pass_share.py")
    spec = importlib.util.spec_from_file_location("second_pass_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    value = reader.read({"report": report})
    assert value is None if expected is None \
        else value == pytest.approx(expected)
