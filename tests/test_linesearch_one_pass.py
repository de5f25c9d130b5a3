"""The one-pass line search of the multinomial fit against the generic one.

``glm_lbfgs_batched`` evaluates its ``ls_trials`` trial losses either as
``jax.vmap`` of the caller's ``data_loss`` (generic) or through the
caller's ``trial_data_loss`` (``models/linear.py`` hands one for the
multinomial loss: the class reduction unrolled over class planes, the
label term taken off the trial axis).  Same mathematics, another order
of summation: the trial losses agree to rounding, are non-finite in the
same places, each lane picks the same step, and a whole fit ends at the
same coefficients.  XLA:CPU; what the rewrite compiles to on the chip is
pinned in ``tests/test_scopes_tpu_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models import linear
from spark_sklearn_tpu.ops import solvers

N, D, B, T = 240, 12, 6, 16
C1 = 1e-4


def _draw(kind, k, fit_intercept, seed=0):
    """(Z, Zp, alphas, wT, y1h) as the solver would hold them."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)).astype(np.float32)
    y = rng.integers(0, k, size=N)
    y1h = np.eye(k, dtype=np.float32)[y]
    scale = 1e4 if kind == "huge" else 1.0

    def logits(sc):
        W = (sc * rng.normal(size=(B, k, D))).astype(np.float32)
        Z = np.einsum("nd,bkd->nbk", X, W)
        if fit_intercept:
            Z = Z + (sc * rng.normal(size=(B, k))).astype(np.float32)[None]
        return Z.astype(np.float32)

    Z, Zp = logits(scale), logits(scale)
    wT = rng.uniform(0.5, 1.5, size=(N, B)).astype(np.float32)
    a0 = np.ones((B,), np.float32)
    if kind == "zero_weight":
        wT[::3] = 0.0
        wT[5, :] = 0.0
    if kind == "overflow":
        # lane 0's largest step overflows float32: that trial is inf
        a0[0] = 3e38
    alphas = a0[None, :] * (0.5 ** np.arange(T, dtype=np.float32))[:, None]
    return tuple(jnp.asarray(a) for a in (Z, Zp, alphas, wT, y1h))


def _pick(losses, alphas, f, dginit):
    """The solver's Armijo pick (ops/solvers.py, glm_lbfgs.linesearch)."""
    armijo = losses <= f[None, :] + C1 * alphas * dginit[None, :]
    first_ok = jnp.argmax(armijo, axis=0)
    return jnp.where(jnp.any(armijo, axis=0), first_ok, T - 1)


@pytest.mark.parametrize("fit_intercept", [True, False],
                         ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("k", [3, 10])
@pytest.mark.parametrize("kind",
                         ["ordinary", "huge", "overflow", "zero_weight"])
def test_trial_losses_match_generic(kind, k, fit_intercept):
    Z, Zp, alphas, wT, y1h = _draw(kind, k, fit_intercept)
    generic = jax.jit(jax.vmap(
        lambda a: linear._multinomial_loss(Z + a[None, :, None] * Zp,
                                           wT, y1h)))(alphas)
    one_pass = jax.jit(linear._multinomial_trial_losses)(
        Z, Zp, alphas, wT, y1h)
    assert one_pass.shape == generic.shape == (T, B)
    finite = np.isfinite(np.asarray(generic))
    np.testing.assert_array_equal(np.isfinite(np.asarray(one_pass)), finite)
    if kind == "overflow":
        assert not finite[0, 0] and finite[1:, 1:].all()
    else:
        assert finite.all()
    np.testing.assert_allclose(np.asarray(one_pass)[finite],
                               np.asarray(generic)[finite], rtol=1e-5)
    # the same step is picked: Armijo from the loss and the slope at a = 0
    f, dginit = jax.jvp(lambda a: linear._multinomial_loss(
        Z + a[None, :, None] * Zp, wT, y1h),
        (jnp.zeros((B,)),), (jnp.ones((B,)),))
    np.testing.assert_array_equal(
        np.asarray(_pick(one_pass, alphas, f, dginit)),
        np.asarray(_pick(generic, alphas, f, dginit)))


def _ten_class_problem():
    rng = np.random.default_rng(3)
    n, d, k = 600, 20, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    Wt = rng.normal(size=(d, k)).astype(np.float32)
    y = np.argmax(X @ Wt + rng.gumbel(size=(n, k)), axis=1)
    return X, y.astype(np.int32), k


@pytest.mark.parametrize("fit_intercept", [True, False],
                         ids=["intercept", "no_intercept"])
def test_fit_ends_where_the_generic_fit_ends(monkeypatch, fit_intercept):
    X, y, k = _ten_class_problem()
    n, d = X.shape
    lanes = 12
    fam = linear.LogisticRegressionFamily
    data = {"X": jnp.asarray(X), "y": jnp.asarray(y),
            "y1h": jnp.asarray(np.eye(k, dtype=np.float32)[y])}
    meta = {"n_classes": k, "classes": np.arange(k), "n_features": d}
    static = {"max_iter": 100, "fit_intercept": fit_intercept}
    dyn = {"C": jnp.asarray(np.logspace(-3, 1, lanes), jnp.float32)}
    w = jnp.ones((lanes, n), jnp.float32)

    def fit():
        return jax.jit(lambda: fam.fit_task_batched(
            dyn, static, data, w, meta))()

    handed = []
    real = solvers.glm_lbfgs_batched

    def withheld(*args, trial_data_loss=None, **kw):
        handed.append(trial_data_loss)
        return real(*args, **kw)

    one_pass = fit()
    monkeypatch.setattr(solvers, "glm_lbfgs_batched", withheld)
    generic = fit()
    assert len(handed) == 1 and handed[0] is not None
    assert fam.linesearch_one_pass(static, meta)
    assert bool(np.all(generic["converged"]))
    # XLA:CPU sums a variadic reduce row after row, so the one-pass losses
    # carry a few more ulps here than on the chip and a lane may stop at
    # its rounding floor (the solver's stall exit) a hair above tol
    assert int(np.sum(one_pass["converged"])) >= lanes - 2
    assert abs(int(one_pass["n_iter"][0]) - int(generic["n_iter"][0])) <= 1
    np.testing.assert_allclose(np.asarray(one_pass["coef"]),
                               np.asarray(generic["coef"]), atol=1e-3)
    # the unpenalised intercept is the objective's flattest direction:
    # two stops inside the same tol differ most there
    np.testing.assert_allclose(np.asarray(one_pass["intercept"]),
                               np.asarray(generic["intercept"]), atol=5e-3)


@pytest.mark.parametrize("n_classes,penalty,expected", [
    (10, "l2", 1), (2, "l2", 0), (10, "elasticnet", 0)],
    ids=["multinomial", "binary", "multinomial_fista"])
def test_report_says_which_line_search_ran(monkeypatch, n_classes, penalty,
                                           expected):
    """``linesearch_one_pass_per_launch`` reads 1 where the launch's line
    search was the one-pass evaluator, and the family's word agrees with
    what ``fit_task_batched`` hands the solver."""
    from sklearn.linear_model import LogisticRegression
    X, y, _ = _ten_class_problem()
    y = y % n_classes
    handed = []
    real = solvers.glm_lbfgs_batched

    def spy(*args, **kw):
        handed.append(kw.get("trial_data_loss"))
        return real(*args, **kw)

    monkeypatch.setattr(solvers, "glm_lbfgs_batched", spy)
    # statics no other test searches with, so the program is traced here
    # (not taken from the process's program cache) and the spy sees it
    est_kw = {"max_iter": 31}
    if penalty == "elasticnet":
        est_kw.update(penalty="elasticnet", l1_ratio=0.5, solver="saga")
    gs = sst.GridSearchCV(LogisticRegression(**est_kw),
                          {"C": [0.0371, 0.371, 3.71]}, cv=3,
                          backend="tpu", refit=False).fit(X, y)
    rep = gs.search_report
    series = rep["linesearch_one_pass_per_launch"]
    assert len(series) == len(rep["solver_iters_per_launch"]) > 0
    assert set(series) == {expected}
    if penalty == "elasticnet":
        assert handed == []         # FISTA: no L-BFGS line search at all
    else:
        assert handed and all((h is not None) == bool(expected)
                              for h in handed)
