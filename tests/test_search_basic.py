"""Oracle tests for the flagship path (SURVEY §7.2): our GridSearchCV on a
virtual 8-device mesh vs sklearn's serial GridSearchCV on the same splits.

This is the reference's single most important testing idea transplanted
(SURVEY §4): the reference vendored sklearn's own search tests and re-pointed
them at spark_sklearn.GridSearchCV(sc, ...); here the oracle is sklearn run
serially, scores must agree to float32-training tolerance and the
cv_results_ key schema must agree exactly.
"""

import numpy as np
import pytest
from sklearn.linear_model import LogisticRegression as SkLogReg
from sklearn.linear_model import Ridge as SkRidge
from sklearn.model_selection import GridSearchCV as SkGridSearchCV
from sklearn.model_selection import KFold, StratifiedKFold

import spark_sklearn_tpu as sst


def _expected_keys(n_splits, scorer="score", train=False):
    keys = {"mean_fit_time", "std_fit_time", "mean_score_time",
            "std_score_time", "params",
            f"mean_test_{scorer}", f"std_test_{scorer}",
            f"rank_test_{scorer}"}
    keys |= {f"split{i}_test_{scorer}" for i in range(n_splits)}
    if train:
        keys |= {f"mean_train_{scorer}", f"std_train_{scorer}"}
        keys |= {f"split{i}_train_{scorer}" for i in range(n_splits)}
    return keys


class TestGridSearchLogReg:
    def test_matches_sklearn_oracle(self, digits):
        X, y = digits
        X, y = X[:900], y[:900]
        grid = {"C": [0.01, 0.1, 1.0, 10.0]}
        cv = StratifiedKFold(n_splits=3)

        ours = sst.GridSearchCV(
            SkLogReg(max_iter=120), grid, cv=cv).fit(X, y)
        theirs = SkGridSearchCV(
            SkLogReg(max_iter=120), grid, cv=cv).fit(X, y)

        a = ours.cv_results_["mean_test_score"]
        b = theirs.cv_results_["mean_test_score"]
        np.testing.assert_allclose(a, b, atol=5e-3)
        assert ours.best_params_ == theirs.best_params_
        # schema parity (sklearn _search.py:1208-1290)
        assert _expected_keys(3) <= set(ours.cv_results_)
        assert "param_C" in ours.cv_results_
        assert isinstance(ours.cv_results_["param_C"], np.ma.MaskedArray)

    def test_best_estimator_predicts(self, digits):
        X, y = digits
        gs = sst.GridSearchCV(
            SkLogReg(max_iter=100), {"C": [0.1, 1.0]}, cv=3).fit(X, y)
        assert gs.best_estimator_ is not None
        assert gs.predict(X[:10]).shape == (10,)
        assert gs.score(X, y) > 0.9
        assert gs.refit_time_ > 0
        assert gs.n_splits_ == 3
        assert not gs.multimetric_
        assert np.array_equal(gs.classes_, np.unique(y))

    def test_legacy_sc_convention(self, digits):
        """Reference API: GridSearchCV(sc, estimator, grid) — grid_search.py."""
        X, y = digits

        class FakeSparkContext:
            pass

        gs = sst.GridSearchCV(
            FakeSparkContext(), SkLogReg(max_iter=50), {"C": [1.0]},
            cv=3).fit(X, y)
        assert gs.best_score_ > 0.9

    def test_return_train_score(self, digits):
        X, y = digits
        gs = sst.GridSearchCV(
            SkLogReg(max_iter=100), {"C": [0.1, 1.0]}, cv=3,
            return_train_score=True).fit(X, y)
        assert _expected_keys(3, train=True) <= set(gs.cv_results_)
        # train score >= test score in aggregate for this easy problem
        assert (gs.cv_results_["mean_train_score"].mean()
                >= gs.cv_results_["mean_test_score"].mean() - 1e-3)

    def test_multinomial_and_binary(self, digits):
        X, y = digits
        # binary subset
        m = y < 2
        gs = sst.GridSearchCV(
            SkLogReg(max_iter=100), {"C": [1.0]}, cv=3).fit(X[m], y[m])
        assert gs.best_score_ > 0.98

    def test_verbose_prints(self, digits, capsys):
        X, y = digits
        sst.GridSearchCV(
            SkLogReg(max_iter=50), {"C": [1.0, 2.0]}, cv=3,
            verbose=1).fit(X, y)
        out = capsys.readouterr().out
        assert "Fitting 3 folds for each of 2 candidates" in out


class TestGridSearchRidge:
    def test_ridge_oracle(self, diabetes):
        X, y = diabetes
        grid = {"alpha": [0.1, 1.0, 10.0, 100.0]}
        cv = KFold(n_splits=4)
        ours = sst.GridSearchCV(SkRidge(), grid, cv=cv).fit(X, y)
        theirs = SkGridSearchCV(SkRidge(), grid, cv=cv).fit(X, y)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=2e-3)
        assert ours.best_params_ == theirs.best_params_


class TestRandomizedSearch:
    def test_randomized_matches_sampler(self, digits):
        X, y = digits
        from scipy.stats import loguniform
        dist = {"C": loguniform(1e-3, 1e2)}
        ours = sst.RandomizedSearchCV(
            SkLogReg(max_iter=100), dist, n_iter=5, cv=3,
            random_state=42).fit(X, y)
        theirs = sst.RandomizedSearchCV(
            SkLogReg(max_iter=100), dist, n_iter=5, cv=3,
            random_state=42, backend="host").fit(X, y)
        # same random_state -> identical candidates (sklearn ParameterSampler)
        assert [p["C"] for p in ours.cv_results_["params"]] == \
               [p["C"] for p in theirs.cv_results_["params"]]
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=5e-3)


class TestTierBFallback:
    def test_unregistered_estimator_runs_on_host(self, digits):
        X, y = digits
        from sklearn.tree import DecisionTreeClassifier
        gs = sst.GridSearchCV(
            DecisionTreeClassifier(random_state=0),
            {"max_depth": [2, 4]}, cv=3).fit(X, y)
        assert set(gs.cv_results_["params"][0]) == {"max_depth"}
        assert gs.best_score_ > 0.5

    def test_host_backend_forced(self, digits):
        X, y = digits
        gs = sst.GridSearchCV(
            SkLogReg(max_iter=100), {"C": [1.0]}, cv=3,
            backend="host").fit(X, y)
        assert gs.best_score_ > 0.9


class TestErrorScore:
    def test_error_score_masks_failures(self, digits):
        X, y = digits
        # C large enough to overflow float32 exp -> non-finite path exercised
        # by an impossible tol; instead force failure via Tier B with a
        # broken estimator
        from sklearn.base import BaseEstimator, ClassifierMixin

        class Broken(ClassifierMixin, BaseEstimator):
            def __init__(self, fail=True):
                self.fail = fail

            def fit(self, X, y):
                if self.fail:
                    raise ValueError("boom")
                self.classes_ = np.unique(y)
                return self

            def predict(self, X):
                return np.zeros(len(X), dtype=int)

        from sklearn.exceptions import FitFailedWarning
        with pytest.warns(FitFailedWarning, match="fits failed out of"):
            gs = sst.GridSearchCV(
                Broken(), {"fail": [True, False]}, cv=3,
                error_score=0.0).fit(X, y)
        assert gs.cv_results_["mean_test_score"][0] == 0.0


class TestCompileGroups:
    def test_mixed_static_dynamic_grid(self, digits):
        """penalty=None vs l2 forces two compile groups (SURVEY §7.3 #3)."""
        X, y = digits
        gs = sst.GridSearchCV(
            SkLogReg(max_iter=100),
            [{"C": [0.5, 1.0], "penalty": ["l2"]},
             {"penalty": [None]}],
            cv=3).fit(X, y)
        assert len(gs.cv_results_["params"]) == 3
        assert np.all(np.isfinite(gs.cv_results_["mean_test_score"]))


class TestSklearnEstimatorContract:
    def test_clone_and_repr(self, digits):
        """Search estimators must satisfy sklearn's introspection contract
        (get_params/clone/repr) — regression for *args in __init__."""
        from sklearn.base import clone
        from sklearn.linear_model import LogisticRegression as SkLogReg
        gs = sst.GridSearchCV(SkLogReg(), {"C": [1.0]}, cv=3)
        gs2 = clone(gs)
        assert gs2.param_grid == {"C": [1.0]}
        assert "GridSearchCV" in repr(gs)
        rs = sst.RandomizedSearchCV(SkLogReg(), {"C": [1.0]}, n_iter=1)
        assert clone(rs).n_iter == 1
        assert "RandomizedSearchCV" in repr(rs)


class TestSparseInput:
    def test_scipy_sparse_compiled_matches_dense(self, digits):
        import scipy.sparse as sp
        X, y = digits
        Xs = sp.csr_matrix(X)
        dense = sst.GridSearchCV(
            SkLogReg(max_iter=100), {"C": [1.0]}, cv=3,
            backend="tpu", refit=False).fit(X, y)
        sparse = sst.GridSearchCV(
            SkLogReg(max_iter=100), {"C": [1.0]}, cv=3,
            backend="tpu", refit=False).fit(Xs, y)
        np.testing.assert_allclose(
            dense.cv_results_["mean_test_score"],
            sparse.cv_results_["mean_test_score"], atol=1e-6)

    def test_csrmatrix_container_input(self, digits):
        import scipy.sparse as sp
        X, y = digits
        c = sst.CSRMatrix.from_scipy(sp.csr_matrix(X))
        gs = sst.GridSearchCV(
            SkLogReg(max_iter=100), {"C": [1.0]}, cv=3).fit(c, y)
        assert gs.best_score_ > 0.9  # refit on scipy-converted X works

    def test_sparse_host_path_untouched(self, digits):
        import scipy.sparse as sp
        from sklearn.tree import DecisionTreeClassifier
        X, y = digits
        Xs = sp.csr_matrix(X)
        gs = sst.GridSearchCV(
            DecisionTreeClassifier(random_state=0), {"max_depth": [3]},
            cv=3).fit(Xs, y)
        assert gs.best_score_ > 0.4


class TestParamPrevalidation:
    def test_invalid_static_value_gets_error_score(self, digits):
        """A candidate whose static param would crash tracing (SVC
        degree='junk') is excluded from the launch and recorded as a
        failed fit — the valid candidates still run compiled."""
        from sklearn.svm import SVC
        X, y = digits
        m = y < 2
        with pytest.warns(Warning):
            gs = sst.GridSearchCV(
                SVC(), {"degree": [3, "junk"]}, cv=3, backend="tpu",
                error_score=np.nan, refit=False).fit(X[m][:150], y[m][:150])
        scores = gs.cv_results_["mean_test_score"]
        good = gs.cv_results_["param_degree"] == 3
        assert np.isfinite(scores[good]).all()
        assert np.isnan(scores[~good]).all()
        assert gs.cv_results_["mean_score_time"][~good][0] == 0.0

    def test_error_score_raise_no_fallback(self, digits):
        """error_score='raise' with an invalid candidate raises sklearn's
        own exception, with NO fall-back-to-host warning or host re-run."""
        from sklearn.svm import LinearSVC
        from sklearn.utils._param_validation import InvalidParameterError
        X, y = digits
        m = y < 2
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error", UserWarning)
            with pytest.raises(InvalidParameterError):
                sst.GridSearchCV(
                    LinearSVC(), {"C": [-1.0, 1.0]}, cv=3,
                    error_score="raise").fit(X[m][:120], y[m][:120])

    def test_candidate_overrides_invalid_base_param(self, digits):
        """A candidate that OVERRIDES the base estimator's invalid value
        with a valid one must fit normally (sklearn clones + set_params
        before validating, so the base's C=-1 never reaches fit)."""
        from sklearn.svm import LinearSVC
        X, y = digits
        m = y < 2
        gs = sst.GridSearchCV(
            LinearSVC(C=-1.0), {"C": [0.5, 1.0]}, cv=3,
            error_score=np.nan, refit=False).fit(X[m][:150], y[m][:150])
        assert np.isfinite(gs.cv_results_["mean_test_score"]).all()

    def test_all_candidates_invalid_raises(self, digits):
        """When EVERY fit fails prevalidation, the search raises like
        sklearn's _warn_or_raise_about_fit_failures — even with a
        numeric error_score."""
        from sklearn.svm import LinearSVC
        X, y = digits
        m = y < 2
        with pytest.raises(ValueError, match="All the .* fits failed"):
            sst.GridSearchCV(
                LinearSVC(), {"C": [-1.0, -2.0]}, cv=3,
                error_score=np.nan, refit=False).fit(X[m][:120], y[m][:120])

    def test_verbose_end_lines_show_error_score(self, digits, capsys):
        """verbose>2 END lines print error_score for failed candidates,
        not the garbage a degenerate lane computed (verbose=3 because
        scores appear at verbose>2 only — sklearn's exact gating,
        pinned by tests/test_obs.py)."""
        from sklearn.svm import LinearSVC
        X, y = digits
        m = y < 2
        with pytest.warns(Warning):
            sst.GridSearchCV(
                LinearSVC(), {"C": [0.0, 1.0]}, cv=3, verbose=3,
                error_score=np.nan, refit=False).fit(X[m][:120], y[m][:120])
        out = capsys.readouterr().out
        assert out.count("score=nan") == 3          # the C=0 candidate
        assert len([ln for ln in out.splitlines()
                    if "] END" in ln]) == 6         # 2 candidates x 3 folds


class TestMoreOracles:
    def test_linear_regression_rank_deficient_min_norm(self):
        """On rank-deficient X the compiled OLS must return sklearn's
        minimum-norm lstsq solution, not a tiny-ridge approximation
        (VERDICT round-1 weak #8)."""
        from sklearn.linear_model import LinearRegression
        rng = np.random.default_rng(0)
        X4 = rng.normal(size=(60, 4))
        X = np.hstack([X4, X4[:, :2]]).astype(np.float32)  # rank 4 of 6
        y = (X4[:, 0] - 2 * X4[:, 1]
             + 0.1 * rng.normal(size=60)).astype(np.float32)
        sk = LinearRegression().fit(X, y)
        gs = sst.GridSearchCV(
            LinearRegression(), {"fit_intercept": [True]}, cv=3,
            backend="tpu", refit=True).fit(X, y)
        np.testing.assert_allclose(
            gs.best_estimator_.coef_, sk.coef_, atol=1e-4)
        assert abs(np.linalg.norm(gs.best_estimator_.coef_)
                   - np.linalg.norm(sk.coef_)) < 1e-4

    def test_elasticnet_lasso_oracle(self, diabetes):
        from sklearn.linear_model import ElasticNet, Lasso
        from sklearn.model_selection import GridSearchCV as SkGS
        X, y = diabetes
        yn = ((y - y.mean()) / y.std()).astype(np.float32)
        grid = {"alpha": [0.001, 0.01, 0.1]}
        ours = sst.GridSearchCV(
            ElasticNet(max_iter=2000), grid, cv=3, backend="tpu").fit(X, yn)
        theirs = SkGS(ElasticNet(max_iter=2000), grid, cv=3).fit(X, yn)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=0.02)
        lou = sst.GridSearchCV(
            Lasso(max_iter=2000), grid, cv=3, backend="tpu").fit(X, yn)
        lth = SkGS(Lasso(max_iter=2000), grid, cv=3).fit(X, yn)
        np.testing.assert_allclose(
            lou.cv_results_["mean_test_score"],
            lth.cv_results_["mean_test_score"], atol=0.02)

    def test_compiled_error_score_masks_nonfinite(self, digits):
        """error_score on the COMPILED path: a candidate engineered to
        produce non-finite scores is masked, not fatal."""
        X, y = digits
        from sklearn.exceptions import FitFailedWarning
        with pytest.warns(FitFailedWarning, match="non-finite"):
            gs = sst.GridSearchCV(
                SkLogReg(max_iter=50),
                {"C": [1.0, float("nan")]}, cv=3, backend="tpu",
                error_score=-1.0, refit=False).fit(X, y)
        assert gs.cv_results_["mean_test_score"][1] == -1.0
        assert gs.cv_results_["mean_test_score"][0] > 0.8

    def test_compiled_error_score_raise(self, digits):
        # C=nan fails sklearn's own param validation, which the compiled
        # tier now reproduces host-side (round-2 prevalidation): the
        # exception is sklearn's InvalidParameterError, as on the host path
        X, y = digits
        with pytest.raises(ValueError, match="parameter of LogisticRegr"):
            sst.GridSearchCV(
                SkLogReg(max_iter=50), {"C": [float("nan")]}, cv=3,
                backend="tpu", error_score="raise", refit=False).fit(X, y)

    def test_pipeline_with_tree_final_resolution(self, digits):
        """Pipelines ending in a tree family compile iff every transformer
        is monotone per-feature (quantile binning is invariant under
        those, so the codes the tree consumes are provably unchanged)."""
        from sklearn.decomposition import PCA
        from sklearn.ensemble import GradientBoostingClassifier
        from sklearn.pipeline import Pipeline
        from sklearn.preprocessing import StandardScaler
        from spark_sklearn_tpu.models.base import resolve_family
        from spark_sklearn_tpu.models.pipeline import (
            BinnedInvariantPipelineFamily)
        pipe = Pipeline([("s", StandardScaler()),
                         ("g", GradientBoostingClassifier())])
        assert isinstance(resolve_family(pipe),
                          BinnedInvariantPipelineFamily)
        mixed = Pipeline([("p", PCA(n_components=5)),
                          ("g", GradientBoostingClassifier())])
        assert resolve_family(mixed) is None

    def test_bf16_matmul_score_parity(self, digits):
        """bf16 MXU matmuls must stay within a small tolerance of fp32."""
        X, y = digits
        grid = {"C": [0.1, 1.0, 10.0]}
        fp32 = sst.GridSearchCV(
            SkLogReg(max_iter=100), grid, cv=3, backend="tpu",
            refit=False).fit(X, y)
        bf16 = sst.GridSearchCV(
            SkLogReg(max_iter=100), grid, cv=3, backend="tpu",
            refit=False, config=sst.TpuConfig(bf16_matmul=True)).fit(X, y)
        np.testing.assert_allclose(
            fp32.cv_results_["mean_test_score"],
            bf16.cv_results_["mean_test_score"], atol=0.015)


class TestL1Logistic:
    def test_l1_logistic_binary_oracle(self, digits):
        """Elastic-net logistic (proximal FISTA) vs sklearn saga."""
        from sklearn.model_selection import GridSearchCV as SkGS
        X, y = digits
        m = y < 2
        Xb, yb = X[m], y[m]
        grid = {"C": [0.05, 0.5]}
        est = SkLogReg(l1_ratio=1.0, solver="saga", max_iter=300)
        ours = sst.GridSearchCV(est, grid, cv=3, backend="tpu",
                                refit=False).fit(Xb, yb)
        theirs = SkGS(est, grid, cv=3).fit(Xb, yb)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=0.01)

    def test_elasticnet_multinomial_oracle(self, digits):
        from sklearn.model_selection import GridSearchCV as SkGS
        X, y = digits
        Xs, ys = X[:600], y[:600]
        est = SkLogReg(l1_ratio=0.5, solver="saga", max_iter=200)
        ours = sst.GridSearchCV(est, {"C": [0.5]}, cv=3, backend="tpu",
                                refit=False).fit(Xs, ys)
        theirs = SkGS(est, {"C": [0.5]}, cv=3).fit(Xs, ys)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=0.02)

    def test_l1_produces_sparser_coefs_than_l2(self, digits):
        """Sanity: the l1 path actually soft-thresholds (sparsity)."""
        import jax.numpy as jnp
        from spark_sklearn_tpu.models.linear import LogisticRegressionFamily
        X, y = digits
        m = y < 2
        data, meta = LogisticRegressionFamily.prepare_data(X[m], y[m])
        dd = {k: jnp.asarray(v) for k, v in data.items()}
        w = jnp.ones((2, int(m.sum())), jnp.float32)
        C = jnp.asarray([0.05, 0.05], jnp.float32)
        l1 = LogisticRegressionFamily.fit_task_batched(
            {"C": C}, {"penalty": "l1", "max_iter": 200, "tol": 1e-5},
            dd, w, meta)
        l2 = LogisticRegressionFamily.fit_task_batched(
            {"C": C}, {"max_iter": 200, "tol": 1e-5}, dd, w, meta)
        nz_l1 = int(np.sum(np.abs(np.asarray(l1["coef"][0])) > 1e-6))
        nz_l2 = int(np.sum(np.abs(np.asarray(l2["coef"][0])) > 1e-6))
        assert nz_l1 < nz_l2

    def test_lbfgs_stops_by_sklearns_rule(self, digits):
        """sklearn hands scipy the MEAN loss, so its `tol` bounds the
        gradient of the sum-loss objective divided by the summed sample
        weights.  Held without that factor the solver runs ~n_samples
        times tighter than sklearn, burns max_iter, and lands on a more
        overfit model wherever regularisation is weak (C = 1000 on
        digits: 1e-2 off sklearn's mean_test_score)."""
        import warnings

        import jax.numpy as jnp
        from spark_sklearn_tpu.models.linear import LogisticRegressionFamily
        X, y = digits
        tr = np.arange(len(y)) % 5 != 0
        data, meta = LogisticRegressionFamily.prepare_data(X, y)
        dd = {k: jnp.asarray(v) for k, v in data.items()}
        C, tol, max_iter = 1000.0, 1e-4, 1000
        m = LogisticRegressionFamily.fit_task_batched(
            {"C": jnp.asarray([C], jnp.float32)},
            {"max_iter": max_iter, "tol": tol}, dd,
            jnp.asarray(tr[None, :], jnp.float32), meta)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            sk = SkLogReg(C=C, tol=tol, max_iter=max_iter).fit(X[tr], y[tr])
        n_sk, n_ours = int(sk.n_iter_[0]), int(m["n_iter"][0])
        assert n_sk < max_iter and n_ours < max_iter
        assert 0.5 * n_sk <= n_ours <= 2 * n_sk, (n_ours, n_sk)
        # stopped inside sklearn's ball, not n_samples times deeper
        W = np.asarray(m["coef"][0], np.float64)
        b = np.asarray(m["intercept"][0], np.float64)
        Z = X[tr] @ W.T + b
        P = np.exp(Z - Z.max(1, keepdims=True))
        P /= P.sum(1, keepdims=True)
        R = P - np.eye(10)[y[tr]]
        n = int(tr.sum())
        g = max(np.abs(R.T @ X[tr] / n + W / (C * n)).max(),
                np.abs(R.mean(0)).max())
        assert tol / 10 < g <= 1.5 * tol, g
