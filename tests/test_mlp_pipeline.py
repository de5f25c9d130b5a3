"""MLP and Pipeline compiled-family tests (BASELINE config #5 path)."""

import numpy as np
import pytest
from sklearn.linear_model import LogisticRegression as SkLogReg
from sklearn.neural_network import MLPClassifier, MLPRegressor
from sklearn.pipeline import Pipeline, make_pipeline
from sklearn.preprocessing import StandardScaler

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models.base import resolve_family


class TestMLP:
    def test_mlp_classifier_learns(self, digits):
        """Re-read against sklearn when the minibatches became sklearn's
        (PR 33: 6 steps an epoch on a fold's 1 198 training rows, where
        the all-rows batches took 9): sklearn itself scores 0.9004 /
        0.9060 / 0.9082 on random_state 0 / 1 / 2 here."""
        from sklearn.model_selection import GridSearchCV
        X, y = digits
        est = MLPClassifier(hidden_layer_sizes=(64,), max_iter=30,
                            random_state=0)
        grid = {"alpha": [1e-4, 1e-2]}
        gs = sst.GridSearchCV(est, grid, cv=3, backend="tpu").fit(X, y)
        sk = GridSearchCV(est, grid, cv=3, refit=False).fit(X, y)
        ours = gs.cv_results_["mean_test_score"]
        assert ours.max() > 0.88
        assert np.abs(ours - sk.cv_results_["mean_test_score"]).max() < 0.02
        assert gs.best_estimator_ is not None

    def test_mlp_regressor_learns(self, diabetes):
        X, y = diabetes
        yn = (y - y.mean()) / y.std()
        gs = sst.GridSearchCV(
            MLPRegressor(hidden_layer_sizes=(32,), max_iter=100,
                         random_state=0),
            {"alpha": [1e-4]}, cv=3, backend="tpu").fit(X, yn)
        assert gs.cv_results_["mean_test_score"].max() > 0.2

    def test_mlp_close_to_sklearn(self, digits):
        """Accuracy parity band (not exact — different shuffles/init)."""
        X, y = digits
        ours = sst.GridSearchCV(
            MLPClassifier(hidden_layer_sizes=(50,), max_iter=50,
                          random_state=0),
            {"alpha": [1e-4]}, cv=3, backend="tpu").fit(X, y)
        theirs = sst.GridSearchCV(
            MLPClassifier(hidden_layer_sizes=(50,), max_iter=50,
                          random_state=0),
            {"alpha": [1e-4]}, cv=3, backend="host").fit(X, y)
        assert abs(ours.best_score_ - theirs.best_score_) < 0.05

    def test_diverging_candidate_gets_error_score(self, digits):
        # a lr=1e6 MLP fit diverges to NaN weights on the device; that is
        # a FAILED fit (error_score + FitFailedWarning), not a recorded
        # garbage score — sklearn error_score semantics, compiled tier
        # (sklearn parity note: with solver='adam' the lr=1e6 fit stays
        # FINITE in sklearn too and records a chance-level score — only
        # the sgd path genuinely overflows to NaN on both sides)
        from sklearn.exceptions import FitFailedWarning
        X, y = digits
        gs = sst.GridSearchCV(
            MLPClassifier(hidden_layer_sizes=(16,), max_iter=15,
                          random_state=0, solver="sgd"),
            {"learning_rate_init": [1e-3, 1e6]}, cv=3, backend="tpu",
            error_score=-7.0, refit=False)
        with pytest.warns(FitFailedWarning, match="fits failed"):
            gs.fit(X, y)
        scores = gs.cv_results_["mean_test_score"]
        assert np.isfinite(scores[0]) and scores[0] != -7.0  # sane cand
        assert scores[1] == -7.0        # diverged candidate masked

    def test_diverging_candidate_error_score_raise(self, digits):
        X, y = digits
        gs = sst.GridSearchCV(
            MLPClassifier(hidden_layer_sizes=(16,), max_iter=15,
                          random_state=0, solver="sgd"),
            {"learning_rate_init": [1e6]}, cv=3, backend="tpu",
            error_score="raise", refit=False)
        with pytest.raises(ValueError, match="non-finite"):
            gs.fit(X, y)

    def test_mlp_binary_roc_auc_compiled(self, digits):
        # binary decision must be a 1-D margin so roc_auc traces; the full
        # (n, 2) logits used to crash the compiled scorer at trace time
        X, y = digits
        mask = y < 2
        X2, y2 = X[mask], y[mask]
        gs = sst.GridSearchCV(
            MLPClassifier(hidden_layer_sizes=(32,), max_iter=30,
                          random_state=0),
            {"alpha": [1e-4, 1e-2]}, cv=3, backend="tpu",
            scoring="roc_auc").fit(X2, y2)
        assert gs.search_report["backend"] == "tpu"
        assert gs.cv_results_["mean_test_score"].max() > 0.95

    def test_early_stopping_stays_compiled(self, digits):
        """early_stopping holds out validation rows, restores the best
        weights, and stays on the compiled tier (round-2: previously a
        host fallback)."""
        X, y = digits
        gs = sst.GridSearchCV(
            MLPClassifier(hidden_layer_sizes=(16,), max_iter=20,
                          early_stopping=True, random_state=0),
            {"alpha": [1e-4]}, cv=3).fit(X, y)
        assert gs.search_report["backend"] == "tpu"
        assert gs.best_score_ > 0.5

    def test_loss_plateau_stops_before_max_iter(self, digits):
        """sklearn's tol/n_iter_no_change training-loss plateau rule is
        compiled: a converged net reports n_iter < max_iter."""
        from spark_sklearn_tpu.models.base import resolve_family
        X, y = digits
        m = y < 2
        Xs, ys = X[m][:200], y[m][:200]
        est = MLPClassifier(hidden_layer_sizes=(8,), max_iter=500,
                            random_state=0, tol=1e-3)
        fam = resolve_family(est)
        data, meta = fam.prepare_data(Xs, ys)
        model = fam.fit({}, fam.extract_params(est), data,
                        np.ones(len(ys), np.float32), meta)
        assert int(model["n_iter"]) < 500
        # and end-to-end through the search it stays compiled
        gs = sst.GridSearchCV(est, {"alpha": [1e-4]}, cv=3).fit(Xs, ys)
        assert gs.search_report["backend"] == "tpu"
        assert gs.best_score_ > 0.9

    def test_sgd_schedules_stay_compiled(self, digits):
        X, y = digits
        m = y < 3
        for sched in ("invscaling", "adaptive"):
            # invscaling decays lr by (samples_seen)^-0.5, so it needs a
            # large lr_init to learn at all (sklearn behaves the same)
            gs = sst.GridSearchCV(
                MLPClassifier(hidden_layer_sizes=(16,), max_iter=40,
                              solver="sgd", learning_rate=sched,
                              learning_rate_init=0.2, random_state=0),
                {"alpha": [1e-4]}, cv=3).fit(X[m][:250], y[m][:250])
            assert gs.search_report["backend"] == "tpu", sched
            assert gs.best_score_ > 0.8, sched


class TestPipeline:
    def test_resolves_to_compiled_family(self):
        pipe = Pipeline([("scale", StandardScaler()),
                         ("clf", SkLogReg())])
        fam = resolve_family(pipe)
        assert fam is not None
        assert fam.dynamic_params == {"clf__C": np.float32,
                                      "clf__tol": np.float32}

    def test_unsupported_step_returns_none(self):
        from sklearn.feature_selection import SelectKBest
        pipe = Pipeline([("sel", SelectKBest(k=2)), ("clf", SkLogReg())])
        assert resolve_family(pipe) is None

    def test_pipeline_grid_oracle(self, digits):
        """Config #5 shape: scaler + estimator with step__param routing."""
        from sklearn.model_selection import GridSearchCV as SkGS
        X, y = digits
        pipe = Pipeline([("scale", StandardScaler()),
                         ("clf", SkLogReg(max_iter=200))])
        grid = {"clf__C": [0.1, 1.0, 10.0]}
        ours = sst.GridSearchCV(pipe, grid, cv=3, backend="tpu").fit(X, y)
        theirs = SkGS(pipe, grid, cv=3).fit(X, y)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=7e-3)
        assert ours.best_params_ == theirs.best_params_

    def test_pipeline_svc_grid_oracle(self, digits):
        """Config #2 shape with a scaler: Pipeline(StandardScaler, SVC)
        stays compiled (task-batched per-fold transform composition)."""
        from sklearn.model_selection import GridSearchCV as SkGS
        from sklearn.svm import SVC as SkSVC
        X, y = digits
        m = y < 6
        X, y = X[m][:300], y[m][:300]
        pipe = Pipeline([("scale", StandardScaler()),
                         ("clf", SkSVC())])
        grid = {"clf__C": [0.5, 2.0], "clf__gamma": [0.01, 0.05]}
        ours = sst.GridSearchCV(pipe, grid, cv=3, backend="tpu").fit(X, y)
        assert ours.search_report["backend"] == "tpu"
        theirs = SkGS(pipe, grid, cv=3).fit(X, y)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=2e-2)
        assert ours.best_params_ == theirs.best_params_

    def test_pipeline_svc_gamma_scale_oracle(self, digits):
        # gamma='scale' must resolve against the TRANSFORMED per-fold X
        from sklearn.model_selection import GridSearchCV as SkGS
        from sklearn.svm import SVC as SkSVC
        X, y = digits
        X, y = X[:400], y[:400]
        pipe = Pipeline([("scale", StandardScaler()),
                         ("clf", SkSVC(gamma="scale"))])
        grid = {"clf__C": [1.0, 4.0]}
        ours = sst.GridSearchCV(pipe, grid, cv=3, backend="tpu").fit(X, y)
        theirs = SkGS(pipe, grid, cv=3).fit(X, y)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=2e-2)

    def test_pipeline_gbdt_binned_invariant_oracle(self, digits):
        """Scaler+GBDT compiles via binning invariance (monotone
        per-feature steps cannot change quantile codes)."""
        from sklearn.ensemble import GradientBoostingClassifier as SkGBC
        from sklearn.model_selection import GridSearchCV as SkGS
        X, y = digits
        mask = y < 3
        X, y = X[mask][:300], y[mask][:300]
        pipe = Pipeline([("scale", StandardScaler()),
                         ("clf", SkGBC(n_estimators=20, max_depth=2,
                                       random_state=0))])
        grid = {"clf__learning_rate": [0.1, 0.3]}
        ours = sst.GridSearchCV(pipe, grid, cv=3, backend="tpu").fit(X, y)
        assert ours.search_report["backend"] == "tpu"
        theirs = SkGS(pipe, grid, cv=3).fit(X, y)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=5e-2)
        assert ours.best_params_ == theirs.best_params_

    def test_pipeline_pca_gbdt_falls_back(self, digits):
        # PCA mixes features: binning invariance does not hold -> host
        from sklearn.decomposition import PCA
        from sklearn.ensemble import GradientBoostingClassifier as SkGBC
        pipe = Pipeline([("pca", PCA(n_components=8)),
                         ("clf", SkGBC(n_estimators=5))])
        assert resolve_family(pipe) is None

    def test_pipeline_sample_weight_goes_host(self, digits):
        # sklearn raises on bare sample_weight to Pipeline.fit; the host
        # path reproduces that contract instead of silently weighting
        X, y = digits
        pipe = Pipeline([("scale", StandardScaler()),
                         ("clf", SkLogReg(max_iter=50))])
        gs = sst.GridSearchCV(pipe, {"clf__C": [1.0]}, cv=3, backend="tpu")
        with pytest.raises(ValueError, match="not supported"):
            gs.fit(X, y, sample_weight=np.ones(len(y)))

    def test_pipeline_mlp_grid(self, digits):
        X, y = digits
        pipe = make_pipeline(
            StandardScaler(),
            MLPClassifier(hidden_layer_sizes=(32,), max_iter=30,
                          random_state=0))
        grid = {"mlpclassifier__alpha": [1e-4, 1e-1]}
        gs = sst.GridSearchCV(pipe, grid, cv=3, backend="tpu").fit(X, y)
        assert gs.cv_results_["mean_test_score"].max() > 0.9
        assert set(gs.best_params_) == {"mlpclassifier__alpha"}


class TestPCAPipeline:
    def test_pca_logreg_oracle(self, digits):
        """Pipeline(PCA + LogReg) compiled vs sklearn on the same splits."""
        from sklearn.decomposition import PCA
        from sklearn.model_selection import GridSearchCV as SkGS
        X, y = digits
        pipe = Pipeline([("pca", PCA(n_components=20)),
                         ("clf", SkLogReg(max_iter=200))])
        grid = {"clf__C": [0.1, 1.0]}
        ours = sst.GridSearchCV(pipe, grid, cv=3, backend="tpu").fit(X, y)
        theirs = SkGS(pipe, grid, cv=3).fit(X, y)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=0.015)
        assert ours.best_params_ == theirs.best_params_

    def test_pca_whiten(self, digits):
        from sklearn.decomposition import PCA
        X, y = digits
        pipe = Pipeline([("pca", PCA(n_components=16, whiten=True)),
                         ("clf", SkLogReg(max_iter=200))])
        gs = sst.GridSearchCV(pipe, {"clf__C": [1.0]}, cv=3,
                              backend="tpu").fit(X, y)
        assert gs.best_score_ > 0.85

    def test_pca_randomized_solver_falls_back(self, digits):
        from sklearn.decomposition import PCA
        X, y = digits
        pipe = Pipeline([("pca", PCA(n_components=8,
                                     svd_solver="randomized",
                                     random_state=0)),
                         ("clf", SkLogReg(max_iter=100))])
        with pytest.warns(UserWarning, match="falling back"):
            gs = sst.GridSearchCV(pipe, {"clf__C": [1.0]},
                                  cv=3).fit(X[:300], y[:300])
        assert gs.best_score_ > 0.5
