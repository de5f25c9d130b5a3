"""Compiled KNN families vs sklearn oracles.

The TPU-first design point under test: ALL n_neighbors x weights candidates
share one neighbour search of the rows in blocks, one selection a row
(models/neighbors.py), so the whole grid forms one compile group."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.model_selection import GridSearchCV, StratifiedKFold
from sklearn.neighbors import KNeighborsClassifier, KNeighborsRegressor
from sklearn.pipeline import Pipeline
from sklearn.preprocessing import StandardScaler

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models import neighbors
from spark_sklearn_tpu.models.neighbors import KNeighborsClassifierFamily

#: a device-memory budget under which a 500-row search of the grid below
#: goes through its neighbour search in three blocks or more
SMALL_BUDGET = 1_500_000
GRID = {"n_neighbors": [1, 3, 5, 9], "weights": ["uniform", "distance"]}


def _both(estimator, grid, X, y, config=None, **kw):
    """(the compiled search, scikit-learn's own) on the same folds."""
    ours = sst.GridSearchCV(estimator, grid, backend="tpu", config=config,
                            **kw).fit(X, y)
    theirs = GridSearchCV(estimator, grid, **kw).fit(X, y)
    return ours, theirs


class TestKNNClassifier:
    def test_grid_matches_sklearn(self, digits):
        X, y = digits
        Xs, ys = X[:500], y[:500]
        grid = {"n_neighbors": [1, 3, 5, 9],
                "weights": ["uniform", "distance"]}
        ours = sst.GridSearchCV(KNeighborsClassifier(), grid, cv=3,
                                backend="tpu").fit(Xs, ys)
        assert ours.search_report["backend"] == "tpu"
        # ONE compile group: weights is a per-task choice read off the same
        # neighbour lists as n_neighbors (both weightings' votes are summed
        # in the one search), so neither splits the grid
        assert ours.search_report["n_compile_groups"] == 1
        theirs = sst.GridSearchCV(KNeighborsClassifier(), grid, cv=3,
                                  backend="host").fit(Xs, ys)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=1e-5)
        assert ours.best_params_ == theirs.best_params_

    def test_binary_predict_proba_scoring(self, digits):
        X, y = digits
        m = y < 2
        Xs, ys = X[m][:250], y[m][:250]
        ours = sst.GridSearchCV(
            KNeighborsClassifier(), {"n_neighbors": [3, 7]}, cv=3,
            scoring="accuracy", backend="tpu").fit(Xs, ys)
        theirs = sst.GridSearchCV(
            KNeighborsClassifier(), {"n_neighbors": [3, 7]}, cv=3,
            scoring="accuracy", backend="host").fit(Xs, ys)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=1e-6)

    def test_unsupported_metric_falls_back(self, digits):
        X, y = digits
        gs = sst.GridSearchCV(
            KNeighborsClassifier(metric="manhattan"),
            {"n_neighbors": [3]}, cv=3).fit(X[:200], y[:200])
        assert gs.search_report["backend"] == "host"


class TestKNNRegressor:
    def test_grid_matches_sklearn(self, diabetes):
        X, y = diabetes
        grid = {"n_neighbors": [2, 5, 10],
                "weights": ["uniform", "distance"]}
        ours = sst.GridSearchCV(KNeighborsRegressor(), grid, cv=3,
                                backend="tpu").fit(X, y)
        assert ours.search_report["backend"] == "tpu"
        theirs = sst.GridSearchCV(KNeighborsRegressor(), grid, cv=3,
                                  backend="host").fit(X, y)
        np.testing.assert_allclose(
            ours.cv_results_["mean_test_score"],
            theirs.cv_results_["mean_test_score"], atol=1e-5)
        assert ours.best_params_ == theirs.best_params_


class TestKValidation:
    def test_n_neighbors_exceeding_fold_train_raises(self, digits):
        """ADVICE r3: sklearn raises at kneighbors() when n_neighbors
        exceeds a fold's train count; the compiled tier used to clip
        silently to k=n_train.  Both backends must refuse such grids."""
        import pytest as _pt
        from sklearn.neighbors import KNeighborsClassifier

        X, y = digits
        idx = np.concatenate([np.where(y == 0)[0][:6],
                              np.where(y == 1)[0][:6]])
        Xs, ys = X[idx], y[idx]           # cv=3 -> 8 train rows per fold
        with _pt.raises(ValueError, match="n_neighbors"):
            sst.GridSearchCV(
                KNeighborsClassifier(), {"n_neighbors": [3, 10]},
                cv=3, backend="tpu").fit(Xs, ys)

    def test_valid_k_still_compiles(self, digits):
        from sklearn.neighbors import KNeighborsClassifier

        X, y = digits
        Xs, ys = X[:60], y[:60]
        gs = sst.GridSearchCV(
            KNeighborsClassifier(), {"n_neighbors": [3, 5]},
            cv=3, backend="tpu").fit(Xs, ys)
        assert gs.search_report["backend"] == "tpu"


class TestBlockedSearch:
    """The neighbour search in row blocks, sized by the memory ledger."""

    @pytest.mark.parametrize("train_scores", [False, True])
    def test_blocks_under_a_small_budget_match_sklearn(self, digits,
                                                       train_scores):
        """Three blocks or more for 500 rows, in which no whole number of
        blocks fits (the last shares rows with the one before), both
        weightings in one compile group, and with train scores
        scikit-learn's meaning: a row against each fold that trains on
        it, itself at distance 0 among its neighbours."""
        X, y = digits
        ours, theirs = _both(
            KNeighborsClassifier(), GRID, X[:500], y[:500],
            config=sst.TpuConfig(hbm_budget_bytes=SMALL_BUDGET), cv=3,
            return_train_score=train_scores)
        rep = ours.search_report
        assert rep["backend"] == "tpu" and rep["n_compile_groups"] == 1
        assert rep["n_launches"] == 1
        [blocks], [rows] = (rep["knn_blocks_per_launch"],
                            rep["knn_block_rows_per_launch"])
        assert blocks >= 3 and blocks * rows > 500
        for key in ["mean_test_score"] + (["mean_train_score"]
                                          if train_scores else []):
            np.testing.assert_allclose(ours.cv_results_[key],
                                       theirs.cv_results_[key], atol=1e-6)

    def test_regressor_blocks_match_sklearn(self, diabetes):
        X, y = diabetes
        ours, theirs = _both(
            KNeighborsRegressor(), {"n_neighbors": [1, 4, 10],
                                    "weights": ["uniform", "distance"]},
            X, y, config=sst.TpuConfig(hbm_budget_bytes=800_000),
            cv=3, return_train_score=True)
        assert ours.search_report["n_compile_groups"] == 1
        assert ours.search_report["knn_blocks_per_launch"][0] >= 3
        for key in ("mean_test_score", "mean_train_score"):
            np.testing.assert_allclose(ours.cv_results_[key],
                                       theirs.cv_results_[key], rtol=1e-4,
                                       atol=1e-5)

    def test_max_k_is_the_smallest_folds_training_rows(self, digits):
        """Every training row of the smallest fold is a neighbour."""
        X, y = digits
        Xs, ys = X[:90], y[:90]
        cv = StratifiedKFold(3)
        smallest = min(len(tr) for tr, _ in cv.split(Xs, ys))
        ours, theirs = _both(
            KNeighborsClassifier(),
            {"n_neighbors": [1, smallest],
             "weights": ["uniform", "distance"]}, Xs, ys,
            config=sst.TpuConfig(hbm_budget_bytes=400_000), cv=cv)
        assert ours.search_report["knn_blocks_per_launch"][0] >= 3
        np.testing.assert_allclose(ours.cv_results_["mean_test_score"],
                                   theirs.cv_results_["mean_test_score"],
                                   atol=1e-6)

    def test_exact_duplicates_take_the_distance_vote(self, digits):
        """A row whose twin trains in its fold is at distance 0 from it:
        under weights="distance" the twin takes the whole vote
        (scikit-learn's zero-distance rule), where a uniform vote of k
        neighbours can outvote it."""
        X, y = digits
        rng = np.random.default_rng(0)
        order = rng.permutation(300)
        Xd = np.concatenate([X[:150], X[:150]])[order]
        yd = np.concatenate([y[:150], y[:150]])[order]
        ours, theirs = _both(
            KNeighborsClassifier(), {"n_neighbors": [1, 7, 15],
                                     "weights": ["uniform", "distance"]},
            Xd, yd, config=sst.TpuConfig(hbm_budget_bytes=SMALL_BUDGET),
            cv=3)
        np.testing.assert_allclose(ours.cv_results_["mean_test_score"],
                                   theirs.cv_results_["mean_test_score"],
                                   atol=1e-6)
        scores = {(p["n_neighbors"], p["weights"]): s for p, s in zip(
            ours.cv_results_["params"], ours.cv_results_["mean_test_score"])}
        for k in (7, 15):
            assert scores[k, "distance"] > scores[k, "uniform"]

    @pytest.mark.parametrize("train_scores", [False, True])
    def test_one_selection_a_row_unless_training_rows_are_scored(
            self, digits, train_scores):
        X, y = digits
        gs = sst.GridSearchCV(
            KNeighborsClassifier(), GRID, cv=3, backend="tpu",
            return_train_score=train_scores,
            config=sst.TpuConfig(hbm_budget_bytes=SMALL_BUDGET)).fit(
                X[:500], y[:500])
        rep = gs.search_report
        rows = rep["knn_blocks_per_launch"][0] \
            * rep["knn_block_rows_per_launch"][0]
        assert rep["knn_selections_per_launch"] == [
            rows * (3 if train_scores else 1)]
        assert rep["knn_distance_entries_per_launch"] == [rows * 500]

    def test_splits_that_do_not_partition_select_a_row_a_fold(self, digits):
        """ShuffleSplit leaves some rows out of every fold's training set
        and tests others twice: the lists are then a fold's own."""
        from sklearn.model_selection import ShuffleSplit
        X, y = digits
        cv = ShuffleSplit(n_splits=3, test_size=0.3, train_size=0.5,
                          random_state=0)
        ours, theirs = _both(KNeighborsClassifier(), GRID, X[:300],
                             y[:300], cv=cv)
        assert ours.search_report["knn_selections_per_launch"] == [900]
        np.testing.assert_allclose(ours.cv_results_["mean_test_score"],
                                   theirs.cv_results_["mean_test_score"],
                                   atol=1e-6)

    def test_a_scaler_in_front_takes_the_host_path(self, digits):
        """A scaler moves every distance: a Pipeline ending in a KNN is
        not the bare KNN (it was scored as one, without its scaler,
        before), and takes scikit-learn's own path."""
        X, y = digits
        Xs = X[:300].copy()
        Xs[:, 5] *= 50.0
        pipe = Pipeline([("sc", StandardScaler()),
                         ("knn", KNeighborsClassifier())])
        ours = sst.GridSearchCV(pipe, {"knn__n_neighbors": [3, 5]},
                                cv=3).fit(Xs, y[:300])
        theirs = GridSearchCV(pipe, {"knn__n_neighbors": [3, 5]},
                              cv=3).fit(Xs, y[:300])
        assert ours.search_report["backend"] == "host"
        np.testing.assert_allclose(ours.cv_results_["mean_test_score"],
                                   theirs.cv_results_["mean_test_score"])


class TestLaunchTiles:
    META = {"n_classes": 10, "classes": np.arange(10), "n_features": 64,
            "n_samples": 700, "max_k": 9, "held_out_once": True}

    def test_block_rows_even_out_the_blocks(self):
        assert neighbors.block_rows(70_000, 42_500) == 35_000
        assert neighbors.block_rows(70_000, 11_000) == 10_000
        assert neighbors.block_rows(500, 166) == 128
        assert neighbors.block_rows(500, 10_000) == 500
        assert neighbors.block_rows(5, 0) == 5

    def test_the_block_fills_what_the_budget_leaves(self):
        """The block's price, the tables' and the candidates' fit the
        budget beside the resident arrays, and one block fewer would
        not."""
        fam = KNeighborsClassifierFamily
        n, folds, nc, resident = 700, 5, 8, 300_000
        budget = 4_000_000
        facts = fam.launch_tiles({}, self.META, folds, nc, n_samples=n,
                                 budget_bytes=budget,
                                 resident_bytes=resident,
                                 return_train=False)
        R = facts[neighbors._BLOCK_ROWS]
        blocks = -(-n // R)

        def used(rows):
            ws = fam.launch_workspace(n, self.META, folds, static={
                **facts, neighbors._BLOCK_ROWS: rows})
            return resident + ws["fixed_bytes"] + nc * (
                ws["per_candidate_bytes"] + 2 * folds * n * 4)
        assert 1 < blocks and used(R) <= budget
        assert used(neighbors.block_rows(n, -(-n // (blocks - 1)))) > budget

    def test_train_scores_and_loose_folds_select_a_row_a_fold(self):
        fam = KNeighborsClassifierFamily
        kw = dict(n_samples=700, budget_bytes=0, resident_bytes=0)
        assert not fam.launch_tiles({}, self.META, 5, 8, return_train=False,
                                    **kw)[neighbors._EVERY_FOLD]
        assert fam.launch_tiles({}, self.META, 5, 8, return_train=True,
                                **kw)[neighbors._EVERY_FOLD]
        assert fam.launch_tiles({}, {**self.META, "held_out_once": False},
                                5, 8, return_train=False,
                                **kw)[neighbors._EVERY_FOLD]

    def test_without_a_budget_a_block_holds_a_gib(self):
        facts = KNeighborsClassifierFamily.launch_tiles(
            {}, self.META, 5, 60, n_samples=70_000, budget_bytes=0,
            resident_bytes=0, return_train=False)
        R = facts[neighbors._BLOCK_ROWS]
        assert R * 70_000 * 4 <= 1 << 30 and R % neighbors._ROW_TILE == 0

    def test_no_compiled_array_is_n_by_n(self):
        """The launch at 4 blocks holds (128, 500) distances and no
        (500, 500) array, in either selection."""
        n, d, folds, nc = 500, 16, 3, 8
        meta = {**self.META, "n_samples": n, "n_features": d}
        for every in (False, True):
            static = {"weights": "uniform", "__n_folds__": folds,
                      neighbors._BLOCK_ROWS: 128,
                      neighbors._EVERY_FOLD: every}
            text = jax.jit(
                lambda dyn, X, y, w: KNeighborsClassifierFamily.
                fit_task_batched(dyn, static, {"X": X, "y": y}, w, meta)
            ).lower(
                {"n_neighbors": jax.ShapeDtypeStruct((nc * folds,),
                                                     jnp.int32)},
                jax.ShapeDtypeStruct((n, d), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((nc * folds, n), jnp.float32)
            ).as_text()
            assert "tensor<128x500xf32>" in text
            assert not re.search(r"tensor<(\d+x)*500x500x", text)


class TestSharedDistances:
    def test_rbf_kernel_is_the_inline_expansion_to_the_bit(self):
        """``svm._kernel``'s RBF branch through ``ops/pairwise.sq_dists``
        gives the bits of the expansion it inlined before, on XLA:CPU."""
        from spark_sklearn_tpu.models.svm import _kernel

        def inline(X1, X2, gamma):
            sq1 = jnp.sum(X1 * X1, axis=1)
            sq2 = jnp.sum(X2 * X2, axis=1)
            d2 = sq1[:, None] - 2.0 * (X1 @ X2.T) + sq2[None, :]
            return jnp.exp(-gamma * jnp.maximum(d2, 0.0))

        rng = np.random.default_rng(7)
        X1 = rng.random((97, 33), np.float32)
        X2 = rng.random((61, 33), np.float32)
        for gamma in (0.01, 0.5, 3.0):
            got = jax.jit(lambda a, b, g: _kernel(a, b, "rbf", g, 3, 0.0))(
                X1, X2, np.float32(gamma))
            want = jax.jit(inline)(X1, X2, np.float32(gamma))
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
