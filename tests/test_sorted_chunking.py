"""Convergence-sorted chunking (round 4 perf): a lockstep launch
executes the max iteration count over its lanes, so one wide launch pays
the slowest candidate's iterations for EVERY candidate.  Sorting a big
compile group by the family's difficulty proxy (GLM: ascending C) and
splitting it into ~8 narrower launches lets the easy launches early-exit
— same compiled program (uniform chunk width), same cv_results_ order.

Correctness: converged lanes are frozen exactly inside the batched
solvers (ops/solvers.py masks the STEP, so x stops moving), which makes
per-candidate results independent of launch grouping — scores must
match the unsorted run to float-exactness, while total executed
iterations (sum of per-launch max x lanes) must strictly drop.
"""

import numpy as np

import spark_sklearn_tpu as sst


def _run(digits, sort, n_cand=64, max_iter=60):
    from sklearn.linear_model import LogisticRegression

    X, y = digits
    Xs, ys = X[:500], y[:500]
    grid = {"C": list(np.logspace(-4, 3, n_cand))}
    cfg = sst.TpuConfig(sort_candidates=sort)
    gs = sst.GridSearchCV(
        LogisticRegression(max_iter=max_iter), grid, cv=3,
        backend="tpu", refit=False, config=cfg).fit(Xs, ys)
    assert gs.search_report["backend"] == "tpu"
    return gs


class TestSortedChunking:
    def test_scores_match_and_iterations_drop(self, digits):
        sorted_gs = _run(digits, sort=True)
        unsorted_gs = _run(digits, sort=False)

        # same per-candidate scores in the USER's candidate order.
        # Tolerance, not equality: XLA tiles the lane-batched matmuls
        # differently at different launch widths, and float32 rounding
        # diverges chaotically over ~60 iterations on digits'
        # never-converging lanes (observed: +-1 test sample on a few
        # folds) — the same noise any re-grouping of the grid produces.
        np.testing.assert_allclose(
            sorted_gs.cv_results_["mean_test_score"],
            unsorted_gs.cv_results_["mean_test_score"], atol=0.01)
        assert abs(sorted_gs.best_score_
                   - unsorted_gs.best_score_) < 0.01

        # the mechanism: several graded launches vs one wide launch,
        # and strictly less executed lockstep work
        rs, ru = sorted_gs.search_report, unsorted_gs.search_report

        def executed(rep):
            return sum(i * l for i, l in zip(
                rep["solver_iters_per_launch"], rep["lanes_per_launch"]))

        assert rs["n_launches"] > ru["n_launches"]
        assert executed(rs) < executed(ru), (
            rs["solver_iters_per_launch"], ru["solver_iters_per_launch"])
        # easy launches must genuinely early-exit below the cap
        assert min(rs["solver_iters_per_launch"]) < \
            max(rs["solver_iters_per_launch"])

    def test_small_grids_stay_single_launch(self, digits):
        # below the sorting threshold nothing changes
        gs = _run(digits, sort=True, n_cand=8)
        assert gs.search_report["n_launches"] == 1


class TestTreeSortedChunking:
    def test_forest_counts_share_one_launch(self):
        """Round 4 graded a forest's launches by n_estimators (lockstep
        lanes: a launch grew max-over-lanes trees for every lane).  Since
        PR 36 a launch grows ONE forest a fold and reads it at every
        count, so chunks graded by count would only regrow the prefix:
        the forest families have no convergence proxy, and the group is
        one launch whatever `sort_candidates` says."""
        from sklearn.ensemble import RandomForestClassifier

        rng = np.random.RandomState(0)
        X = rng.randn(300, 8).astype(np.float32)
        y = rng.randint(0, 3, size=300)
        # 32 candidates: once 4 graded launches of 8 on the virtual test
        # mesh (12, 20, 28 and 36 trees)
        grid = {"n_estimators": list(range(5, 37))}

        runs = {}
        for sort in (True, False):
            cfg = sst.TpuConfig(sort_candidates=sort)
            gs = sst.GridSearchCV(
                RandomForestClassifier(max_depth=4, random_state=0),
                grid, cv=2, refit=False, backend="tpu",
                config=cfg).fit(X, y)
            runs[sort] = gs

        for gs in runs.values():
            rep = gs.search_report
            assert rep["solver_iters_per_launch"] == [36]
            assert rep["trees_grown_per_launch"] == [2 * 36]
            assert rep["trees_per_candidate"] == grid["n_estimators"]
        assert np.array_equal(
            runs[True].cv_results_["mean_test_score"],
            runs[False].cv_results_["mean_test_score"])

    def test_constant_proxy_stays_single_launch(self):
        # a grid varying only in OTHER params must not pay the launch
        # split: the proxy is constant, sorting is skipped
        from sklearn.ensemble import GradientBoostingRegressor

        rng = np.random.RandomState(0)
        X = rng.randn(200, 5).astype(np.float32)
        y = (X[:, 0] + 0.1 * rng.randn(200)).astype(np.float32)
        gs = sst.GridSearchCV(
            GradientBoostingRegressor(n_estimators=15, max_depth=2,
                                      random_state=0),
            {"learning_rate": [0.02, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5,
                               0.8]},
            cv=2, refit=False, backend="tpu").fit(X, y)
        assert gs.search_report["n_launches"] == 1
