"""The kernel-SVM cell's yardstick and the program against it, on XLA:CPU
at a small size: the benchmark's plain reference (``benchmark/
reference_svc.py``) against scikit-learn's libsvm, the program's
``cv_results_`` against the reference, and the counters the cell's
per-layer metrics read.  Nothing here is timed."""

import importlib.util
import os

import numpy as np
import pytest
from sklearn.model_selection import StratifiedKFold
from sklearn.svm import SVC

import spark_sklearn_tpu as sst

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _bench(name):
    """A benchmark file imported by its path, under a name of its own (the
    benchmark's modules are called ``check``, ``generate``, ...)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _data(n, k, seed=5):
    spec = dict(n_samples=n, n_features=784, n_classes=k, n_folds=5,
                latent=32, separation=0.6, pixel_noise=1.0, base_seed=seed)
    X, y = _bench("generate").make_data(spec)
    config = {"estimator": {"params": {"kernel": "rbf"}}, "data": spec}
    return X, y, config, list(StratifiedKFold(5).split(X, y))


CANDIDATES = [{"C": c, "gamma": g} for c in (0.3, 10.0)
              for g in (0.004, 0.03)]
#: a decision this close to 0 may fall on either side: the reference stops
#: at a KKT gap of 1e-4, which is how far an intercept taken from the
#: middle of the feasible interval may sit from libsvm's (tol 1e-6); the
#: widest difference of a decision value on this data is 1.4e-4 (XLA:CPU)
MARGIN = 5e-4


@pytest.fixture(scope="module")
def reference():
    return _bench("reference_svc")


@pytest.mark.parametrize("n_classes", [3, 10])
def test_reference_agrees_with_libsvm(reference, n_classes):
    """Decision values, their signs and the predictions of every pair
    machine on the last fold's test rows, 600 rows x 3 and x 10 classes."""
    X, y, config, splits = _data(600, n_classes)
    scores, iters, decisions = reference.svc_ovo_cv_scores(
        X, y, splits, CANDIDATES, config, decisions=True)
    assert iters.max() < reference.MAX_ITER     # stopped by its KKT gap
    train, test = splits[-1]
    for cand, ref_dec, ref_score in zip(CANDIDATES, decisions,
                                        scores[:, -1]):
        # break_ties: predict = argmax of the ovr decision function,
        # which is the vote with its confidence tie-break
        sk = SVC(kernel="rbf", tol=1e-6, break_ties=True, **cand).fit(
            X[train], y[train])
        sk_score = sk.score(X[test], y[test])
        sk.decision_function_shape, sk.break_ties = "ovo", False
        sk_dec = sk.decision_function(X[test])
        assert np.abs(sk_dec - ref_dec).max() < MARGIN
        clear = np.abs(sk_dec) > MARGIN
        assert clear.mean() > 0.99
        assert np.array_equal(np.sign(sk_dec)[clear],
                              np.sign(ref_dec)[clear])
        # at most the rows with a decision inside the margin may flip
        flips = (~clear).any(axis=1).sum()
        assert abs(sk_score - ref_score) <= flips / len(test) + 1e-7


def test_reference_control_is_another_result(reference):
    """The control (rows, Gram and alphas in bfloat16) runs and does not
    reproduce the float32 decisions."""
    import jax.numpy as jnp
    X, y, config, splits = _data(300, 3)
    _, _, exact = reference.svc_ovo_cv_scores(
        X, y, splits, CANDIDATES[2:3], config, decisions=True)
    scores, _, coarse = reference.svc_ovo_cv_scores(
        X, y, splits, CANDIDATES[2:3], config, dtype=jnp.bfloat16,
        decisions=True)
    assert np.all((scores >= 0) & (scores <= 1))
    assert np.abs(exact[0] - coarse[0]).max() > 1e-3


@pytest.fixture(scope="module")
def searched():
    X, y, config, splits = _data(600, 3)
    grid = {"C": [0.3, 10.0], "gamma": [0.004, 0.03]}
    search = sst.GridSearchCV(SVC(kernel="rbf"), grid, cv=StratifiedKFold(5),
                              backend="tpu", refit=False).fit(X, y)
    return search, X, y, config, splits


def test_program_against_the_reference(reference, searched):
    search, X, y, config, splits = searched
    want, _ = reference.svc_ovo_cv_scores(X, y, splits, CANDIDATES, config)
    got = np.stack([search.cv_results_[f"split{i}_test_score"]
                    for i in range(5)], axis=1)
    assert [dict(p) for p in search.cv_results_["params"]] == CANDIDATES
    # 120 test rows a fold: one flipped prediction is 8.3e-3.  The program
    # stops at tol 1e-3 (the reference at a KKT gap of 1e-4) and works on
    # the masked full kernel matrix, so a row whose decision is within
    # about 1e-2 of zero may fall on the other side: one flip a split.
    assert np.abs(got - want).max() <= 1.0 / 120 + 1e-6
    assert np.abs(got - want).mean() <= 0.25 / 120


def test_search_report_counts_the_duals(searched):
    search = searched[0]
    rep = search.search_report
    lanes = rep["lanes_per_launch"]
    # one kernel matrix for the two C of a gamma (padding included: a
    # padded candidate repeats the last one, so pads pair up as well)
    assert rep["gram_builds_per_launch"] == [n // 5 // 2 for n in lanes]
    assert rep["dual_subproblems_per_launch"] == [n * 3 for n in lanes]
    iters = rep["dual_iters_per_candidate"]
    assert len(iters) == 4 and min(iters) > 0
    # a candidate's folds share its count: the launch's sum is over its
    # tasks, the padding candidates' among them (8 virtual devices here)
    padding = sum(lanes) // 5 - len(iters)
    assert 5 * sum(iters) <= sum(rep["solver_iters_sum_per_launch"]) \
        <= 5 * (sum(iters) + padding * max(iters))
    assert max(rep["solver_iters_per_launch"]) == max(iters)
    # three balanced classes: the duals ran block-compact, on their own
    # two blocks of 200 rows
    assert rep["dual_rows_per_launch"] == [400] * len(lanes)
    # the ledger prices one candidate's Gram matrix, its bfloat16 copy and
    # the decision cache
    group = rep["memory"]["groups"][0]
    assert group["fixed_bytes"] >= 600 * 600 * 6
    assert group["chunk_bytes"] >= group["workspace_bytes"] > 0


def test_other_families_report_no_dual_counters():
    from sklearn.linear_model import LogisticRegression
    X, y, _, _ = _data(200, 3)
    rep = sst.GridSearchCV(LogisticRegression(max_iter=20), {"C": [1.0]},
                           cv=3, backend="tpu", refit=False).fit(
                               X[:, :20], y).search_report
    for name in ("gram_builds_per_launch", "dual_subproblems_per_launch",
                 "dual_rows_per_launch", "dual_iters_per_candidate"):
        assert name not in rep
    assert "workspace_bytes" not in rep["memory"]["groups"][0]


@pytest.mark.parametrize("config", [
    {}, {"max_tasks_per_batch": 9}, {"max_tasks_per_batch": 9,
                                     "chunk_loop": "scan"}],
    ids=["one-chunk", "fused-chunks", "scanned-chunks"])
def test_every_launch_path_keeps_the_candidates_counts(config):
    """The count a candidate comes back the same through the first
    chunk's separate fit launch, the fused chunks and the scanned segment,
    in ``cv_results_`` order, padding left out."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((90, 5)).astype(np.float32)
    y = np.arange(90) % 3
    grid = {"C": np.logspace(-1, 2, 19).tolist()}
    rep = sst.GridSearchCV(
        SVC(kernel="rbf"), grid, cv=3, refit=False, backend="tpu",
        config=sst.TpuConfig(**config)).fit(X, y).search_report
    iters = rep["dual_iters_per_candidate"]
    assert len(iters) == 19 and min(iters) > 0
    assert iters == sorted(iters)           # a wider box takes longer
    assert iters[0] < 20 and iters[-1] == 300   # ... up to the cap
    assert len(rep["gram_builds_per_launch"]) == len(rep["lanes_per_launch"])
    if config:
        assert len(rep["lanes_per_launch"]) > 1
