"""What a launch reports, whichever path ran it (search/launch.py).

Two parts.  The characterisation part pins what ``search_report`` says of
the solvers (the eight series below) and ``cv_results_`` across the four
launch paths of ``search/grid.py::_run_groups``: the default (each group's
first chunk as separate fit and score launches, the others fused), the
never-fused path (``fuse_fit_score=False``), the scanned segment
(``chunk_loop="scan"``) and a fused chunk recovered by OOM bisection
(``fault_plan``).  It was written first and passes on the tree before
``LaunchResult`` existed; where that tree's paths disagreed, the case is
listed in ``_DEVIATIONS`` with its reason, and the default path's value is
the one kept.  The unit part exercises ``LaunchResult`` on the host.
"""

import numpy as np
import pytest

import spark_sklearn_tpu as sst

SERIES = (
    "solver_iters_per_launch", "solver_iters_sum_per_launch",
    "lanes_per_launch", "linesearch_one_pass_per_launch",
    "linesearch_second_pass_per_launch", "gram_builds_per_launch",
    "dual_subproblems_per_launch", "dual_iters_per_candidate")

FOLDS = 3
#: 8 candidates a launch on the suite's 8 virtual devices: 19 candidates
#: make three chunks (8, 8 and 3 + 5 padding)
CONFIG = {"max_tasks_per_batch": 8 * FOLDS}
PATHS = {
    "default": {},
    "unfused": {"fuse_fit_score": False},
    "scan": {"chunk_loop": "scan"},
    # launches 0-2 are the first chunk's fit, score and calibration;
    # launch 3 is the second chunk's fused launch
    "oom_bisect": {"fault_plan": "oom@3", "retry_backoff_s": 0.01},
}


def _data(n_classes):
    rng = np.random.default_rng(0)
    X = rng.standard_normal((120, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int64) if n_classes == 2 \
        else np.arange(120) % n_classes
    return X, y


def _family(name):
    from sklearn.linear_model import LogisticRegression
    from sklearn.naive_bayes import GaussianNB
    from sklearn.svm import SVC
    if name == "multinomial":
        return (LogisticRegression(max_iter=20),
                {"C": np.logspace(-3, 1, 19).tolist()}, _data(3))
    if name == "binary":
        return (LogisticRegression(max_iter=20),
                {"C": np.logspace(-3, 1, 19).tolist()}, _data(2))
    if name == "svc_rbf":
        return (SVC(kernel="rbf"),
                {"C": np.logspace(-1, 2, 19).tolist()}, _data(3))
    assert name == "no_solver"
    return (GaussianNB(),
            {"var_smoothing": np.logspace(-9, -1, 19).tolist()}, _data(3))


_RUNS = {}


def _run(family, path):
    if (family, path) not in _RUNS:
        est, grid, (X, y) = _family(family)
        gs = sst.GridSearchCV(
            est, grid, cv=FOLDS, refit=False, backend="tpu",
            config=sst.TpuConfig(**CONFIG, **PATHS[path])).fit(X, y)
        rep = gs.search_report
        if path == "oom_bisect":
            assert rep["faults"]["bisections"] >= 1, rep["faults"]
        if path == "scan":
            assert rep["chunkloop"]["n_chunks_scanned"] == 3
        cv = {k: np.asarray(v) for k, v in gs.cv_results_.items()
              if "time" not in k and k != "params"}
        _RUNS[family, path] = ({k: rep.get(k) for k in SERIES}, cv)
    return _RUNS[family, path]


#: (path, series) -> why that path's value is not the default path's.
#: Read off the tree before LaunchResult (the parent of PR 31).
_DEVIATIONS = {
    ("oom_bisect", "solver_iters_sum_per_launch"):
        "each half of a bisected chunk is padded again to the shard "
        "multiple (4 real candidates to 8), so the recovery computed more "
        "lanes than the launch it replaces and the sum, which is over the "
        "lanes a launch computed, says so: [192, 336, 168] against [192, "
        "168, 168] for the multinomial grid.  At the parent SVC alone "
        "read the default path's value here, because its per-task counts "
        "rode in the sum's slot and were cut to the real tasks in this "
        "path only; a sum is a sum for every family now, so the entry is "
        "held to >= the default path's.",
}


@pytest.mark.parametrize("path", [p for p in PATHS if p != "default"])
@pytest.mark.parametrize("family", ["multinomial", "binary", "svc_rbf",
                                    "no_solver"])
def test_every_path_reports_what_the_default_path_reports(family, path):
    want, want_cv = _run(family, "default")
    got, got_cv = _run(family, path)
    assert set(got_cv) == set(want_cv)
    for k in want_cv:
        np.testing.assert_array_equal(got_cv[k], want_cv[k], err_msg=k)
    for name in SERIES:
        if (path, name) in _DEVIATIONS and want[name] is not None:
            # launch 1 is the recovered chunk; the others are untouched
            assert got[name][0::2] == want[name][0::2]
            assert got[name][1] >= want[name][1]
            continue
        assert got[name] == want[name], (name, got[name], want[name])
    # what the default path itself reports, by family
    n_launches = 3
    if family == "no_solver":
        assert all(want[name] is None for name in SERIES)
        return
    assert len(want["solver_iters_per_launch"]) == n_launches
    assert want["lanes_per_launch"] == [8 * FOLDS] * n_launches
    assert want["linesearch_one_pass_per_launch"] == \
        [int(family == "multinomial")] * n_launches
    if family != "multinomial":
        assert want["linesearch_second_pass_per_launch"] == [0] * n_launches
    if family == "svc_rbf":
        # gamma is static here, so the 19 candidates are ONE run of one
        # kernel; a chunk of 8 is not made of whole runs of 19 and builds
        # a kernel a candidate (tests/test_svc_kernel_groups.py)
        assert want["gram_builds_per_launch"] == [8] * n_launches
        assert want["dual_subproblems_per_launch"] == \
            [8 * FOLDS * 3] * n_launches
        iters = want["dual_iters_per_candidate"]
        assert len(iters) == 19 and min(iters) > 0
        assert max(iters) == max(want["solver_iters_per_launch"])
    else:
        for name in SERIES[5:]:
            assert want[name] is None


# ---------------------------------------------------------------------------
# LaunchResult on the host
# ---------------------------------------------------------------------------


def _result(n, n_folds=2, base=0.0, **stats):
    """`n` candidates of one scorer with train scores; stats by name."""
    from spark_sklearn_tpu.search.launch import LaunchResult
    cells = base + np.arange(n * n_folds, dtype=np.float32).reshape(
        n, n_folds)
    return LaunchResult({"score": cells}, {"score": -cells},
                        np.zeros((n, n_folds), bool),
                        {k: np.asarray(v, np.int32)
                         for k, v in stats.items()})


def _host_filled(n, n_folds=2):
    from spark_sklearn_tpu.search.launch import LaunchResult
    cells = np.full((n, n_folds), 7.0, np.float32)
    return LaunchResult.host_fill({"score": cells}, {"score": cells}, n,
                                  n_folds)


def _merge(a, b):
    from spark_sklearn_tpu.search.launch import LaunchResult
    return LaunchResult.merge(a, b)


def _on_device(res):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.asarray, res)


_UNIT_CASES = {
    # max with max, sum with sum
    "merge_scalars": (
        lambda: _merge(_result(2, solver_iters=5, solver_iters_sum=12),
                       _result(3, solver_iters=9, solver_iters_sum=20)),
        5, {"solver_iters": 9, "solver_iters_sum": 32}),
    # a per-task vector with a per-task vector: candidate-major concat
    "merge_per_task": (
        lambda: _merge(_result(1, solver_iters=3, dual_iters=[3, 3]),
                       _result(2, solver_iters=4, dual_iters=[4, 4, 2, 2])),
        3, {"solver_iters": 4, "dual_iters": [3, 3, 4, 4, 2, 2]}),
    # an empty side (host fallback, quarantine fill) is the identity of
    # max and sum, and reads -1 for its tasks in a per-task stat
    "merge_empty_right": (
        lambda: _merge(_result(2, solver_iters=5, solver_iters_sum=12,
                               dual_iters=[5, 5, 1, 1]), _host_filled(1)),
        3, {"solver_iters": 5, "solver_iters_sum": 12,
            "dual_iters": [5, 5, 1, 1, -1, -1]}),
    "merge_empty_left": (
        lambda: _merge(_host_filled(2),
                       _result(1, solver_iters=5, dual_iters=[5, 5])),
        3, {"solver_iters": 5, "dual_iters": [-1, -1, -1, -1, 5, 5]}),
    "merge_both_empty": (
        lambda: _merge(_host_filled(2), _host_filled(1)), 3, {}),
    # a FuseSpec member's view at a non-zero offset: the rows and the
    # per-task stat are cut, the launch's scalars stay whole
    "slice_at_offset": (
        lambda: _result(4, solver_iters=6, solver_iters_sum=40,
                        dual_iters=[1, 1, 2, 2, 3, 3, 4, 4]).slice(1, 2, 2),
        2, {"solver_iters": 6, "solver_iters_sum": 40,
            "dual_iters": [2, 2, 3, 3]}),
    # device arrays in, numpy out, cut to the real rows and their tasks
    "to_host_cuts_padding": (
        lambda: _on_device(
            _result(4, solver_iters=6,
                    dual_iters=[1, 1, 2, 2, 3, 3, 4, 4])).to_host(3, 2),
        3, {"solver_iters": 6, "dual_iters": [1, 1, 2, 2, 3, 3]}),
    # one step of a scanned segment's stacked result
    "scan_step": (
        lambda: _on_device(_result(
            3, solver_iters=[6, 6, 8],
            dual_iters=[[1, 1], [2, 2], [3, 3]])).to_host().step(1),
        2, {"solver_iters": 6, "dual_iters": [2, 2]}),
}


@pytest.mark.parametrize("case", sorted(_UNIT_CASES))
def test_launch_result_on_the_host(case):
    build, n_rows, want_stats = _UNIT_CASES[case]
    res = build()
    for leaf in (res.test["score"], res.train["score"], res.bad):
        assert isinstance(leaf, np.ndarray)
        assert leaf.shape == ((2,) if case == "scan_step" else (n_rows, 2))
    assert set(res.stats) == set(want_stats)
    for k, v in want_stats.items():
        np.testing.assert_array_equal(res.stats[k], v, err_msg=k)
    if case == "slice_at_offset":
        np.testing.assert_array_equal(res.test["score"],
                                      [[2.0, 3.0], [4.0, 5.0]])
        np.testing.assert_array_equal(res.train["score"],
                                      [[-2.0, -3.0], [-4.0, -5.0]])
    if case == "merge_scalars":
        np.testing.assert_array_equal(
            res.test["score"][:, 0], [0.0, 2.0, 0.0, 2.0, 4.0])
    if case == "merge_empty_left":
        np.testing.assert_array_equal(
            res.train["score"], [[7.0, 7.0], [7.0, 7.0], [-0.0, -1.0]])
