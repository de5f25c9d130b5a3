"""The kernel-SVM cell's files end to end on XLA:CPU at a tiny size, each
run in a process of its own (``rehearse_svc.py``): the result line with
the cell's reference and readers, ``correct`` coming out false where the
dual's alphas are left at zero, and the work model against a hand count."""

import json
import os
import subprocess
import sys

import pytest

import work_svc

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def rehearse(*args, env=None):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_svc.py"), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, **(env or {})})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return (json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout,
            proc.stderr)


def test_tiny_svc_window_and_last_line():
    result, out, err = rehearse("--seconds", "0.5")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"search_wall_s", "setup_s"}
    assert set(result["compared"]) == {"structure", "mean_abs_gap",
                                       "max_abs_gap", "converged_bias"}
    assert result["attempted"] % 20 == 0      # 2 C x 2 gamma x 5 folds
    assert "programs built in window 0" in out
    assert err.strip().splitlines()[-1] == "correct: true"


def test_tiny_svc_traced_reports_the_cells_counters(tmp_path):
    """The traced run's line: the program's counts through the cell's own
    readers; XLA:CPU has no device plane, so no device metric appears and
    no reader raises on its absence."""
    result, _, _ = rehearse(
        "--trace", "1", env={"BENCH_TEST_TRACE_DIR": str(tmp_path / "t")})
    metrics = result["metrics"]
    assert metrics["svc.dual_iters"]["value"] > 0
    assert metrics["build.window_compiles"]["value"] == 0
    for device_metric in ("svc.device_s", "svc.project_share",
                          "svc.gram_share", "box_fista_svc_roofline",
                          "search.mfu", "solver.iter_lanes",
                          "glm_lbfgs_batched_roofline"):
        assert device_metric not in metrics


def test_alphas_left_at_zero_read_not_correct():
    result, _, err = rehearse("--fault", "zero_alphas")
    assert result["correct"] is False
    assert err.strip().splitlines()[-1] == "correct: false"
    assert [k for k, v in result["compared"].items()
            if v["value"] > v["limit"]]


CONFIG = {"data": {"n_samples": 20000, "n_features": 784, "n_classes": 10,
                   "n_folds": 5}}


def test_work_against_a_hand_count():
    # ParameterGrid order, gamma last: candidates 0 and 2 share a gamma
    report = {"dual_iters_per_candidate": [300, 100, 200, 50]}
    needs = work_svc.svc_rbf_ovo_dual(CONFIG, 4, report, n_gammas=2)
    # a pair's own rows: 16 000 training rows a fold, two classes of ten
    m, duals = 3200, 5 * 45
    assert needs["fit_flops"] == 2.0 * m * m * duals * 650
    # one bfloat16 read of the 20 000^2 matrix an iteration of the
    # slowest candidate of each gamma: 300 + 100
    assert needs["fit_bytes"] == 400 * 20000 * 20000 * 2
    assert needs["flops"] == pytest.approx(
        needs["fit_flops"] + 20000 * 20000 * 784
        + 2.0 * 4000 * m * duals * 4)
    # gammas unknown: every candidate on one read, a bound for any grid
    assert work_svc.svc_rbf_ovo_dual(CONFIG, 4, report)["fit_bytes"] == \
        300 * 20000 * 20000 * 2
    assert (work_svc.svc_rbf_ovo_dual(CONFIG, 4, report, n_gammas=4)
            ["fit_bytes"] == 650 * 20000 * 20000 * 2)


@pytest.mark.parametrize("report", [
    {}, {"dual_iters_per_candidate": [300, 100, 200]},
    {"dual_iters_per_candidate": [300, -1, 200, 50]},
    {"solver_iters_sum_per_launch": [3250], "lanes_per_launch": [40]}])
def test_work_is_none_without_every_candidates_count(report):
    assert work_svc.svc_rbf_ovo_dual(CONFIG, 4, report) is None
    assert work_svc.candidate_iters(report, 4) is None


def test_flops_from_the_launches_sums_where_nothing_was_padded():
    """A program from before the count a candidate: the FLOPs (and so
    ``search.mfu``) from the launches' sums over tasks, no bytes."""
    full = work_svc.svc_rbf_ovo_dual(
        CONFIG, 4, {"dual_iters_per_candidate": [300, 100, 200, 50]})
    sums = work_svc.svc_rbf_ovo_dual(
        CONFIG, 4, {"solver_iters_sum_per_launch": [2000, 1250],
                    "lanes_per_launch": [10, 10]})
    assert sums["flops"] == full["flops"]
    assert sums["fit_flops"] == full["fit_flops"]
    assert sums["fit_bytes"] is None


def test_required_flops_are_a_39th_of_the_dense_products():
    """What the masked-full-Gram formulation costs: the program's product
    is (225, n) @ (n, n) an iteration, the pairs' own rows need 225 m^2."""
    needs = work_svc.svc_rbf_ovo_dual(
        CONFIG, 1, {"dual_iters_per_candidate": [1]})
    dense = 2.0 * 225 * 20000 * 20000
    assert dense / needs["fit_flops"] == pytest.approx(39.0625)
