"""The k-NN cell's files end to end on XLA:CPU at a tiny size, each run in a
process of its own (``rehearse_knn.py``): the result line with the cell's
estimator factory, reference, work model and readers, and ``correct`` coming
out false under each planted fault."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import rehearse_knn    # noqa: E402


def rehearse(*args, env=None):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_knn.py"), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, **(env or {})})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return (json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout,
            proc.stderr)


def test_tiny_knn_window_and_last_line():
    result, out, err = rehearse("--seconds", "0.5")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"search_wall_s", "setup_s"}
    assert set(result["compared"]) == {"structure", "mean_abs_gap",
                                       "max_abs_gap"}
    assert result["attempted"] % 24 == 0      # 4 k x 2 weights x 3 folds
    # on XLA:CPU the program's neighbours are the reference's
    assert result["compared"]["max_abs_gap"]["value"] < 1e-6
    assert "programs built in window 0" in out
    assert err.strip().splitlines()[-1] == "correct: true"


def test_tiny_knn_traced_reports_the_cells_counters(tmp_path):
    """The traced run's line: the program's counts through the cell's own
    readers; XLA:CPU has no device plane, so no device metric appears and
    no reader raises on its absence."""
    result, _, _ = rehearse(
        "--trace", "1", env={"BENCH_TEST_TRACE_DIR": str(tmp_path / "t")})
    metrics = result["metrics"]
    # ONE neighbour search of the 1 200 rows against every row
    assert metrics["knn.distance_entries"]["value"] == 1200 * 1200
    assert metrics["build.window_compiles"]["value"] == 0
    for device_metric in ("knn.device_s", "knn.select_share",
                          "knn_neighbors_roofline", "search.mfu",
                          "boost.device_s", "forest.device_s",
                          "mlp.device_s", "svc.device_s",
                          "solver.iter_lanes"):
        assert device_metric not in metrics


@pytest.mark.parametrize("fault", rehearse_knn.FAULTS)
def test_planted_fault_reads_not_correct(fault):
    result, _, err = rehearse("--fault", fault)
    assert result["correct"] is False
    assert err.strip().splitlines()[-1] == "correct: false"
    assert [k for k, v in result["compared"].items()
            if v["value"] > v["limit"]]
