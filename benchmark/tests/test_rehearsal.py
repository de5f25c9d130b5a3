"""The runner's functions end to end on XLA:CPU at a tiny size, each run in
a process of its own (``rehearse.py``): the window loop, the shape of the
last line, ``correct``, the counts; the same on four virtual devices for
the four-chip path; and ``correct`` coming out false under each fault the
cells can have."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def rehearse(*args, env=None):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, **(env or {})})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return (json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout,
            proc.stderr)


def check_last_line(result, chips):
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    # XLA:CPU reports no memory and has no device plane: no device number
    assert result["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in result["device"]
    assert set(result["compared"]) == {"structure", "mean_abs_gap",
                                       "max_abs_gap", "converged_bias"}
    for v in result["compared"].values():
        assert set(v) == {"value", "limit"}


def test_one_chip_window_and_last_line():
    result, out, err = rehearse("--chips", "1", "--seconds", "0.5")
    check_last_line(result, 1)
    assert result["correct"] is True
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"search_wall_s", "setup_s"}
    # the window is a closed loop of whole searches: attempted counts the
    # fits of every search it completed, and the reading is the whole
    # window over its searches (never a statistic of single searches)
    walls = [json.loads(line.split(": ", 1)[1])["wall_s"]
             for line in out.splitlines() if line.startswith("search ")]
    assert result["attempted"] == 40 * len(walls)
    assert result["metrics"]["search_wall_s"]["value"] >= \
        sum(walls) / len(walls) - 1e-3
    assert "programs built in window 0" in out
    # each number compared beside its limit, as standard error's last lines
    tail = err.strip().splitlines()[-5:]
    assert tail[-1] == "correct: true"
    assert all(line.startswith("compared ") for line in tail[:4])


def test_window_holds_several_searches():
    result, out, _ = rehearse("--chips", "1", "--seconds", "4")
    n = sum(line.startswith("search ") for line in out.splitlines())
    assert n >= 2 and result["attempted"] == 40 * n
    assert result["correct"] is True


def test_four_virtual_devices_traced(tmp_path):
    """A ``chips: 4`` cell's path: lanes sharded over a four-device mesh,
    the reference knowing no mesh.  The traced run reports the program's
    counts; XLA:CPU has no device plane, so no device metric appears."""
    result, out, _ = rehearse(
        "--chips", "4", "--trace", "1",
        env={"BENCH_TEST_TRACE_DIR": str(tmp_path / "trace")})
    check_last_line(result, 4)
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in ("plan.launches", "solver.iter_lanes",
                 "build.window_compiles", "faults.recoveries",
                 "search.host_share", "pipeline.compute_share"):
        assert name in metrics, name
    assert metrics["build.window_compiles"]["value"] == 0
    assert metrics["faults.recoveries"]["value"] == 0
    for device_metric in ("device.idle_share", "device.peak_bytes",
                          "glm_lbfgs_batched_roofline", "search.mfu"):
        assert device_metric not in metrics
    assert "breakdown" not in result


@pytest.mark.parametrize("fault", ["unchanged", "half", "swapped",
                                   "altered"])
def test_fault_under_the_harness_reads_not_correct(fault):
    result, _, err = rehearse("--fault", fault)
    assert result["correct"] is False
    assert err.strip().splitlines()[-1] == "correct: false"
    over = [k for k, v in result["compared"].items()
            if v["value"] > v["limit"]]
    assert over, result["compared"]
