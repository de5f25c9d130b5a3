"""Checkpoint/resume through a REAL process death (VERDICT r4 next #7).

The unit tests in test_components.py cover resume after a clean run;
this is the crash-consistency e2e: a subprocess search is SIGKILLed
mid-chunk, a resumed search completes from the streamed jsonl, and its
cv_results_ matches an uninterrupted run's bit-for-bit on every
non-timing column (SURVEY §5.4 — the analog of the reference losing a
Spark executor mid-job)."""

import os
import signal
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

import spark_sklearn_tpu as sst

_CHILD = """
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from sklearn.datasets import load_digits
from sklearn.linear_model import LogisticRegression
import spark_sklearn_tpu as sst

X, y = load_digits(return_X_y=True)
X = (X / 16.0).astype(np.float32)
cfg = sst.TpuConfig(checkpoint_dir={ckpt_dir!r})
gs = sst.GridSearchCV(
    LogisticRegression(max_iter=100),
    {{"C": np.logspace(-3, 2, 40).tolist()}},
    cv=2, backend="tpu", refit=False, config=cfg)
gs.fit(X, y)
print("CHILD_FINISHED", flush=True)
"""


def _checkpoint_records(ckpt_dir):
    total = 0
    for name in os.listdir(ckpt_dir):
        if name.endswith(".jsonl"):
            with open(os.path.join(ckpt_dir, name)) as f:
                total += sum(1 for _ in f)
    return total


#: the shared family fixture matrix (mirrors test_pipeline's): each
#: entry is (estimator factory, grid, config kwargs forcing several
#: chunks/groups, hung launch index for run 1).  The hung index names a
#: launch past the first durable chunk record so run 1 dies genuinely
#: mid-compile-group.
def _family_matrix():
    from sklearn.decomposition import PCA
    from sklearn.linear_model import LogisticRegression
    from sklearn.naive_bayes import GaussianNB
    from sklearn.neighbors import KNeighborsClassifier
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler

    def _pipe():
        return Pipeline([("sc", StandardScaler()),
                         ("pca", PCA(random_state=0)),
                         ("clf", LogisticRegression(max_iter=10))])
    return {
        # sorted chunking: 5+ chunks in one group; hung@5 = a fused
        # steady-state chunk
        "logreg": (lambda: LogisticRegression(max_iter=10),
                   {"C": np.logspace(-2, 1, 40).tolist()}, {}, 5),
        # 20 candidates chunked at width 8 (max_tasks_per_batch=16,
        # cv=2): fit/score/calibrate + 2 fused; hung@4 = last fused
        "gnb": (lambda: GaussianNB(),
                {"var_smoothing": np.logspace(-9, -3, 20).tolist()},
                {"max_tasks_per_batch": 16}, 4),
        # ONE compile group (weights is a per-task choice): 20
        # candidates chunked at width 8, a neighbour search a launch;
        # the first chunk's fit/score/calibrate are durable before hung@3
        # kills the second chunk's fused launch
        "knn": (lambda: KNeighborsClassifier(),
                {"n_neighbors": list(range(1, 11)),
                 "weights": ["uniform", "distance"]},
                {"max_tasks_per_batch": 16}, 3),
        # shared-prefix Pipeline: two compile groups (n_components is
        # shape-static), each fanned over one cached prefix; hung@3 =
        # group 2's score launch, with group 1's chunk AND both prefix
        # npz payloads already durable — the resume must replay the
        # journalled prefix plan (PlanKey.prefix) without recompute
        "pipeline": (_pipe,
                     {"pca__n_components": [8, 16],
                      "clf__C": [0.1, 1.0, 10.0]}, {}, 3),
    }


@pytest.mark.parametrize("fam", ["logreg", "gnb", "knn", "pipeline"])
def test_mid_group_fault_retry_resume_parity(digits, tmp_path, fam):
    """Recovery-vs-parity across the family matrix: run 1 dies to an
    injected hang mid-compile-group (earlier chunks durable); run 2
    resumes AND hits an injected transient fault that the supervisor
    retries; the recovered cv_results_ must be exact-equal to an
    uninterrupted fault-free baseline."""
    make_est, grid, cfg_kw, hung_at = _family_matrix()[fam]
    X, y = digits
    Xs, ys = X[:240], y[:240]

    def run(config):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return sst.GridSearchCV(
                make_est(), grid, cv=2, refit=False, backend="tpu",
                config=config).fit(Xs, ys)

    baseline = run(sst.TpuConfig(**cfg_kw))

    ckpt = str(tmp_path / "ckpt")
    with pytest.raises(TimeoutError):
        run(sst.TpuConfig(checkpoint_dir=ckpt,
                          fault_plan=f"hung@{hung_at}", **cfg_kw))
    n_durable = sum(
        1 for name in os.listdir(ckpt) if name.endswith(".jsonl")
        for line in open(os.path.join(ckpt, name))
        if '"chunk_id"' in line)
    assert n_durable >= 1, "the hang left nothing durable"

    # resume: launch index 0 is the first LIVE (non-resumed) launch —
    # the retried-by-supervisor fault lands mid-recovery
    resumed = run(sst.TpuConfig(checkpoint_dir=ckpt,
                                fault_plan="transient@0",
                                retry_backoff_s=0.01, **cfg_kw))
    rep = resumed.search_report
    assert rep["n_chunks_resumed"] >= 1
    assert rep["faults"]["retries"] >= 1

    for key, col in baseline.cv_results_.items():
        if "time" in key:
            continue
        if key == "params":
            assert col == resumed.cv_results_[key]
        else:
            np.testing.assert_array_equal(
                np.asarray(col), np.asarray(resumed.cv_results_[key]),
                err_msg=key)


#: the family-matrix child for the kill -9 drill: brownout-stretched
#: launches (bit-exact, just slow) widen the kill window so the SIGKILL
#: lands genuinely mid-search for every family; launch 0 runs clean so
#: the first chunk record is durable fast.
_FAMILY_CHILD = """
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from sklearn.datasets import load_digits
{est_import}
import spark_sklearn_tpu as sst

X, y = load_digits(return_X_y=True)
X = (X / 16.0).astype(np.float32)
X, y = X[:240], y[:240]
cfg = sst.TpuConfig(
    checkpoint_dir={ckpt_dir!r},
    fault_plan=",".join("slow@%d:0.4" % i for i in range(1, 13)),
    **{cfg_kw!r})
gs = sst.GridSearchCV({est_expr}, {grid!r}, cv=2, backend="tpu",
                      refit=False, config=cfg)
gs.fit(X, y)
print("CHILD_FINISHED", flush=True)
"""

#: per-family child pieces for the subprocess drill (import line +
#: constructor expression, matching _family_matrix's estimators)
_FAMILY_CHILD_EST = {
    "logreg": ("from sklearn.linear_model import LogisticRegression",
               "LogisticRegression(max_iter=10)"),
    "gnb": ("from sklearn.naive_bayes import GaussianNB",
            "GaussianNB()"),
    "knn": ("from sklearn.neighbors import KNeighborsClassifier",
            "KNeighborsClassifier()"),
    "pipeline": ("from sklearn.pipeline import Pipeline\n"
                 "from sklearn.preprocessing import StandardScaler\n"
                 "from sklearn.decomposition import PCA\n"
                 "from sklearn.linear_model import LogisticRegression",
                 "Pipeline([('sc', StandardScaler()), "
                 "('pca', PCA(random_state=0)), "
                 "('clf', LogisticRegression(max_iter=10))])"),
}


@pytest.mark.slow
@pytest.mark.parametrize("fam", ["logreg", "gnb", "knn", "pipeline"])
def test_sigkill_family_matrix_resume_parity(digits, tmp_path, fam):
    """The family matrix through a REAL ``kill -9`` (not an injected
    in-process hang): a subprocess search per family is SIGKILLed after
    its first durable chunk — signal death exercises the
    unflushed-buffer path the checkpoint WAL must survive — and the
    resumed search must match an uninterrupted run bit-for-bit."""
    make_est, grid, cfg_kw, _ = _family_matrix()[fam]
    est_import, est_expr = _FAMILY_CHILD_EST[fam]
    ckpt_dir = str(tmp_path / "ckpt")
    os.makedirs(ckpt_dir)
    child_src = _FAMILY_CHILD.format(
        est_import=est_import, est_expr=est_expr,
        ckpt_dir=ckpt_dir, cfg_kw=cfg_kw, grid=grid)
    child = subprocess.Popen(
        [sys.executable, "-c", child_src],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    deadline = time.time() + 600
    try:
        while time.time() < deadline:
            n_durable = sum(
                1 for name in os.listdir(ckpt_dir)
                if name.endswith(".jsonl")
                for line in open(os.path.join(ckpt_dir, name))
                if '"chunk_id"' in line)
            if n_durable >= 1:
                break
            if child.poll() is not None:
                pytest.fail(
                    "child exited before the kill window: "
                    f"rc={child.returncode} "
                    f"err={child.stderr.read()[-800:]}")
            time.sleep(0.1)
        else:
            pytest.fail("no durable chunk record within the window")
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=60)
    finally:
        if child.poll() is None:
            child.kill()
    assert child.returncode == -signal.SIGKILL

    X, y = digits
    Xs, ys = X[:240], y[:240]

    def run(config):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return sst.GridSearchCV(
                make_est(), grid, cv=2, refit=False, backend="tpu",
                config=config).fit(Xs, ys)

    resumed = run(sst.TpuConfig(checkpoint_dir=ckpt_dir, **cfg_kw))
    assert resumed.search_report["n_chunks_resumed"] >= 1
    fresh = run(sst.TpuConfig(**cfg_kw))
    for key, col in fresh.cv_results_.items():
        if "time" in key:
            continue   # resumed chunks carry the DEAD run's walls
        if key == "params":
            assert col == resumed.cv_results_[key]
        else:
            np.testing.assert_array_equal(
                np.asarray(col), np.asarray(resumed.cv_results_[key]),
                err_msg=key)


@pytest.mark.slow
def test_sigkill_mid_search_then_resume_matches_uninterrupted(
        digits, tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    os.makedirs(ckpt_dir)
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD.format(ckpt_dir=ckpt_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    # wait until SOME chunks are durable, then kill between chunks'
    # writes — a hard death with the search genuinely half done
    deadline = time.time() + 600
    try:
        while time.time() < deadline:
            if _checkpoint_records(ckpt_dir) >= 2:
                break
            if child.poll() is not None:
                pytest.fail(
                    "child exited before the kill window: "
                    f"rc={child.returncode} err={child.stderr.read()[-800:]}")
            time.sleep(0.2)
        else:
            pytest.fail("no checkpoint records within the window")
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=60)
    finally:
        if child.poll() is None:
            child.kill()
    assert child.returncode == -signal.SIGKILL
    n_before = _checkpoint_records(ckpt_dir)
    assert n_before >= 2

    X, y = digits   # the conftest fixture matches the child's data prep
    grid = {"C": np.logspace(-3, 2, 40).tolist()}
    from sklearn.linear_model import LogisticRegression

    resumed = sst.GridSearchCV(
        LogisticRegression(max_iter=100), grid, cv=2, backend="tpu",
        refit=False, config=sst.TpuConfig(checkpoint_dir=ckpt_dir))
    resumed.fit(X, y)
    # the dead process's completed chunks were NOT recomputed
    assert resumed.search_report["n_chunks_resumed"] >= 1
    assert resumed.search_report["n_launches"] >= 1

    fresh = sst.GridSearchCV(
        LogisticRegression(max_iter=100), grid, cv=2, backend="tpu",
        refit=False).fit(X, y)

    for key, col in fresh.cv_results_.items():
        if "time" in key:
            continue   # resumed chunks carry the DEAD run's walls
        if key == "params":
            assert col == resumed.cv_results_[key]
        elif np.asarray(col).dtype.kind in "fc":
            np.testing.assert_array_equal(
                np.asarray(col), np.asarray(resumed.cv_results_[key]),
                err_msg=key)
        else:
            assert np.array_equal(np.asarray(col),
                                  np.asarray(resumed.cv_results_[key])), key
