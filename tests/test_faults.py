"""Fault-tolerant launch supervisor (parallel/faults.py).

The contract under test (ISSUE 3): transient device errors retry with
backoff and leave `cv_results_` EXACT-equal to a fault-free run; OOM
chunks bisect (re-padded relaunch, still exact) and bottom out into
per-candidate host execution; hung launches fail the search with a
clean TimeoutError naming the chunk/compile group while completed
chunks stay resumable; fatal errors propagate unchanged.  All of it is
driven by the deterministic fault-injection plan on CPU — identical at
every pipeline depth — and every recovery is visible in
`search_report["faults"]`.
"""

import json
import os
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.parallel import faults
from spark_sklearn_tpu.parallel.faults import (
    FATAL,
    HUNG,
    OOM,
    TRANSIENT,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    LaunchSupervisor,
    LaunchTimeoutError,
    classify_error,
    register_classifier,
)


def _data():
    rng = np.random.RandomState(0)
    X = rng.randn(96, 6).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int64)
    return X, y


def _grid():
    return {"C": np.logspace(-2, 1, 40).tolist()}


def _fit(X, y, config=None, scoring=None, return_train_score=False,
         backend="tpu"):
    from sklearn.linear_model import LogisticRegression
    return sst.GridSearchCV(
        LogisticRegression(max_iter=10), _grid(), cv=2, refit=False,
        backend=backend, scoring=scoring,
        return_train_score=return_train_score, config=config).fit(X, y)


def _non_time_results(gs):
    return {k: v for k, v in gs.cv_results_.items()
            if "time" not in k and k != "params"}


def _assert_exact_equal(ra, rb):
    assert set(ra) == set(rb)
    for k in ra:
        np.testing.assert_array_equal(
            np.asarray(ra[k]), np.asarray(rb[k]), err_msg=k)


@pytest.fixture(scope="module")
def baseline():
    X, y = _data()
    return _fit(X, y)


# ---------------------------------------------------------------------------
# Plan parsing + taxonomy
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_parse_string(self):
        plan = FaultPlan.parse("transient@3, OOM@5x2, hung@7")
        assert plan.specs == (
            FaultSpec(3, "transient", 1), FaultSpec(5, "oom", 2),
            FaultSpec(7, "hung", 1))
        assert plan.match(5, 0).fault_class == "oom"
        assert plan.match(5, 1).fault_class == "oom"
        assert plan.match(5, 2) is None
        assert plan.match(4, 0) is None

    def test_parse_structured(self):
        plan = FaultPlan.parse([(1, "transient"), (2, "fatal", 3),
                                {"index": 4, "class": "oom"}])
        assert len(plan) == 3
        assert plan.match(2, 2).count == 3

    def test_bad_tokens(self):
        with pytest.raises(ValueError, match="bad fault-plan token"):
            FaultPlan.parse("bogus@1")
        with pytest.raises(ValueError, match="bad fault-plan token"):
            FaultPlan.parse("transient#1")
        with pytest.raises(ValueError, match="duplicate"):
            FaultPlan.parse("transient@1,oom@1")
        with pytest.raises(ValueError, match="unknown fault class"):
            FaultPlan.parse([(1, "sideways")])

    def test_env_resolution(self, monkeypatch):
        monkeypatch.setenv("SST_FAULT_PLAN", "transient@9")
        plan = FaultPlan.resolve(None)
        assert plan.match(9, 0).fault_class == "transient"
        # an explicit config plan wins over the env
        plan = FaultPlan.resolve(sst.TpuConfig(fault_plan="oom@2"))
        assert plan.match(2, 0).fault_class == "oom"
        assert plan.match(9, 0) is None

    def test_session_validates_plan_early(self):
        with pytest.raises(ValueError, match="bad fault-plan token"):
            sst.TpuSession(sst.TpuConfig(fault_plan="garbage"))


class TestTaxonomy:
    def test_marker_classification(self):
        assert classify_error(
            RuntimeError("RESOURCE_EXHAUSTED: out of HBM")) == OOM
        assert classify_error(MemoryError()) == OOM
        assert classify_error(
            RuntimeError("UNAVAILABLE: socket closed")) == TRANSIENT
        assert classify_error(RuntimeError("ABORTED: retry")) == TRANSIENT
        assert classify_error(TypeError("bad arg")) == FATAL
        assert classify_error(ValueError("nope")) == FATAL
        # a backend that cannot come up (another process holds the
        # chip) fails at once — UNAVAILABLE in its text must not buy a
        # back-off retry
        assert classify_error(RuntimeError(
            "Unable to initialize backend 'tpu': UNAVAILABLE: TPU "
            "initialization failed")) == FATAL
        assert classify_error(RuntimeError(
            "ABORTED: The TPU is already in use by process with pid "
            "1234")) == FATAL

    def test_injected_and_timeout(self):
        assert classify_error(InjectedFault("transient", "x")) == TRANSIENT
        assert classify_error(InjectedFault("oom_deep", "x")) == OOM
        err = LaunchTimeoutError("0:0:8", 0, 1.5)
        assert classify_error(err) == HUNG
        assert isinstance(err, TimeoutError)
        assert "0:0:8" in str(err) and "compile group 0" in str(err)

    def test_custom_classifier_extension(self):
        class WeirdBackendError(Exception):
            pass

        def classify(exc):
            return TRANSIENT if isinstance(exc, WeirdBackendError) else None

        register_classifier(classify)
        try:
            assert classify_error(WeirdBackendError()) == TRANSIENT
            # other errors still hit the built-in rules
            assert classify_error(TypeError()) == FATAL
        finally:
            faults._CUSTOM_CLASSIFIERS.remove(classify)


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------


class TestWatchdog:
    def test_timeout_raises_named_error(self, monkeypatch):
        monkeypatch.setattr(faults, "_block_until_ready",
                            lambda out: time.sleep(5.0) or out)
        sup = LaunchSupervisor(sst.TpuConfig(launch_timeout_s=0.2))
        t0 = time.perf_counter()
        with pytest.raises(LaunchTimeoutError) as ei:
            sup.wait_ready(object(), key="2:0:8", group=2)
        assert time.perf_counter() - t0 < 2.0
        assert "2:0:8" in str(ei.value)
        assert "compile group 2" in str(ei.value)

    def test_fast_wait_passes_through(self):
        sup = LaunchSupervisor(sst.TpuConfig(launch_timeout_s=5.0))
        obj = (1, "x")
        assert sup.wait_ready(obj, key="k") == obj

    def test_blocker_exception_reraised(self, monkeypatch):
        def boom(out):
            raise RuntimeError("UNAVAILABLE: flaky")
        monkeypatch.setattr(faults, "_block_until_ready", boom)
        sup = LaunchSupervisor(sst.TpuConfig(launch_timeout_s=5.0))
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            sup.wait_ready(object(), key="k")

    def test_no_timeout_is_plain_wait(self):
        sup = LaunchSupervisor(sst.TpuConfig())
        obj = object()
        assert sup.wait_ready(obj, key="k") is obj


# ---------------------------------------------------------------------------
# End-to-end injection: the acceptance drills
# ---------------------------------------------------------------------------

# launch order for the 40-candidate sorted logreg grid: fit(0),
# score(1), calibrate(2), then fused steady-state chunks (3+) on any
# device count — so 4 and 6 always name fused chunks
_PLAN = "transient@4,oom@6"


class TestInjectionRecovery:
    @pytest.mark.parametrize("depth", [2, 0])
    def test_transient_and_oom_recover_exact(self, baseline, depth):
        """The acceptance criterion: one TRANSIENT + one OOM injected at
        fixed launch indices; fit completes, faults counters show the
        recovery, and cv_results_ is EXACT-equal to the fault-free run
        — in the pipelined mode AND the synchronous escape hatch."""
        X, y = _data()
        cfg = sst.TpuConfig(fault_plan=_PLAN, retry_backoff_s=0.01,
                            pipeline_depth=depth)
        gs = _fit(X, y, config=cfg)
        f = gs.search_report["faults"]
        assert f["retries"] >= 1, f
        assert f["bisections"] >= 1, f
        assert f["injected"] >= 2, f
        assert f["by_class"].get("transient", 0) >= 1
        assert f["by_class"].get("oom", 0) >= 1
        _assert_exact_equal(_non_time_results(baseline),
                            _non_time_results(gs))

    def test_multimetric_train_scores_recover_exact(self):
        """Bisection must merge multi-scorer test AND train cells."""
        X, y = _data()
        kw = dict(scoring=["accuracy", "neg_log_loss"],
                  return_train_score=True)
        clean = _fit(X, y, **kw)
        cfg = sst.TpuConfig(fault_plan=_PLAN, retry_backoff_s=0.01)
        gs = _fit(X, y, config=cfg, **kw)
        assert gs.search_report["faults"]["bisections"] >= 1
        _assert_exact_equal(_non_time_results(clean),
                            _non_time_results(gs))

    def test_first_chunk_oom_goes_to_host(self, baseline):
        """OOM on the fit launch (no bisect hook): the whole chunk
        degrades to per-candidate host execution; the score launch
        consumes the stashed cells instead of launching."""
        X, y = _data()
        cfg = sst.TpuConfig(fault_plan="oom@0", retry_backoff_s=0.01)
        gs = _fit(X, y, config=cfg)
        f = gs.search_report["faults"]
        assert f["host_fallbacks"] >= 1, f
        assert np.all(np.isfinite(gs.cv_results_["mean_test_score"]))
        # host cells are sklearn's own float64 answers — tolerance, not
        # bitwise, against the compiled fault-free run
        np.testing.assert_allclose(
            baseline.cv_results_["mean_test_score"],
            gs.cv_results_["mean_test_score"], atol=1e-4)

    def test_oom_deep_bottoms_out_to_host(self, baseline):
        """Sticky OOM re-fails every bisected sub-range: the recursion
        deterministically reaches single candidates and runs them on
        the host with sklearn error_score semantics."""
        X, y = _data()
        cfg = sst.TpuConfig(fault_plan="oom_deep@5", retry_backoff_s=0.01)
        gs = _fit(X, y, config=cfg)
        f = gs.search_report["faults"]
        assert f["bisections"] >= 1, f
        assert f["host_fallbacks"] >= 2, f
        np.testing.assert_allclose(
            baseline.cv_results_["mean_test_score"],
            gs.cv_results_["mean_test_score"], atol=1e-4)

    def test_retry_budget_exhaustion_raises(self):
        X, y = _data()
        cfg = sst.TpuConfig(fault_plan="transient@4x5",
                            max_launch_retries=2, retry_backoff_s=0.01)
        with pytest.raises(InjectedFault):
            _fit(X, y, config=cfg)

    def test_fatal_propagates_compiled(self):
        X, y = _data()
        cfg = sst.TpuConfig(fault_plan="fatal@1")
        with pytest.raises(InjectedFault):
            _fit(X, y, config=cfg)

    def test_device_failure_propagates_without_host_rerun(self):
        """backend=None is no softer than backend="tpu" for a compiler,
        runtime or device failure: it propagates, it is never traded
        for a silent sklearn re-run of the whole grid."""
        X, y = _data()
        cfg = sst.TpuConfig(fault_plan="fatal@1")
        with warnings.catch_warnings():
            warnings.simplefilter("error")     # no fallback warning
            with pytest.raises(InjectedFault):
                _fit(X, y, config=cfg, backend=None)

    @pytest.mark.parametrize("exc_type", [NotImplementedError, TypeError,
                                          ValueError])
    def test_lowering_refusal_propagates(self, monkeypatch, exc_type):
        """The host fallback is an allow-list of ONE type
        (NotCompiledError).  What jax raises when a program cannot be
        lowered for the platform — before anything has launched — is a
        NotImplementedError / TypeError / ValueError, and it must not
        be traded for a host run under the default backend."""
        from jax._src.interpreters import mlir
        from sklearn.linear_model import LogisticRegression

        def refuse(*a, **kw):
            raise exc_type("MLIR translation rule for primitive 'foo' "
                           "not found for platform tpu")

        monkeypatch.setattr(mlir, "lower_jaxpr_to_module", refuse)
        X, y = _data()
        with warnings.catch_warnings():
            warnings.simplefilter("error")     # no fallback warning
            with pytest.raises(exc_type, match="not found for platform"):
                # max_iter no other test uses: a program that must lower
                sst.GridSearchCV(
                    LogisticRegression(max_iter=7), {"C": [0.3, 3.0]},
                    cv=2, refit=False).fit(X, y)

    def test_error_after_device_work_propagates(self, monkeypatch):
        """A failure of the compiled tier after its launches completed
        propagates under backend=None like any other non-refusal."""
        from spark_sklearn_tpu.search import grid as grid_mod

        X, y = _data()
        real = grid_mod.BaseSearchTPU._run_groups

        def run_then_fail(self, **kw):
            real(self, **kw)
            raise ValueError("late engine failure")

        monkeypatch.setattr(grid_mod.BaseSearchTPU, "_run_groups",
                            run_then_fail)
        with pytest.raises(ValueError, match="late engine failure"):
            _fit(X, y, backend=None)

    def test_family_refusal_still_falls_back_and_records_cause(self):
        """A family's own refusal (NotCompiledError) is the one thing
        backend=None answers with the host tier; the host report's
        faults block names the cause.  backend="tpu" raises it."""
        from sklearn.discriminant_analysis import (
            LinearDiscriminantAnalysis)
        rng = np.random.RandomState(0)
        X = rng.randn(90, 4).astype(np.float32)
        y = np.repeat(np.arange(3), 30)
        with pytest.warns(UserWarning, match="falling back"):
            gs = sst.GridSearchCV(
                LinearDiscriminantAnalysis(solver="svd"),
                {"tol": [1e-4, 1e-3]}, cv=2, refit=False).fit(X, y)
        assert gs.search_report["backend"] == "host"
        assert "not compiled" in \
            gs.search_report["faults"]["fallback_exception"]
        with pytest.raises(sst.NotCompiledError, match="not compiled"):
            sst.GridSearchCV(
                LinearDiscriminantAnalysis(solver="svd"),
                {"tol": [1e-4, 1e-3]}, cv=2, refit=False,
                backend="tpu").fit(X, y)

    def test_clean_run_reports_zeroed_faults(self, baseline):
        f = baseline.search_report["faults"]
        assert f["retries"] == 0 and f["bisections"] == 0
        assert f["host_fallbacks"] == 0 and f["timeouts"] == 0
        assert f["injected"] == 0 and f["events"] == []

    def test_hung_fails_clean_and_resumes(self, baseline, tmp_path):
        """A hung launch fails the search with a TimeoutError naming
        the chunk/compile group; chunks finalized before it are durable
        and a resume completes exact-equal to the fault-free run."""
        X, y = _data()
        cfg = sst.TpuConfig(fault_plan="hung@5", launch_timeout_s=30.0,
                            checkpoint_dir=str(tmp_path))
        with pytest.raises(TimeoutError) as ei:
            _fit(X, y, config=cfg)
        assert "compile group 0" in str(ei.value)
        assert ei.value.key in str(ei.value)
        # the fault was journaled durably before the failure
        ckpt_file = [p for p in os.listdir(tmp_path)
                     if p.endswith(".jsonl")][0]
        lines = [json.loads(ln) for ln in
                 open(tmp_path / ckpt_file).read().splitlines()]
        assert any("fault_chunk_id" in rec for rec in lines)
        assert sum("chunk_id" in rec for rec in lines) >= 1

        resumed = _fit(X, y, config=sst.TpuConfig(
            checkpoint_dir=str(tmp_path)))
        assert resumed.search_report["n_chunks_resumed"] >= 1
        assert resumed.search_report["faults"]["timeouts"] == 0
        _assert_exact_equal(_non_time_results(baseline),
                            _non_time_results(resumed))

    def test_keyboard_interrupt_never_falls_back(self, monkeypatch):
        """The narrowed dispatch guard: an interactive abort propagates
        instead of silently re-running the grid on the host."""
        X, y = _data()
        from spark_sklearn_tpu.search.grid import BaseSearchTPU

        def boom(self, *a, **kw):
            raise KeyboardInterrupt

        monkeypatch.setattr(BaseSearchTPU, "_fit_compiled", boom)
        with pytest.raises(KeyboardInterrupt):
            _fit(X, y, backend=None)


# ---------------------------------------------------------------------------
# Checkpoint satellites: atomic npz + fault journal
# ---------------------------------------------------------------------------


class TestCheckpointAtomicity:
    def _tree(self):
        return {"coef": np.arange(6.0).reshape(2, 3),
                "intercept": np.ones(2)}

    def test_save_is_atomic_and_leaves_no_temp(self, tmp_path):
        from spark_sklearn_tpu.utils.checkpoint import (load_pytree,
                                                        save_pytree)
        p = str(tmp_path / "m.npz")
        save_pytree(p, self._tree())
        assert os.listdir(tmp_path) == ["m.npz"]
        back = load_pytree(p, like=self._tree())
        np.testing.assert_allclose(back["coef"], self._tree()["coef"])
        # extension-less path keeps numpy's ".npz" append behavior —
        # and load mirrors the normalization, so a journal pointer
        # saved without the extension (the prefix payload path)
        # round-trips to the file save actually wrote
        save_pytree(str(tmp_path / "bare"), self._tree())
        assert (tmp_path / "bare.npz").exists()
        back = load_pytree(str(tmp_path / "bare"), like=self._tree())
        np.testing.assert_allclose(back["coef"], self._tree()["coef"])

    def test_truncated_npz_fails_loud_and_resaves_clean(self, tmp_path):
        """A crash mid-save must never poison the next resume: the
        truncated-archive failure mode raises cleanly on load, and an
        atomic re-save over it restores a loadable file."""
        from spark_sklearn_tpu.utils.checkpoint import (load_pytree,
                                                        save_pytree)
        p = str(tmp_path / "m.npz")
        save_pytree(p, self._tree())
        size = os.path.getsize(p)
        with open(p, "r+b") as f:          # simulate the torn write
            f.truncate(size // 2)
        with pytest.raises(Exception):
            load_pytree(p, like=self._tree())
        save_pytree(p, self._tree())        # os.replace over the wreck
        back = load_pytree(p, like=self._tree())
        np.testing.assert_allclose(back["intercept"], np.ones(2))

    def test_fault_journal_never_masquerades_as_chunk(self, tmp_path):
        from spark_sklearn_tpu.utils.checkpoint import SearchCheckpoint
        ck = SearchCheckpoint(str(tmp_path), "k1")
        ck.put("0:0:8", {"test": {"score": [[1.0]]}})
        ck.note_fault("0:8:16", {"class": "transient", "attempt": 1})
        assert ck.n_done == 1
        re = SearchCheckpoint(str(tmp_path), "k1")
        assert re.n_done == 1
        assert re.get("0:8:16") is None
        assert len(re.faults) == 1
        assert re.faults[0]["class"] == "transient"


# ---------------------------------------------------------------------------
# Multihost satellite: per-worker deadline, straggler reaping, blame
# ---------------------------------------------------------------------------


class TestMultihostWait:
    def _proc(self, code):
        return subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    def test_straggler_killed_and_named(self):
        from spark_sklearn_tpu.utils.multihost import _wait_procs
        procs = [self._proc("print('ok')"),
                 self._proc("import time; time.sleep(60)")]
        t0 = time.perf_counter()
        outs, failed, timed_out = _wait_procs(procs, timeout_s=3.0)
        assert time.perf_counter() - t0 < 45
        assert timed_out == [1]
        assert failed == []
        assert "ok" in outs[0]
        assert "<killed" in outs[1]
        assert procs[1].poll() is not None   # reaped, not leaked

    def test_failure_fast_kills_peers_and_blames_index(self):
        from spark_sklearn_tpu.utils.multihost import _wait_procs
        procs = [self._proc("import sys; sys.exit(3)"),
                 self._proc("import time; time.sleep(60)")]
        t0 = time.perf_counter()
        outs, failed, timed_out = _wait_procs(
            procs, timeout_s=120.0, grace_s=1.0)
        # the sleeper was killed on the 1s grace, not the 120s budget
        assert time.perf_counter() - t0 < 45
        assert failed == [0]
        assert timed_out == [1]
