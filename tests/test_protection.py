"""Self-protecting service tests (spark_sklearn_tpu protection layer).

Contracts under test:
  - deadlines: ``search_deadline_s`` raises ``SearchDeadlineError``
    under ``partial_results="raise"`` and degrades gracefully under
    ``"best_effort"`` — un-run candidates land at sklearn-exact
    ``error_score`` and the pinned ``search_report["protection"]``
    block names every shed candidate;
  - poison-candidate quarantine: a chunk that bottoms to single-lane
    and still faults FATAL K times is quarantined to ``error_score``
    instead of killing the search; sibling chunks stay bit-exact;
  - persistent-fault degradation: an unrecoverable fault under
    best_effort returns a declared-partial result, never a crash;
  - predictive admission: a search whose ledger-modeled footprint
    cannot fit ``hbm_budget_bytes`` is rejected with a structured
    ``AdmissionError`` before any device work;
  - brownout injection: ``slow@N:F`` stalls a launch F seconds and is
    journalled under its own fault class with scores bit-exact;
  - telemetry: admission/protection counters and the snapshot's
    ``protection`` block;
  - the protection-off escape hatch: no block in the report, results
    byte-identical to the unprotected engine.
"""

import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.obs import telemetry as tel
from spark_sklearn_tpu.obs.metrics import PROTECTION_BLOCK_SCHEMA
from spark_sklearn_tpu.parallel.faults import (
    FaultPlan,
    InjectedFault,
    SearchDeadlineError,
    protection_block,
    protection_enabled,
)
from spark_sklearn_tpu.serve.executor import AdmissionError, SearchExecutor

from sklearn.linear_model import LogisticRegression


rng = np.random.RandomState(0)
X = rng.randn(96, 6).astype(np.float32)
y = (X[:, 0] + 0.25 * rng.randn(96) > 0).astype(np.int64)


def logreg_search(config=None, error_score=np.nan, n=24):
    return sst.GridSearchCV(
        LogisticRegression(max_iter=10),
        {"C": np.logspace(-2, 1, n).tolist()}, cv=2, refit=False,
        backend="tpu", config=config, error_score=error_score)


def scores(search):
    return search.cv_results_["mean_test_score"]


def shed_candidates(prot):
    out = []
    for entry in prot["shed"]:
        out.extend(entry["candidates"])
    return sorted(out)


def quarantined_candidates(prot):
    out = []
    for entry in prot["quarantined"]:
        out.extend(entry["candidates"])
    return sorted(out)


# ---------------------------------------------------------------------------
# Protection block: schema pin + verdict grammar
# ---------------------------------------------------------------------------


class TestProtectionBlock:
    def test_block_matches_schema(self):
        cfg = sst.TpuConfig(partial_results="best_effort")
        block = protection_block(cfg)
        assert set(block) == {d.name for d in PROTECTION_BLOCK_SCHEMA}
        assert block["enabled"] is True
        assert block["verdict"] == "complete"
        assert block["partial"] is False

    def test_verdict_composes_causes(self):
        cfg = sst.TpuConfig(partial_results="best_effort",
                            search_deadline_s=5.0)
        block = protection_block(
            cfg, deadline_hit=True,
            shed=[{"reason": "deadline", "chunk": 0,
                   "candidates": [1, 2]},
                  {"reason": "fault", "chunk": None,
                   "candidates": [3]}],
            quarantined=[{"key": "k", "group": 0, "candidates": [0],
                          "error": "x", "n_faults": 3}],
            elapsed_s=5.5)
        assert block["verdict"] == "partial-deadline+quarantine+fault"
        assert block["partial"] is True
        assert block["n_candidates_shed"] == 3
        assert block["n_quarantined"] == 1
        assert block["deadline_s"] == 5.0

    def test_protection_enabled_gate(self):
        assert protection_enabled(sst.TpuConfig()) is False
        assert protection_enabled(
            sst.TpuConfig(search_deadline_s=1.0)) is True
        assert protection_enabled(
            sst.TpuConfig(partial_results="best_effort")) is True
        assert protection_enabled(
            sst.TpuConfig(admission_mode="predictive")) is True


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_raise_mode_raises_with_context(self):
        cfg = sst.TpuConfig(search_deadline_s=1e-9)
        with pytest.raises(SearchDeadlineError) as ei:
            logreg_search(cfg).fit(X, y)
        assert ei.value.deadline_s == 1e-9
        assert ei.value.n_remaining > 0

    def test_best_effort_sheds_to_error_score(self):
        cfg = sst.TpuConfig(search_deadline_s=1e-9,
                            partial_results="best_effort")
        s = logreg_search(cfg, error_score=-7.0).fit(X, y)
        prot = s.search_report["protection"]
        assert prot["verdict"] == "partial-deadline"
        assert prot["deadline_hit"] is True and prot["partial"] is True
        assert prot["n_candidates_shed"] == 24
        assert shed_candidates(prot) == list(range(24))
        assert all(e["reason"] == "deadline" for e in prot["shed"])
        np.testing.assert_array_equal(scores(s), np.full(24, -7.0))
        # shed candidates never ran: their fold times are zeroed
        assert s.cv_results_["mean_fit_time"].sum() == 0.0

    def test_generous_deadline_stays_complete_and_exact(self):
        ref = logreg_search().fit(X, y)
        cfg = sst.TpuConfig(search_deadline_s=600.0,
                            partial_results="best_effort")
        s = logreg_search(cfg).fit(X, y)
        np.testing.assert_array_equal(scores(s), scores(ref))
        prot = s.search_report["protection"]
        assert prot["verdict"] == "complete"
        assert prot["deadline_hit"] is False
        assert prot["partial"] is False
        assert 0.0 < prot["elapsed_s"] < 600.0


# ---------------------------------------------------------------------------
# Poison-candidate quarantine
# ---------------------------------------------------------------------------


class TestQuarantine:
    def test_sticky_fatal_chunk_quarantined_search_survives(self):
        """``fatal_deep@0`` keeps the first chunk faulting FATAL at
        every bisection width, so each single-lane range trips the
        K-strike rule: the chunk's candidates land at error_score and
        every other chunk stays bit-exact with the solo run."""
        ref = logreg_search(
            sst.TpuConfig(max_tasks_per_batch=16)).fit(X, y)
        cfg = sst.TpuConfig(fault_plan="fatal_deep@0",
                            max_tasks_per_batch=16,
                            partial_results="best_effort",
                            quarantine_fatal_k=2,
                            retry_backoff_s=0.01)
        s = logreg_search(cfg, error_score=-9.0).fit(X, y)
        prot = s.search_report["protection"]
        assert prot["verdict"] == "partial-quarantine"
        assert prot["partial"] is True
        bad = quarantined_candidates(prot)
        assert bad == list(range(8))          # the whole first chunk
        assert prot["n_quarantined"] == len(prot["quarantined"])
        got = scores(s)
        np.testing.assert_array_equal(got[bad], np.full(len(bad), -9.0))
        ok = [i for i in range(24) if i not in bad]
        np.testing.assert_array_equal(got[ok], scores(ref)[ok])
        for entry in prot["quarantined"]:
            assert entry["n_faults"] >= 2
            assert "InjectedFault" in entry["error"]

    def test_transient_fatal_recovers_bit_exact(self):
        """A non-sticky ``fatal@N`` re-runs clean after isolation —
        quarantine never fires and the result is complete + exact."""
        ref = logreg_search(
            sst.TpuConfig(max_tasks_per_batch=16)).fit(X, y)
        cfg = sst.TpuConfig(fault_plan="fatal@3",
                            max_tasks_per_batch=16,
                            partial_results="best_effort",
                            quarantine_fatal_k=2,
                            retry_backoff_s=0.01)
        s = logreg_search(cfg).fit(X, y)
        np.testing.assert_array_equal(scores(s), scores(ref))
        prot = s.search_report["protection"]
        assert prot["verdict"] == "complete"
        assert prot["n_quarantined"] == 0 and prot["partial"] is False

    def test_protection_off_fatal_still_raises(self):
        cfg = sst.TpuConfig(fault_plan="fatal_deep@0",
                            max_tasks_per_batch=16,
                            retry_backoff_s=0.01)
        with pytest.raises(InjectedFault):
            logreg_search(cfg).fit(X, y)


# ---------------------------------------------------------------------------
# Persistent-fault graceful degradation
# ---------------------------------------------------------------------------


class TestDegradation:
    def test_unrecoverable_fault_returns_declared_partial(self):
        """Quarantine disabled (k=0): the sticky FATAL is
        unrecoverable, and best_effort converts the would-be crash
        into a declared-partial result with every un-run candidate at
        error_score."""
        cfg = sst.TpuConfig(fault_plan="fatal_deep@0",
                            max_tasks_per_batch=16,
                            partial_results="best_effort",
                            quarantine_fatal_k=0,
                            retry_backoff_s=0.01)
        s = logreg_search(cfg, error_score=-5.0).fit(X, y)
        prot = s.search_report["protection"]
        assert prot["verdict"] == "partial-fault"
        assert prot["n_candidates_shed"] == 24
        assert shed_candidates(prot) == list(range(24))
        assert any(e.get("error") for e in prot["shed"])
        np.testing.assert_array_equal(scores(s), np.full(24, -5.0))


# ---------------------------------------------------------------------------
# Predictive admission
# ---------------------------------------------------------------------------


class TestPredictiveAdmission:
    def test_oversized_footprint_rejected_before_any_launch(self):
        cfg = sst.TpuConfig(admission_mode="predictive",
                            hbm_budget_bytes=1024)
        ex = SearchExecutor(cfg)
        s = logreg_search(cfg)
        try:
            with pytest.raises(AdmissionError) as ei:
                ex.submit(s, X, y)
        finally:
            ex.shutdown()
        exc = ei.value
        assert exc.reason == "footprint"
        assert exc.retry_after_s is None   # resubmitting will not help
        # provably predictive: rejected before any device work
        assert not hasattr(s, "cv_results_")

    def test_fitting_footprint_admits_and_stays_exact(self):
        ref = logreg_search().fit(X, y)
        cfg = sst.TpuConfig(admission_mode="predictive")
        ex = SearchExecutor(cfg)
        try:
            s = logreg_search(cfg)
            got = ex.submit(s, X, y).result(timeout=180)
            np.testing.assert_array_equal(scores(got), scores(ref))
            prot = got.search_report["protection"]
            assert prot["mode"] == "predictive"
            assert prot["verdict"] == "complete"
        finally:
            ex.shutdown()

    def test_admission_error_structured_fields(self):
        exc = AdmissionError("m", reason="queue-full", retry_after_s=1.5,
                             tenant="t0", n_active=1, n_pending=2,
                             max_concurrent=3, max_queued=4)
        assert exc.reason == "queue-full"
        assert exc.retry_after_s == 1.5
        assert exc.tenant == "t0"
        assert (exc.n_active, exc.n_pending) == (1, 2)
        assert (exc.max_concurrent, exc.max_queued) == (3, 4)


# ---------------------------------------------------------------------------
# Brownout injection (slow@N:F)
# ---------------------------------------------------------------------------


class TestBrownout:
    def test_slow_token_parses_factor(self):
        plan = FaultPlan.parse("slow@3:0.25")
        (spec,) = plan.specs
        assert (spec.index, spec.fault_class, spec.count, spec.factor) \
            == (3, "slow", 1, 0.25)

    def test_brownout_journalled_and_bit_exact(self):
        ref = logreg_search(
            sst.TpuConfig(max_tasks_per_batch=16)).fit(X, y)
        cfg = sst.TpuConfig(fault_plan="slow@1:0.05",
                            max_tasks_per_batch=16)
        s = logreg_search(cfg).fit(X, y)
        np.testing.assert_array_equal(scores(s), scores(ref))
        faults = s.search_report["faults"]
        assert faults["by_class"].get("slow", 0) == 1, faults


# ---------------------------------------------------------------------------
# Telemetry: admission + protection counters
# ---------------------------------------------------------------------------


@pytest.fixture()
def svc():
    service = tel.get_telemetry()

    def force_off():
        while service.enabled:
            if service.disable():
                break

    force_off()
    service.reset()
    yield service
    force_off()
    service.reset()


class TestProtectionTelemetry:
    def test_counters_roll_up_into_snapshot(self, svc):
        svc.enable()
        tel.note_admission("admitted", "t0")
        tel.note_admission("queued", "t0")
        tel.note_admission("rejected", "t0", "footprint")
        tel.note_admission("rejected", "t1", "queue-full")
        tel.note_protection("shed", 3)
        tel.note_protection("quarantined")
        tel.note_protection("deadline_hit")
        prot = svc.snapshot()["protection"]
        assert prot == {
            "admitted_total": 1,
            "queued_total": 1,
            "rejected_total": 2,
            "rejected_by_reason": {"footprint": 1, "queue-full": 1},
            "shed_total": 3,
            "quarantined_total": 1,
            "deadline_hits_total": 1,
        }

    def test_disabled_hooks_record_nothing(self, svc):
        tel.note_admission("rejected", "t0", "footprint")
        tel.note_protection("shed", 5)
        prot = svc.snapshot()["protection"]
        assert prot["rejected_total"] == 0
        assert prot["shed_total"] == 0
        assert prot["rejected_by_reason"] == {}


# ---------------------------------------------------------------------------
# Protection-off escape hatch
# ---------------------------------------------------------------------------


class TestProtectionOff:
    def test_no_block_and_exact_when_off(self):
        s = logreg_search().fit(X, y)
        assert "protection" not in s.search_report
        protected = logreg_search(
            sst.TpuConfig(partial_results="best_effort")).fit(X, y)
        np.testing.assert_array_equal(scores(s), scores(protected))
        assert "protection" in protected.search_report
