"""Multi-tenant search service tests (spark_sklearn_tpu/serve/).

Covers the executor's whole contract: bit-exact parity of submitted
searches vs their solo runs (single and concurrent, mixed families),
deterministic DRR fair share within 10% of configured tenant weights,
admission control, cancellation (drained queue, resumable journal,
released data-plane quota), per-tenant quota isolation in the plane,
fault-injection isolation between tenants, and the single-search
fastpath's zero-queue-overhead invariants.
"""

import threading
import time

import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu import serve
from spark_sklearn_tpu.obs.metrics import SCHEDULER_BLOCK_SCHEMA
from spark_sklearn_tpu.parallel.dataplane import DataPlane
from spark_sklearn_tpu.parallel.pipeline import LaunchItem
from spark_sklearn_tpu.serve.executor import (
    AdmissionError,
    SearchCancelledError,
    SearchExecutor,
    SearchHandle,
    _Reply,
    _Request,
)

from sklearn.linear_model import LogisticRegression
from sklearn.naive_bayes import GaussianNB
from sklearn.neighbors import KNeighborsClassifier


rng = np.random.RandomState(0)
X = rng.randn(96, 6).astype(np.float32)
y = (X[:, 0] + 0.25 * rng.randn(96) > 0).astype(np.int64)

C_GRID = np.logspace(-2, 1, 24).tolist()
VS_GRID = np.logspace(-9, -5, 24).tolist()


def logreg_search(config=None):
    return sst.GridSearchCV(LogisticRegression(max_iter=10),
                            {"C": C_GRID}, cv=2, refit=False,
                            backend="tpu", config=config)


def gnb_search(config=None):
    return sst.GridSearchCV(GaussianNB(), {"var_smoothing": VS_GRID},
                            cv=2, refit=False, backend="tpu",
                            config=config)


def knn_search(config=None):
    return sst.GridSearchCV(KNeighborsClassifier(),
                            {"n_neighbors": [1, 3, 5]}, cv=2,
                            refit=False, backend="tpu", config=config)


def scores(search):
    return search.cv_results_["mean_test_score"]


def wait_for(cond, timeout=60.0, interval=0.005):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(interval)
    return False


class _BlockingSearch:
    """Duck-typed 'search' whose fit blocks until released — the
    admission/cancellation unit-test stand-in (no device work)."""

    config = None

    def __init__(self):
        self.release = threading.Event()
        self.started = threading.Event()
        self.ran = False

    def fit(self, X, y=None, **params):
        self.started.set()
        assert self.release.wait(30.0), "blocking search never released"
        self.ran = True
        return self


# ---------------------------------------------------------------------------
# Schema pin
# ---------------------------------------------------------------------------


#: schema keys that ride only when fusion is resolved ON (the schema
#: marks them conditional) — the standalone/disabled block stays
#: byte-identical to the pre-fusion engine
FUSION_KEYS = {"n_fused", "lanes_donated", "lanes_borrowed",
               "fusion_saved_launches"}


class TestSchedulerBlock:
    def test_disabled_shape_matches_schema(self):
        block = serve.report_block(None)
        assert set(block) == \
            {d.name for d in SCHEDULER_BLOCK_SCHEMA} - FUSION_KEYS
        assert block["enabled"] is False
        assert block["n_dispatches"] == 0

    def test_enabled_shape_matches_schema(self):
        ex = SearchExecutor()
        handle = SearchHandle("t/s1", "t", 2.0)
        block = ex.search_block(handle)
        assert set(block) == {d.name for d in SCHEDULER_BLOCK_SCHEMA}
        assert block["enabled"] is True
        assert block["tenant"] == "t" and block["weight"] == 2.0


# ---------------------------------------------------------------------------
# Single search: parity, fastpath, fit() sugar, overhead
# ---------------------------------------------------------------------------


class TestSingleSearch:
    def test_submit_parity_and_fastpath(self):
        ref = logreg_search().fit(X, y)
        sess = sst.createLocalTpuSession("serve-single")
        try:
            fut = sess.submit(logreg_search(), X, y)
            got = fut.result(timeout=180)
            np.testing.assert_array_equal(scores(got), scores(ref))
            sch = got.search_report["scheduler"]
            # alone in the session: every dispatch short-circuits
            # inline — today's order, zero queue hops, zero waits
            assert sch["enabled"] is True
            assert sch["n_dispatches"] > 0
            assert sch["n_fastpath"] == sch["n_dispatches"]
            assert sch["queue_wait_s"] == 0.0
            assert got.search_report["pipeline"][
                "queue_wait_wall_s"] == 0.0
            assert fut.done() and not fut.cancelled()
            assert fut.progress()["state"] == "done"
        finally:
            sess.stop()

    def test_fit_is_submit_sugar_for_attached_search(self):
        ref = gnb_search().fit(X, y)
        sess = sst.createLocalTpuSession("serve-sugar")
        try:
            attached = sess.attach(gnb_search())
            got = attached.fit(X, y)
            assert got is attached
            np.testing.assert_array_equal(scores(got), scores(ref))
            assert got.search_report["scheduler"]["enabled"] is True
        finally:
            sess.stop()

    def test_standalone_fit_reports_disabled_scheduler(self):
        got = logreg_search().fit(X, y)
        sch = got.search_report["scheduler"]
        assert sch["enabled"] is False and sch["n_dispatches"] == 0

    def test_single_search_overhead_pinned(self):
        """The solo-submit path must match plain fit: structurally
        (all-fastpath, zero queue waits — the invariants that make the
        <=2% wall-clock contract hold by construction) and in measured
        wall within a CI-tolerant envelope."""
        def plain():
            t0 = time.perf_counter()
            logreg_search().fit(X, y)
            return time.perf_counter() - t0

        def submitted():
            sess = sst.createLocalTpuSession("serve-overhead")
            try:
                s = logreg_search()
                t0 = time.perf_counter()
                sess.submit(s, X, y).result(timeout=180)
                wall = time.perf_counter() - t0
                sch = s.search_report["scheduler"]
                assert sch["n_fastpath"] == sch["n_dispatches"]
                assert sch["queue_wait_s"] == 0.0
                return wall
            finally:
                sess.stop()

        plain()          # warm programs so both arms measure steady state
        submitted()
        t_plain = min(plain() for _ in range(3))
        t_sub = min(submitted() for _ in range(3))
        # structural zero-overhead is asserted above; the wall check
        # catches gross regressions without CI-noise flakiness
        assert t_sub <= t_plain * 1.25 + 0.05, (t_sub, t_plain)


# ---------------------------------------------------------------------------
# Concurrency: bit-exact parity + interleave
# ---------------------------------------------------------------------------


class TestConcurrentSearches:
    def test_two_concurrent_bit_exact_and_interleaved(self):
        cfg = sst.TpuConfig(max_tasks_per_batch=16)
        ref_a = logreg_search(cfg).fit(X, y)
        ref_b = gnb_search(cfg).fit(X, y)
        sess = sst.createLocalTpuSession("serve-pair")
        try:
            ex = sess.executor
            ex.pause()   # collect one queued chunk from each search
            fa = sess.submit(logreg_search(cfg), X, y)
            fb = sess.submit(gnb_search(cfg), X, y)
            assert wait_for(lambda: ex.queued_count() >= 2), \
                ex.stats()
            ex.resume()
            a = fa.result(timeout=300)
            b = fb.result(timeout=300)
            np.testing.assert_array_equal(scores(a), scores(ref_a))
            np.testing.assert_array_equal(scores(b), scores(ref_b))
            sa = a.search_report["scheduler"]
            sb = b.search_report["scheduler"]
            # the paused start guarantees the first two dispatches come
            # from different searches: the device stream interleaved
            assert sa["n_interleaved"] + sb["n_interleaved"] > 0
            assert sa["interleave_frac"] > 0 or \
                sb["interleave_frac"] > 0
            # fair-share waiting is accounted as queue wait, not
            # dispatch (the geometry cost model's input stays clean)
            pipeline_qw = (a.search_report["pipeline"]["queue_wait_wall_s"]
                           + b.search_report["pipeline"][
                               "queue_wait_wall_s"])
            assert pipeline_qw > 0.0
        finally:
            sess.stop()

    @pytest.mark.slow
    def test_three_mixed_families_bit_exact(self):
        cfg = sst.TpuConfig(max_tasks_per_batch=16)
        refs = [logreg_search(cfg).fit(X, y), gnb_search(cfg).fit(X, y),
                knn_search(cfg).fit(X, y)]
        sess = sst.createLocalTpuSession("serve-mixed")
        try:
            searches = [logreg_search(cfg), gnb_search(cfg),
                        knn_search(cfg)]
            futs = [sess.submit(s, X, y) for s in searches]
            got = [f.result(timeout=300) for f in futs]
            for g, r in zip(got, refs):
                np.testing.assert_array_equal(scores(g), scores(r))
                assert g.search_report["scheduler"]["enabled"] is True
        finally:
            sess.stop()

    def test_x64_family_schedules_exclusively(self):
        """A wants_float64 family (ridge) flips the process-global jax
        x64 flag for its fit, so the executor runs it with no
        concurrent searches — both it and a normally-scheduled search
        stay bit-exact with their solo runs."""
        from sklearn.linear_model import Ridge
        yr = (X @ np.arange(6, dtype=np.float32)
              + 0.1 * rng.randn(96)).astype(np.float32)

        def ridge_search():
            return sst.GridSearchCV(
                Ridge(), {"alpha": np.logspace(-3, 2, 12).tolist()},
                cv=2, refit=False, backend="tpu")

        ref_r = ridge_search().fit(X, yr)
        ref_l = logreg_search().fit(X, y)
        sess = sst.createLocalTpuSession("serve-x64")
        try:
            fr = sess.submit(ridge_search(), X, yr)
            fl = sess.submit(logreg_search(), X, y)
            assert fr._handle.exclusive and not fl._handle.exclusive
            r = fr.result(timeout=300)
            lo = fl.result(timeout=300)
            np.testing.assert_array_equal(scores(r), scores(ref_r))
            np.testing.assert_array_equal(scores(lo), scores(ref_l))
        finally:
            sess.stop()

    def test_fault_injection_isolated_between_tenants(self):
        """``oom@k`` on one tenant's search recovers through bisection
        with exact scores while the other tenant's concurrent search
        records zero faults."""
        cfg_ok = sst.TpuConfig(max_tasks_per_batch=16,
                               tenant="healthy")
        cfg_bad = sst.TpuConfig(max_tasks_per_batch=16, tenant="faulty",
                                fault_plan="oom@3",
                                retry_backoff_s=0.01)
        ref_a = logreg_search(
            sst.TpuConfig(max_tasks_per_batch=16)).fit(X, y)
        ref_b = gnb_search(
            sst.TpuConfig(max_tasks_per_batch=16)).fit(X, y)
        sess = sst.createLocalTpuSession("serve-faults")
        try:
            ex = sess.executor
            ex.pause()
            fa = sess.submit(logreg_search(cfg_bad), X, y)
            fb = sess.submit(gnb_search(cfg_ok), X, y)
            assert wait_for(lambda: ex.queued_count() >= 2), ex.stats()
            ex.resume()
            a = fa.result(timeout=300)
            b = fb.result(timeout=300)
            np.testing.assert_array_equal(scores(a), scores(ref_a))
            np.testing.assert_array_equal(scores(b), scores(ref_b))
            assert a.search_report["faults"]["bisections"] >= 1, \
                a.search_report["faults"]
            fb_block = b.search_report["faults"]
            assert fb_block["bisections"] == 0 and \
                fb_block["retries"] == 0 and \
                fb_block["host_fallbacks"] == 0, fb_block
        finally:
            sess.stop()


# ---------------------------------------------------------------------------
# Fair share: deterministic DRR over synthetic items
# ---------------------------------------------------------------------------


class TestFairShare:
    @staticmethod
    def _drive(ex, handle, n, cost, work_s=0.005):
        """Enqueue n synthetic requests for handle; returns replies."""
        replies = []
        for i in range(n):
            item = LaunchItem(key=f"{handle.id}:{i}", kind="fused",
                              n_tasks=cost,
                              launch=lambda p: time.sleep(0.0))
            req = _Request(
                handle=handle, item=item,
                launch=lambda p, w=work_s: time.sleep(w),
                payload=None, cost=cost, state={"counted": False},
                t_enqueued=time.perf_counter(), reply=_Reply())
            ex._enqueue(req)
            replies.append(req.reply)
        return replies

    def test_drr_shares_track_weights_within_10pct(self):
        """Deep queues for two tenants with weights 1:3 — the dispatch
        stream's shares (read from the scheduler block at the heavy
        tenant's drain point) land within 10% of 0.25/0.75."""
        ex = SearchExecutor(sst.TpuConfig(scheduler_quantum=8))
        h_light = SearchHandle("light/s1", "light", 1.0)
        h_heavy = SearchHandle("heavy/s1", "heavy", 3.0)
        ex.pause()
        n = 40
        self._drive(ex, h_light, n, cost=8)
        heavy_replies = self._drive(ex, h_heavy, n, cost=8)
        ex.resume()
        for r in heavy_replies:
            r.result()
        # scheduler-block shares measured the moment the heavy tenant
        # drains: the contended window, before the light tenant's
        # backlog equalizes the totals
        block = ex.search_block(h_heavy)
        shares = block["tenant_shares"]
        assert abs(shares["heavy"] - 0.75) <= 0.10, block
        assert abs(shares["light"] - 0.25) <= 0.10, block
        # and the raw dispatch journal's contended prefix agrees
        log = ex.dispatch_log()[:n]
        heavy_cost = sum(c for _, t, c in log if t == "heavy")
        total = sum(c for _, _, c in log)
        assert abs(heavy_cost / total - 0.75) <= 0.10, log
        assert block["queue_wait_s"] > 0.0
        ex.shutdown()

    def test_tenant_inflight_cap_blocks_dispatch(self):
        ex = SearchExecutor(sst.TpuConfig(tenant_max_inflight=1))
        h = SearchHandle("capped/s1", "capped", 1.0)
        state1 = {"counted": False}
        state2 = {"counted": False}
        reqs = []
        for state in (state1, state2):
            item = LaunchItem(key="k", launch=lambda p: None, n_tasks=1)
            req = _Request(handle=h, item=item, launch=lambda p: None,
                           payload=None, cost=1, state=state,
                           t_enqueued=time.perf_counter(),
                           reply=_Reply())
            ex._enqueue(req)
            reqs.append(req)
        # first dispatches; second must stay queued behind the cap
        reqs[0].reply.result()
        assert not wait_for(lambda: ex.queued_count() == 0, timeout=0.5)
        assert ex.queued_count("capped") == 1
        # finalizing the first frees the cap
        ex._note_done(h, state1)
        reqs[1].reply.result()
        assert wait_for(lambda: ex.queued_count() == 0, timeout=5)
        ex.shutdown()


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_reject_beyond_bounded_queue(self):
        ex = SearchExecutor(sst.TpuConfig(max_concurrent_searches=1,
                                          max_queued_searches=0))
        s1, s2 = _BlockingSearch(), _BlockingSearch()
        fut1 = ex.submit(s1, X, y)
        assert s1.started.wait(10)
        with pytest.raises(AdmissionError):
            ex.submit(s2, X, y)
        s1.release.set()
        assert fut1.result(timeout=30) is s1
        ex.shutdown()

    def test_queued_search_starts_when_slot_frees(self):
        ex = SearchExecutor(sst.TpuConfig(max_concurrent_searches=1,
                                          max_queued_searches=1))
        s1, s2 = _BlockingSearch(), _BlockingSearch()
        fut1 = ex.submit(s1, X, y)
        assert s1.started.wait(10)
        fut2 = ex.submit(s2, X, y)
        assert fut2.progress()["state"] == "queued"
        assert not s2.started.is_set()
        s1.release.set()
        assert fut1.result(timeout=30) is s1
        assert s2.started.wait(10)
        s2.release.set()
        assert fut2.result(timeout=30) is s2
        ex.shutdown()

    def test_submit_after_shutdown_rejects(self):
        ex = SearchExecutor()
        ex.shutdown()
        with pytest.raises(AdmissionError):
            ex.submit(_BlockingSearch(), X, y)

    def test_submit_storm_admits_or_rejects_exactly(self):
        """N threads racing submit against a 1-running/3-queued
        executor: every submit either returns a live future or raises
        a structured AdmissionError — admitted + rejected == N, no
        lost futures, and the executor still serves work after the
        storm."""
        ex = SearchExecutor(sst.TpuConfig(max_concurrent_searches=1,
                                          max_queued_searches=3))
        n = 16
        searches = [_BlockingSearch() for _ in range(n)]
        admitted, rejected = [], []
        lock = threading.Lock()
        barrier = threading.Barrier(n)

        def storm(s):
            barrier.wait(10)
            try:
                fut = ex.submit(s, X, y)
            except AdmissionError as exc:
                with lock:
                    rejected.append(exc)
            else:
                with lock:
                    admitted.append((s, fut))

        threads = [threading.Thread(target=storm, args=(s,))
                   for s in searches]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
        assert len(admitted) + len(rejected) == n
        # capacity is exact under blocking searches: 1 running + 3
        # queued admitted, everyone else sheds with machine-readable
        # queue state
        assert len(admitted) == 4, (len(admitted), len(rejected))
        for exc in rejected:
            assert exc.reason == "queue-full"
            assert exc.max_concurrent == 1 and exc.max_queued == 3
        # every admitted search runs to completion once released
        for s, _ in admitted:
            s.release.set()
        for s, fut in admitted:
            assert fut.result(timeout=60) is s and s.ran
        # executor survived the storm: a fresh submit completes
        tail = _BlockingSearch()
        tail.release.set()
        assert ex.submit(tail, X, y).result(timeout=30) is tail
        ex.shutdown()


# ---------------------------------------------------------------------------
# Cancellation
# ---------------------------------------------------------------------------


class TestCancellation:
    def test_cancel_queued_search_never_starts(self):
        ex = SearchExecutor(sst.TpuConfig(max_concurrent_searches=1,
                                          max_queued_searches=2))
        s1, s2 = _BlockingSearch(), _BlockingSearch()
        fut1 = ex.submit(s1, X, y)
        assert s1.started.wait(10)
        fut2 = ex.submit(s2, X, y)
        assert fut2.cancel() is True
        with pytest.raises(SearchCancelledError):
            fut2.result(timeout=30)
        assert fut2.cancelled()
        s1.release.set()
        fut1.result(timeout=30)
        assert not s2.started.is_set() and not s2.ran
        assert fut2.cancel() is False      # already finished
        ex.shutdown()

    def test_cancel_midrun_leaves_journal_resumable(self, tmp_path):
        """Cancel a running search after some chunks completed: the
        checkpoint journal keeps them, a fresh identical search
        resumes them, and the tenant's data-plane quota is released."""
        big_grid = {"C": np.logspace(-2, 1, 96).tolist()}

        def big_search(config):
            return sst.GridSearchCV(LogisticRegression(max_iter=10),
                                    big_grid, cv=2, refit=False,
                                    backend="tpu", config=config)

        cfg = sst.TpuConfig(max_tasks_per_batch=16,
                            checkpoint_dir=str(tmp_path),
                            tenant="cancel-me",
                            dataplane_tenant_bytes=64 * 2 ** 20)
        ref = big_search(sst.TpuConfig(max_tasks_per_batch=16)).fit(X, y)
        sess = sst.createLocalTpuSession("serve-cancel")
        try:
            ex = sess.executor
            fut = sess.submit(big_search(cfg), X, y)
            # let at least one chunk finalize (durable in the journal;
            # pipeline depth 2 guarantees finalizes once 4 dispatched),
            # then hold the loop so the NEXT chunk sits queued
            assert wait_for(
                lambda: fut.progress()["dispatched"] >= 4, timeout=120)
            ex.pause()
            # the search either finished already (too fast) or its next
            # dispatch is queued/on the way — both paths are exercised
            # across CI runs; only assert cancellation semantics when
            # cancel actually won the race
            won = False
            if not fut.done():
                wait_for(lambda: ex.queued_count() >= 1, timeout=5)
                won = fut.cancel()
            ex.resume()
            if won:
                with pytest.raises(SearchCancelledError):
                    fut.result(timeout=60)
                assert fut.progress()["state"] == "cancelled"
                from spark_sklearn_tpu.parallel.dataplane import (
                    get_dataplane)
                assert wait_for(lambda: get_dataplane().tenant_usage(
                    "cancel-me") == 0, timeout=10)
            else:
                fut.result(timeout=120)
        finally:
            sess.stop()
        # resume: identical search, same journal — completed chunks
        # restore instead of relaunching; scores exact either way
        cfg2 = sst.TpuConfig(max_tasks_per_batch=16,
                             checkpoint_dir=str(tmp_path))
        resumed = big_search(cfg2).fit(X, y)
        np.testing.assert_array_equal(scores(resumed), scores(ref))
        assert resumed.search_report["n_chunks_resumed"] > 0

    def test_cancelled_error_is_no_retry(self):
        exc = SearchCancelledError("x")
        assert getattr(exc, "_sst_cancelled") is True
        from spark_sklearn_tpu.parallel.faults import LaunchSupervisor
        sup = LaunchSupervisor(sst.TpuConfig(retry_backoff_s=0.0))
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            raise SearchCancelledError("cancelled mid-launch")

        with pytest.raises(SearchCancelledError):
            sup.call(boom, key="c0")
        assert calls["n"] == 1                 # no retry
        assert sup.faults["retries"] == 0
        assert sup.faults["events"] == []      # not journalled as fault


# ---------------------------------------------------------------------------
# Data-plane tenant quotas
# ---------------------------------------------------------------------------


class TestTenantQuota:
    @staticmethod
    def _arr(seed, kb=64):
        r = np.random.RandomState(seed)
        return r.randn(kb * 1024 // 8).astype(np.float64)

    def test_over_quota_tenant_evicts_its_own_lru(self):
        plane = DataPlane(byte_budget=1 << 30)
        plane.set_tenant_quota("t1", 160 * 1024)
        a = self._arr(1)
        b = self._arr(2)
        c = self._arr(3)
        plane.put(a, None, tenant="t1")
        plane.put(b, None, tenant="t1")
        assert plane.tenant_usage("t1") == a.nbytes + b.nbytes
        plane.put(c, None, tenant="t1")    # over quota: evicts `a`
        assert plane.evictions == 1
        assert plane.tenant_usage("t1") <= 160 * 1024
        # b and c still resident (hits), a was the LRU victim
        h0 = plane.hits
        plane.put(b, None, tenant="t1")
        plane.put(c, None, tenant="t1")
        assert plane.hits == h0 + 2
        m0 = plane.misses
        plane.put(a, None, tenant="t1")    # re-uploads
        assert plane.misses == m0 + 1

    def test_global_pressure_cannot_evict_within_quota_tenant(self):
        """Tenant t2 blowing past the global budget evicts its OWN
        entries; t1's residents (within t1's quota) survive."""
        plane = DataPlane(byte_budget=320 * 1024)
        plane.set_tenant_quota("t1", 160 * 1024)
        plane.set_tenant_quota("t2", 160 * 1024)
        a1, a2 = self._arr(1), self._arr(2)
        plane.put(a1, None, tenant="t1")
        plane.put(a2, None, tenant="t1")
        for seed in range(10, 16):         # t2 cycles many arrays
            plane.put(self._arr(seed), None, tenant="t2")
        h0 = plane.hits
        plane.put(a1, None, tenant="t1")
        plane.put(a2, None, tenant="t1")
        assert plane.hits == h0 + 2, plane.stats()
        assert plane.tenant_usage("t1") == a1.nbytes + a2.nbytes

    def test_release_tenant_unpins_and_zeroes_usage(self):
        plane = DataPlane(byte_budget=1 << 30)
        plane.set_tenant_quota("t1", 1 << 30)
        a = self._arr(1)
        plane.put(a, None, tenant="t1")
        assert plane.tenant_usage("t1") == a.nbytes
        freed = plane.release_tenant("t1")
        assert freed == a.nbytes
        assert plane.tenant_usage("t1") == 0
        # entry survives as an unowned hit until LRU pressure
        h0 = plane.hits
        plane.put(a, None, tenant="t2")
        assert plane.hits == h0 + 1

    def test_shared_prefix_digest_does_not_cross_charge(self):
        """Two tenants whose searches share a prefix digest share the
        derived buffer — but the bytes stay charged to the tenant that
        materialized it; the second tenant rides for free."""
        plane = DataPlane(byte_budget=1 << 30)
        plane.set_tenant_quota("t1", 1 << 20)
        plane.set_tenant_quota("t2", 1 << 20)
        made = []
        key = ("dg-abc", "maskfp", "xfp", "shard0")

        def maker():
            made.append(1)
            return self._arr(7)

        dev, hit = plane.derived(key, maker, 64 * 1024,
                                 label="prefix.xt", tenant="t1")
        assert not hit and len(made) == 1
        assert plane.tenant_usage("t1") == 64 * 1024
        dev2, hit2 = plane.derived(key, maker, 64 * 1024,
                                   label="prefix.xt", tenant="t2")
        assert hit2 and dev2 is dev and len(made) == 1
        assert plane.tenant_usage("t2") == 0
        assert plane.tenant_usage("t1") == 64 * 1024
        assert plane.bytes_derived == 64 * 1024

    def test_tenant_pressure_cannot_evict_shared_prefix(self):
        """Tenant t2 blowing its quota on its OWN derived buffers
        evicts its own LRU — never the shared digest t1 owns."""
        plane = DataPlane(byte_budget=1 << 30)
        plane.set_tenant_quota("t1", 256 * 1024)
        plane.set_tenant_quota("t2", 160 * 1024)
        shared_key = ("dg-shared", "maskfp", "xfp", "shard0")
        plane.derived(shared_key, lambda: self._arr(1), 64 * 1024,
                      label="prefix.xt", tenant="t1")
        for seed in range(20, 25):
            plane.derived(("dg-%d" % seed, "m", "x", "s"),
                          lambda s=seed: self._arr(s), 64 * 1024,
                          label="prefix.xt", tenant="t2")
        assert plane.tenant_usage("t2") <= 160 * 1024
        assert plane.evictions >= 1
        # t1's shared matrix is still resident: a hit, zero recompute
        made = []
        _, hit = plane.derived(shared_key,
                               lambda: made.append(1) or self._arr(1),
                               64 * 1024, label="prefix.xt",
                               tenant="t2")
        assert hit and not made
        assert plane.tenant_usage("t1") == 64 * 1024
