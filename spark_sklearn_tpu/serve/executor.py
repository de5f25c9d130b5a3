"""Multi-tenant search service — the async fair-share executor.

The reference's whole reason to exist was a *shared* Spark cluster:
many users submitting grid searches against one pool of executors
(reference: grid_search.py over a long-lived SparkContext).  Before
this module the TPU rebuild was a single-search owner of the device —
``GridSearchCV.fit`` blocked, and a second search in the same process
queued behind the first at Python level with no fairness, no admission
control and no shared accounting.  Online shared-cluster tuning
(arXiv:2309.01901) and gang-scheduled accelerator stages (JAMPI,
arXiv:2005.12048) are the reference designs this executor brings to
the session:

  - :class:`SearchExecutor` (owned by
    :class:`~spark_sklearn_tpu.utils.session.TpuSession`) runs the ONE
    device-dispatch loop (the ``sst-dispatch`` thread).  Submitted
    searches run their fits on worker threads and their chunk
    ``LaunchItem`` dispatches route through a shared queue, tagged
    with a tenant id and search handle, while each search's own
    stage/compile/gather threads keep overlapping host work with
    device compute exactly as before;
  - **fair share** — deficit round-robin over tenants, weighted by
    ``TpuConfig(tenant_weight)``: per scheduling round each tenant
    earns ``scheduler_quantum x weight`` dispatch credit in task
    units, so a weight-3 tenant's chunks interleave onto the device at
    3x a weight-1 tenant's rate while both have chunks queued;
  - **admission control** — ``max_concurrent_searches`` running slots,
    a bounded ``max_queued_searches`` waiting line, per-tenant
    in-flight chunk caps (``tenant_max_inflight``), all rejecting with
    a clean :class:`AdmissionError` instead of unbounded queueing;
  - **tenant byte quotas** — each search's broadcast uploads are
    charged to its tenant in the device data plane
    (``TpuConfig(dataplane_tenant_bytes)``), so one tenant cannot
    evict another's resident X/y (parallel/dataplane.py);
  - **single-search short circuit** — with one active search and empty
    queues a dispatch runs inline on the search's own thread (no queue
    hop, no cross-thread handoff): the solo path keeps today's
    dispatch order and wall time;
  - **cancellation** — :meth:`SearchFuture.cancel` drains the search's
    queued chunks, fails its next dispatch with
    :class:`SearchCancelledError` (never retried, never host-fallback
    re-run), releases the tenant's data-plane charge when its last
    search ends, and leaves the checkpoint journal resumable.

Everything downstream of the dispatch queue is per-search and rides
along unchanged at LaunchItem granularity: the fault supervisor's
retry/bisection, the geometry planner, the checkpoint journal and the
program store all keep their contracts, so every submitted search's
``cv_results_`` is bit-exact with its solo run.

Observability: the per-search ``search_report["scheduler"]`` block
(schema pinned in ``obs.metrics.SCHEDULER_BLOCK_SCHEMA``) records
queue waits, the interleave fraction and the measured per-tenant
shares; ``serve.submit`` / ``sched.queue.wait`` / ``sched.dispatch``
spans land on the trace timeline.

NOTE on per-search counters under concurrency: the data-plane byte
totals, persistent-cache hit counts and ``n_compiles`` are process-
global deltas, so concurrent searches' traffic may bleed into each
other's numbers — scores never do.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from spark_sklearn_tpu.obs import heartbeat as _heartbeat
from spark_sklearn_tpu.obs import telemetry as _telemetry
from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs.trace import get_tracer, set_correlation
from spark_sklearn_tpu.parallel.pipeline import FusedLaunch, LaunchItem
from spark_sklearn_tpu.serve.journal import JOURNAL_STATE_BY_HANDLE_STATE
from spark_sklearn_tpu.utils.locks import named_rlock

logger = get_logger(__name__)

__all__ = [
    "AdmissionError",
    "SearchCancelledError",
    "SearchExecutor",
    "SearchFuture",
    "SearchHandle",
    "current_binding",
    "report_block",
    "resolve_fusion",
    "resolve_fusion_max_width",
    "resolve_fusion_window_ms",
]

DEFAULT_TENANT = "default"

#: handle.queue_waits is bounded so a million-chunk search cannot grow
#: an unbounded list; the mean/max aggregates keep counting past it
_MAX_WAIT_SAMPLES = 4096

#: bounded global dispatch journal (handle id, tenant, cost) — the
#: fair-share tests read share ratios from its prefix
_MAX_DISPATCH_LOG = 4096


class AdmissionError(RuntimeError):
    """A submission was rejected by admission control: the executor's
    running slots (``max_concurrent_searches``) AND its bounded waiting
    line (``max_queued_searches``) are full, the executor is shutting
    down, or predictive admission (``TpuConfig.admission_mode=
    "predictive"``) priced the search out before any device work.
    Resubmit later, or raise the limits.

    Machine-readable fields: ``reason`` ("queue-full" | "shutdown" |
    "footprint" | "deadline-unmeetable"), ``retry_after_s`` (a hint,
    None when resubmitting will not help by itself), ``tenant``, and
    the queue/slot state at rejection (``n_active`` / ``n_pending`` /
    ``max_concurrent`` / ``max_queued``)."""

    def __init__(self, message: str, *, reason: str = "",
                 retry_after_s: Optional[float] = None,
                 tenant: Optional[str] = None, n_active: int = 0,
                 n_pending: int = 0, max_concurrent: int = 0,
                 max_queued: int = 0):
        super().__init__(message)
        self.reason = reason
        self.retry_after_s = retry_after_s
        self.tenant = tenant
        self.n_active = int(n_active)
        self.n_pending = int(n_pending)
        self.max_concurrent = int(max_concurrent)
        self.max_queued = int(max_queued)


class SearchCancelledError(RuntimeError):
    """The search was cancelled via :meth:`SearchFuture.cancel`.
    Raised from :meth:`SearchFuture.result` and from the cancelled
    search's next dispatch.  Completed chunks stay durable in the
    checkpoint journal, so an identically-configured search resumes
    them."""

    #: consumed by faults.LaunchSupervisor: cancellation is an
    #: instruction, not a fault — no retry, no recovery, no journal
    _sst_cancelled = True


# ---------------------------------------------------------------------------
# Thread-local binding: which (executor, handle) the current thread's
# search runs under.  Set by the executor's worker threads; consulted
# by grid._run_groups to route LaunchItems and tag data-plane uploads.
# ---------------------------------------------------------------------------

_TLS = threading.local()


@dataclasses.dataclass(frozen=True)
class _Binding:
    executor: "SearchExecutor"
    handle: "SearchHandle"

    @property
    def tenant(self) -> str:
        return self.handle.tenant


def current_binding() -> Optional[_Binding]:
    """The executor binding of the calling thread's search, or None
    when the search runs standalone (a plain ``fit()`` call)."""
    return getattr(_TLS, "binding", None)


def resolve_tenant(config) -> str:
    """Tenant id under ``config``: ``TpuConfig.tenant``, else the
    ``SST_TENANT`` env var, else ``"default"``."""
    t = getattr(config, "tenant", None)
    if t:
        return str(t)
    return os.environ.get("SST_TENANT") or DEFAULT_TENANT


def resolve_weight(config) -> float:
    """Fair-share weight under ``config``: ``TpuConfig.tenant_weight``,
    else the ``SST_TENANT_WEIGHT`` env var, else 1.0."""
    w = getattr(config, "tenant_weight", None)
    if w is None:
        env = os.environ.get("SST_TENANT_WEIGHT")
        if env:
            try:
                w = float(env)
            except ValueError:
                w = None
    return max(float(w), 1e-6) if w is not None else 1.0


def resolve_fusion(config) -> bool:
    """Cross-search launch fusion under ``config``:
    ``TpuConfig.fusion``, else the ``SST_FUSION`` env var, else True.
    False is the exact escape hatch — every chunk dispatches solo."""
    f = getattr(config, "fusion", None)
    if f is not None:
        return bool(f)
    env = os.environ.get("SST_FUSION", "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return False
    return True


def resolve_fusion_window_ms(config) -> float:
    """Fusion peer-wait window (milliseconds):
    ``TpuConfig.fusion_window_ms``, else ``SST_FUSION_WINDOW_MS``,
    else 5.0.  0 disables the hold (fusion still coalesces peers that
    are ALREADY queued when a fusable head dispatches)."""
    v = getattr(config, "fusion_window_ms", None)
    if v is None:
        env = os.environ.get("SST_FUSION_WINDOW_MS")
        if env:
            try:
                v = float(env)
            except ValueError:
                v = None
    return max(0.0, float(v)) if v is not None else 5.0


def resolve_fusion_max_width(config) -> int:
    """Fused-launch real-lane cap: ``TpuConfig.fusion_max_width``,
    else ``SST_FUSION_MAX_WIDTH``, else 0 = bounded only by the member
    plans' own width caps."""
    v = getattr(config, "fusion_max_width", None)
    if v is None:
        env = os.environ.get("SST_FUSION_MAX_WIDTH")
        if env:
            try:
                v = int(env)
            except ValueError:
                v = None
    return max(0, int(v)) if v is not None else 0


class SearchHandle:
    """Executor-side state of one submitted search.  Mutable counters
    are owned by the executor's lock; readers snapshot through
    :meth:`SearchExecutor.search_block` / :meth:`SearchFuture.progress`.
    """

    def __init__(self, hid: str, tenant: str, weight: float,
                 exclusive: bool = False):
        self.id = hid
        self.tenant = tenant
        self.weight = weight
        #: wants_float64 searches flip the process-wide jax x64 flag,
        #: so they are scheduled exclusively (no concurrent searches)
        self.exclusive = exclusive
        self.cancelled = False
        self.state = "queued"      # queued|running|done|failed|cancelled
        self.n_dispatched = 0      # chunks dispatched (routed + fastpath)
        self.n_fastpath = 0        # single-search inline dispatches
        self.n_interleaved = 0     # dispatches preceded by another search
        self.cost_dispatched = 0   # task units dispatched
        self.inflight = 0          # chunks dispatched, not yet finalized
        self.planned = 0           # live chunk estimate (progress())
        #: successive-halving view (SearchExecutor.note_rung): current
        #: rung index and the surviving-candidate fraction — the
        #: tenant's EFFECTIVE in-flight cap scales by the fraction, so
        #: a halving search's device claim shrinks as rungs retire
        #: candidates instead of holding rung-0's reservation
        self.rung = -1             # -1 = not a halving search
        self.rung_frac = 1.0
        #: bounded {tenant, wait_s} records — tenant-stamped so samples
        #: merged across concurrent searches still attribute per tenant
        self.queue_waits: List[Dict[str, Any]] = []
        self.queue_wait_s = 0.0
        self.queue_wait_max_s = 0.0
        self.t_start: Optional[float] = None
        self.t_end: Optional[float] = None
        #: perf_counter instant the search's deadline expires, stamped
        #: at SUBMIT when TpuConfig.search_deadline_s is set — queue
        #: wait counts against the budget, and grid's protection
        #: context reads this instead of starting its own clock
        self.t_deadline: Optional[float] = None
        #: per-tenant dispatched-cost snapshot at search start — the
        #: window the report's tenant shares are measured over
        self.cost_window_before: Dict[str, int] = {}
        self.tenant_shares: Dict[str, float] = {}
        self.share_frac = 0.0
        #: cross-search launch fusion counters (owned by the executor
        #: lock like every counter above; reported only when fusion is
        #: resolved ON, so fusion=False blocks stay byte-identical)
        self.n_fused = 0             # dispatches served by a fused launch
        self.lanes_donated = 0       # real peer lanes this search's fused
        #                              heads carried for other searches
        self.lanes_borrowed = 0      # own real lanes run in peers' launches
        self.fusion_saved_launches = 0  # solo launches fusing avoided


class _Tenant:
    """One tenant's scheduler state: its FIFO request queue, DRR
    deficit, and in-flight chunk count across all of its searches."""

    __slots__ = ("name", "weight", "deficit", "queue", "inflight",
                 "cost_total")

    def __init__(self, name: str, weight: float):
        self.name = name
        self.weight = weight
        self.deficit = 0.0
        self.queue: deque = deque()
        self.inflight = 0
        self.cost_total = 0


@dataclasses.dataclass
class _Request:
    """One chunk dispatch waiting in the fair-share queue."""

    handle: SearchHandle
    item: LaunchItem
    launch: Callable[[Any], Any]
    payload: Any
    cost: int
    state: Dict[str, Any]          # per-item wrapper state
    t_enqueued: float
    t_dequeued: float = 0.0
    reply: Any = None              # threading.Event-backed _Reply


class _Reply:
    """Minimal one-shot future for a dispatch reply (stdlib Future
    would work, but this keeps the executor's locking story explicit
    and exception-type-transparent)."""

    __slots__ = ("_evt", "_out", "_exc")

    def __init__(self):
        self._evt = threading.Event()
        self._out = None
        self._exc: Optional[BaseException] = None

    def set_result(self, out) -> None:
        self._out = out
        self._evt.set()

    def set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._evt.set()

    def result(self):
        self._evt.wait()
        if self._exc is not None:
            raise self._exc
        return self._out


class SearchFuture:
    """Handle to a submitted search: ``result()`` blocks for the
    fitted estimator, ``cancel()`` aborts, ``progress()`` reports the
    live chunk-dispatch state."""

    def __init__(self, executor: "SearchExecutor", handle: SearchHandle,
                 search):
        self._executor = executor
        self._handle = handle
        self._search = search
        self._done = threading.Event()
        self._exc: Optional[BaseException] = None

    # -- executor side ---------------------------------------------------
    def _finish(self, exc: Optional[BaseException]) -> None:
        self._exc = exc
        self._done.set()

    # -- consumer side ---------------------------------------------------
    @property
    def handle_id(self) -> str:
        """The executor's handle id (``tenant/sN``) — what the service
        journal links a recovered entry's successor to."""
        return self._handle.id

    def done(self) -> bool:
        return self._done.is_set()

    def cancelled(self) -> bool:
        return self._handle.state == "cancelled"

    def result(self, timeout: Optional[float] = None):
        """The fitted search estimator.  Raises whatever ``fit``
        raised; a cancelled search raises
        :class:`SearchCancelledError`."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"search {self._handle.id!r} not done after {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._search

    def exception(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"search {self._handle.id!r} not done after {timeout}s")
        return self._exc

    def cancel(self) -> bool:
        """Cancel the search: queued chunks drain immediately, the next
        dispatch raises, queued-but-unstarted searches never start.
        Returns False when the search already finished."""
        return self._executor.cancel(self._handle)

    def progress(self) -> Dict[str, Any]:
        """Live progress: state, chunks dispatched, the planned live-
        chunk estimate (known once geometry is planned) and their
        ratio.  With the in-flight heartbeat on
        (``TpuConfig.heartbeat`` / ``SST_HEARTBEAT``) a ``heartbeat``
        sub-dict adds intra-segment ``steps_done/steps_total`` and a
        blended ETA, so a scanned rung no longer freezes progress for
        its whole multi-minute launch."""
        return self._executor.progress(self._handle)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class SearchExecutor:
    """The session-owned async search service.  See the module
    docstring for the architecture; the public surface is
    :meth:`submit` (-> :class:`SearchFuture`), :meth:`wrap_items`
    (consumed by ``grid._run_groups``), :meth:`search_block` /
    :func:`report_block` (the ``search_report["scheduler"]`` block)
    and :meth:`shutdown`."""

    def __init__(self, config=None, name: str = "sst-serve"):
        self.config = config
        self.name = name
        # reentrant: helpers called under the lock (start/accounting)
        # take it again themselves, so each is safe standalone
        self._lock = named_rlock("serve.SearchExecutor._lock")
        self._work = threading.Event()      # a queue may be non-empty
        self._gate = threading.Event()      # cleared = paused (tests/drain)
        self._gate.set()
        self._stop = False
        #: set at shutdown START: rejects new submissions immediately
        #: while the dispatch loop keeps serving active searches'
        #: queued chunks until they finish (_stop ends the loop)
        self._closing = False
        self._thread: Optional[threading.Thread] = None
        self._tenants: Dict[str, _Tenant] = {}
        self._rr = 0                        # DRR rotation cursor
        self._seq = 0
        self._active: List[SearchHandle] = []
        self._pending: deque = deque()      # (handle, future, thunk)
        self._workers: List[threading.Thread] = []
        self._last_handle: Optional[SearchHandle] = None
        self._cost_by_tenant: Dict[str, int] = {}
        self._dispatch_log: deque = deque(maxlen=_MAX_DISPATCH_LOG)
        #: recent completed-search walls (seconds) — predictive
        #: admission's queue-wait forecast divides the waiting line by
        #: running-slot count and multiplies by the p50 of these
        self._recent_walls: deque = deque(maxlen=64)
        self._quantum = max(1, int(getattr(config, "scheduler_quantum",
                                           64) or 64))
        self._max_concurrent = max(1, int(getattr(
            config, "max_concurrent_searches", 8) or 8))
        self._max_queued = max(0, int(getattr(
            config, "max_queued_searches", 16) or 0))
        self._tenant_cap = max(0, int(getattr(
            config, "tenant_max_inflight", 0) or 0))
        #: cross-search launch fusion (ISSUE 14): same-program chunks
        #: from different searches coalesce into one wide launch
        self._fusion = resolve_fusion(config)
        self._fusion_window_s = resolve_fusion_window_ms(config) / 1000.0
        self._fusion_max_width = resolve_fusion_max_width(config)
        #: hint from _pop_next to _loop: a fusable head is being held
        #: inside its fusion window — sleep a sliver, don't hot-spin
        self._fuse_defer = False
        #: durable service journal (serve/journal.py), bound by the
        #: session via attach_journal.  None (the default) is the
        #: exact no-op: every _journal_note_* early-outs, zero writes.
        self._journal = None

    # -- durable journal hooks -------------------------------------------
    def attach_journal(self, journal) -> None:
        """Bind the session's :class:`~spark_sklearn_tpu.serve.journal.
        ServiceJournal`.  All journal notes are called OUTSIDE
        ``self._lock`` (the journal has its own lock and fsyncs on
        append — never under the scheduler's lock)."""
        self._journal = journal

    def _journal_note_submitted(self, handle, search, X, y, cfg,
                                recovered_from: str = "") -> None:
        # caller does NOT hold self._lock
        if self._journal is None:
            return
        from spark_sklearn_tpu.serve import journal as _svc_journal
        try:
            est = getattr(search, "estimator", None)
            family = type(est).__name__ if est is not None \
                else type(search).__name__
            digest = _svc_journal.submission_digest(search, X, y)
            fp = _svc_journal.data_fingerprint(X, y)
        except (TypeError, ValueError) as exc:
            # non-array data the fingerprint cannot hash: journal the
            # submission anyway (state tracking still recovers it),
            # just without a verifiable binding
            logger.warning("service journal: fingerprint failed for "
                           "%s (%r)", handle.id, exc, handle=handle.id)
            family, digest, fp = type(search).__name__, "", ""
        self._journal.record_submission(
            handle.id, tenant=handle.tenant, weight=handle.weight,
            family=family, structure_digest=digest,
            data_fingerprint=fp,
            checkpoint_dir=getattr(cfg, "checkpoint_dir", None) or "",
            config=cfg, recovered_from=recovered_from)

    def _journal_note_state(self, handle, state: str, **extra) -> None:
        # caller does NOT hold self._lock
        if self._journal is None:
            return
        self._journal.record_transition(handle.id, state, **extra)

    # -- submission ------------------------------------------------------
    def submit(self, search, X, y=None, fit_params: Optional[dict] = None,
               tenant: Optional[str] = None,
               weight: Optional[float] = None,
               recovered_from: str = "") -> SearchFuture:
        """Run ``search.fit(X, y, **fit_params)`` on a worker thread
        under this executor's fair-share scheduling and return a
        :class:`SearchFuture`.  Tenant identity and weight resolve from
        the search's own config (or the executor's) unless passed
        explicitly.  Raises :class:`AdmissionError` when both the
        running slots and the bounded waiting line are full."""
        cfg = getattr(search, "config", None) or self.config
        tenant = tenant or resolve_tenant(cfg)
        weight = weight if weight is not None else resolve_weight(cfg)
        exclusive = self._needs_exclusive(search)
        predictive = str(getattr(cfg, "admission_mode", "static")
                         or "static") == "predictive"
        deadline_s = getattr(cfg, "search_deadline_s", None)
        # the footprint check prices the search against the HBM budget
        # with the memory ledger's model — computed OUTSIDE self._lock
        # (the ledger has its own lock) and before any state mutation
        footprint_exc = self._admission_footprint_check(
            search, X, y, cfg, tenant) if predictive else None
        try:
            with get_tracer().span("serve.submit", tenant=tenant):
                with self._lock:
                    if self._stop or self._closing:
                        raise AdmissionError(
                            "executor is shut down; no new searches",
                            reason="shutdown", tenant=tenant,
                            n_active=len(self._active),
                            n_pending=len(self._pending),
                            max_concurrent=self._max_concurrent,
                            max_queued=self._max_queued)
                    if footprint_exc is not None:
                        raise footprint_exc
                    queue_now = bool(self._pending) or \
                        not self._can_start_new(exclusive)
                    if queue_now and predictive and deadline_s:
                        # SLO forecast: a search that would provably
                        # blow its whole deadline waiting in line is
                        # refused NOW, not after queueing device-less
                        # for deadline_s and shedding everything
                        forecast = self._queue_wait_forecast_locked()
                        if forecast is not None and \
                                forecast > float(deadline_s):
                            raise AdmissionError(
                                f"admission deferred for tenant "
                                f"{tenant!r}: forecast queue wait "
                                f"{forecast:.1f}s exceeds "
                                f"search_deadline_s={deadline_s:g}s",
                                reason="deadline-unmeetable",
                                retry_after_s=round(forecast, 3),
                                tenant=tenant,
                                n_active=len(self._active),
                                n_pending=len(self._pending),
                                max_concurrent=self._max_concurrent,
                                max_queued=self._max_queued)
                    if queue_now and \
                            len(self._pending) >= self._max_queued:
                        # reject BEFORE any state mutation: a refused
                        # submission must not bump the sequence or
                        # rewrite its tenant's live fair-share weight
                        raise AdmissionError(
                            f"admission rejected for tenant {tenant!r}: "
                            f"{len(self._active)} running (max "
                            f"{self._max_concurrent}) and "
                            f"{len(self._pending)} queued (max "
                            f"{self._max_queued})",
                            reason="queue-full",
                            retry_after_s=self._wall_p50_locked(),
                            tenant=tenant,
                            n_active=len(self._active),
                            n_pending=len(self._pending),
                            max_concurrent=self._max_concurrent,
                            max_queued=self._max_queued)
                    self._seq += 1
                    hid = f"{tenant}/s{self._seq}"
                    handle = SearchHandle(hid, tenant, weight,
                                          exclusive=exclusive)
                    if deadline_s:
                        # the deadline clock starts at SUBMIT: queue
                        # wait spends the same budget device time does
                        handle.t_deadline = time.perf_counter() \
                            + float(deadline_s)
                    future = SearchFuture(self, handle, search)
                    handle.future = future
                    t = self._tenants.get(tenant)
                    if t is None:
                        t = self._tenants[tenant] = _Tenant(tenant,
                                                            weight)
                    else:
                        t.weight = weight  # latest ADMITTED search wins
                    thunk = self._make_worker(handle, future, search,
                                              X, y,
                                              dict(fit_params or {}))
                    # FIFO honesty: while anything is already waiting,
                    # new arrivals wait behind it — otherwise a pending
                    # exclusive (x64) search could be starved forever
                    # by a stream of immediately-startable submissions
                    if queue_now:
                        self._pending.append((handle, future, thunk))
                        logger.info(
                            "search %s queued (tenant=%s, %d running)",
                            hid, tenant, len(self._active),
                            handle=hid, tenant=tenant)
                    else:
                        self._start_locked(handle, thunk)
        except AdmissionError as exc:
            # telemetry outside the lock (hook discipline); the
            # rejection carries its machine-readable reason
            _telemetry.note_admission("rejected", tenant,
                                      getattr(exc, "reason", "") or "")
            # the shed submission never got a handle: journal the
            # refusal itself so the workload record is complete
            if self._journal is not None:
                self._journal.record_transition(
                    f"{tenant}/rejected", "shed", tenant=tenant,
                    reason=getattr(exc, "reason", "") or "")
            raise
        # durable WAL entry BEFORE the future is handed back: a crash
        # after this point leaves a non-terminal record recover() owes
        self._journal_note_submitted(handle, search, X, y, cfg,
                                     recovered_from=recovered_from)
        _telemetry.note_admission("queued" if queue_now else "admitted",
                                  tenant)
        return future

    def _admission_footprint_check(self, search, X, y, cfg,
                                   tenant: str) -> Optional[AdmissionError]:
        """Predictive admission's HBM pricing: model the search's
        MINIMUM feasible footprint (broadcast residents + one single-
        candidate chunk, scaled by the ledger's learned safety margin)
        and refuse when even that cannot fit ``hbm_budget_bytes`` — no
        geometry could launch it, so rejecting costs zero device work.
        Returns the error to raise, or None to admit."""
        from spark_sklearn_tpu.obs import memory as _obs_memory
        from spark_sklearn_tpu.parallel import memledger as _memledger
        ledger = _memledger.ledger_for(cfg)
        if ledger is None:
            return None
        budget = _obs_memory.resolve_hbm_budget(cfg)
        if not budget:
            return None
        grid = getattr(search, "param_grid", None)
        if not isinstance(grid, dict):
            grid = getattr(search, "param_distributions", None)
        if not isinstance(grid, dict) or X is None:
            return None
        import numpy as np
        dyn: Dict[str, Any] = {}
        for name, vals in grid.items():
            try:
                arr = np.asarray(list(vals)
                                 if not hasattr(vals, "dtype") else vals)
            # non-materializable values (e.g. scipy distributions)
            # just mean this param stages nothing predictable — the
            # admission probe models what it can, never fails a
            # submit; nothing has launched yet, so the fault taxonomy
            # does not apply
            # sstlint: disable=swallowed-exception,launch-except-taxonomy
            except Exception:
                continue
            if arr.dtype.kind in "fiub":
                dyn[name] = arr[:1]
        cv = getattr(search, "cv", None)
        n_folds = cv if isinstance(cv, int) else \
            int(getattr(cv, "n_splits", 0) or 0) or 5
        n = int(getattr(X, "shape", (len(X),))[0])
        fp = _memledger.model_group_footprint(
            dyn, 1, n_folds, task_batched=True, n_samples=n,
            return_train=bool(getattr(search, "return_train_score",
                                      False)))
        # true dataset bytes: dense nbytes, or the CSR component sum
        # for sparse X (scipy sparse has no .nbytes — the old getattr
        # spelling priced it at zero and dense-equivalent pricing would
        # over-reject by orders of magnitude)
        x_bytes = _memledger.dataset_nbytes(X)
        y_bytes = _memledger.dataset_nbytes(y)
        from spark_sklearn_tpu.search import stream as _stream
        if _stream.resolve_data_mode(cfg) == "stream":
            # streamed submission: X is never wholly resident — price
            # the double-buffered shard slab the stream planner will
            # actually keep on device
            x_bytes = min(x_bytes,
                          2 * _stream.resolve_shard_bytes(cfg))
        # broadcast residents: X/y replicas + the base fold masks
        # (train + test, int32) the data plane keeps device-resident
        resident = x_bytes + y_bytes + 2 * n_folds * n * 4
        margin = max(1.0, float(getattr(ledger, "safety_margin", 1.0)))
        modeled = int((resident + fp["chunk_bytes"]) * margin)
        if modeled <= int(budget):
            return None
        with self._lock:
            state = (len(self._active), len(self._pending))
        return AdmissionError(
            f"admission rejected for tenant {tenant!r}: modeled "
            f"footprint {modeled} byte(s) (residents {resident} + "
            f"minimum chunk {fp['chunk_bytes']}, margin "
            f"{margin:.2f}) exceeds hbm_budget_bytes={int(budget)}",
            reason="footprint", retry_after_s=None, tenant=tenant,
            n_active=state[0], n_pending=state[1],
            max_concurrent=self._max_concurrent,
            max_queued=self._max_queued)

    def _wall_p50_locked(self) -> Optional[float]:
        # caller holds the lock
        if not self._recent_walls:
            return None
        vals = sorted(self._recent_walls)
        return round(float(vals[len(vals) // 2]), 3)

    def _queue_wait_forecast_locked(self) -> Optional[float]:
        """p50-of-recent-walls x the waiting line's depth in running-
        slot waves — None until at least one search completed (no
        data beats a wrong forecast)."""
        p50 = self._wall_p50_locked()
        if p50 is None:
            return None
        waves = -(-(len(self._pending) + 1) // max(
            1, self._max_concurrent))
        return p50 * waves

    def _needs_exclusive(self, search) -> bool:
        """wants_float64 families flip the process-global jax x64 flag
        for their whole fit — concurrent searches would trace under the
        wrong dtype, so they schedule exclusively."""
        if getattr(search, "backend", None) == "host":
            return False
        est = getattr(search, "estimator", None)
        if est is None:
            return False
        try:
            from spark_sklearn_tpu.models.base import resolve_family
            fam = resolve_family(est)
        # resolution failing here just means the search decides its own
        # tier later; non-exclusive is the safe default because only
        # RESOLVED wants_float64 families touch the x64 flag — this is
        # an admission-time probe, not a launch failure to classify
        # sstlint: disable=swallowed-exception,launch-except-taxonomy
        except Exception:
            return False
        return bool(getattr(fam, "wants_float64", False))

    def _apply_tenant_quota(self, cfg, tenant: str) -> None:
        quota = int(getattr(cfg, "dataplane_tenant_bytes", 0) or 0)
        if quota <= 0:
            return
        from spark_sklearn_tpu.parallel import dataplane as _dataplane
        plane = _dataplane.plane_for(cfg)
        if plane is not None:
            plane.set_tenant_quota(tenant, quota)

    def _can_start(self, handle: SearchHandle) -> bool:
        return self._can_start_new(handle.exclusive)

    def _can_start_new(self, exclusive: bool) -> bool:
        # caller holds the lock
        if any(h.exclusive for h in self._active):
            return False
        if exclusive:
            return not self._active
        return len(self._active) < self._max_concurrent

    def _start_locked(self, handle: SearchHandle, thunk) -> None:
        with self._lock:
            self._active.append(handle)
            handle.state = "running"
            handle.t_start = time.perf_counter()
            handle.cost_window_before = dict(self._cost_by_tenant)
            worker = threading.Thread(
                target=thunk, name=f"{self.name}-{handle.id}",
                daemon=True)
            self._workers.append(worker)
        worker.start()

    def _make_worker(self, handle, future, search, X, y, fit_params):
        cfg = getattr(search, "config", None) or self.config

        def run():
            # durable "running" transition first thing on the worker
            # thread — outside the executor lock, before any fit work
            self._journal_note_state(handle, "running")
            _TLS.binding = _Binding(self, handle)
            # tenant/handle correlation: stamped onto every span and
            # structured log record this thread (and the pipeline
            # workers it spawns) emits, so a multi-tenant trace or
            # flight bundle attributes each event to its search
            set_correlation({"tenant": handle.tenant,
                             "handle": handle.id})
            exc: Optional[BaseException] = None
            try:
                if handle.cancelled:
                    raise SearchCancelledError(
                        f"search {handle.id!r} cancelled before start")
                # tenant byte quota in the device data plane — applied
                # at worker START so searches admitted via the waiting
                # line get it too (the plane has its own lock)
                self._apply_tenant_quota(cfg, handle.tenant)
                search.fit(X, y, **fit_params)
            # the worker is a thread boundary: EVERY failure (cancel
            # included) must marshal to the future's consumer via
            # future._finish below instead of dying on a daemon thread
            # — the fault taxonomy already ran inside fit's supervisor
            # sstlint: disable=broad-except-swallow,launch-except-taxonomy
            except BaseException as e:
                exc = e
            finally:
                _TLS.binding = None
                set_correlation(None)
                if exc is None:
                    # surface the search doctor's one-line diagnosis on
                    # the serving channel, so a fleet operator sees the
                    # critical path without opening the report
                    attr = (getattr(search, "search_report", None)
                            or {}).get("attribution") or {}
                    if attr.get("verdict"):
                        logger.info(
                            "search %s doctor: %s", handle.id,
                            attr["verdict"], handle=handle.id,
                            tenant=handle.tenant,
                            dominant=attr.get("dominant", ""),
                            regression=(attr.get("regression") or {})
                            .get("status", ""))
                self._finish_search(handle, exc)
                future._finish(exc)
        return run

    def _finish_search(self, handle: SearchHandle,
                       exc: Optional[BaseException]) -> None:
        release_tenant = None
        with self._lock:
            if handle in self._active:
                self._active.remove(handle)
            handle.t_end = time.perf_counter()
            if exc is None and handle.t_start is not None:
                # completed walls feed the admission SLO forecast
                self._recent_walls.append(handle.t_end - handle.t_start)
            if exc is None:
                # includes a cancel that lost the race to a completed
                # fit: the results are valid, so the future resolves
                handle.state = "done"
            elif isinstance(exc, SearchCancelledError):
                handle.state = "cancelled"
            elif handle.state != "cancelled":
                handle.state = "failed"
            t = self._tenants.get(handle.tenant)
            if t is not None and handle.inflight:
                t.inflight = max(0, t.inflight - handle.inflight)
                handle.inflight = 0
            # prune finished worker threads: a long-lived serving
            # session must not accumulate a Thread object per
            # historical search
            self._workers = [w for w in self._workers if w.is_alive()]
            self._update_shares(handle)
            # a cancelled tenant with no other live searches releases
            # its data-plane charge (outside the lock, below)
            if handle.state == "cancelled" and not any(
                    h.tenant == handle.tenant
                    for h in self._active) and not any(
                    p[0].tenant == handle.tenant for p in self._pending):
                release_tenant = handle.tenant
            while self._pending and self._can_start(self._pending[0][0]):
                nxt_handle, _, nxt_thunk = self._pending.popleft()
                if nxt_handle.cancelled:
                    continue
                self._start_locked(nxt_handle, nxt_thunk)
            self._work.set()    # re-evaluate runnability (caps freed)
        if release_tenant is not None:
            from spark_sklearn_tpu.parallel import dataplane as _dataplane
            plane = _dataplane.get_dataplane()
            freed = plane.release_tenant(release_tenant)
            logger.info("tenant %s: released %d data-plane byte(s) on "
                        "cancellation", release_tenant, freed,
                        tenant=release_tenant)
        # terminal transition in the WAL (outside the lock): after this
        # line a restart owes this search nothing
        self._journal_note_state(
            handle, JOURNAL_STATE_BY_HANDLE_STATE.get(handle.state,
                                                      handle.state))
        logger.info("search %s %s (%d chunk(s) dispatched, %d fastpath)",
                    handle.id, handle.state, handle.n_dispatched,
                    handle.n_fastpath, handle=handle.id,
                    state=handle.state)

    def _update_shares(self, handle: SearchHandle) -> None:
        # caller holds the lock; window = [search start, now]
        before = handle.cost_window_before or {}
        deltas = {t: c - before.get(t, 0)
                  for t, c in self._cost_by_tenant.items()}
        deltas = {t: c for t, c in deltas.items() if c > 0}
        total = sum(deltas.values())
        if total > 0:
            handle.tenant_shares = {
                t: round(c / total, 4) for t, c in sorted(deltas.items())}
            handle.share_frac = round(
                handle.cost_dispatched / total, 4)

    # -- cancellation ----------------------------------------------------
    def cancel(self, handle: SearchHandle) -> bool:
        drained: List[_Request] = []
        with self._lock:
            if handle.state in ("done", "failed", "cancelled"):
                return False
            handle.cancelled = True
            was_queued = handle.state == "queued"
            handle.state = "cancelled"
            t = self._tenants.get(handle.tenant)
            if t is not None:
                keep = deque()
                for req in t.queue:
                    # queued requests are not yet in flight (the cap
                    # counts dispatched-unfinalized chunks), so drain
                    # needs no in-flight adjustment
                    (drained if req.handle is handle else keep).append(req)
                t.queue = keep
            if was_queued:
                self._pending = deque(
                    p for p in self._pending if p[0] is not handle)
            self._work.set()
        exc = SearchCancelledError(
            f"search {handle.id!r} was cancelled "
            f"({len(drained)} queued chunk(s) drained)")
        for req in drained:
            req.reply.set_exception(exc)
        if was_queued:
            # never started: no worker will ever _finish it
            self._finish_search(handle, exc)
            handle.future._finish(exc)
        logger.info("search %s cancelled (%d queued chunk(s) drained)",
                    handle.id, len(drained), handle=handle.id)
        # black box: a cancellation is an operator-visible incident —
        # bundle the scheduler state + recent events for the postmortem
        # (dir checked FIRST: without one, no state is even copied)
        if _telemetry.resolve_flight_dir(self.config) is not None:
            rec = _telemetry.flight_recorder()
            sched = {**self.stats(),
                     "dispatch_log": self.dispatch_log()[-256:]}
            ctx = {"handle": handle.id, "tenant": handle.tenant,
                   "drained": len(drained)}
            if handle.t_deadline is not None and \
                    time.perf_counter() >= handle.t_deadline:
                # a cancel AFTER deadline expiry is a protection
                # verdict, not an operator whim: tag the bundle so the
                # postmortem tooling groups it with shed/quarantine
                rec.protection_dump("deadline-expired",
                                    reason="cancelled",
                                    config=self.config, scheduler=sched,
                                    context=ctx)
            else:
                rec.dump("cancelled", config=self.config,
                         scheduler=sched, context=ctx)
        return True

    def progress(self, handle: SearchHandle) -> Dict[str, Any]:
        # the heartbeat hub owns its own named lock — query it BEFORE
        # taking ours (no cross-module lock nesting).  None (heartbeat
        # off / no scanned segments yet) leaves the dict unchanged, so
        # the pre-heartbeat progress shape is byte-identical.
        hb = _heartbeat.get_hub().progress_for_handle(handle.id)
        with self._lock:
            frac = (min(1.0, handle.n_dispatched / handle.planned)
                    if handle.planned else None)
            out = {
                "state": handle.state,
                "tenant": handle.tenant,
                "dispatched": handle.n_dispatched,
                "planned": handle.planned,
                "frac": frac,
            }
            if handle.rung >= 0:
                out["rung"] = handle.rung
                out["rung_frac"] = round(handle.rung_frac, 4)
            if hb is not None:
                # intra-segment steps_done/steps_total + blended ETA:
                # the scanned rung no longer freezes progress for a
                # whole multi-minute launch
                out["heartbeat"] = hb
            return out

    def note_planned(self, handle: SearchHandle, n: int) -> None:
        """Live-chunk estimate from the search's geometry plan, for
        :meth:`SearchFuture.progress`."""
        with self._lock:
            handle.planned = int(n)

    def note_rung(self, handle: SearchHandle, itr: int,
                  n_candidates: int, frac: float) -> None:
        """A halving search's rung transition (search/halving.py):
        records the rung index and surviving-candidate fraction.  The
        fraction scales the tenant's effective in-flight chunk cap in
        :meth:`_pop_next` — as rungs retire candidates the search's
        claim on the shared device shrinks with them, freeing dispatch
        slots for other tenants mid-search instead of at search end."""
        with self._lock:
            handle.rung = int(itr)
            handle.rung_frac = min(1.0, max(float(frac), 0.0)) or 1.0
        logger.info(
            "search %s entered halving rung %d (%d candidate(s), "
            "share %.3f)", handle.id, itr, n_candidates,
            handle.rung_frac, handle=handle.id, rung=int(itr))

    def _effective_cap(self, tenant_name: str) -> int:
        """The tenant's in-flight chunk cap, scaled by its active
        halving searches' surviving fraction (caller holds the lock).
        0 = unbounded.  Any active NON-halving search of the tenant
        pins the fraction to 1.0 — the tenant-wide cap must never
        starve an exhaustive search because a sibling halving search
        reached a late rung."""
        cap = self._tenant_cap
        if not cap:
            return 0
        frac = 0.0
        seen = False
        for h in self._active:
            if h.tenant != tenant_name:
                continue
            seen = True
            frac = max(frac, 1.0 if h.rung < 0 else h.rung_frac)
            if frac >= 1.0:
                return cap
        if not seen:
            return cap
        return max(1, int(math.ceil(cap * frac)))

    # -- item wrapping (the grid._run_groups seam) -----------------------
    def wrap_items(self, handle: SearchHandle, items):
        """Wrap a search's LaunchItem stream so every dispatch routes
        through the shared fair-share queue (lazily — the pipeline's
        stage-ahead behavior is preserved).  Applied UNDER the fault
        supervisor's wrapper, so retries re-enter the queue and one
        tenant's recovery runs on its own search's threads, never on
        the shared dispatch loop."""
        for item in items:
            yield self._wrap_one(handle, item)

    def _wrap_one(self, handle: SearchHandle,
                  item: LaunchItem) -> LaunchItem:
        inner_launch = item.launch
        inner_finalize = item.finalize
        # DRR billing is in task units: a scanned segment (kind="scan",
        # chunk_loop="scan") carries the SUM of its member chunks' real
        # lanes in n_tasks, so its one coarse launch debits the tenant
        # exactly what the per-chunk launches it replaced would have
        cost = max(1, int(item.n_tasks or 0))
        #: first_wait = the dispatch-phase call's queue wait (the
        #: pipeline calls launch exactly once; later calls are
        #: supervisor retries whose walls land in the wait phase) —
        #: only it may be subtracted from dispatch_s.  queue_wait_s
        #: totals every attempt for the reported timings.
        state: Dict[str, Any] = {"counted": False, "queue_wait_s": 0.0,
                                 "first_wait": None}

        def routed_launch(payload, item=item):
            if handle.cancelled:
                raise SearchCancelledError(
                    f"search {handle.id!r} was cancelled")
            if self._try_fastpath(handle, cost, state):
                # single active search, empty queues: dispatch inline —
                # today's order, zero queue hops (and zero wait: a
                # later ROUTED retry must not claim the first-wait
                # slot, its wall is not in dispatch_s)
                if state["first_wait"] is None:
                    state["first_wait"] = 0.0
                self._note_dispatch_out(handle, cost, None,
                                        fastpath=True, key=item.key)
                return inner_launch(payload)
            req = _Request(handle=handle, item=item, launch=inner_launch,
                           payload=payload, cost=cost, state=state,
                           t_enqueued=time.perf_counter(), reply=_Reply())
            self._enqueue(req)
            with get_tracer().span("sched.queue.wait", key=item.key,
                                   tenant=handle.tenant):
                out = req.reply.result()
            wait = max(0.0, req.t_dequeued - req.t_enqueued)
            state["queue_wait_s"] += wait
            if state["first_wait"] is None:
                state["first_wait"] = wait
            return out

        def routed_finalize(host, tm):
            qw = state["queue_wait_s"]
            first = state["first_wait"] or 0.0
            state["queue_wait_s"] = 0.0
            state["first_wait"] = None
            if qw:
                # keep fair-share waiting out of dispatch_s — the
                # geometry cost model prices launch overhead from it,
                # and contention is not overhead of THIS launch.  Only
                # the dispatch-phase (first) wait is in dispatch_s;
                # retry waits landed in the wait phase's wall
                tm.queue_wait_s += qw
                tm.dispatch_s = max(0.0, tm.dispatch_s - first)
            self._note_done(handle, state)
            if inner_finalize is not None:
                inner_finalize(host, tm)

        return LaunchItem(
            key=item.key, launch=routed_launch, stage=item.stage,
            gather=item.gather, finalize=routed_finalize,
            group=item.group, kind=item.kind, n_tasks=item.n_tasks,
            n_chunks=item.n_chunks, wait=item.wait, bisect=item.bisect,
            host_fallback=item.host_fallback, fuse=item.fuse)

    def _try_fastpath(self, handle: SearchHandle, cost: int,
                      state: Dict[str, Any]) -> bool:
        if not self._gate.is_set():
            return False
        with self._lock:
            if self._stop or len(self._active) != 1 \
                    or self._active[0] is not handle:
                return False
            if any(t.queue for t in self._tenants.values()):
                return False
            handle.n_fastpath += 1
            self._account_dispatch(handle, cost)
            self._count_inflight(handle, state)
            return True

    def _count_inflight(self, handle: SearchHandle,
                        state: Dict[str, Any]) -> None:
        # caller holds the lock; in flight = dispatched, not finalized.
        # counted at most once per item (a supervisor retry re-routes
        # the SAME item, which is still in flight)
        if not state.get("counted"):
            state["counted"] = True
            handle.inflight += 1
            t = self._tenants.get(handle.tenant)
            if t is not None:
                t.inflight += 1

    def _account_dispatch(self, handle: SearchHandle, cost: int) -> None:
        with self._lock:
            handle.n_dispatched += 1
            handle.cost_dispatched += cost
            if self._last_handle is not None and \
                    self._last_handle is not handle:
                handle.n_interleaved += 1
            self._last_handle = handle
            t = self._tenants.get(handle.tenant)
            if t is not None:
                t.cost_total += cost
            self._cost_by_tenant[handle.tenant] = \
                self._cost_by_tenant.get(handle.tenant, 0) + cost
            self._dispatch_log.append((handle.id, handle.tenant, cost))

    def _enqueue(self, req: _Request) -> None:
        self._ensure_loop()
        with self._lock:
            if self._stop:
                # the dispatch loop is gone: failing loudly beats a
                # request that would sit unserved forever (the search's
                # supervisor surfaces this as a fatal launch error)
                req.reply.set_exception(AdmissionError(
                    "executor is shut down; chunk dispatch refused"))
                return
            t = self._tenants.get(req.handle.tenant)
            if t is None:
                t = self._tenants[req.handle.tenant] = _Tenant(
                    req.handle.tenant, req.handle.weight)
            t.queue.append(req)
            self._work.set()

    def _note_done(self, handle: SearchHandle,
                   state: Dict[str, Any]) -> None:
        with self._lock:
            if state.get("counted"):
                state["counted"] = False
                handle.inflight = max(0, handle.inflight - 1)
                t = self._tenants.get(handle.tenant)
                if t is not None:
                    t.inflight = max(0, t.inflight - 1)
                    self._work.set()   # a capped tenant may be runnable

    # -- the shared dispatch loop ----------------------------------------
    def _ensure_loop(self) -> None:
        with self._lock:
            if self._stop:
                return
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, name="sst-dispatch", daemon=True)
                self._thread.start()

    def _loop(self) -> None:
        while not self._stop:
            if not self._gate.wait(0.1):
                continue
            if not self._work.wait(0.1):
                continue
            try:
                req = self._pop_next()
                if req is not None:
                    self._run_request(req)
                else:
                    with self._lock:
                        defer = self._fuse_defer
                        self._fuse_defer = False
                    if defer:
                        # a fusable head is holding for a same-program
                        # peer inside its fusion window: sleep a sliver
                        # instead of hot-spinning on the still-set work
                        # event
                        time.sleep(0.0005)
            # defensive: a scheduler bug must degrade to a logged error
            # + the next poll, never a silently-dead dispatch loop with
            # every search hung on its reply (launch failures never
            # reach here — _run_request marshals them to the reply)
            # sstlint: disable=broad-except-swallow,launch-except-taxonomy
            except Exception as exc:
                logger.warning("dispatch loop error (%r); continuing",
                               exc)
                time.sleep(0.05)

    def _pop_next(self) -> Optional[_Request]:
        """Deficit round-robin: rotate over tenants; a visited tenant
        earns ``quantum x weight`` credit when its head does not fit,
        and dispatches while its head's cost fits the deficit."""
        with self._lock:
            names = sorted(self._tenants)
            n = len(names)
            runnable = 0
            now = time.perf_counter()
            for off in range(n):
                idx = (self._rr + off) % n
                t = self._tenants[names[idx]]
                if not t.queue:
                    continue
                cap = self._effective_cap(t.name)
                if cap and t.inflight >= cap:
                    # in-flight chunks count the head itself once it
                    # dispatches, so >= holds the cap exactly (the cap
                    # shrinks with a halving tenant's surviving rung
                    # fraction — see note_rung)
                    continue
                runnable += 1
                head = t.queue[0]
                if self._fusion and self._fusion_window_s > 0.0 \
                        and head.item.fuse is not None \
                        and not head.handle.cancelled \
                        and now - head.t_enqueued < self._fusion_window_s \
                        and not self._has_fuse_peer_locked(head):
                    # fusion window: hold a fusable head briefly — a
                    # same-program peer from another search may arrive
                    # and fill its padded lanes.  The head stays at its
                    # queue front (FIFO intact) and dispatches solo
                    # once the window expires peer-less.  Scanned
                    # segments (kind="scan") never enter: their
                    # stacked step axis admits no peer lanes, so
                    # grid.py yields them with fuse=None (and turns
                    # cross-search fusion off for the whole search
                    # when chunk_loop="scan").
                    self._fuse_defer = True
                    continue
                if t.deficit < head.cost:
                    t.deficit += self._quantum * t.weight
                if t.deficit < head.cost:
                    continue          # earns more credit next round
                t.queue.popleft()
                t.deficit -= head.cost
                if not t.queue:
                    t.deficit = 0.0   # classic DRR: idle queues reset
                    self._rr = (idx + 1) % n
                elif t.deficit >= t.queue[0].cost:
                    # remaining credit covers the next head: stay on
                    # this tenant (one request returns per call, so the
                    # cursor must hold the burst a weight-w quantum
                    # grants — advancing every pop would flatten DRR
                    # into unweighted round-robin)
                    self._rr = idx
                else:
                    self._rr = (idx + 1) % n
                head.t_dequeued = time.perf_counter()
                self._account_dispatch(head.handle, head.cost)
                self._count_inflight(head.handle, head.state)
                wait = head.t_dequeued - head.t_enqueued
                h = head.handle
                h.queue_wait_s += wait
                h.queue_wait_max_s = max(h.queue_wait_max_s, wait)
                if len(h.queue_waits) < _MAX_WAIT_SAMPLES:
                    # tenant-stamped sample (ISSUE 8 satellite): merged
                    # samples from concurrent searches still attribute,
                    # so bench/fleet derive PER-TENANT p50/p95 from it
                    h.queue_waits.append(
                        {"tenant": h.tenant, "wait_s": round(wait, 6)})
                return head
            if runnable == 0:
                self._work.clear()
            return None

    def _note_dispatch_out(self, handle: SearchHandle, cost: int,
                           wait_s: Optional[float], fastpath: bool,
                           key: str = "") -> None:
        """Fleet-telemetry + flight-recorder dispatch notes — always
        called OUTSIDE the executor lock, so telemetry introduces no
        cross-module lock nesting.  ``wait_s`` is None for fastpath
        dispatches (they never queued; the SLO wait percentiles cover
        routed dispatches only, like the scheduler block's sample)."""
        _telemetry.note_dispatch(handle.tenant, cost, wait_s=wait_s)
        _telemetry.flight_recorder().note(
            "dispatch", handle=handle.id, tenant=handle.tenant,
            cost=cost, key=key,
            wait_s=round(wait_s, 6) if wait_s is not None else 0.0,
            fastpath=fastpath)

    def _run_request(self, req: _Request) -> None:
        if self._fusion and req.item.fuse is not None \
                and not req.handle.cancelled:
            peers = self._claim_fusion_peers(req)
            if peers:
                self._run_fused([req] + peers)
                return
        self._note_dispatch_out(
            req.handle, req.cost,
            max(0.0, req.t_dequeued - req.t_enqueued),
            fastpath=False, key=req.item.key)
        if req.handle.cancelled:
            self._note_done(req.handle, req.state)
            req.reply.set_exception(SearchCancelledError(
                f"search {req.handle.id!r} was cancelled"))
            return
        self._dispatch_solo(req)

    def _dispatch_solo(self, req: _Request) -> None:
        tr = get_tracer()
        t_busy0 = time.perf_counter()
        try:
            with tr.span("sched.dispatch", key=req.item.key,
                         tenant=req.handle.tenant, handle=req.handle.id,
                         cost=req.cost):
                out = req.launch(req.payload)
        # the dispatch loop is a thread boundary: every launch failure
        # (including injected faults) marshals back to the owning
        # search's thread, where the fault supervisor classifies it —
        # nothing is swallowed and other tenants keep dispatching
        # sstlint: disable=broad-except-swallow,launch-except-taxonomy
        except BaseException as exc:
            _telemetry.note_sched_busy(time.perf_counter() - t_busy0)
            req.reply.set_exception(exc)
            return
        _telemetry.note_sched_busy(time.perf_counter() - t_busy0)
        req.reply.set_result(out)

    # -- cross-search launch fusion --------------------------------------
    def _has_fuse_peer_locked(self, head: _Request) -> bool:
        """Is a same-program (equal FuseSpec key) request from another
        live search queued anywhere?  Caller holds the lock."""
        key = head.item.fuse.key
        for t in self._tenants.values():
            for r in t.queue:
                if r is head or r.handle.cancelled:
                    continue
                f = r.item.fuse
                if f is not None and f.key == key:
                    return True
        return False

    def _claim_fusion_peers(self, head: _Request) -> List[_Request]:
        """Pop every queued same-program peer that fits the fused
        width, within DRR credit — each claimed peer gets the exact
        head-equivalent dequeue accounting (dispatch/cost/in-flight
        counters, deficit charge, wait sample), so fair-share ratios
        and the scheduler block stay truthful under fusion."""
        spec = head.item.fuse
        claimed: List[_Request] = []
        now = time.perf_counter()
        with self._lock:
            if self._stop:
                return []
            shard = max(1, int(spec.shard))
            total = int(spec.n)
            bound = int(spec.max_width)   # HBM width ceiling; 0 = none
            for name in sorted(self._tenants):
                t = self._tenants[name]
                if not t.queue:
                    continue
                cap = self._effective_cap(name)
                # the head's tenant already earned its quantum in
                # _pop_next this round — a second top-up here would
                # double its round credit and skew fair share
                topped = name == head.handle.tenant
                for r in list(t.queue):
                    if r is head or r.handle.cancelled:
                        continue
                    f = r.item.fuse
                    if f is None or f.key != spec.key:
                        continue
                    if cap and t.inflight >= cap:
                        break
                    new_total = total + int(f.n)
                    padded = -(-new_total // shard) * shard
                    f_bound = int(f.max_width)
                    limit = min((b for b in (bound, f_bound) if b > 0),
                                default=0)
                    if limit and padded > limit:
                        continue
                    if self._fusion_max_width and \
                            new_total > self._fusion_max_width:
                        continue
                    if t.deficit < r.cost:
                        # same credit law as _pop_next: at most one
                        # quantum top-up per tenant per claim pass
                        if topped:
                            continue
                        topped = True
                        t.deficit += self._quantum * t.weight
                        if t.deficit < r.cost:
                            continue
                    t.queue.remove(r)
                    t.deficit -= r.cost
                    if not t.queue:
                        t.deficit = 0.0   # classic DRR: idle queues reset
                    r.t_dequeued = now
                    self._account_dispatch(r.handle, r.cost)
                    self._count_inflight(r.handle, r.state)
                    wait = r.t_dequeued - r.t_enqueued
                    h = r.handle
                    h.queue_wait_s += wait
                    h.queue_wait_max_s = max(h.queue_wait_max_s, wait)
                    if len(h.queue_waits) < _MAX_WAIT_SAMPLES:
                        h.queue_waits.append(
                            {"tenant": h.tenant,
                             "wait_s": round(wait, 6)})
                    claimed.append(r)
                    total = new_total
                    if f_bound:
                        bound = min(bound, f_bound) if bound else f_bound
        return claimed

    def _run_fused(self, members: List[_Request]) -> None:
        """ONE device launch serving every member's chunk, results
        scattered back per member reply.  A launch failure is delivered
        to every live member: each search's own fault supervisor then
        recovers over only ITS [lo, hi) range (member-boundary-first
        bisection), so one tenant's poison candidate never retries
        another tenant's rows."""
        live: List[_Request] = []
        for r in members:
            self._note_dispatch_out(
                r.handle, r.cost,
                max(0.0, r.t_dequeued - r.t_enqueued),
                fastpath=False, key=r.item.key)
            if r.handle.cancelled:
                # a member cancelled between claim and launch drops out
                # without touching its peers' launch
                self._note_done(r.handle, r.state)
                r.reply.set_exception(SearchCancelledError(
                    f"search {r.handle.id!r} was cancelled"))
            else:
                live.append(r)
        if not live:
            return
        if len(live) == 1:
            # every peer dropped out: the survivor dispatches solo on
            # its own already-staged payload — no fusion accounting
            self._dispatch_solo(live[0])
            return
        fl = FusedLaunch([r.item.fuse for r in live])
        tr = get_tracer()
        t_busy0 = time.perf_counter()
        try:
            with tr.span("sched.fuse", key=live[0].item.key,
                         tenant=live[0].handle.tenant,
                         n_members=len(live), lanes=fl.padded_width(),
                         cost=sum(r.cost for r in live)):
                fl.run()
        # same thread boundary as _dispatch_solo: the failure marshals
        # to EVERY member search's supervisor, each of which recovers
        # over its own candidate range only
        # sstlint: disable=broad-except-swallow,launch-except-taxonomy
        except BaseException as exc:
            _telemetry.note_sched_busy(time.perf_counter() - t_busy0)
            for r in live:
                r.reply.set_exception(exc)
            return
        _telemetry.note_sched_busy(time.perf_counter() - t_busy0)
        head = live[0]
        n_head = int(head.item.fuse.n)
        donated = fl.n_total - n_head
        borrowed: Dict[str, int] = {}
        with self._lock:
            for i, r in enumerate(live):
                r.handle.n_fused += 1
                if i == 0:
                    r.handle.lanes_donated += donated
                    r.handle.fusion_saved_launches += len(live) - 1
                else:
                    n_r = int(r.item.fuse.n)
                    r.handle.lanes_borrowed += n_r
                    borrowed[r.handle.tenant] = \
                        borrowed.get(r.handle.tenant, 0) + n_r
        # telemetry + flight notes outside the lock (hook discipline)
        _telemetry.note_fusion(
            head.handle.tenant, n_members=len(live),
            lanes_total=fl.padded_width(), lanes_real=fl.n_total,
            saved_launches=len(live) - 1, borrowed=borrowed)
        _telemetry.flight_recorder().note(
            "fuse", key=head.item.key, n_members=len(live),
            lanes=fl.padded_width(),
            tenants=[r.handle.tenant for r in live])
        for i, r in enumerate(live):
            r.reply.set_result(fl.member_result(i))

    # -- drain/test aids -------------------------------------------------
    def pause(self) -> None:
        """Hold the dispatch loop (requests keep queueing) — the
        drain/test aid behind deterministic interleave assertions."""
        self._gate.clear()

    def resume(self) -> None:
        self._gate.set()

    def queued_count(self, tenant: Optional[str] = None) -> int:
        with self._lock:
            return sum(len(t.queue) for name, t in self._tenants.items()
                       if tenant is None or name == tenant)

    def dispatch_log(self) -> List[Any]:
        """Bounded (handle id, tenant, cost) journal in dispatch
        order — what the fair-share tests assert ratios from."""
        with self._lock:
            return list(self._dispatch_log)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "n_active": len(self._active),
                "n_pending": len(self._pending),
                "tenants": {
                    name: {"weight": t.weight, "queued": len(t.queue),
                           "inflight": t.inflight,
                           "cost_total": t.cost_total}
                    for name, t in sorted(self._tenants.items())},
            }

    def telemetry_gauges(self) -> Dict[str, Any]:
        """Sampler provider (obs/telemetry.py): the scheduler gauges
        the fleet endpoint polls — total queue depth plus the
        active/pending search counts."""
        with self._lock:
            return {
                "queue_depth": sum(
                    len(t.queue) for t in self._tenants.values()),
                "n_active": len(self._active),
                "n_pending": len(self._pending),
            }

    # -- reporting -------------------------------------------------------
    def search_block(self, handle: SearchHandle) -> Dict[str, Any]:
        """The search's rendered ``search_report["scheduler"]`` block
        (schema pinned in ``obs.metrics.SCHEDULER_BLOCK_SCHEMA``)."""
        with self._lock:
            self._update_shares(handle)
            n = handle.n_dispatched
            routed = max(0, n - handle.n_fastpath)
            block = {
                "enabled": True,
                "tenant": handle.tenant,
                "handle": handle.id,
                "weight": handle.weight,
                "n_dispatches": n,
                "n_fastpath": handle.n_fastpath,
                "n_interleaved": handle.n_interleaved,
                "interleave_frac": round(
                    handle.n_interleaved / n, 4) if n else 0.0,
                "queue_wait_s": round(handle.queue_wait_s, 4),
                "queue_wait_mean_s": round(
                    handle.queue_wait_s / routed, 6) if routed else 0.0,
                "queue_wait_max_s": round(handle.queue_wait_max_s, 6),
                "share_frac": handle.share_frac,
                "tenant_shares": dict(handle.tenant_shares),
                "waits": [dict(w) for w in handle.queue_waits],
            }
            if self._fusion:
                # fusion keys ride only when fusion is resolved ON —
                # fusion=False (and standalone report_block) blocks
                # stay byte-identical to the pre-fusion engine
                block.update({
                    "n_fused": handle.n_fused,
                    "lanes_donated": handle.lanes_donated,
                    "lanes_borrowed": handle.lanes_borrowed,
                    "fusion_saved_launches":
                        handle.fusion_saved_launches,
                })
            return block

    # -- lifecycle -------------------------------------------------------
    def shutdown(self, wait: bool = True,
                 timeout: Optional[float] = 30.0) -> None:
        """Stop accepting searches, cancel the waiting line, let active
        searches finish (their queued chunks still dispatch), then stop
        the dispatch loop."""
        with self._lock:
            if self._stop or self._closing:
                return
            # reject new submissions NOW; the dispatch loop keeps
            # serving the active searches' queued chunks until their
            # workers finish below
            self._closing = True
            pending = list(self._pending)
            self._pending.clear()
            workers = list(self._workers)
        exc = AdmissionError("executor shut down before the search "
                            "started")
        for handle, future, _ in pending:
            handle.cancelled = True
            handle.state = "cancelled"
            # a queued search cancelled by shutdown is SHED work: the
            # journal marks it terminal so a restart does not re-admit
            # something the operator deliberately drained
            self._journal_note_state(handle, "shed", reason="shutdown")
            future._finish(exc)
        if wait:
            for w in workers:
                w.join(timeout)
        with self._lock:
            self._stop = True
            thread = self._thread
            # drain every still-queued request (a worker that outlived
            # the join timeout, or wait=False): failing its reply beats
            # a dispatch blocked forever on a dead loop
            stranded = []
            for t in self._tenants.values():
                stranded.extend(t.queue)
                t.queue.clear()
        for req in stranded:
            req.reply.set_exception(AdmissionError(
                "executor shut down with the chunk still queued"))
        self._gate.set()
        self._work.set()
        if wait and thread is not None and thread.is_alive():
            thread.join(timeout)

    def __repr__(self) -> str:
        s = self.stats()
        return (f"SearchExecutor({self.name!r}, active={s['n_active']}, "
                f"pending={s['n_pending']}, "
                f"tenants={sorted(s['tenants'])})")


def report_block(binding: Optional[_Binding]) -> Dict[str, Any]:
    """The ``search_report["scheduler"]`` block for a search running
    under ``binding`` — the zeroed ``enabled: False`` shape for a
    standalone fit, so the report schema never changes shape."""
    if binding is None:
        return {
            "enabled": False, "tenant": "", "handle": "", "weight": 0.0,
            "n_dispatches": 0, "n_fastpath": 0, "n_interleaved": 0,
            "interleave_frac": 0.0, "queue_wait_s": 0.0,
            "queue_wait_mean_s": 0.0, "queue_wait_max_s": 0.0,
            "share_frac": 0.0, "tenant_shares": {}, "waits": [],
        }
    return binding.executor.search_block(binding.handle)
