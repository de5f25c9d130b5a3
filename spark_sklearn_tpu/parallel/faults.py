"""Fault-tolerant launch supervisor — the engine's Spark-resilience story.

The reference gets fault tolerance for free from Spark: a failed task is
retried on another executor, and a dead search is re-run wholesale
(SURVEY §5.4).  The TPU-native engine has no executors to lean on — a
single transient ``XlaRuntimeError``, a RESOURCE_EXHAUSTED on an
oversized chunk, or a hung launch used to kill the whole
``GridSearchCV.fit``, with the offline checkpoint as the only recovery.
This module supplies the missing contract around every ``LaunchItem``
the chunk pipeline executes (``parallel/pipeline.py``):

  - **error taxonomy** — every failure classifies as ``TRANSIENT`` /
    ``OOM`` / ``HUNG`` / ``FATAL`` (:func:`classify_error`, extensible
    via :func:`register_classifier`);
  - **retry with exponential backoff + jitter** for ``TRANSIENT``
    faults, under per-launch (``TpuConfig.max_launch_retries``) and
    per-search (``max_search_retries``) budgets.  A retry re-runs the
    item's own ``stage -> launch -> wait`` phases: same program, same
    inputs, bit-identical scores;
  - **graceful OOM degradation** — an ``OOM`` launch is bisected into
    halves (the item's ``bisect`` hook re-pads lanes via
    ``parallel/taskgrid.pad_chunk`` and relaunches at the narrower
    width), recursing down to single candidates and finally falling
    back to per-candidate host execution with exact sklearn
    ``error_score`` semantics (the item's ``host_fallback`` hook);
  - **watchdog timeouts** — ``TpuConfig.launch_timeout_s`` bounds the
    blocking ``jax.block_until_ready`` wait; a launch that exceeds it
    fails the search with a clean :class:`LaunchTimeoutError` naming
    the chunk and compile group instead of hanging the gather thread
    forever (previously-finalized chunks are already durable in the
    checkpoint, so the failed search resumes);
  - **deterministic fault injection** — ``TpuConfig(fault_plan=...)``
    or the ``SST_FAULT_PLAN`` env var inject any taxonomy class at
    chosen launch indices (``"transient@3,oom@5"``), so CPU tests
    exercise every recovery path with no flaky hardware required.

Every recovery event lands in the metrics registry
(``search_report["faults"]`` — schema pinned in
``obs.metrics.FAULTS_BLOCK_SCHEMA``), in ``launch.retry`` /
``launch.bisect`` / ``launch.host_fallback`` trace spans, and in
structured log lines.
"""

from __future__ import annotations

import copy
import dataclasses
import os
import re
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from spark_sklearn_tpu.obs import telemetry as _telemetry
from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.parallel.pipeline import LaunchItem
from spark_sklearn_tpu.utils.locks import named_lock

_slog = get_logger(__name__)

__all__ = [
    "TRANSIENT",
    "OOM",
    "HUNG",
    "FATAL",
    "FaultPlan",
    "FaultSpec",
    "InjectedFault",
    "LaunchTimeoutError",
    "LaunchSupervisor",
    "SearchDeadlineError",
    "classify_error",
    "is_oom",
    "protection_block",
    "protection_enabled",
    "register_classifier",
]


# ---------------------------------------------------------------------------
# Taxonomy
# ---------------------------------------------------------------------------

#: retry with backoff: the device hiccuped but the program is fine
TRANSIENT = "transient"
#: bisect the chunk / fall back to host: the launch was too big
OOM = "oom"
#: fail the search cleanly: the launch never came back
HUNG = "hung"
#: re-raise unchanged: a real bug (or an unsupported combo the search
#: engine's own compiled->host fallback knows how to handle)
FATAL = "fatal"

#: plan-only pseudo-class: OOM that also fails every multi-candidate
#: bisected sub-range, forcing recovery all the way to the host path
OOM_DEEP = "oom_deep"

#: plan-only pseudo-class: FATAL that stays sticky through bisection —
#: every isolated sub-range re-fails down to single-lane, which is how
#: tests drive a poison candidate into quarantine deterministically
FATAL_DEEP = "fatal_deep"

#: plan-only brownout: the launch is not failed, it is STALLED for the
#: token's factor seconds before running (``slow@5:0.05`` = a 50 ms
#: brownout at launch index 5) — the chaos harness's degraded-device
#: event
SLOW = "slow"

_CLASSES = (TRANSIENT, OOM, HUNG, FATAL, OOM_DEEP, FATAL_DEEP, SLOW)

#: message substrings marking a device error as OOM / transient.  XLA
#: runtime errors carry their grpc-style status name in the message
#: (RESOURCE_EXHAUSTED, UNAVAILABLE, ...), so string matching is the
#: stable cross-version classifier.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "Resource exhausted", "Failed to allocate")
_TRANSIENT_MARKERS = ("UNAVAILABLE", "ABORTED", "CANCELLED",
                      "DEADLINE_EXCEEDED", "Socket closed",
                      "connection reset", "transient")

#: checked BEFORE the transient markers: a backend that cannot come up
#: (jax: "Unable to initialize backend 'tpu': UNAVAILABLE: ..." — the
#: chip belongs to another process) will not come up on a retry either,
#: so it fails at once instead of sleeping through the back-off budget
_FATAL_MARKERS = ("Unable to initialize backend", "TPU is already in use")

#: user-extensible classifiers, consulted first: fn(exc) -> class | None
_CUSTOM_CLASSIFIERS: List[Callable[[BaseException], Optional[str]]] = []


def register_classifier(fn: Callable[[BaseException], Optional[str]]) -> None:
    """Prepend a custom error classifier.  ``fn(exc)`` returns one of
    the taxonomy classes, or None to defer to the built-in rules —
    the extension point for backend-specific error shapes."""
    _CUSTOM_CLASSIFIERS.insert(0, fn)


class InjectedFault(RuntimeError):
    """A fault raised by the deterministic injection plan.  Carries its
    taxonomy class explicitly so classification never guesses."""

    def __init__(self, fault_class: str, message: str):
        super().__init__(message)
        self.fault_class = fault_class
        #: OOM_DEEP faults stay sticky through bisection: every
        #: multi-candidate sub-range re-fails, forcing host fallback
        self.sst_sticky_oom = fault_class == OOM_DEEP
        #: FATAL_DEEP faults stay sticky through isolation: every
        #: sub-range re-fails down to single-lane, so the quarantine
        #: counter deterministically reaches its K
        self.sst_sticky_fatal = fault_class == FATAL_DEEP


class LaunchTimeoutError(TimeoutError):
    """A launch exceeded its watchdog budget.  ``mode="wall"`` is the
    classic whole-launch ``TpuConfig.launch_timeout_s`` expiry;
    ``mode="heartbeat"`` means a scanned launch's in-flight beats
    (``obs/heartbeat.py``) went silent for ``heartbeat_timeout_s`` —
    the error then names the last scan step that beat, so a postmortem
    knows WHERE inside the multi-minute launch the device died."""

    def __init__(self, key: str, group: int, timeout_s: float,
                 injected: bool = False, mode: str = "wall",
                 last_step: Optional[int] = None,
                 steps_total: Optional[int] = None):
        if mode == "heartbeat":
            at = (f"last beat at scan step {last_step}"
                  if last_step is not None
                  else "no beat ever arrived")
            msg = (f"launch {key!r} (compile group {group}) heartbeat "
                   f"went silent for heartbeat_timeout_s={timeout_s}s "
                   f"({at} of {steps_total} step(s))")
        else:
            msg = (f"launch {key!r} (compile group {group}) exceeded "
                   f"launch_timeout_s={timeout_s}s")
        super().__init__(msg + (" [injected]" if injected else ""))
        self.key = key
        self.group = group
        self.timeout_s = timeout_s
        self.injected = injected
        self.mode = mode
        self.last_step = last_step
        self.steps_total = steps_total


class SearchDeadlineError(RuntimeError):
    """The search exceeded ``TpuConfig.search_deadline_s`` under
    ``partial_results="raise"``.  Under ``"best_effort"`` the deadline
    sheds the remaining candidates to ``error_score`` instead of
    raising this."""

    def __init__(self, deadline_s: float, elapsed_s: float,
                 n_remaining: int = 0):
        super().__init__(
            f"search exceeded search_deadline_s={deadline_s:g}s "
            f"(elapsed {elapsed_s:.3f}s, {n_remaining} candidate(s) "
            "un-run); set partial_results='best_effort' for a declared-"
            "partial cv_results_ instead")
        self.deadline_s = deadline_s
        self.elapsed_s = elapsed_s
        self.n_remaining = n_remaining


def _normalize_class(cls: str) -> str:
    """Collapse the plan-only pseudo-classes onto the 4-way taxonomy
    recovery actually dispatches on."""
    if cls == OOM_DEEP:
        return OOM
    if cls == FATAL_DEEP:
        return FATAL
    if cls == SLOW:
        return TRANSIENT
    return cls


def classify_error(exc: BaseException) -> str:
    """Map an exception to its taxonomy class.

    Conservative by design: anything not positively identified as
    transient or OOM is FATAL, so genuine bugs keep today's behavior
    (propagate immediately; the search engine's own compiled->host
    fallback still applies) instead of burning a retry budget."""
    for fn in _CUSTOM_CLASSIFIERS:
        cls = fn(exc)
        if cls in _CLASSES:
            return _normalize_class(cls)
    if isinstance(exc, InjectedFault):
        return _normalize_class(exc.fault_class)
    if isinstance(exc, LaunchTimeoutError):
        return HUNG
    if isinstance(exc, MemoryError):
        return OOM
    msg = f"{type(exc).__name__}: {exc}"
    if any(m in msg for m in _FATAL_MARKERS):
        return FATAL
    if any(m in msg for m in _OOM_MARKERS):
        return OOM
    if any(m in msg for m in _TRANSIENT_MARKERS):
        return TRANSIENT
    return FATAL


def is_oom(exc: BaseException) -> bool:
    return classify_error(exc) == OOM


# ---------------------------------------------------------------------------
# Deterministic fault-injection plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Inject `fault_class` at launch `index` for its first `count`
    attempts (count=1: the launch fails once and the first retry
    succeeds).  ``factor`` carries the class's scalar knob: for the
    ``slow`` brownout class, absolute seconds the launch is stalled
    before running; for ``hung`` under the heartbeat watchdog
    (``heartbeat_timeout_s`` set and the launch is a live scanned
    segment), the scan STEP after which beats go silent — the drill
    the watchdog must catch naming that step (``hung@IDX:STEP``)."""

    index: int
    fault_class: str
    count: int = 1
    factor: float = 0.0


_PLAN_TOKEN = re.compile(
    r"(?i)^(transient|oom_deep|oom|hung|fatal_deep|fatal|slow)"
    r"@(\d+)(?:x(\d+))?(?::([0-9.]+))?$")


class FaultPlan:
    """Deterministic injection schedule over supervised launch indices.

    Spec forms (``TpuConfig(fault_plan=...)`` / ``SST_FAULT_PLAN``):

      - string: comma-separated ``CLASS@INDEX[xCOUNT]`` tokens, e.g.
        ``"transient@3,oom@5"`` or ``"transient@2x3"`` (fail 3
        consecutive attempts — enough to exhaust a retry budget);
      - sequence of ``FaultSpec`` / ``(index, class[, count])`` tuples /
        ``{"index": .., "class": .., "count": ..}`` dicts.

    Launch indices count the supervised ``LaunchItem``s in dispatch
    order (resumed chunks launch nothing and are not counted), which is
    identical at every ``pipeline_depth`` — so a plan reproduces the
    same faults in the pipelined run and the synchronous escape hatch.
    """

    def __init__(self, specs: Sequence[FaultSpec] = ()):
        self._by_index: Dict[int, FaultSpec] = {}
        for s in specs:
            if s.fault_class not in _CLASSES:
                raise ValueError(
                    f"unknown fault class {s.fault_class!r}; expected one "
                    f"of {_CLASSES}")
            if s.index in self._by_index:
                raise ValueError(
                    f"duplicate fault-plan entry for launch index "
                    f"{s.index}")
            self._by_index[s.index] = s

    def __bool__(self) -> bool:
        return bool(self._by_index)

    def __len__(self) -> int:
        return len(self._by_index)

    @property
    def specs(self) -> Tuple[FaultSpec, ...]:
        return tuple(self._by_index[i] for i in sorted(self._by_index))

    def match(self, index: int, attempt: int) -> Optional[FaultSpec]:
        """The spec to fire for this (launch index, attempt number), or
        None.  attempt counts from 0 (the first try)."""
        spec = self._by_index.get(index)
        if spec is not None and attempt < spec.count:
            return spec
        return None

    @classmethod
    def parse(cls, spec: Any) -> "FaultPlan":
        if spec is None:
            return cls(())
        if isinstance(spec, FaultPlan):
            return spec
        if isinstance(spec, str):
            out = []
            for tok in spec.split(","):
                tok = tok.strip()
                if not tok:
                    continue
                m = _PLAN_TOKEN.match(tok)
                if m is None:
                    raise ValueError(
                        f"bad fault-plan token {tok!r}; expected "
                        "CLASS@INDEX[xCOUNT][:FACTOR] with CLASS in "
                        f"{_CLASSES}, e.g. 'transient@3,oom@5,"
                        "slow@7:0.05'")
                out.append(FaultSpec(int(m.group(2)), m.group(1).lower(),
                                     int(m.group(3) or 1),
                                     float(m.group(4) or 0.0)))
            return cls(out)
        out = []
        for entry in spec:
            if isinstance(entry, FaultSpec):
                out.append(entry)
            elif isinstance(entry, dict):
                out.append(FaultSpec(
                    int(entry["index"]),
                    str(entry.get("class",
                                  entry.get("fault_class"))).lower(),
                    int(entry.get("count", 1)),
                    float(entry.get("factor", 0.0))))
            else:
                idx, fcls = entry[0], entry[1]
                count = entry[2] if len(entry) > 2 else 1
                factor = entry[3] if len(entry) > 3 else 0.0
                out.append(FaultSpec(int(idx), str(fcls).lower(),
                                     int(count), float(factor)))
        return cls(out)

    @classmethod
    def resolve(cls, config=None) -> "FaultPlan":
        """The active plan: ``TpuConfig.fault_plan`` when set, else the
        ``SST_FAULT_PLAN`` environment variable, else empty."""
        spec = getattr(config, "fault_plan", None) if config is not None \
            else None
        if spec is None:
            spec = os.environ.get("SST_FAULT_PLAN") or None
        return cls.parse(spec)


# ---------------------------------------------------------------------------
# Supervisor
# ---------------------------------------------------------------------------


class _Recovered:
    """Marker wrapping an already-gathered HOST result produced by a
    recovery path (bisection merge or host fallback).  The wrapped
    item's wait/gather phases pass it through / unwrap it, so the
    original finalize runs unchanged — writing cells and the checkpoint
    record under the original chunk id."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value


#: indirection so tests can substitute a controllable blocker
_block_until_ready = jax.block_until_ready

#: cap on per-search recovery-event records kept in the report
_MAX_EVENTS = 64

#: process-wide mutex serializing recovery relaunches across
#: concurrently-recovering searches.  A fused-launch fault scatters to
#: every member, so member supervisors routinely bisect at the same
#: moment on their own threads; steady-state launches serialize on the
#: executor's dispatch loop, which makes these recovery attempts the
#: only same-instant device entry from multiple host threads — a
#: combination observed to wedge the CPU backend (both threads parked
#: inside execute, zero progress).  The device is serial anyway, so
#: holding this across an attempt costs recovery nothing, and the loop
#: never takes it: one tenant's recovery still cannot stall another's
#: steady-state dispatch.
_RECOVERY_EXEC_LOCK = named_lock("faults._RECOVERY_EXEC_LOCK")


class LaunchSupervisor:
    """Wrap the search's ``LaunchItem`` stream with retry / bisection /
    watchdog / injection semantics.

    Usage (``search/grid.py _run_groups``)::

        sup = LaunchSupervisor(config, faults=metrics.struct("faults"),
                               ckpt=ckpt)
        pipe.run(sup.wrap(chunk_items()))

    The fault-free fast path costs one try/except per launch phase; the
    watchdog thread only exists while ``launch_timeout_s`` is set.
    Recovery runs on whichever thread hit the failure (the dispatch
    thread for synchronous launch errors, the gather thread for errors
    surfacing at ``block_until_ready``) — already-dispatched launches
    keep computing meanwhile.
    """

    def __init__(self, config=None, faults: Optional[Dict[str, Any]] = None,
                 ckpt=None, verbose: int = 0, reset_faults: bool = True,
                 memory_info: Optional[
                     Callable[[str, int], Dict[str, Any]]] = None):
        self.max_launch_retries = int(
            getattr(config, "max_launch_retries", 2) or 0)
        self.max_search_retries = int(
            getattr(config, "max_search_retries", 16) or 0)
        self.retry_backoff_s = float(
            getattr(config, "retry_backoff_s", 0.5) or 0.0)
        self.retry_backoff_mult = float(
            getattr(config, "retry_backoff_mult", 2.0) or 1.0)
        self.retry_jitter_frac = float(
            getattr(config, "retry_jitter_frac", 0.25) or 0.0)
        self.launch_timeout_s = getattr(config, "launch_timeout_s", None)
        #: heartbeat-aware watchdog (obs/heartbeat.py): a scanned
        #: launch with a live hub segment is declared HUNG when its
        #: beats go silent this long — launches without one (per-chunk
        #: path, heartbeat off) keep the wall-clock semantics above
        self.heartbeat_timeout_s = getattr(
            config, "heartbeat_timeout_s", None)
        #: keys whose hung injection capped the beat stream instead of
        #: raising at launch: wait_ready treats them as wedged even
        #: though the drill's device work completes (guarded by
        #: self._lock)
        self._hb_stall_keys: set = set()
        self.plan = FaultPlan.resolve(config)
        self.verbose = int(verbose)
        self._ckpt = ckpt
        #: kept for flight-recorder dumps (TpuConfig.flight_dir /
        #: SST_FLIGHT_DIR resolve at dump time)
        self._config = config
        #: device-memory forensics hook (search/grid.py): (key, group)
        #: -> {modeled_bytes, budget_bytes, ...} stamped onto every OOM
        #: event, so bisection outcomes show what the footprint model
        #: believed — and train its safety margin
        self._memory_info = memory_info
        #: one OOM bundle per search — a deep bisection storm must not
        #: dump a bundle per sub-range (guarded by self._lock)
        self._oom_dumped = False
        self._tracer = get_tracer()
        self._lock = named_lock("faults.LaunchSupervisor._lock")
        self._seq = 0
        self._retries_used = 0
        # count of in-flight sticky (oom_deep) recoveries, not a bool:
        # concurrent recoveries on the dispatch and gather threads each
        # enter/leave independently, and a saved-prev restore would let
        # one recovery clobber the other's flag
        self._sticky_oom = 0
        # same shape for sticky (fatal_deep) isolations
        self._sticky_fatal = 0
        # poison-candidate quarantine (self-protecting service): active
        # only under partial_results="best_effort".  A launch key whose
        # single-lane range faults FATAL quarantine_k times is written
        # to error_score instead of killing the search.
        self.quarantine_k = (
            int(getattr(config, "quarantine_fatal_k", 3) or 0)
            if str(getattr(config, "partial_results", "raise")
                   or "raise") == "best_effort" else 0)
        self._fatal_counts: Dict[str, int] = {}
        # one FATAL bundle per launch key while quarantine is counting
        # to K — K identical failures must not dump K bundles
        self._fatal_dumped: set = set()
        self.faults: Dict[str, Any] = faults if faults is not None else {}
        defaults = {
            "retries": 0, "bisections": 0, "host_fallbacks": 0,
            "timeouts": 0, "injected": 0, "by_class": {}, "events": [],
        }
        if reset_faults:
            self.faults.update(defaults)
        else:
            # a halving search wraps each rung in its own supervisor
            # over ONE shared faults struct: later rungs keep the
            # earlier rungs' recovery record instead of zeroing it
            for k, v in defaults.items():
                self.faults.setdefault(k, v)

    # -- accounting ------------------------------------------------------
    def _count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.faults[name] += n

    def _mem_extra(self, key: str, group: int) -> Dict[str, Any]:
        """Modeled-vs-budget bytes for an OOM event (the device-memory
        ledger's forensics; empty when no hook is installed).  Must
        never turn a recovery into a second failure."""
        if self._memory_info is None:
            return {}
        try:
            return dict(self._memory_info(key, group) or {})
        # forensics only: a broken lookup loses the memory annotation,
        # never the recovery itself — the fault being annotated is
        # already classified by the caller
        # sstlint: disable=broad-except-swallow,swallowed-exception,launch-except-taxonomy
        except Exception:
            return {}

    def _hb_extra(self, exc: Optional[BaseException]) -> Dict[str, Any]:
        """The heartbeat watchdog's forensics for a HUNG verdict: which
        scan step last beat before the silence — stamped onto the fault
        event and the flight bundle so a postmortem names the step."""
        if not isinstance(exc, LaunchTimeoutError) or \
                exc.mode != "heartbeat":
            return {}
        return {"watchdog_mode": exc.mode,
                "last_step": exc.last_step,
                "steps_total": exc.steps_total}

    def _record_event(self, key: str, group: int, cls: str, action: str,
                      exc: Optional[BaseException], attempt: int) -> None:
        mem = self._mem_extra(key, group) if cls == OOM else {}
        hb = self._hb_extra(exc) if cls == HUNG else {}
        with self._lock:
            by = self.faults["by_class"]
            by[cls] = by.get(cls, 0) + 1
            ev = self.faults["events"]
            if len(ev) < _MAX_EVENTS:
                ev.append({
                    "key": key, "group": group, "class": cls,
                    "action": action, "attempt": attempt,
                    "error": (f"{type(exc).__name__}: {exc}"[:200]
                              if exc is not None else ""),
                    **mem, **hb})
        if self._ckpt is not None:
            # durable fault journal: a resume after a failed recovery
            # still knows which chunk was in trouble (and the completed
            # chunks' result records are already streamed)
            try:
                self._ckpt.note_fault(key, {
                    "class": cls, "action": action, "attempt": attempt,
                    "error": (f"{type(exc).__name__}: {exc}"[:200]
                              if exc is not None else "")})
            except OSError:
                _slog.warning("fault journal write failed for %s", key)
        # fleet telemetry + the flight recorder's event ring (both
        # called outside self._lock; the telemetry hook is an exact
        # no-op when the service is disabled)
        _telemetry.note_fault(cls, action)
        _telemetry.flight_recorder().note(
            "fault", key=key, group=group, fault_class=cls,
            action=action, attempt=attempt,
            error=(f"{type(exc).__name__}: {exc}"[:200]
                   if exc is not None else ""))
        self._maybe_flight_dump(key, group, cls, action, exc, attempt)

    def _maybe_flight_dump(self, key: str, group: int, cls: str,
                           action: str, exc: Optional[BaseException],
                           attempt: int) -> None:
        """Black-box bundles for the incidents worth a postmortem:
        FATAL raises, watchdog timeouts, and the FIRST OOM recovery of
        the search (the 3 a.m. OOM the flight recorder exists for —
        deduped so a deep bisection storm dumps one bundle, not one
        per sub-range).  No-op unless ``TpuConfig.flight_dir`` /
        ``SST_FLIGHT_DIR`` names a directory — checked FIRST so the
        default no-dump configuration never pays the payload copy."""
        if _telemetry.resolve_flight_dir(self._config) is None:
            return
        reason = None
        if cls == FATAL and action == "raise":
            if self.quarantine_k:
                # quarantine counts the SAME launch key failing K
                # times: one bundle per key, not one per attempt
                with self._lock:
                    if key in self._fatal_dumped:
                        return
                    self._fatal_dumped.add(key)
            reason = "fatal"
        elif cls == HUNG:
            reason = "watchdog-timeout"
        elif cls == OOM and action == "recover":
            with self._lock:
                if self._oom_dumped:
                    return
                self._oom_dumped = True
            reason = "oom"
        if reason is None:
            return
        with self._lock:
            faults_copy = copy.deepcopy(self.faults)
        mem = self._mem_extra(key, group) if cls == OOM else {}
        hb = self._hb_extra(exc) if cls == HUNG else {}
        _telemetry.flight_recorder().dump(
            reason, config=self._config, faults=faults_copy,
            context={"key": key, "group": group, "class": cls,
                     "action": action, "attempt": attempt,
                     "error": (f"{type(exc).__name__}: {exc}"[:300]
                               if exc is not None else ""),
                     **mem, **hb})

    def record_bisection(self, key: str, group: int,
                         fault_class: str = OOM) -> None:
        """Called by the item's bisect hook once per split — OOM
        recovery by default; FATAL when the quarantine path isolates a
        poison range (search/grid.py exec_fused_range)."""
        self._count("bisections")
        self._record_event(key, group, fault_class, "bisect", None, 0)
        _slog.warning("launch %s: %s — bisecting the chunk", key,
                      fault_class, key=key, group=group)

    def record_host_fallback(self, key: str, group: int, n_tasks: int) -> None:
        """Called by recovery paths when a range degrades to per-
        candidate host execution."""
        self._count("host_fallbacks")
        self._record_event(key, group, OOM, "host_fallback", None, 0)
        _slog.warning(
            "launch %s: bisection bottomed out — running %d task(s) on "
            "the host with sklearn error_score semantics", key, n_tasks,
            key=key, group=group, n_tasks=n_tasks)

    # -- poison-candidate quarantine -------------------------------------
    def note_fatal(self, key: str) -> int:
        """Count one FATAL fault on a single-lane range, returning the
        total for that launch key — the quarantine counter the fused-
        range recursion in search/grid.py compares against K."""
        with self._lock:
            n = self._fatal_counts.get(key, 0) + 1
            self._fatal_counts[key] = n
        return n

    def record_quarantine(self, key: str, group: int,
                          exc: BaseException, n_faults: int) -> None:
        """A single-lane range faulted FATAL K times: journal the
        quarantine verdict, tell telemetry, and dump a protection
        bundle — the search itself continues with the candidate
        written to error_score."""
        self._record_event(key, group, FATAL, "quarantine", exc,
                           n_faults)
        _telemetry.note_protection("quarantined")
        self._protection_dump("quarantine", key, group, exc,
                              extra={"n_faults": n_faults,
                                     "quarantine_k": self.quarantine_k})
        _slog.warning(
            "launch %s: single-lane range faulted FATAL %d time(s) — "
            "quarantining the candidate to error_score (the search "
            "continues)", key, n_faults, key=key, group=group)

    def _protection_dump(self, verdict: str, key: str, group: int,
                         exc: Optional[BaseException],
                         extra: Optional[Dict[str, Any]] = None) -> None:
        """One protection-verdict flight bundle (no-op unless a flight
        directory is configured)."""
        if _telemetry.resolve_flight_dir(self._config) is None:
            return
        with self._lock:
            faults_copy = copy.deepcopy(self.faults)
        _telemetry.flight_recorder().protection_dump(
            verdict, config=self._config, faults=faults_copy,
            context={"key": key, "group": group,
                     "error": (f"{type(exc).__name__}: {exc}"[:300]
                               if exc is not None else ""),
                     **(extra or {})})

    # -- injection -------------------------------------------------------
    def _maybe_inject(self, st: Dict[str, Any]) -> None:
        spec = self.plan.match(st["index"], st["attempt"])
        if spec is None:
            return
        self._count("injected")
        item = st["item"]
        _slog.warning(
            "fault plan: injecting %s at launch %d (%s) attempt %d",
            spec.fault_class, st["index"], item.key, st["attempt"],
            key=item.key, fault_class=spec.fault_class,
            attempt=st["attempt"])
        if spec.fault_class == SLOW:
            # a brownout stalls the launch instead of failing it: the
            # chaos harness's degraded-device event — journaled like a
            # fault so soak runs can assert it happened, but the launch
            # itself proceeds and stays bit-exact
            self._record_event(item.key, item.group, SLOW, "brownout",
                               None, st["attempt"])
            if spec.factor > 0.0:
                time.sleep(spec.factor)
            return
        if spec.fault_class == HUNG:
            if self.heartbeat_timeout_s:
                # heartbeat-mode stall drill: instead of failing at
                # launch, silence the beat stream after step FACTOR on
                # the live scanned segment — the heartbeat watchdog in
                # wait_ready must detect the silence and name the step
                from spark_sklearn_tpu.obs import heartbeat as _hb
                if _hb.get_hub().cap_beats(item.key,
                                           int(spec.factor)):
                    with self._lock:
                        self._hb_stall_keys.add(item.key)
                    return
            raise LaunchTimeoutError(
                item.key, item.group, float(self.launch_timeout_s or 0.0),
                injected=True)
        marker = ("RESOURCE_EXHAUSTED: " if spec.fault_class
                  in (OOM, OOM_DEEP) else "")
        raise InjectedFault(
            spec.fault_class,
            f"{marker}injected {spec.fault_class} fault at launch index "
            f"{st['index']} ({item.key}), attempt {st['attempt']}")

    def inject_subrange(self, n_real: int) -> None:
        """Consulted by bisected sub-launches: under a sticky
        (``oom_deep``) fault every sub-range re-fails — single
        candidates included — so the recursion deterministically
        bottoms out into the per-candidate host path.  A sticky
        (``fatal_deep``) fault does the same with FATAL, driving the
        single-lane range into the quarantine counter."""
        if self._sticky_oom:
            self._count("injected")
            raise InjectedFault(
                OOM, "RESOURCE_EXHAUSTED: injected sticky OOM on a "
                     f"bisected sub-range of {n_real} candidate(s)")
        if self._sticky_fatal:
            self._count("injected")
            raise InjectedFault(
                FATAL_DEEP, "injected sticky FATAL on an isolated "
                            f"sub-range of {n_real} candidate(s)")

    # -- watchdog --------------------------------------------------------
    def wait_ready(self, out, key: str = "", group: int = 0):
        """``jax.block_until_ready`` bounded by the watchdog budget.

        Two modes: the classic whole-launch ``launch_timeout_s`` wall
        clock, and — when ``heartbeat_timeout_s`` is set AND the hub
        owns a live scanned segment for ``key`` — a heartbeat poll
        that declares the launch HUNG when in-flight beats go silent,
        naming the last scan step that beat (a scanned rung can
        legitimately run for many minutes; its beats must not).

        The blocking wait runs on a disposable daemon thread; on
        timeout the search fails with :class:`LaunchTimeoutError`
        (naming the chunk and compile group) while the wedged wait
        thread is abandoned — the one leak a hung device costs, instead
        of a gather thread hung forever."""
        if isinstance(out, _Recovered):
            return out
        hub = None
        hb_timeout = float(self.heartbeat_timeout_s or 0.0)
        if hb_timeout > 0.0 and key:
            from spark_sklearn_tpu.obs import heartbeat as _hb
            h = _hb.get_hub()
            if h.live_segment(key):
                hub = h
        if not self.launch_timeout_s and hub is None:
            return _block_until_ready(out)
        box: Dict[str, Any] = {}
        done = threading.Event()

        def blocker():
            try:
                box["out"] = _block_until_ready(out)
            # nothing is swallowed here: the watchdog thread marshals
            # EVERY exception (KeyboardInterrupt included) back to the
            # waiting caller, which re-raises it below
            # sstlint: disable=broad-except-swallow,launch-except-taxonomy
            except BaseException as exc:       # re-raised on the caller
                box["exc"] = exc
            finally:
                done.set()

        threading.Thread(target=blocker, daemon=True,
                         name="sst-watchdog-wait").start()
        if hub is None:
            if not done.wait(float(self.launch_timeout_s)):
                raise LaunchTimeoutError(key, group,
                                         float(self.launch_timeout_s))
        else:
            with self._lock:
                stalled = key in self._hb_stall_keys
            t0 = time.perf_counter()
            poll = max(0.005, min(hb_timeout / 4.0, 0.25))
            while True:
                if done.is_set():
                    # an injected stall's drill work completes; the
                    # watchdog must still see the silence, so keep
                    # polling staleness instead of returning
                    finished = True
                    time.sleep(poll)
                else:
                    finished = done.wait(poll)
                st = hub.staleness(key)
                if finished and (not stalled or st is None):
                    break
                if st is not None and st["age_s"] >= hb_timeout:
                    raise LaunchTimeoutError(
                        key, group, hb_timeout, injected=stalled,
                        mode="heartbeat", last_step=st["last_step"],
                        steps_total=st["n_steps"])
                if self.launch_timeout_s and \
                        time.perf_counter() - t0 \
                        > float(self.launch_timeout_s):
                    raise LaunchTimeoutError(
                        key, group, float(self.launch_timeout_s))
        if "exc" in box:
            raise box["exc"]
        return box["out"]

    # -- retry loop shared by wrapped items and bisected sub-launches ----
    def _backoff_delay(self, key: str, attempt: int) -> float:
        base = self.retry_backoff_s * (
            self.retry_backoff_mult ** max(0, attempt - 1))
        if self.retry_jitter_frac <= 0.0:
            return base
        # deterministic jitter: reproducible runs need reproducible
        # sleeps, so the jitter hashes (key, attempt) instead of
        # sampling a live RNG
        u = zlib.crc32(f"{key}:{attempt}".encode()) / 2 ** 32
        return base * (1.0 + self.retry_jitter_frac * (u - 0.5))

    def _take_retry_budget(self, key: str) -> bool:
        with self._lock:
            if self._retries_used >= self.max_search_retries:
                return False
            self._retries_used += 1
            self.faults["retries"] += 1
        return True

    def _retry_gate(self, key: str, group: int, attempt: int,
                    exc: Exception) -> None:
        """The one transient-retry policy: consume budget, journal the
        event, back off — or re-raise `exc` when a budget is spent.
        Shared by the wrapped-item recovery loop and bisected
        sub-launch retries so the two paths cannot drift."""
        if attempt > self.max_launch_retries or \
                not self._take_retry_budget(key):
            self._record_event(key, group, TRANSIENT,
                               "retries_exhausted", exc, attempt)
            self._protection_dump(
                "retries-exhausted", key, group, exc,
                extra={"attempt": attempt,
                       "retries_used": self._retries_used,
                       "max_launch_retries": self.max_launch_retries,
                       "max_search_retries": self.max_search_retries})
            _slog.warning(
                "launch %s: transient fault but retry budget exhausted "
                "(%d/%d per launch, %d/%d per search)", key,
                attempt - 1, self.max_launch_retries, self._retries_used,
                self.max_search_retries, key=key)
            raise exc
        self._record_event(key, group, TRANSIENT, "retry", exc, attempt)
        delay = self._backoff_delay(key, attempt)
        _slog.warning(
            "launch %s: transient fault (%r), retry %d/%d in %.3fs",
            key, exc, attempt, self.max_launch_retries, delay,
            key=key, attempt=attempt)
        time.sleep(delay)

    def call(self, fn: Callable[[], Any], key: str, group: int = 0,
             n_real: Optional[int] = None):
        """Run ``fn`` (a full stage->launch->wait->gather closure used
        by bisected sub-launches) under transient-retry semantics.  OOM
        and HUNG propagate to the caller — the bisection recursion in
        the item's hook decides what OOM means at its depth."""
        attempt = 0
        while True:
            try:
                if n_real is not None:
                    self.inject_subrange(n_real)
                if attempt == 0:
                    with _RECOVERY_EXEC_LOCK:
                        return fn()
                with self._tracer.span("launch.retry", key=key,
                                       group=group, attempt=attempt):
                    with _RECOVERY_EXEC_LOCK:
                        return fn()
            except Exception as exc:
                if getattr(exc, "_sst_cancelled", False):
                    # a cancelled search (serve.SearchCancelledError) is
                    # an instruction, not a fault: no retry, no event
                    raise
                cls = classify_error(exc)
                if cls != TRANSIENT:
                    if cls != OOM:
                        self._record_event(key, group, cls, "raise", exc,
                                           attempt)
                    if cls == HUNG:
                        self._count("timeouts")
                    raise
                attempt += 1
                self._retry_gate(key, group, attempt, exc)

    # -- item wrapping ---------------------------------------------------
    def wrap(self, items):
        """Wrap an iterable of LaunchItems (lazily — the pipeline's
        stage-ahead behavior is preserved)."""
        for item in items:
            idx = self._seq
            self._seq += 1
            yield self._wrap_one(item, idx)

    def _wrap_one(self, item: LaunchItem, index: int) -> LaunchItem:
        st = {"item": item, "index": index, "attempt": 0}

        def guarded_launch(payload):
            try:
                self._maybe_inject(st)
                return item.launch(payload)
            except Exception as exc:
                return self._recover(st, exc)

        def guarded_wait(out):
            if isinstance(out, _Recovered):
                return out
            try:
                return self.wait_ready(out, key=item.key, group=item.group)
            except Exception as exc:
                return self._recover(st, exc)

        def guarded_gather(out):
            if isinstance(out, _Recovered):
                return out.value
            return item.gather(out) if item.gather is not None else None

        return LaunchItem(
            key=item.key, launch=guarded_launch, stage=item.stage,
            gather=guarded_gather, finalize=item.finalize,
            group=item.group, kind=item.kind, n_tasks=item.n_tasks,
            n_chunks=item.n_chunks, wait=guarded_wait)

    # -- recovery --------------------------------------------------------
    def _recover(self, st: Dict[str, Any], exc: Exception):
        item = st["item"]
        while True:
            if getattr(exc, "_sst_cancelled", False):
                # cancellation (serve.SearchFuture.cancel) must unwind
                # the search promptly: no retry budget, no recovery
                # hooks, no fault journal entry — the checkpoint's
                # completed chunks already make the search resumable
                raise exc
            cls = classify_error(exc)
            if cls == FATAL:
                if self.quarantine_k and item.bisect is not None:
                    # poison-candidate isolation: split the range and
                    # re-run the halves instead of killing the search
                    # — the fused-range recursion in search/grid.py
                    # counts single-lane FATALs into quarantine
                    self._record_event(item.key, item.group, cls,
                                       "isolate", exc, st["attempt"])
                    sticky = bool(getattr(exc, "sst_sticky_fatal",
                                          False))
                    with self._tracer.span("launch.isolate",
                                           key=item.key,
                                           group=item.group):
                        if sticky:
                            with self._lock:
                                self._sticky_fatal += 1
                        try:
                            return _Recovered(item.bisect(self))
                        finally:
                            if sticky:
                                with self._lock:
                                    self._sticky_fatal -= 1
                # a real bug: propagate unchanged (the search engine's
                # compiled->host fallback still applies above us)
                self._record_event(item.key, item.group, cls, "raise",
                                   exc, st["attempt"])
                raise exc
            if cls == HUNG:
                self._count("timeouts")
                self._record_event(item.key, item.group, cls, "fail",
                                   exc, st["attempt"])
                _slog.warning(
                    "launch %s (group %d): watchdog timeout — failing "
                    "the search cleanly (completed chunks are already "
                    "checkpointed)", item.key, item.group, key=item.key)
                if isinstance(exc, LaunchTimeoutError):
                    raise exc
                raise LaunchTimeoutError(
                    item.key, item.group,
                    float(self.launch_timeout_s or 0.0)) from exc
            if cls == OOM:
                return self._recover_oom(st, exc)
            # TRANSIENT: exponential backoff + jitter, then re-run the
            # item's own phases — same program, same inputs
            st["attempt"] += 1
            self._retry_gate(item.key, item.group, st["attempt"], exc)
            try:
                with self._tracer.span("launch.retry", key=item.key,
                                       group=item.group,
                                       attempt=st["attempt"]):
                    self._maybe_inject(st)
                    payload = item.stage() if item.stage is not None \
                        else None
                    out = item.launch(payload)
                    return self.wait_ready(out, key=item.key,
                                           group=item.group)
            except Exception as e:
                exc = e

    def _recover_oom(self, st: Dict[str, Any], exc: Exception):
        item = st["item"]
        self._record_event(item.key, item.group, OOM, "recover", exc,
                           st["attempt"])
        sticky = bool(getattr(exc, "sst_sticky_oom", False))
        if item.bisect is not None:
            with self._tracer.span("launch.bisect", key=item.key,
                                   group=item.group):
                # the sticky count is shared supervisor state read by
                # every bisected sub-launch; recoveries can run on the
                # dispatch AND gather threads concurrently, so each
                # sticky recovery holds its own +1 for its duration
                if sticky:
                    with self._lock:
                        self._sticky_oom += 1
                try:
                    return _Recovered(item.bisect(self))
                finally:
                    if sticky:
                        with self._lock:
                            self._sticky_oom -= 1
        if item.host_fallback is not None:
            self.record_host_fallback(item.key, item.group, item.n_tasks)
            with self._tracer.span("launch.host_fallback", key=item.key,
                                   group=item.group):
                return _Recovered(item.host_fallback())
        _slog.warning(
            "launch %s: OOM with no bisect/host_fallback hook — "
            "propagating", item.key, key=item.key)
        raise exc


# ---------------------------------------------------------------------------
# Protection block (search_report["protection"])
# ---------------------------------------------------------------------------


def protection_enabled(config) -> bool:
    """Whether the self-protecting layer is active for this config.
    False is the exact-no-op escape hatch: no protection block, reports
    and cv_results_ byte-identical to the pre-protection engine."""
    return bool(getattr(config, "search_deadline_s", None)) or \
        str(getattr(config, "partial_results", "raise")
            or "raise") != "raise" or \
        str(getattr(config, "admission_mode", "static")
            or "static") != "static"


def protection_block(config, *, deadline_hit: bool = False,
                     shed: Sequence[Dict[str, Any]] = (),
                     quarantined: Sequence[Dict[str, Any]] = (),
                     elapsed_s: float = 0.0) -> Dict[str, Any]:
    """Render the pinned ``search_report["protection"]`` block (schema:
    ``obs.metrics.PROTECTION_BLOCK_SCHEMA``).  ``shed`` entries name
    candidates written to error_score without running (deadline or
    persistent-fault degradation); ``quarantined`` entries name poison
    candidates isolated after K single-lane FATALs."""
    shed = [dict(e) for e in shed]
    quarantined = [dict(e) for e in quarantined]
    causes = []
    if deadline_hit:
        causes.append("deadline")
    if quarantined:
        causes.append("quarantine")
    if any(e.get("reason") == "fault" for e in shed):
        causes.append("fault")
    partial = bool(shed or quarantined)
    verdict = "complete" if not causes and not partial else \
        "partial-" + "+".join(causes or ["declared"])
    return {
        "enabled": True,
        "mode": str(getattr(config, "admission_mode", "static")
                    or "static"),
        "partial_results": str(getattr(config, "partial_results",
                                       "raise") or "raise"),
        "deadline_s": float(getattr(config, "search_deadline_s", 0.0)
                            or 0.0),
        "deadline_hit": bool(deadline_hit),
        "elapsed_s": float(elapsed_s),
        "partial": partial,
        "n_candidates_shed": sum(
            len(e.get("candidates", ())) for e in shed),
        "n_quarantined": len(quarantined),
        "shed": shed,
        "quarantined": quarantined,
        "verdict": verdict,
    }


# ---------------------------------------------------------------------------
# Crash-marker context (the service journal's unclean-shutdown bundle)
# ---------------------------------------------------------------------------


def crash_marker_context(nonterminal: Dict[str, Dict[str, Any]],
                         lease_info: Optional[Dict[str, Any]] = None,
                         ) -> Dict[str, Any]:
    """The ``context`` block of a crash-marker flight bundle.

    Dumped by a session that fences a stale service-journal lease
    (serve/journal.py): the previous owner died without a clean
    shutdown, and the bundle's context names who it was, how stale its
    heartbeat stamp had grown, and every search it still owed —
    exactly what the postmortem (and ``tools/sst_doctor.py``) needs
    before the recovered searches overwrite the scene."""
    lease_info = dict(lease_info or {})
    prev = dict(lease_info.get("previous") or {})
    owed = []
    for handle in sorted(nonterminal):
        rec = nonterminal[handle]
        owed.append({
            "handle": handle,
            "tenant": str(rec.get("tenant", "")),
            "state": str(rec.get("state", "")),
            "family": str(rec.get("family", "")),
            "structure_digest": str(rec.get("structure_digest", "")),
            "checkpoint_dir": str(rec.get("checkpoint_dir", "")),
        })
    return {
        "crash_marker": True,
        "previous_pid": int(prev.get("pid", 0) or 0),
        "previous_owner": str(prev.get("owner", "")),
        "lease_stamp_unix_s": float(prev.get("ts_unix_s", 0.0) or 0.0),
        "n_nonterminal": len(owed),
        "nonterminal": owed,
    }
