"""Named locks + the opt-in runtime lock-order recorder.

Every lock the engine's threads contend on is created through
:func:`named_lock` / :func:`named_rlock` with a stable dotted name
(``"dataplane._TOTALS_LOCK"``, ``"schedule.GroupPlan._lock"``, ...).  Two
consumers build on the names:

  - ``tools/sstlint`` finds the lock registry STATICALLY (the factory
    calls are its anchor) and checks the acquisition graph for cycles,
    cross-module nesting, and shared-state mutation outside the
    owning lock;
  - under ``SST_LOCKCHECK=1`` the factories return instrumented locks
    that record the ACTUAL acquisition orders while the test suite
    runs.  An order inversion (lock A taken under B on one thread and
    B under A on another — the deadlock precondition the static pass
    can only approximate) is recorded with both stacks and fails the
    suite via the conftest hook; holds longer than
    ``SST_LOCKCHECK_HOLD_S`` (default 1.0 s — e.g. a lock held across
    a blocking ``device_put``/``block_until_ready`` that stalls every
    other thread) are reported as warnings.

Off (the default) the factories return plain ``threading`` locks:
zero overhead, zero behavior change.
"""

from __future__ import annotations

import os
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "CheckedLock",
    "LockOrderRecorder",
    "get_recorder",
    "lockcheck_enabled",
    "named_lock",
    "named_rlock",
]


def lockcheck_enabled() -> bool:
    """Is the runtime recorder active (``SST_LOCKCHECK=1``)?  Read at
    each factory call so tests may flip it; locks created earlier keep
    whatever instrumentation they were born with."""
    return os.environ.get("SST_LOCKCHECK", "").strip().lower() in (
        "1", "true", "on", "yes")


def _hold_threshold_s() -> float:
    try:
        return float(os.environ.get("SST_LOCKCHECK_HOLD_S", "1.0"))
    except ValueError:
        return 1.0


class LockOrderRecorder:
    """Accumulates acquisition-order edges across all instrumented
    locks.

    An *edge* (A -> B) means some thread acquired B while holding A.
    An *inversion* is a pair of edges (A -> B) and (B -> A): two
    threads interleaving those paths can deadlock.  Inversions are
    recorded once per unordered pair, with the stacks of both sides.
    """

    def __init__(self):
        self._mu = threading.Lock()
        #: (held, acquired) -> {"thread", "stack"} of the first observation
        self.edges: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self.inversions: List[Dict[str, Any]] = []
        self.long_holds: List[Dict[str, Any]] = []
        self._tls = threading.local()

    # -- per-thread held stack -------------------------------------------
    def _held(self) -> List[str]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    # -- recording -------------------------------------------------------
    def note_acquired(self, name: str) -> None:
        held = self._held()
        if held and held[-1] != name:
            stack = "".join(traceback.format_stack(limit=8)[:-2])
            th = threading.current_thread().name
            with self._mu:
                for h in held:
                    if h == name:      # reentrant: never a self-edge
                        continue
                    edge = (h, name)
                    if edge not in self.edges:
                        self.edges[edge] = {"thread": th, "stack": stack}
                        rev = self.edges.get((name, h))
                        if rev is not None:
                            self.inversions.append({
                                "locks": (h, name),
                                "thread_a": rev["thread"],
                                "stack_a": rev["stack"],
                                "thread_b": th,
                                "stack_b": stack,
                            })
        held.append(name)

    def note_released(self, name: str, held_s: float) -> None:
        held = self._held()
        # locks may legitimately release out of LIFO order
        for i in range(len(held) - 1, -1, -1):
            if held[i] == name:
                del held[i]
                break
        if held_s >= _hold_threshold_s():
            with self._mu:
                self.long_holds.append({
                    "lock": name, "held_s": round(held_s, 4),
                    "thread": threading.current_thread().name,
                })

    # -- consumption -----------------------------------------------------
    def report(self) -> Dict[str, Any]:
        with self._mu:
            return {
                "n_edges": len(self.edges),
                "edges": sorted(self.edges),
                "inversions": list(self.inversions),
                "long_holds": list(self.long_holds),
            }

    def reset(self) -> None:
        with self._mu:
            self.edges.clear()
            self.inversions.clear()
            self.long_holds.clear()


_RECORDER = LockOrderRecorder()


def get_recorder() -> LockOrderRecorder:
    """The process-global recorder every instrumented lock reports
    to (tests may construct private :class:`LockOrderRecorder`\\ s)."""
    return _RECORDER


class CheckedLock:
    """A named wrapper over a ``threading`` lock that reports its
    acquisition order and hold times to a :class:`LockOrderRecorder`.

    Supports the context-manager protocol plus ``acquire``/``release``
    and reentrant inner locks (an RLock re-acquisition records
    nothing — it cannot order against itself)."""

    __slots__ = ("_lock", "name", "_recorder", "_depth", "_t_acquired")

    def __init__(self, lock, name: str,
                 recorder: Optional[LockOrderRecorder] = None):
        self._lock = lock
        self.name = name
        self._recorder = recorder if recorder is not None else _RECORDER
        self._depth = threading.local()
        self._t_acquired = threading.local()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._lock.acquire(blocking, timeout)
        if got:
            depth = getattr(self._depth, "n", 0)
            self._depth.n = depth + 1
            if depth == 0:
                self._t_acquired.t = time.perf_counter()
                self._recorder.note_acquired(self.name)
        return got

    def release(self) -> None:
        depth = getattr(self._depth, "n", 0) - 1
        self._depth.n = depth
        if depth == 0:
            held_s = time.perf_counter() - getattr(
                self._t_acquired, "t", time.perf_counter())
            self._recorder.note_released(self.name, held_s)
        self._lock.release()

    def __enter__(self) -> "CheckedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False

    def locked(self) -> bool:
        # threading.RLock grows .locked() only in 3.14; fall back to
        # this thread's recursion depth so the instrumented variant
        # never diverges from the plain one by raising
        inner = getattr(self._lock, "locked", None)
        if inner is not None:
            return inner()
        return getattr(self._depth, "n", 0) > 0

    def __repr__(self) -> str:
        return f"CheckedLock({self.name!r})"


def named_lock(name: str):
    """A ``threading.Lock`` registered under ``name`` — instrumented
    when ``SST_LOCKCHECK=1``, a plain lock otherwise."""
    if lockcheck_enabled():
        return CheckedLock(threading.Lock(), name)
    return threading.Lock()


def named_rlock(name: str):
    """A ``threading.RLock`` registered under ``name`` — instrumented
    when ``SST_LOCKCHECK=1``, a plain RLock otherwise."""
    if lockcheck_enabled():
        return CheckedLock(threading.RLock(), name)
    return threading.RLock()
