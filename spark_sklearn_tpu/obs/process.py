"""The process ledger — what happens once a process, recorded always.

A search's spans and counters describe one search.  What a process
pays before its first search answers — the import chain, the caller's
own start-up (on a TPU host, the client's), every program traced,
lowered, compiled or loaded from the persistent cache, and the seconds
a dispatching thread stood waiting for a build — happens once, before
any tracer is switched on and before a profiler session starts.  This
module records those few things unconditionally, bounded, and hands
them to the readers through the report they already get:
``search_report["process"]`` (``obs/metrics.py::PROCESS_BLOCK_SCHEMA``),
cumulative to the end of that search, and :func:`process_report` for an
operator asking why a first search took a minute.

It is the ONE place the program listens to jax's monitoring events:

  - ``register_scalar_listener`` — the *enter* of a trace / lower /
    backend-compile phase (``dispatch.log_elapsed_time`` records its
    start time as a scalar), which gives each building thread a stack:
    a phase's seconds are its own, less the phases nested in it (the
    inner ``jit`` traced inside an outer one, the small eager program
    compiled while a big one is traced), so the totals are seconds of
    a thread's wall and count nothing twice;
  - ``register_event_time_span_listener`` — the *exit* of the same
    three phases, with ``fun_name``; the callback runs on the thread
    that builds;
  - ``register_event_duration_secs_listener`` —
    ``cache_retrieval_time_sec``, which jax records INSIDE the
    ``backend_compile_duration`` it fires just before, on the same
    thread: the two are paired by thread and the load is taken out of
    the enclosing phase (``xla_s`` = backend - retrieval: on a cache hit
    what is left is jax's bookkeeping, on a miss the compile);
  - ``register_event_listener`` — the persistent cache's hit / miss /
    consulted events.

One record a program built or loaded; the totals are exact, of the
records the 64 longest are kept.  The callbacks fire on compile events
only; a search that builds nothing makes two ledger calls, the stamps
of its ``fit``.
"""

from __future__ import annotations

import heapq
import threading
import time
from typing import Any, Dict, List, Optional

from spark_sklearn_tpu.obs.trace import current_search
from spark_sklearn_tpu.utils.locks import named_lock

__all__ = [
    "ProcessLedger",
    "building",
    "fit_begin",
    "fit_end",
    "join_build",
    "note_store",
    "persistent_cache_counts",
    "process_report",
    "touch",
]

#: records kept (the longest), and fits stamped (the first)
MAX_BUILDS = 64
MAX_FITS = 8
#: phases of one build follow each other on a thread; a trace that
#: ended longer ago than this belongs to no later build (eval_shape)
_ORPHAN_GAP_S = 1.0
_MAX_PENDING = 32

_TRACE, _LOWER, _BACKEND = "trace", "lower", "backend"
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
    "/jax/core/compile/backend_compile_duration": _BACKEND,
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_CONSULTED = "/jax/compilation_cache/compile_requests_use_cache"

#: the name (prefix) of the thread the compile-ahead executor builds on
#: (``parallel/pipeline.py`` names it from here): a build there costs
#: the wall only what a ``compile.wait`` span says
COMPILE_THREAD = "sst-compile"


def _base(name: str) -> str:
    """``jit(f)`` (the lowering's and the back end's name) -> ``f`` (the
    trace's)."""
    head, paren, rest = name.partition("(")
    if paren and rest.endswith(")"):
        return rest[:-1]
    return name


class _Frame:
    """One open phase of a building thread (or the thread's root): the
    seconds of the phases that closed inside it, and the traces and
    lowerings closed inside it that no build has taken yet, as
    ``[base name, trace_s, lower_s, t0_s, t1_s]``."""

    __slots__ = ("kind", "child_s", "pending")

    def __init__(self, kind: Optional[str]):
        self.kind = kind
        self.child_s = 0.0
        self.pending: List[list] = []

    def take(self, entry: list) -> None:
        """A closed phase's own seconds wait here for their build: the
        back end's exit of the same name takes them, else the enclosing
        phase's program does."""
        pending = self.pending
        if pending and entry[3] - pending[-1][4] > _ORPHAN_GAP_S:
            del pending[:]
        if pending and pending[-1][0] == entry[0]:
            last = pending[-1]
            last[1] += entry[1]
            last[2] += entry[2]
            last[3] = min(last[3], entry[3])
            last[4] = entry[4]
        else:
            pending.append(entry)
            if len(pending) > _MAX_PENDING:
                del pending[0]

    def fold(self, entry: list) -> list:
        """``entry`` with everything still pending here added to it."""
        for _, trace_s, lower_s, t0_s, _ in self.pending:
            entry[1] += trace_s
            entry[2] += lower_s
            entry[3] = min(entry[3], t0_s)
        del self.pending[:]
        return entry


class ProcessLedger:
    """The ledger of one process (``_LEDGER``; tests feed private ones
    through the same callbacks)."""

    def __init__(self, origin: Optional[float] = None):
        self._lock = named_lock("process.ProcessLedger._lock")
        self._tls = threading.local()
        #: perf_counter of the package import's first stamp: every
        #: ``*_s`` the ledger reports is relative to it
        self.origin = time.perf_counter() if origin is None else origin
        self.import_s = 0.0
        self.import_own_s = 0.0
        self.import_by_root: Dict[str, float] = {}
        self.first_call_s: Optional[float] = None
        self.fits: List[Dict[str, Any]] = []
        self.totals = {"n_programs": 0, "n_cache_hits": 0,
                       "n_cache_misses": 0, "trace_s": 0.0,
                       "lower_s": 0.0, "xla_s": 0.0, "cache_load_s": 0.0}
        self.wait_s = 0.0          # sum of compile.wait
        self.blocking_s = 0.0      # sum of builds that ran inside a fit
        #: jax's own hit / miss events (``persistent_cache_counts``)
        self.cache_events = {"hits": 0, "misses": 0}
        #: ledger calls, for the test that a warm fit makes two
        self.calls = 0
        self._seq = 0
        self._builds: List[Any] = []       # heap of (seconds, seq, record)
        self._union_s = 0.0                # closed part of the union
        self._union_open: List[List[float]] = []   # disjoint, ascending

    # -- a building thread's state ---------------------------------------
    def _thread(self):
        tls = self._tls
        if getattr(tls, "stack", None) is None:
            tls.stack = []
            tls.root = _Frame(None)
            tls.retrieval_s = 0.0
            tls.cache = "off"
            tls.label = None
            tls.collector = None
        return tls

    # -- jax's monitoring events -----------------------------------------
    def on_scalar(self, event: str, value, **kwargs) -> None:
        kind = _PHASES.get(event)
        if kind is not None:
            self._thread().stack.append(_Frame(kind))

    def on_event(self, event: str, **kwargs) -> None:
        if event == _CACHE_CONSULTED:
            self._thread().cache = "miss"      # until a hit says otherwise
        elif event == _CACHE_HIT:
            self._thread().cache = "hit"
            with self._lock:
                self.cache_events["hits"] += 1
        elif event == _CACHE_MISS:
            # recorded where jax WRITES an entry: a compile under the
            # cache's thresholds consults the cache and records neither
            with self._lock:
                self.cache_events["misses"] += 1

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == _RETRIEVAL:
            self._thread().retrieval_s += float(duration)

    def on_time_span(self, event: str, start: float, end: float,
                     **kwargs) -> None:
        kind = _PHASES.get(event)
        if kind is None:
            return
        # jax's stamps are time.time(): this clock's end is now
        t1_s = time.perf_counter() - self.origin
        dur = max(0.0, float(end) - float(start))
        tls = self._thread()
        frame = None
        while tls.stack:
            top = tls.stack.pop()
            if top.kind == kind:
                frame = top
                break
        if frame is None:                      # an exit with no enter
            frame = _Frame(kind)
        parent = tls.stack[-1] if tls.stack else tls.root
        own_s = max(0.0, dur - frame.child_s)
        parent.child_s += dur
        name = str(kwargs.get("fun_name", ""))
        base = _base(name)
        if kind != _BACKEND:
            with self._lock:
                self.totals[kind + "_s"] += own_s
            # inner traces that no build took are this program's own
            parent.take(frame.fold(
                [base, own_s if kind == _TRACE else 0.0,
                 own_s if kind == _LOWER else 0.0, t1_s - dur, t1_s]))
            return
        mine = frame.fold([base, 0.0, 0.0, t1_s - dur, t1_s])
        pending = parent.pending
        if pending and pending[-1][0] == base \
                and mine[3] - pending[-1][4] <= _ORPHAN_GAP_S:
            _, trace_s, lower_s, t0_s, _ = pending.pop()
            mine[1] += trace_s
            mine[2] += lower_s
            mine[3] = min(mine[3], t0_s)
        load_s = min(tls.retrieval_s, own_s)
        cache = tls.cache
        tls.retrieval_s, tls.cache = 0.0, "off"
        self._close(
            name=name, t0_s=mine[3], t1_s=t1_s,
            trace_s=mine[1], lower_s=mine[2], cache_load_s=load_s,
            xla_s=own_s - load_s, cache=cache, counts=True)

    # -- records ---------------------------------------------------------
    def _close(self, name, t0_s, t1_s, trace_s, lower_s, cache_load_s,
               xla_s, cache, counts) -> None:
        """One program built or loaded.  ``counts``: one of jax's builds
        (a store lookup adds its seconds and a record, not a program)."""
        tls = self._thread()
        thread = threading.current_thread().name
        search = current_search()
        blocking = search is not None \
            and not thread.startswith(COMPILE_THREAD)
        seconds = trace_s + lower_s + cache_load_s + xla_s
        record = {"name": name, "label": tls.label, "thread": thread,
                  "search": search, "t0_s": t0_s, "t1_s": t1_s,
                  "trace_s": trace_s, "lower_s": lower_s,
                  "cache_load_s": cache_load_s, "xla_s": xla_s,
                  "cache": cache, "blocking": blocking}
        if tls.collector is not None:
            c = tls.collector
            c["cache"] = cache
            c["cache_load_s"] += cache_load_s
            c["trace_s"] += trace_s
            c["lower_s"] += lower_s
        with self._lock:
            self.calls += 1
            tot = self.totals
            if counts:
                tot["n_programs"] += 1
                tot["n_cache_hits"] += cache == "hit"
                tot["n_cache_misses"] += cache == "miss"
            tot["xla_s"] += xla_s
            tot["cache_load_s"] += cache_load_s
            if blocking:
                self.blocking_s += seconds
            self._seq += 1
            heapq.heappush(self._builds, (seconds, self._seq, record))
            if len(self._builds) > MAX_BUILDS:
                heapq.heappop(self._builds)
            self._union_s += _cover(self._union_open, t0_s, t1_s)

    def note_store(self, op: str, t0: float, t1: float,
                   hit: bool = False) -> None:
        """A ``programstore`` lookup or publish (perf_counter stamps),
        as a build with nothing traced or lowered: a hit's seconds are a
        load, a miss's or a publish's are what the persistent cache's
        own write is to jax — part of having compiled."""
        seconds = max(0.0, t1 - t0)
        loaded = op == "load" and hit
        self._close(
            name="programstore." + op, t0_s=t0 - self.origin,
            t1_s=t1 - self.origin, trace_s=0.0, lower_s=0.0,
            cache_load_s=seconds if loaded else 0.0,
            xla_s=0.0 if loaded else seconds,
            cache="hit" if loaded else "miss", counts=False)

    # -- the wait, the import, the fits ----------------------------------
    def add_wait(self, seconds: float) -> None:
        with self._lock:
            self.calls += 1
            self.wait_s += seconds

    def note_import(self, stamps) -> None:
        """``(root, perf_counter)`` pairs of the package's ``__init__``:
        the first opens the import, each later one closes the import of
        a third-party root, or of the package's own modules (``""``)."""
        by_root: Dict[str, float] = {}
        own = 0.0
        for (_, a), (root, b) in zip(stamps, stamps[1:]):
            if root:
                by_root[root] = by_root.get(root, 0.0) + (b - a)
            else:
                own += b - a
        with self._lock:
            self.origin = stamps[0][1]
            self.import_s = stamps[-1][1] - stamps[0][1]
            self.import_own_s = own
            self.import_by_root = by_root

    def touch(self) -> None:
        """The first call into the program after its import."""
        if self.first_call_s is None:
            now = time.perf_counter() - self.origin
            with self._lock:
                if self.first_call_s is None:
                    self.first_call_s = now

    def fit_begin(self) -> None:
        now = time.perf_counter() - self.origin
        search = current_search()
        with self._lock:
            self.calls += 1
            if self.first_call_s is None:
                self.first_call_s = now
            if len(self.fits) < MAX_FITS:
                self.fits.append({"search": search, "t0_s": now,
                                  "t1_s": None})

    def fit_end(self) -> Dict[str, Any]:
        now = time.perf_counter() - self.origin
        search = current_search()
        with self._lock:
            self.calls += 1
            for rec in self.fits:
                if rec["search"] == search and rec["t1_s"] is None:
                    rec["t1_s"] = now
        return self.report()

    # -- the block -------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        """``search_report["process"]``: everything up to now."""
        with self._lock:
            tot = dict(self.totals)
            builds = sorted((dict(rec) for _, _, rec in self._builds),
                            key=lambda r: r["t0_s"])
            union_s = self._union_s + sum(
                hi - lo for lo, hi in self._union_open)
            return {
                "import_s": self.import_s,
                "import_own_s": self.import_own_s,
                "import_by_root": dict(self.import_by_root),
                "first_call_s": self.first_call_s,
                "fits": [dict(rec) for rec in self.fits],
                "n_programs": tot["n_programs"],
                "n_cache_hits": tot["n_cache_hits"],
                "n_cache_misses": tot["n_cache_misses"],
                "trace_s": tot["trace_s"],
                "lower_s": tot["lower_s"],
                "xla_s": tot["xla_s"],
                "cache_load_s": tot["cache_load_s"],
                "build_union_s": union_s,
                "build_blocked_s": self.wait_s + self.blocking_s,
                "builds": builds,
            }


def _cover(open_: List[List[float]], a: float, b: float) -> float:
    """Add [a, b] to a union of intervals kept as a disjoint, ascending
    list; returns the seconds of the list's head folded away once the
    list is long.  Records close in the order of their ends, so a new
    one merges with a tail of the list only."""
    while open_ and open_[-1][1] >= a:
        lo, hi = open_.pop()
        a, b = min(a, lo), max(b, hi)
    open_.append([a, b])
    if len(open_) > MAX_BUILDS:
        lo, hi = open_.pop(0)
        return hi - lo
    return 0.0


# ---------------------------------------------------------------------------
# the process's ledger and its listeners
# ---------------------------------------------------------------------------

_LEDGER = ProcessLedger()


def _install() -> None:
    # no ImportError guard: if jax moves this module the ledger must
    # fail loudly, not read a silent zero
    from jax._src import monitoring
    monitoring.register_scalar_listener(
        lambda event, value, **kw: _LEDGER.on_scalar(event, value, **kw))
    monitoring.register_event_time_span_listener(
        lambda event, start, end, **kw:
        _LEDGER.on_time_span(event, start, end, **kw))
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw:
        _LEDGER.on_duration(event, duration, **kw))
    monitoring.register_event_listener(
        lambda event, **kw: _LEDGER.on_event(event, **kw))


_install()


def persistent_cache_counts() -> Dict[str, int]:
    """Cumulative persistent-compile-cache hits/misses this process
    (jax's own events).  Callers snapshot before/after a search and
    report the delta."""
    return dict(_LEDGER.cache_events)


def process_report() -> Dict[str, Any]:
    """What this process paid once, up to now: the import (by
    third-party root), the first call into the program, its first fits,
    and every program built or loaded (the ``process`` block of
    ``search_report``, see its schema).  For an operator asking why a
    first search took a minute::

        import spark_sklearn_tpu as sst
        ...
        rep = sst.obs.process_report()
        rep["import_s"], rep["cache_load_s"], rep["build_blocked_s"]
        [b for b in rep["builds"] if b["blocking"]]
    """
    return _LEDGER.report()


def touch() -> None:
    _LEDGER.touch()


def fit_begin() -> None:
    _LEDGER.fit_begin()


def fit_end() -> Dict[str, Any]:
    return _LEDGER.fit_end()


def note_import(stamps) -> None:
    _LEDGER.note_import(stamps)


def note_store(op: str, t0: float, t1: float, hit: bool = False) -> None:
    _LEDGER.note_store(op, t0, t1, hit)


class building:
    """Around a build on this thread: its records carry ``label``, and
    :meth:`attrs` is what they summed to — the attributes the enclosing
    ``compile`` span sets on itself."""

    def __init__(self, label: str):
        self._label = label
        self._sums = {"cache": "off", "cache_load_s": 0.0,
                      "trace_s": 0.0, "lower_s": 0.0}

    def __enter__(self):
        tls = _LEDGER._thread()
        tls.label, tls.collector = self._label, self._sums
        return self

    def __exit__(self, *exc):
        tls = _LEDGER._thread()
        tls.label = tls.collector = None
        return False

    def attrs(self) -> Dict[str, Any]:
        return dict(self._sums)


def join_build(fut, tracer, **attrs):
    """``fut.result()`` of a compile-ahead future.  Where the build is
    still in flight the dispatching thread stands in a ``compile.wait``
    span, and its seconds reach the ledger whatever the tracer's
    state."""
    if fut.done():
        return fut.result()
    t0 = time.perf_counter()
    try:
        with tracer.span("compile.wait", **attrs):
            return fut.result()
    finally:
        _LEDGER.add_wait(time.perf_counter() - t0)
