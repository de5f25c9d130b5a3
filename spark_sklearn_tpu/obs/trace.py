"""Span tracer — thread-aware, nestable, bounded, near-free when off.

Design constraints (ISSUE 2 tentpole):

  - **thread-aware**: every span records the thread it closed on, so
    the pipeline's ``sst-stage`` / ``sst-gather`` / ``sst-compile``
    workers and the dispatching main thread each get their own track in
    the exported trace;
  - **nestable**: ``tracer.span(...)`` is a context manager; nesting
    follows Python's ``with`` stack, so spans on one thread are always
    properly nested (the Chrome trace viewer infers the hierarchy from
    timestamp containment);
  - **monotonic timestamps**: ``time.perf_counter()`` throughout —
    wall-clock adjustments can never produce negative durations;
  - **bounded**: events land in a ``deque(maxlen=...)`` ring buffer
    (default 65536); a pathological span storm evicts the oldest spans
    instead of growing without bound;
  - **overhead budget**: tracing OFF costs one attribute read and one
    ``TraceAnnotation.is_enabled()`` check per instrumentation site
    (the shared no-op span is returned before any allocation) and must
    be bit-exact with uninstrumented behavior; tracing ON is budgeted
    at **<2% of search wall** — spans are per-launch/per-phase (tens
    per search), never per-sample.  Both sides are enforced by
    ``tests/test_obs.py``;
  - **one clock with the device**: while a ``jax.profiler`` trace is
    being recorded (a user's ``TpuConfig(profile_dir=...)``, a
    benchmark's ``start_trace``), every span the vocabulary marks
    ``mirror`` is also written into that trace as the host event
    ``sst.<name>`` (a ``jax.profiler.TraceAnnotation``), whether or not
    this tracer is recording.  The profiler puts it on the axis of the
    device operations, so an idle gap of the device can be named by the
    span that covers it with no clock arithmetic.  The span's
    attributes and the search's number ride along as the event's stats.

Enablement: ``TpuConfig(trace=...)`` per search (``True`` records;
a string records AND exports a Chrome trace there after ``fit``), or
the ``SST_TRACE`` environment variable process-wide (``1``/``true`` to
record, any other value is treated as an export path).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from spark_sklearn_tpu.obs.spans import MIRRORED_SPANS

__all__ = [
    "Tracer",
    "current_correlation",
    "current_search",
    "get_tracer",
    "search_tracing",
    "set_correlation",
]

#: default ring-buffer capacity (events, not bytes)
DEFAULT_BUFFER_SIZE = 65536

# ---------------------------------------------------------------------------
# Correlation context (multi-tenant attribution)
# ---------------------------------------------------------------------------

#: thread-local {tenant, handle} stamped onto every event a thread
#: records (ISSUE 8 satellite: a multi-tenant Perfetto export used to
#: interleave three searches' spans with no way to tell whose is
#: whose).  Set by the serve executor's worker threads; propagated by
#: ChunkPipeline onto its stage/gather/compile workers; None for a
#: standalone fit, so untenanted traces stay byte-identical.
_CORR = threading.local()


#: per-process search numbers: ``search_tracing`` draws one per fit
_SEARCH_NUMBERS = itertools.count(1)


def set_correlation(attrs: Optional[Dict[str, Any]],
                    search: Optional[int] = None) -> None:
    """Bind (or clear, with None) the calling thread's correlation
    attributes, and the number of the search the thread works for.
    Explicit span attributes win over correlation keys on collision.
    The search number goes on mirrored profiler annotations only, so
    an untenanted fit's in-memory events stay byte-identical."""
    _CORR.attrs = dict(attrs) if attrs else None
    _CORR.search = search


def current_correlation() -> Optional[Dict[str, Any]]:
    """The calling thread's correlation attrs, or None."""
    return getattr(_CORR, "attrs", None)


def current_search() -> Optional[int]:
    """The number of the search the calling thread works for (what
    worker threads hand to :func:`set_correlation`), or None."""
    return getattr(_CORR, "search", None)


def _stamp(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """Merge the thread's correlation under explicit attrs (explicit
    keys win).  One getattr when no correlation is set — negligible on
    the recording path, absent entirely when tracing is off."""
    corr = getattr(_CORR, "attrs", None)
    if not corr:
        return attrs
    return {**corr, **attrs}

#: event tuples: (ph, name, t0, t1, track_key, track_name, attrs)
#:   ph "X" — complete span (t0..t1 on one thread or virtual track)
#:   ph "i" — instant event (t1 is None)
#:   ph "b" — async span (may overlap others on its virtual track;
#:            the exporter emits a Chrome b/e pair)
Event = Tuple[str, str, float, Optional[float], Any, str, Dict[str, Any]]


class _NullSpan:
    """Shared no-op span handed out when tracing is disabled — the
    entire cost of an instrumentation site with tracing off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class _Mirror(TraceAnnotation):
    """A span written into the live ``jax.profiler`` trace."""

    __slots__ = ()

    def set(self, **attrs):
        self.set_metadata(**attrs)
        return self


def _mirror(name: str, attrs: Dict[str, Any]) -> Optional[_Mirror]:
    """The profiler annotation of a mirrored span while a profiler
    session is live, else None."""
    if name not in MIRRORED_SPANS or not TraceAnnotation.is_enabled():
        return None
    search = current_search()
    if search is not None:
        attrs = {"search": search, **attrs}
    return _Mirror("sst." + name, **attrs)


class _Span:
    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._t0 = 0.0
        self._ann = _mirror(name, attrs)

    def set(self, **attrs):
        """Attach attributes after the span opened (e.g. results)."""
        self._attrs.update(attrs)
        if self._ann is not None:
            self._ann.set(**attrs)
        return self

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        th = threading.current_thread()
        # deque.append is atomic under the GIL: no lock on the hot path
        self._tracer._events.append(
            ("X", self._name, self._t0, t1, th.ident, th.name,
             _stamp(self._attrs)))
        return False


class Tracer:
    """Recorder of spans/instants into a bounded ring buffer.

    One process-global instance (``get_tracer()``) is shared by every
    instrumented layer; tests may construct private ones.
    """

    def __init__(self, max_events: int = DEFAULT_BUFFER_SIZE):
        self._events: deque = deque(maxlen=max_events)
        self._enabled = False

    # -- lifecycle -------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def max_events(self) -> int:
        return self._events.maxlen or 0

    def enable(self, max_events: Optional[int] = None) -> None:
        if max_events and max_events != self._events.maxlen:
            self._events = deque(self._events, maxlen=int(max_events))
        self._enabled = True

    def disable(self) -> None:
        """Stop recording; already-recorded events stay exportable."""
        self._enabled = False

    def clear(self) -> None:
        self._events.clear()

    # -- recording -------------------------------------------------------
    def span(self, name: str, **attrs):
        """Context manager timing a block on the current thread."""
        if not self._enabled:
            return _mirror(name, attrs) or _NULL_SPAN
        return _Span(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker on the current thread."""
        if not self._enabled:
            return
        th = threading.current_thread()
        self._events.append(
            ("i", name, time.perf_counter(), None, th.ident, th.name,
             _stamp(attrs)))

    def record_span(self, name: str, t0: float, t1: float,
                    track: Optional[str] = None, **attrs) -> None:
        """Retroactively record a span from explicit perf_counter
        timestamps — on the current thread, or on a named virtual track
        (e.g. the ``device`` occupancy track).  Spans on one virtual
        track must not overlap; use :meth:`record_async` when they can.
        """
        if not self._enabled:
            return
        if track is None:
            th = threading.current_thread()
            key, tname = th.ident, th.name
        else:
            key = tname = track
        self._events.append(("X", name, t0, t1, key, tname, _stamp(attrs)))

    def record_async(self, name: str, t0: float, t1: float, track: str,
                     **attrs) -> None:
        """Record a possibly-overlapping span on a virtual track (the
        exporter emits a Chrome async b/e pair, which the viewers lay
        out on parallel lanes)."""
        if not self._enabled:
            return
        self._events.append(("b", name, t0, t1, track, track,
                             _stamp(attrs)))

    # -- consumption -----------------------------------------------------
    def events(self) -> List[Event]:
        """Snapshot of the ring buffer, oldest first."""
        return list(self._events)

    def __len__(self) -> int:
        return len(self._events)


_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer every instrumented layer records to."""
    return _GLOBAL


def _env_spec() -> Tuple[bool, Optional[str]]:
    """(enabled, export_path) requested by the SST_TRACE env var."""
    v = os.environ.get("SST_TRACE", "").strip()
    if not v or v.lower() in ("0", "false", "off", "no"):
        return False, None
    if v.lower() in ("1", "true", "on", "yes"):
        return True, None
    return True, v


def _config_spec(config) -> Tuple[bool, Optional[str]]:
    """(enabled, export_path) requested by TpuConfig.trace."""
    spec = getattr(config, "trace", None) if config is not None else None
    if isinstance(spec, str) and spec:
        return True, spec
    return bool(spec), None


@contextlib.contextmanager
def search_tracing(config=None):
    """Scope the global tracer to one search.

    Enables recording when ``TpuConfig(trace=...)`` or ``SST_TRACE``
    asks for it (clearing the buffer so the export covers exactly this
    search), exports a Chrome trace afterwards when a path was given,
    and restores the tracer's prior state — a tracer something else
    enabled (a bench harness, an outer search) is never cleared or
    disabled here.
    """
    cfg_on, cfg_path = _config_spec(config)
    env_on, env_path = _env_spec()
    path = cfg_path or env_path
    tracer = _GLOBAL
    we_enabled = (cfg_on or env_on) and not tracer.enabled
    if we_enabled:
        tracer.clear()
        tracer.enable(max_events=getattr(config, "trace_buffer_size", None))
    outer_search = current_search()
    _CORR.search = next(_SEARCH_NUMBERS)
    try:
        yield tracer
    finally:
        _CORR.search = outer_search
        if path and (tracer.enabled or we_enabled):
            from spark_sklearn_tpu.obs.export import export_chrome_trace
            try:
                export_chrome_trace(path)
            except OSError:
                from spark_sklearn_tpu.obs.log import get_logger
                get_logger(__name__).debug(
                    "trace export to %r failed", path)
        if we_enabled:
            tracer.disable()


# process-wide opt-in via environment (import-time, so even code that
# never constructs a TpuConfig records)
if _env_spec()[0]:
    _GLOBAL.enable()
