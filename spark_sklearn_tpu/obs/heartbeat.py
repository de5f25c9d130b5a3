"""In-flight device telemetry — scan heartbeats, live progress and ETA.

PR 16's device-resident chunk loop (``chunk_loop="scan"``) melted a
whole compile group's chunks into ONE launch, which blinded every
launch-granularity sense the service had: per-chunk spans,
``SearchFuture.progress()``, the wall-clock ``launch_timeout_s``
watchdog and the telemetry device-busy feed all see a single opaque
multi-minute dispatch.  This module is the sensor layer that restores
intra-launch visibility:

  - :class:`HeartbeatHub` — a process-global, bounded aggregator of
    *beats*.  The scanned program's step body (``search/grid.py``
    ``_build_scan``) threads a ``jax.debug.callback`` beacon that calls
    :func:`device_beat` with ``(segment token, step index)`` while the
    device is still inside the launch; the per-chunk path emits a
    cheap host-side :func:`note_chunk` at dispatch.  Each beat updates
    the owning segment's ``steps_done`` / ``last_step`` /
    ``last_beat_t`` and inter-beat cadence under one named lock.
  - **live progress + ETA** — :meth:`HeartbeatHub.progress_for_handle`
    aggregates a search's live and completed segments into
    ``steps_done/steps_total`` plus an ETA whose per-step estimate
    blends the geometry cost model's prior
    (``launch_overhead_s + lanes x lane_cost_s``) with the observed
    inter-beat cadence, weighting the observation by how many beats
    back it (``serve/executor.py`` surfaces this from ``progress()``).
  - **watchdog feed** — :meth:`HeartbeatHub.staleness` tells the
    launch supervisor (``parallel/faults.py``, ``heartbeat_timeout_s``
    mode) how long ago a live segment last beat and which step it died
    on, so a hung scan is named by STEP, not by a whole-segment
    wall-clock budget.
  - **fleet surfacing** — :func:`heartbeat_block` renders the pinned
    ``search_report["heartbeat"]`` block
    (``obs.metrics.HEARTBEAT_BLOCK_SCHEMA``); :func:`snapshot_block`
    feeds the telemetry snapshot's ``heartbeat`` key (and from there
    the ``sst_heartbeat_*`` Prometheus families and
    ``tools/fleet_top.py``'s per-search progress column).

Enabled via ``TpuConfig(heartbeat=True)`` / ``SST_HEARTBEAT``
(:func:`resolve_heartbeat`).  Off (the default) is an exact no-op: no
callback is traced into the scan program (its presence joins the
program cache key in ``search/grid.py``, so on/off never alias), no
segment registers, ``cv_results_`` and ``search_report`` stay
byte-identical.  On, the contract is <2% traced wall (enforced by
``tests/test_heartbeat.py``), which is why the hub is stdlib-only and
each beat is one dict update under a lock — ``jax`` is never imported
here, so the per-chunk path and the tools can use the hub without
paying the device runtime.
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Any, Dict, List, Optional

from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.utils.locks import named_lock

__all__ = [
    "HEARTBEAT_RING_RECORDS",
    "HeartbeatHub",
    "device_beat",
    "get_hub",
    "heartbeat_block",
    "note_chunk",
    "resolve_heartbeat",
    "snapshot_block",
]

#: bounded beat-record ring (records, not bytes) — the flight
#: recorder's sizing discipline
HEARTBEAT_RING_RECORDS = 4096
#: completed segments kept for end-of-search reporting
MAX_DONE_SEGMENTS = 256
#: per-segment inter-beat gap samples kept for cadence percentiles
MAX_GAP_SAMPLES = 512


def resolve_heartbeat(config=None) -> bool:
    """Whether the in-flight heartbeat beacon is on under ``config``:
    ``TpuConfig.heartbeat``, else the ``SST_HEARTBEAT`` env var, else
    False — off is the exact-no-op default (no callback traced into
    the scan program, byte-identical reports)."""
    hb = getattr(config, "heartbeat", None) if config is not None else None
    if hb is not None:
        return bool(hb)
    env = os.environ.get("SST_HEARTBEAT", "").strip().lower()
    if not env or env in ("0", "false", "off", "no"):
        return False
    return True


def _pct(sorted_vals: List[float], p: float) -> float:
    """Nearest-rank percentile (the ``obs.telemetry.percentile``
    estimator, duplicated so the hub stays import-light)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            int(round(p / 100.0 * (len(sorted_vals) - 1))))
    return float(sorted_vals[i])


class _Segment:
    """One registered scan segment's live heartbeat state."""

    __slots__ = ("key", "token", "group", "segment", "scope", "handle",
                 "tenant", "n_steps", "steps_done", "last_step",
                 "last_beat_t", "t_register", "t_done", "est_step_s",
                 "beat_count", "gaps", "gap_max_s", "cap", "host_s",
                 "complete")

    def __init__(self, key: str, token: int, *, group: int, segment: int,
                 scope: str, handle: str, tenant: str, n_steps: int,
                 est_step_s: float):
        self.key = key
        self.token = token
        self.group = group
        self.segment = segment
        self.scope = scope
        self.handle = handle
        self.tenant = tenant
        self.n_steps = int(n_steps)
        self.steps_done = 0
        self.last_step: Optional[int] = None
        self.last_beat_t: Optional[float] = None
        self.t_register = time.perf_counter()
        self.t_done: Optional[float] = None
        self.est_step_s = float(est_step_s)
        self.beat_count = 0
        self.gaps: deque = deque(maxlen=MAX_GAP_SAMPLES)
        self.gap_max_s = 0.0
        self.cap: Optional[int] = None
        self.host_s = 0.0
        self.complete = False

    def blended_step_s(self) -> float:
        """Per-step estimate blending the geometry cost model's prior
        with the observed inter-beat cadence, the observation weighted
        by its sample count — a fresh segment trusts the model, a
        well-beaten one trusts the device."""
        gaps = sorted(self.gaps)
        cadence = _pct(gaps, 50.0)
        n = len(gaps)
        model = max(0.0, self.est_step_s)
        if n == 0:
            return model
        if model <= 0.0:
            return cadence
        return (model + cadence * n) / (1.0 + n)

    def eta_s(self, now: float) -> float:
        if self.complete:
            return 0.0
        remaining = max(0, self.n_steps - self.steps_done)
        return remaining * self.blended_step_s()


class HeartbeatHub:
    """Process-global bounded aggregator of in-flight beat records.

    Producers: the scan beacon (``jax.debug.callback`` ->
    :meth:`beat`, on jax's callback thread), the per-chunk dispatch
    path (:meth:`emit_chunk`, pipeline threads) and the scan items'
    stage/finalize hooks (register/complete, worker threads).
    Consumers: the executor's ``progress()``, the supervisor's
    heartbeat watchdog, the telemetry snapshot and the report block —
    every access serializes under one named lock, and tracer calls
    happen OUTSIDE it (no cross-module lock nesting)."""

    def __init__(self, max_records: int = HEARTBEAT_RING_RECORDS):
        self._lock = named_lock("heartbeat.HeartbeatHub._lock")
        self._ring: deque = deque(maxlen=int(max_records))
        self._next_token = 1
        self._by_token: Dict[int, _Segment] = {}
        self._live_by_key: Dict[str, _Segment] = {}
        self._done: deque = deque(maxlen=MAX_DONE_SEGMENTS)
        self._beats_total = 0
        self._chunk_beats_total = 0
        self._segments_total = 0
        self._capped_dropped = 0

    # -- segment lifecycle (scan items' stage/finalize hooks) ------------
    def new_scope(self, prefix: str = "fit") -> str:
        """A fresh scope id grouping one search's segments for the
        report block — ``cid_ns`` is empty for plain (non-halving)
        fits, so the hub mints its own; a halving search's rungs share
        the scope minted at rung 0."""
        with self._lock:
            token = self._next_token
            self._next_token += 1
        return f"{prefix}-{token}"

    def register_segment(self, key: str, *, group: int = -1,
                         segment: int = 0, n_steps: int = 0,
                         scope: str = "", handle: str = "",
                         tenant: str = "",
                         est_step_s: float = 0.0) -> int:
        """Announce a scanned launch and get the runtime token its
        beats will carry.  The token is a RUNTIME operand of the cached
        scan program (never a closure capture), so one compiled
        program serves every search's segments."""
        with self._lock:
            token = self._next_token
            self._next_token += 1
            seg = _Segment(key, token, group=int(group),
                           segment=int(segment), scope=scope,
                           handle=handle, tenant=tenant,
                           n_steps=int(n_steps),
                           est_step_s=float(est_step_s))
            # a re-registered key (retry of the same segment) replaces
            # the stale registration; its token dies with it
            old = self._live_by_key.get(key)
            if old is not None:
                self._by_token.pop(old.token, None)
            self._live_by_key[key] = seg
            self._by_token[token] = seg
            self._segments_total += 1
        return token

    def complete_segment(self, key: str) -> None:
        """Mark a segment finished (its finalize ran — scan success OR
        the per-chunk OOM fallback, either way every member chunk's
        results landed), clamping ``steps_done`` to ``n_steps`` so
        progress reaches total even when beats stopped mid-scan."""
        with self._lock:
            seg = self._live_by_key.pop(key, None)
            if seg is None:
                return
            self._by_token.pop(seg.token, None)
            seg.complete = True
            seg.steps_done = seg.n_steps
            seg.t_done = time.perf_counter()
            self._done.append(seg)
        tracer = get_tracer()
        if tracer.enabled and seg.t_done is not None:
            tracer.record_async(f"heartbeat.segment {key}",
                                seg.t_register, seg.t_done,
                                track="progress", group=seg.group,
                                steps=seg.n_steps, beats=seg.beat_count)

    # -- beats -----------------------------------------------------------
    def beat(self, token: int, step: int) -> None:
        """One in-flight beat from the scanned program's step body.
        Runs on jax's callback thread while the device is mid-launch —
        kept to one locked dict update plus an optional tracer instant
        so the <2% overhead contract holds."""
        t0 = time.perf_counter()
        with self._lock:
            seg = self._by_token.get(int(token))
            if seg is None:
                return
            step = int(step)
            if seg.cap is not None and step > seg.cap:
                # injected stall drill: beats past the cap are dropped,
                # so last_step freezes exactly where the plan said
                self._capped_dropped += 1
                return
            now = time.perf_counter()
            if seg.last_beat_t is not None:
                gap = now - seg.last_beat_t
                seg.gaps.append(gap)
                if gap > seg.gap_max_s:
                    seg.gap_max_s = gap
            seg.last_beat_t = now
            seg.last_step = step if seg.last_step is None \
                else max(seg.last_step, step)
            seg.steps_done = max(seg.steps_done,
                                 min(seg.n_steps, step + 1))
            seg.beat_count += 1
            self._beats_total += 1
            self._ring.append({
                "kind": "beat", "key": seg.key, "group": seg.group,
                "segment": seg.segment, "step": step,
                "handle": seg.handle, "t_mono_s": now,
            })
            seg.host_s += time.perf_counter() - t0
            key, group = seg.key, seg.group
        tracer = get_tracer()
        if tracer.enabled:
            tracer.instant("heartbeat.beat", key=key, group=group,
                           step=step)

    def emit_chunk(self, key: str, group: int) -> None:
        """A cheap dispatch-time beat for the per-chunk launch path —
        no device callback, just the hub hearing that chunk ``key``
        entered the device stream."""
        with self._lock:
            self._chunk_beats_total += 1
            self._ring.append({
                "kind": "chunk", "key": str(key), "group": int(group),
                "segment": -1, "step": -1, "handle": "",
                "t_mono_s": time.perf_counter(),
            })

    # -- watchdog + injection feeds --------------------------------------
    def live_segment(self, key: str) -> bool:
        """Whether a registered, un-completed segment owns ``key`` —
        the supervisor's gate for heartbeat-mode waiting."""
        with self._lock:
            return key in self._live_by_key

    def staleness(self, key: str) -> Optional[Dict[str, Any]]:
        """The heartbeat watchdog's view of a live segment: seconds
        since its last beat (registration when none arrived yet), the
        last step that beat, and the segment's step count.  None when
        no live segment owns ``key``."""
        with self._lock:
            seg = self._live_by_key.get(key)
            if seg is None:
                return None
            now = time.perf_counter()
            anchor = seg.last_beat_t if seg.last_beat_t is not None \
                else seg.t_register
            return {
                "age_s": max(0.0, now - anchor),
                "last_step": seg.last_step,
                "steps_done": seg.steps_done,
                "n_steps": seg.n_steps,
            }

    def cap_beats(self, key: str, max_step: int) -> bool:
        """Deterministic stall drill (``fault_plan="hung@I:STEP"``):
        drop every beat past ``max_step`` on ``key``'s live segment so
        the heartbeat goes silent at exactly that step and the
        watchdog's staleness detector fires naming it."""
        with self._lock:
            seg = self._live_by_key.get(key)
            if seg is None:
                return False
            seg.cap = int(max_step)
            return True

    # -- progress / ETA --------------------------------------------------
    def _segments_for(self, *, handle: Optional[str] = None,
                      scope: Optional[str] = None) -> List[_Segment]:
        segs = list(self._live_by_key.values()) + list(self._done)
        if handle is not None:
            segs = [s for s in segs if s.handle == handle]
        if scope is not None:
            segs = [s for s in segs if s.scope == scope]
        return segs

    def _progress_of(self, segs: List[_Segment]) -> Optional[Dict[str, Any]]:
        if not segs:
            return None
        now = time.perf_counter()
        total = sum(s.n_steps for s in segs)
        done = sum(s.steps_done for s in segs)
        return {
            "segments": len(segs),
            "steps_total": int(total),
            "steps_done": int(done),
            "frac": round(done / total, 6) if total else 0.0,
            "eta_s": round(sum(s.eta_s(now) for s in segs), 6),
            "beats": int(sum(s.beat_count for s in segs)),
        }

    def progress_for_handle(self, handle: str) -> Optional[Dict[str, Any]]:
        """Live intra-segment progress for one executor search handle
        — None when the handle has no (heartbeat-registered) segments,
        so a heartbeat-off search's ``progress()`` dict is unchanged."""
        if not handle:
            return None
        with self._lock:
            return self._progress_of(self._segments_for(handle=handle))

    def progress_by_handle(self) -> Dict[str, Dict[str, Any]]:
        """Every handle's progress view — the telemetry snapshot's
        ``heartbeat.searches`` map (what ``tools/fleet_top.py``
        renders as the progress/ETA column)."""
        with self._lock:
            handles = sorted({s.handle for s in self._segments_for()
                              if s.handle})
            return {h: self._progress_of(self._segments_for(handle=h))
                    for h in handles}

    # -- reporting -------------------------------------------------------
    def _scope_stats(self, scope: Optional[str]) -> Dict[str, Any]:
        with self._lock:
            segs = self._segments_for(scope=scope) if scope \
                else self._segments_for()
            gaps = sorted(g for s in segs for g in s.gaps)
            walls = [((s.t_done if s.t_done is not None
                       else time.perf_counter()) - s.t_register)
                     for s in segs]
            wall = sum(walls)
            host = sum(s.host_s for s in segs)
            return {
                "beats": sum(s.beat_count for s in segs),
                "chunk_beats": self._chunk_beats_total,
                "segments": len(segs),
                "steps_total": sum(s.n_steps for s in segs),
                "steps_done": sum(s.steps_done for s in segs),
                "p50": _pct(gaps, 50.0),
                "p95": _pct(gaps, 95.0),
                "stale_max": max([s.gap_max_s for s in segs],
                                 default=0.0),
                "host_s": host,
                "wall_s": wall,
            }

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "beats_total": self._beats_total,
                "chunk_beats_total": self._chunk_beats_total,
                "segments_total": self._segments_total,
                "live_segments": len(self._live_by_key),
                "capped_dropped": self._capped_dropped,
            }

    def reset(self) -> None:
        """Drop all beat/segment state (test isolation)."""
        with self._lock:
            self._ring.clear()
            self._by_token.clear()
            self._live_by_key.clear()
            self._done.clear()
            self._beats_total = 0
            self._chunk_beats_total = 0
            self._segments_total = 0
            self._capped_dropped = 0


_HUB = HeartbeatHub()


def get_hub() -> HeartbeatHub:
    """The process-global heartbeat hub every beacon reports to."""
    return _HUB


def device_beat(token, step) -> None:
    """The ``jax.debug.callback`` target the scan step body calls:
    receives the segment token and step index as numpy scalars while
    the device is mid-launch (``search/grid.py`` makes the jax call —
    this module never imports jax)."""
    _HUB.beat(int(token), int(step))


def note_chunk(key: str, group: int) -> None:
    """Per-chunk dispatch beat (``parallel/pipeline.py`` calls this
    only when the pipeline resolved heartbeat on, so off stays an
    exact no-op)."""
    _HUB.emit_chunk(str(key), int(group))


def heartbeat_block(scope: str = "") -> Dict[str, Any]:
    """Render the ``search_report["heartbeat"]`` block for one
    search's scope (schema pinned in
    ``obs.metrics.HEARTBEAT_BLOCK_SCHEMA``).  Rendered ONLY when the
    heartbeat resolved on — off keeps the report byte-identical to
    the pre-heartbeat shape, like the memory block's discipline."""
    st = _HUB._scope_stats(scope or None)
    wall = st["wall_s"]
    return {
        "enabled": True,
        "beats_total": int(st["beats"]),
        "chunk_beats_total": int(st["chunk_beats"]),
        "n_segments": int(st["segments"]),
        "steps_total": int(st["steps_total"]),
        "steps_done": int(st["steps_done"]),
        "cadence_p50_s": round(st["p50"], 6),
        "cadence_p95_s": round(st["p95"], 6),
        "staleness_max_s": round(st["stale_max"], 6),
        "overhead_est_s": round(st["host_s"], 6),
        "overhead_frac": round(st["host_s"] / wall, 6)
        if wall > 0 else 0.0,
    }


def snapshot_block() -> Dict[str, Any]:
    """The telemetry snapshot's ``heartbeat`` entry: process-wide beat
    totals plus every live search handle's progress/ETA view (the
    fleet endpoint's ``sst_heartbeat_*`` families and
    ``tools/fleet_top.py`` read this)."""
    st = _HUB._scope_stats(None)
    block: Dict[str, Any] = _HUB.stats()
    block["cadence_p50_s"] = round(st["p50"], 6)
    block["cadence_p95_s"] = round(st["p95"], 6)
    block["staleness_max_s"] = round(st["stale_max"], 6)
    block["searches"] = _HUB.progress_by_handle()
    return block
