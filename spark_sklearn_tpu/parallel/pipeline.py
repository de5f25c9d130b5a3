"""Pipelined chunk executor — overlap host work with device compute.

The search engine launches its (candidate x fold) grid as a sequence of
chunked XLA programs.  Run synchronously (stage -> dispatch -> block ->
gather, one chunk at a time) every host phase serializes with the device:
staging chunk k+1's dynamic params, gathering chunk k-1's scores, and
lowering the NEXT compile group's program all stall the accelerator —
exactly the executor-overlap problem of distributed-Spark ML (arXiv:
1612.01437) and the pipelined-dispatch answer of MPMD pipeline training
(arXiv:2412.14374).

`ChunkPipeline` runs the same launch sequence double-buffered:

  - a *stage* thread prepares chunk k+1's host inputs (mask tiling,
    candidate stacking, `device_put`) while chunk k executes;
  - the main thread dispatches launches in order (JAX dispatch is async:
    the call returns as soon as the program is enqueued), so a trace or
    compile triggered by the next compile group's first chunk runs while
    the device is still busy with the previous group;
  - a *gather* thread blocks on each launch's outputs, timestamps device
    readiness, runs the (blocking) `device_get` transfer, and finalizes
    results in dispatch order;
  - a *compile* thread AOT-lowers the next compile group's program
    (`jit(...).lower(...).compile()`) so group boundaries stop stalling
    the device; the persistent compilation cache (below) makes the same
    walk survive process restarts.

`depth=0` is the escape hatch: every phase runs inline on the calling
thread in today's synchronous order, bit-for-bit, for debugging and A/B
benchmarks.  Scores are identical at any depth — the pipeline reorders
*host* work only; every launch sees the same program and the same
inputs.

A per-launch timeline (stage/dispatch/compute/gather walls and the
overlap fraction) accumulates into `pipeline_report()` so the win — or
its absence on a host-bound box — is observable in `search_report`.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import jax

from spark_sklearn_tpu.obs import heartbeat as _heartbeat
from spark_sklearn_tpu.obs import process as _process
from spark_sklearn_tpu.obs import telemetry as _telemetry
from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs.trace import (
    current_correlation,
    current_search,
    get_tracer,
    set_correlation,
)
from spark_sklearn_tpu.parallel import dataplane as _dataplane
from spark_sklearn_tpu.parallel import memledger as _memledger
from spark_sklearn_tpu.parallel import ownership
from spark_sklearn_tpu.utils.locks import named_lock

_slog = get_logger(__name__)

__all__ = [
    "ChunkPipeline",
    "FuseSpec",
    "FusedLaunch",
    "LaunchItem",
    "LaunchTimings",
    "enable_persistent_cache",
    "persistent_cache_counts",
    "precompile",
    "resolve_compile_cache_dir",
]


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache
# ---------------------------------------------------------------------------

#: the persistent cache's hit / miss counters live with the program's
#: other jax-monitoring listeners in ``obs/process.py``, registered at
#: the package's import; the names stay importable from here
_CACHE_EVENTS = _process._LEDGER.cache_events
_LISTENER_LOCK = named_lock("pipeline._LISTENER_LOCK")
persistent_cache_counts = _process.persistent_cache_counts


def _install_cache_listener() -> None:
    """Kept for callers of the old name: the listeners are installed
    where ``obs/process.py`` is imported, once."""


#: where the persistent cache lives when neither the environment nor
#: the TpuConfig names a directory: ONE fixed, git-ignored path inside
#: the checkout.  The path is part of jax's cache key, so a directory
#: that moves between runs (a temp name, a pid, a timestamp) never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_compile_cache_dir(config=None) -> str:
    """The one place the persistent compilation cache directory is
    decided.  ``JAX_COMPILATION_CACHE_DIR`` wins whenever it is set —
    whoever runs the program places the cache, whatever the TpuConfig
    says; then ``TpuConfig.compilation_cache_dir`` /
    ``compile_cache_dir``; then :data:`DEFAULT_COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    named = config.resolved_cache_dir() if config is not None else None
    return named or DEFAULT_COMPILE_CACHE_DIR


#: the directory this process bound (the first enable_persistent_cache
#: call decides) and the different directories asked for since
_BOUND_CACHE_DIR: Optional[str] = None
_REFUSED_CACHE_DIRS: set = set()


def enable_persistent_cache(config=None) -> str:
    """Bind jax's persistent compilation cache for this PROCESS and
    return the bound directory.

    Amortizes the cold python->jaxpr->HLO->binary walk across processes
    (repeated runs, checkpoint-resume restarts): the first process pays
    the XLA compile, every later process with the same program shapes
    reloads the serialized executable.

    The first call decides.  It writes the resolved directory
    (:func:`resolve_compile_cache_dir`), the config's min-compile
    threshold and the two options that put the programs' debug metadata
    (named scopes, one source frame an op) into the cache key to the
    live jax config, once; where neither the
    environment nor the TpuConfig places the cache, a directory the
    user already set in code (``jax.config.update``) is kept over the
    default.  jax itself binds its cache at the process's first compile
    and re-binding is not safe while another thread compiles (one
    session serves several tenants, each with a compile-ahead thread),
    so a later call that names another directory changes nothing and
    says so in the log.  Call this before the process's first compile
    (a `TpuSession`, or the first search, does) — a process that
    compiled with no directory set keeps no cache."""
    global _BOUND_CACHE_DIR
    wanted = resolve_compile_cache_dir(config)
    if _BOUND_CACHE_DIR is None:
        _process.touch()       # the first call into the program
    with _LISTENER_LOCK:
        if _BOUND_CACHE_DIR is None:
            preset = jax.config.jax_compilation_cache_dir
            if wanted == DEFAULT_COMPILE_CACHE_DIR and preset:
                wanted = preset
            if preset != wanted:
                jax.config.update("jax_compilation_cache_dir", wanted)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(getattr(config, "persistent_cache_min_compile_s",
                              0.5)))
            # the programs' jax.named_scope phases (obs/spans.py, kind
            # "scope") are debug metadata, which jax strips from the
            # cache key by default: an executable compiled before a
            # scope existed, or was renamed, would be served under the
            # same key with the old names in it, and a profiler trace
            # would show them.  With the metadata in the key such an
            # entry misses and is compiled again.  The metadata also
            # holds each op's python traceback, and which thread first
            # traced a shared inner function (the compile-ahead thread
            # or the dispatching one) decides the outer frames: keys
            # would differ from run to run.  One frame — the line that
            # made the op, the trace's `source` — is the same whoever
            # called, so the key follows the package's own source and
            # not the caller's script or a race.
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)
            jax.config.update("jax_traceback_in_locations_limit", 1)
            _BOUND_CACHE_DIR = wanted
        elif wanted not in (_BOUND_CACHE_DIR, DEFAULT_COMPILE_CACHE_DIR) \
                and wanted not in _REFUSED_CACHE_DIRS:
            _REFUSED_CACHE_DIRS.add(wanted)
            _slog.warning(
                "compile cache is bound to %r for this process; the "
                "request for %r is ignored", _BOUND_CACHE_DIR, wanted)
        return _BOUND_CACHE_DIR


def precompile(jit_fn, *args):
    """AOT-lower and compile `jit_fn` for the given (abstract or
    concrete) arguments; returns the compiled executable, which produces
    bit-identical results to calling `jit_fn` (same jaxpr, same compile
    options).  Raises whatever tracing/compilation raises — callers fall
    back to the plain jit path.

    Store-backed programs (parallel/programstore.StoredProgram, exposed
    via their ``resolve`` hook) consult the persistent artifact store
    BEFORE any lowering: a hit substitutes the deserialized artifact's
    wrapper — no python->jaxpr walk at all — and a miss exports and
    publishes the program so the next cold process hits.  Either way
    the lower+compile below still AOT-compiles the resulting callable
    on this (compile) thread, so group boundaries never stall the
    device, and the persistent XLA cache covers the binary."""
    resolve = getattr(jit_fn, "resolve", None)
    if resolve is not None:
        jit_fn = resolve(*args)
    return jit_fn.lower(*args).compile()


# ---------------------------------------------------------------------------
# Launch pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LaunchTimings:
    """Per-launch wall breakdown.  `compute_s` is the device-occupancy
    estimate: time from this launch becoming the head of the device
    queue (max of its dispatch time and the previous launch's readiness)
    to its outputs being ready."""

    stage_s: float = 0.0      # host staging work (thread-side wall)
    stage_wait_s: float = 0.0  # un-hidden staging wait on the dispatcher
    dispatch_s: float = 0.0
    compute_s: float = 0.0
    gather_s: float = 0.0
    finalize_s: float = 0.0
    stage_bytes: int = 0      # host->device bytes the stage transferred
    #: time this launch's dispatch spent waiting in the multi-tenant
    #: fair-share queue (serve/executor.py) — subtracted out of
    #: dispatch_s by the executor's item wrapper so contention never
    #: poisons the geometry cost model's launch-overhead estimate
    queue_wait_s: float = 0.0


@dataclasses.dataclass
class LaunchItem:
    """One device launch plus its host-side phases.

    stage    () -> staged payload (host prep + device_put); optional.
    launch   (staged) -> device outputs.  Runs on the dispatching thread
             in submission order; JAX dispatch is async so it returns as
             soon as the program is enqueued (first call may trace and
             compile — that wall lands in `dispatch_s`).
    wait     (device outputs) -> device outputs, blocking until ready;
             optional (default `jax.block_until_ready`).  The fault
             supervisor (parallel/faults.py) installs its watchdog /
             retry / bisection recovery here — errors from async
             dispatch surface at this blocking point.
    gather   (device outputs) -> host results (the blocking transfer);
             optional.
    finalize (host results, LaunchTimings) -> None.  Runs in submission
             order; result-array writes, checkpointing, and report
             accounting belong here.

    `bisect` / `host_fallback` are recovery hooks consumed by the fault
    supervisor, never by the pipeline itself: `bisect(supervisor)`
    re-runs the launch as narrower half-chunks after an OOM and returns
    the merged result in `gather`'s output shape; `host_fallback()`
    computes the same shape per-candidate on the host (exact sklearn
    error_score semantics) when bisection bottoms out.
    """

    key: str
    launch: Callable[[Any], Any]
    stage: Optional[Callable[[], Any]] = None
    gather: Optional[Callable[[Any], Any]] = None
    finalize: Optional[Callable[[Any, LaunchTimings], None]] = None
    group: int = 0
    kind: str = "launch"
    n_tasks: int = 0
    #: chunks this launch serves — 1 for the per-chunk paths, the
    #: scan-segment member count for kind="scan" items (search/grid.py
    #: chunk_loop="scan"): the timeline then pins the launch-boundary
    #: collapse (one record, many chunks)
    n_chunks: int = 1
    wait: Optional[Callable[[Any], Any]] = None
    bisect: Optional[Callable[[Any], Any]] = None
    host_fallback: Optional[Callable[[], Any]] = None
    #: cross-search fusion handle (a FuseSpec) — present only on items
    #: whose launch may be coalesced with same-key peers from OTHER
    #: searches by the multi-tenant executor (serve/executor.py); the
    #: pipeline itself never reads it
    fuse: Optional["FuseSpec"] = None


@dataclasses.dataclass
class FuseSpec:
    """One search's offer to share a device launch with same-program
    peers from other searches.

    The multi-tenant executor groups queued specs by ``key`` — two specs
    with equal keys run the SAME compiled program on concatenable inputs
    (family + compile-group structure + geometry + broadcast-plane
    identity) — and hands each group to a :class:`FusedLaunch`.

    ``run``/``slice_out`` keep the device details inside the member's
    own closure (search/grid.py builds them next to the solo launch
    path), so this layer stays jax-shape-agnostic:

    run(specs)            stage + execute ONE wide launch covering every
                          member's real rows, in list order, padded once
                          at the coalesced width; returns raw device
                          outputs.
    slice_out(out, off, n) a member's view of those outputs — the rows
                          [off, off+n) — in exactly the shape its solo
                          ``gather`` expects.  vmap lanes are
                          independent, so each member's lanes are
                          bit-identical to its solo launch.
    rows()                the member's real (unpadded) host rows per
                          dynamic param — what ``run`` concatenates.
    """

    key: Any                       # hashable program-identity tuple
    n: int                         # real candidate rows this member adds
    shard: int                     # task-shard multiple widths pad to
    max_width: int                 # member's HBM width ceiling (0 = none)
    rows: Callable[[], Dict[str, Any]]
    run: Callable[[List["FuseSpec"]], Any]
    slice_out: Callable[[Any, int, int], Any]


class FusedLaunch(ownership.LaunchOwner):
    """ONE device launch serving many searches' chunks.

    This is the launch-ownership refactor's second owner kind (the first
    is halving's rung context): the fused launch owns the shared device
    program invocation, while every member search keeps its own journal
    lines, fault supervisor and result buffers — one launch, many
    journals/supervisors.  The executor builds one per coalesced group,
    calls :meth:`run` once on its dispatch loop, and scatters the
    per-member outputs back through each member's reply.

    Fault scatter needs no machinery here: an exception from the wide
    launch is delivered to EVERY member, and each member's supervisor
    recovers by re-running only its OWN [lo, hi) range through its solo
    bisect hook — so an OOM/FATAL bisects to member boundaries first,
    then within the faulting member, and one tenant's poison candidate
    never retries another tenant's rows.
    """

    kind = "fused"

    def __init__(self, specs: List[FuseSpec]):
        if not specs:
            raise ValueError("FusedLaunch needs at least one member")
        self.specs = list(specs)
        self.offsets: List[int] = []
        off = 0
        for s in self.specs:
            self.offsets.append(off)
            off += int(s.n)
        #: total real rows across members (pre-padding)
        self.n_total = off
        self._out: Any = None

    def members(self) -> List[FuseSpec]:
        return list(self.specs)

    def padded_width(self) -> int:
        """The coalesced launch width: total real rows padded up to the
        members' (shared) task-shard multiple."""
        shard = max(1, int(self.specs[0].shard))
        return max(shard, -(-self.n_total // shard) * shard)

    def lanes_padding(self) -> int:
        """Padded-lane waste of the fused launch (the A/B quantity vs
        each member padding separately)."""
        return self.padded_width() - self.n_total

    def run(self) -> Any:
        """Execute the one wide launch (lead member's closure does the
        concatenate/pad/upload/dispatch) and memoize the raw output."""
        self._out = self.specs[0].run(self.specs)
        return self._out

    def member_result(self, i: int) -> Any:
        """Member ``i``'s slice of the fused output, in the exact shape
        its solo launch would have produced."""
        if self._out is None:
            raise RuntimeError("FusedLaunch.run() has not been called")
        s = self.specs[i]
        return s.slice_out(self._out, self.offsets[i], int(s.n))


class ChunkPipeline:
    """Run `LaunchItem`s with staging/compile/gather overlapped against
    device compute (`depth` >= 1), or fully synchronously (`depth` == 0).

    `depth` bounds how many launches may be in flight (dispatched, not
    yet finalized) beyond the one being gathered — double buffering at
    depth 1, deeper lookahead beyond.
    """

    def __init__(self, depth: int = 2, verbose: int = 0,
                 heartbeat: bool = False):
        self.depth = max(0, int(depth))
        self.verbose = int(verbose)
        # in-flight heartbeats (obs/heartbeat.py): per-chunk launches
        # emit a cheap dispatch-time beat when the constructing search
        # resolved heartbeat on (scan segments beacon from the device
        # instead); False keeps the exact-no-op default
        self.heartbeat = bool(heartbeat)
        self.timeline: List[Dict[str, Any]] = []
        self._wall_t0: Optional[float] = None
        # the run epoch: the FIRST run()'s start, stable across rung
        # barriers — per-launch t0_s/t1_s are relative to it, so the
        # attribution analyzer can slice the timeline (and clip tracer
        # spans, which carry the same perf_counter timebase) per rung
        self._epoch: Optional[float] = None
        self._wall_s = 0.0
        self._n_precompiled = 0
        self._compile_executor: Optional[ThreadPoolExecutor] = None
        self._compile_futures: List[Future] = []
        self._tracer = get_tracer()
        # the constructing thread's tenant/handle correlation, applied
        # to the stage/gather/compile worker threads so every span and
        # log line they emit attributes to the owning search; the
        # search's number rides along for the mirrored profiler spans
        self._corr = current_correlation()
        self._search = current_search()
        # per compile group: [first dispatch t, last finalize t] — the
        # compile-group boundary spans of the exported trace
        self._group_bounds: Dict[int, List[float]] = {}

    # -- compile-ahead ---------------------------------------------------
    def submit_precompile(self, jit_fn, *args,
                          label: str = "") -> Optional[Future]:
        """Queue an AOT lower+compile on the compile thread (pipelined
        mode only; at depth 0 programs compile where they always did —
        at first dispatch).  Returns a Future of the executable, or None
        when running synchronously."""
        if self.depth == 0:
            return None
        if self._compile_executor is None:
            self._compile_executor = ThreadPoolExecutor(
                max_workers=1,
                thread_name_prefix=_process.COMPILE_THREAD)

        def job():
            set_correlation(self._corr, self._search)
            with self._tracer.span("compile", label=label) as span, \
                    _process.building(label) as built:
                exe = precompile(jit_fn, *args)
                span.set(**built.attrs())
            self._n_precompiled += 1
            # device-memory ledger: harvest the compiled executable's
            # XLA memory_analysis (argument/output/temp bytes) where
            # the backend provides one — ground truth for the parts
            # the shape-level footprint model cannot see (exact no-op
            # when no ledger-enabled search is active)
            _memledger.note_compiled(label, exe)
            return exe

        fut = self._compile_executor.submit(job)
        self._compile_futures.append(fut)
        return fut

    # -- execution -------------------------------------------------------
    def run(self, items) -> None:
        """Consume an iterable of LaunchItems.  Exceptions from any
        phase propagate to the caller (first one wins) after the
        pipeline drains; partial results written by earlier finalizes
        remain (checkpoint-resume picks them up)."""
        self._wall_t0 = time.perf_counter()
        if self._epoch is None:
            self._epoch = self._wall_t0
        try:
            if self.depth == 0:
                self._run_sync(items)
            else:
                self._run_pipelined(items)
        finally:
            self._wall_s += time.perf_counter() - self._wall_t0
            self._wall_t0 = None
            # compile-group boundary spans (async: group g+1's first
            # stage may overlap group g's last finalize)
            for g, (t0, t1) in sorted(self._group_bounds.items()):
                self._tracer.record_async(
                    f"compile-group {g}", t0, t1, track="compile-groups",
                    group=g)
            self._group_bounds.clear()

    def drain(self) -> None:
        """Rung barrier (search/halving.py): block until every queued
        compile-ahead job has finished WITHOUT shutting the compile
        executor down.  The halving scheduler drains between rungs so
        a straggler AOT job can never trace under the next rung's jax
        config (e.g. a wants_float64 family's temporarily-enabled x64
        mode restored at the rung boundary), while the compile thread
        stays warm for the next rung's programs.  `run()` may be
        called again afterwards — the timeline and wall accumulate, so
        one report covers every rung."""
        self._join_builds("drain")
        self._compile_futures = []

    def _join_builds(self, where: str) -> None:
        """Stand (under ``compile.wait``) for every queued build that
        is still in flight."""
        for fut in self._compile_futures:
            if fut.cancelled():
                continue
            try:
                _process.join_build(fut, self._tracer, where=where)
            # AOT compile-ahead is an optimization only: a failed
            # future's consumer already fell back to the jit path, and
            # an unconsumed failure means nothing needed the executable
            # sstlint: disable=launch-except-taxonomy,swallowed-exception
            except Exception:
                pass

    def close(self) -> None:
        """Join the compile thread (AOT jobs trace under the caller's
        jax config — e.g. a temporarily-enabled x64 mode — so they must
        not outlive the enclosing search)."""
        if self._compile_executor is not None:
            for fut in self._compile_futures:
                fut.cancel()
            self._join_builds("close")
            self._compile_executor.shutdown(wait=True)
            self._compile_executor = None
            self._compile_futures = []

    # -- reporting -------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        tl = self.timeline
        walls = {
            "stage_wall_s": sum(t["stage_s"] for t in tl),
            "dispatch_wall_s": sum(t["dispatch_s"] for t in tl),
            "compute_wall_s": sum(t["compute_s"] for t in tl),
            "gather_wall_s": sum(t["gather_s"] for t in tl),
            "finalize_wall_s": sum(t["finalize_s"] for t in tl),
        }
        busy = sum(walls.values())
        wall = self._wall_s
        if self._wall_t0 is not None:     # mid-run snapshot
            wall += time.perf_counter() - self._wall_t0
        host = busy - walls["compute_wall_s"]
        # host work hidden behind device compute, as a fraction of all
        # host work (0 when synchronous: wall ~= busy by construction)
        overlap = 0.0
        if host > 0.0 and wall > 0.0:
            overlap = min(1.0, max(0.0, (busy - wall) / host))
        return {
            "depth": self.depth,
            "n_launches": len(tl),
            "wall_s": round(wall, 4),
            **{k: round(v, 4) for k, v in walls.items()},
            "queue_wait_wall_s": round(
                sum(t.get("queue_wait_s", 0.0) for t in tl), 4),
            "overlap_frac": round(overlap, 4),
            "n_precompiled": self._n_precompiled,
            "stage_bytes_total": sum(
                t.get("stage_bytes", 0) for t in tl),
            "epoch_s": round(self._epoch or 0.0, 6),
            "launches": tl,
        }

    # -- internals -------------------------------------------------------
    @staticmethod
    def _wait_item(item: LaunchItem, out):
        """Block until `out` is ready via the item's wait hook (the
        fault supervisor's interception point) or the plain jax wait.
        Returns the outputs to gather — a recovery may substitute
        them."""
        if item.wait is not None:
            return item.wait(out)
        return jax.block_until_ready(out)

    def _record(self, item: LaunchItem, tm: LaunchTimings,
                t0: Optional[float] = None,
                t1: Optional[float] = None) -> None:
        # fleet telemetry: the launch's device-busy estimate feeds the
        # rolling device-occupancy series (exact no-op when disabled)
        _telemetry.note_launch(tm.compute_s)
        # device-memory ledger: reconcile model vs allocator at the
        # launch boundary (exact no-op off; unmeasurable backends
        # early-out after the first probe)
        _memledger.note_launch_boundary()
        rec = {
            "key": item.key, "group": item.group, "kind": item.kind,
            "n_tasks": item.n_tasks, "n_chunks": int(item.n_chunks),
            "stage_bytes": int(tm.stage_bytes),
            "stage_s": round(tm.stage_s, 6),
            "stage_wait_s": round(tm.stage_wait_s, 6),
            "queue_wait_s": round(tm.queue_wait_s, 6),
            "dispatch_s": round(tm.dispatch_s, 6),
            "compute_s": round(tm.compute_s, 6),
            "gather_s": round(tm.gather_s, 6),
            "finalize_s": round(tm.finalize_s, 6),
        }
        epoch = self._epoch
        if t0 is not None and t1 is not None and epoch is not None:
            rec["t0_s"] = round(t0 - epoch, 6)
            rec["t1_s"] = round(t1 - epoch, 6)
        self.timeline.append(rec)
        if self.verbose > 0:
            # logging channel only (never stdout: launch records have
            # no legacy print contract to preserve)
            _slog.debug(
                "launch %s kind=%s group=%d compute=%.4fs gather=%.4fs",
                item.key, item.kind, item.group, tm.compute_s,
                tm.gather_s, **rec)

    def _note_group(self, group: int, t0: float, t1: float) -> None:
        if not self._tracer.enabled:
            return
        b = self._group_bounds.get(group)
        if b is None:
            self._group_bounds[group] = [t0, t1]
        else:
            b[0] = min(b[0], t0)
            b[1] = max(b[1], t1)

    def _run_sync(self, items) -> None:
        tr = self._tracer
        for item in items:
            tm = LaunchTimings()
            t0 = time.perf_counter()
            if item.stage is not None:
                b0 = _dataplane.bytes_uploaded()
                with tr.span("stage", key=item.key, kind=item.kind,
                             group=item.group):
                    staged = item.stage()
                tm.stage_bytes = _dataplane.bytes_uploaded() - b0
            else:
                staged = None
            t1 = time.perf_counter()
            tm.stage_s = t1 - t0
            with tr.span("dispatch", key=item.key, kind=item.kind,
                         group=item.group):
                out = item.launch(staged)
            if self.heartbeat and item.kind != "scan":
                _heartbeat.note_chunk(item.key, item.group)
            t2 = time.perf_counter()
            tm.dispatch_s = t2 - t1
            with tr.span("compute.wait", key=item.key):
                out = self._wait_item(item, out)
            t3 = time.perf_counter()
            tm.compute_s = t3 - t2
            tr.record_span("compute", t2, t3, track="device",
                           key=item.key, kind=item.kind, group=item.group)
            if item.gather is not None:
                with tr.span("gather", key=item.key):
                    host = item.gather(out)
            else:
                host = None
            t4 = time.perf_counter()
            tm.gather_s = t4 - t3
            if item.finalize is not None:
                with tr.span("finalize", key=item.key):
                    item.finalize(host, tm)
            tm.finalize_s = time.perf_counter() - t4
            t_end = time.perf_counter()
            tr.record_async(f"launch {item.key}", t1, t_end,
                            track="launches", key=item.key,
                            kind=item.kind, group=item.group,
                            n_tasks=item.n_tasks)
            self._note_group(item.group, t1, t_end)
            self._record(item, tm, t0, t_end)

    def _run_pipelined(self, items) -> None:
        depth = self.depth
        tr = self._tracer
        stage_ex = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sst-stage")
        gather_ex = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sst-gather")
        # readiness timestamp of the most recently completed launch —
        # owned by the (single) gather thread
        last_ready = [0.0]
        staged: deque = deque()      # (item, stage Future, t_submitted)
        inflight: deque = deque()    # gather Futures, dispatch order
        it = iter(items)
        exhausted = False

        def staged_call(item):
            set_correlation(self._corr, self._search)
            t0 = time.perf_counter()
            # bytes accounted via the (single) stage thread's delta of
            # the process-wide data-plane counter — supervisor re-stages
            # on recovery threads land in the global counter only
            b0 = _dataplane.bytes_uploaded()
            with tr.span("stage", key=item.key, kind=item.kind,
                         group=item.group):
                payload = item.stage()
            return (payload, time.perf_counter() - t0,
                    _dataplane.bytes_uploaded() - b0)

        def top_up():
            nonlocal exhausted
            while not exhausted and len(staged) < depth + 1:
                try:
                    nxt = next(it)
                except StopIteration:
                    exhausted = True
                    return
                fut = (stage_ex.submit(staged_call, nxt)
                       if nxt.stage is not None else None)
                staged.append((nxt, fut))

        def gather_job(item, out, t_dispatch0, t_dispatched, tm):
            set_correlation(self._corr, self._search)
            with tr.span("compute.wait", key=item.key):
                out = self._wait_item(item, out)
            t_ready = time.perf_counter()
            t_head = max(t_dispatched, last_ready[0])
            tm.compute_s = t_ready - t_head
            last_ready[0] = t_ready
            tr.record_span("compute", t_head, t_ready, track="device",
                           key=item.key, kind=item.kind, group=item.group)
            if item.gather is not None:
                with tr.span("gather", key=item.key):
                    host = item.gather(out)
            else:
                host = None
            t_got = time.perf_counter()
            tm.gather_s = t_got - t_ready
            if item.finalize is not None:
                with tr.span("finalize", key=item.key):
                    item.finalize(host, tm)
            tm.finalize_s = time.perf_counter() - t_got
            t_end = time.perf_counter()
            tr.record_async(f"launch {item.key}", t_dispatch0, t_end,
                            track="launches", key=item.key,
                            kind=item.kind, group=item.group,
                            n_tasks=item.n_tasks)
            self._note_group(item.group, t_dispatch0, t_end)
            self._record(item, tm, t_dispatch0, t_end)

        try:
            top_up()
            while staged:
                item, fut = staged.popleft()
                top_up()   # keep the stage thread fed while we dispatch
                tm = LaunchTimings()
                t0 = time.perf_counter()
                payload = None
                if fut is not None:
                    payload, tm.stage_s, tm.stage_bytes = fut.result()
                t1 = time.perf_counter()
                tm.stage_wait_s = t1 - t0
                with tr.span("dispatch", key=item.key, kind=item.kind,
                             group=item.group):
                    out = item.launch(payload)
                if self.heartbeat and item.kind != "scan":
                    _heartbeat.note_chunk(item.key, item.group)
                t2 = time.perf_counter()
                tm.dispatch_s = t2 - t1
                inflight.append(
                    gather_ex.submit(gather_job, item, out, t1, t2, tm))
                while len(inflight) > depth:
                    inflight.popleft().result()
            while inflight:
                inflight.popleft().result()
        finally:
            # on error: stop feeding, let in-flight work drain, then
            # re-raise from the executor futures above
            for _, fut in staged:
                if fut is not None:
                    fut.cancel()
            stage_ex.shutdown(wait=True)
            gather_ex.shutdown(wait=True)
