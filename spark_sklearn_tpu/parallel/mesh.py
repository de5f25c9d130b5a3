"""Mesh construction and data placement.

This is the TPU-native replacement for the reference's L2/L3 substrate
(reference: grid_search.py uses sc.parallelize / sc.broadcast; the Spark
TorrentBroadcast + BlockManager ship X, y to every executor).  Here the
"cluster" is a `jax.sharding.Mesh` over the chips jax can see, the
"broadcast" is a `device_put` with a fully-replicated NamedSharding over the
ICI mesh, and the "task fan-out" is a sharded leading axis of a vmapped
computation — XLA inserts the collectives.

Two mesh axes:
  - "task": candidates x folds are sharded across this axis (the analog of
    Spark's one-task-per-executor fan-out).
  - "data": optional second axis for sharding samples *within* one fit
    (gradient psum data-parallelism) when X is too large to replicate.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_sklearn_tpu.obs.trace import get_tracer

TASK_AXIS = "task"
DATA_AXIS = "data"


@dataclasses.dataclass
class TpuConfig:
    """Small config dataclass (SURVEY §5.6): defaults to "just works" on
    whatever `jax.devices()` shows.  The reference has no config system of its
    own; constructor kwargs mirror sklearn and cluster behavior came from
    SparkConf.  Here the only knobs are mesh layout and compile behavior.
    """

    devices: Optional[Sequence[Any]] = None   # default: jax.devices()
    n_task_shards: Optional[int] = None       # default: all devices
    n_data_shards: int = 1
    dtype: Any = None                         # default: float32
    # maximum number of (candidate x fold) program instances materialised in
    # one compiled batch; bounds peak HBM for big grids (the search chunks
    # each compile group to at most this many tasks per launch).
    max_tasks_per_batch: int = 8192
    # checkpoint/resume (SURVEY §5.4): completed chunks stream to
    # <checkpoint_dir>/search_<fingerprint>.jsonl and a restarted identical
    # search skips them.
    checkpoint_dir: Optional[str] = None
    # profiling (SURVEY §5.1): wrap the sweep in a jax.profiler trace whose
    # artifacts land here (open with tensorboard / perfetto).  The trace
    # holds the program's own names beside jax's: device ops carry the
    # named scopes of obs/spans.py (glm_lbfgs.*, sst.fit, sst.score) and
    # the mirrored host spans appear as `sst.<span>` events.
    profile_dir: Optional[str] = None
    # NaN debugging (SURVEY §5.2): raise at the first non-finite value
    # inside compiled fits instead of masking it into error_score — the
    # checkify-style sanitizer for our purely-functional programs.
    debug_nans: bool = False
    # bf16 data matmuls with fp32 accumulation (solver state stays fp32),
    # at a small, oracle-tested score tolerance cost.  Not a measured
    # win: the only chip record (docs/BENCH_TPU_2026-07-29.json, digits,
    # d=64, before PRs 1-20) has this arm 8.5% SLOWER than f32 — f32
    # matmuls already take the TPU's default single-pass bf16 precision.
    bf16_matmul: bool = False
    # persistent XLA compilation cache: compiled search programs survive
    # process restarts (jax_compilation_cache_dir), so repeated searches
    # over the same shapes skip the cold compile entirely.  The directory
    # is decided in ONE place (parallel/pipeline.py
    # resolve_compile_cache_dir): a set JAX_COMPILATION_CACHE_DIR wins
    # over both fields below; with neither, the cache lives at one fixed
    # git-ignored path inside the checkout.
    compile_cache_dir: Optional[str] = None
    # preferred spelling of compile_cache_dir (kept above for
    # back-compat); when both are set this one wins.
    compilation_cache_dir: Optional[str] = None
    # jax only persists programs whose XLA compile took at least this
    # long (jax_persistent_cache_min_compile_time_secs); 0.0 caches
    # everything (tests use this to observe hits on tiny programs).
    persistent_cache_min_compile_s: float = 0.5
    # pipelined chunk executor (parallel/pipeline.py): how many chunk
    # launches may be in flight beyond the one being gathered.  Chunk
    # k+1's host staging, chunk k-1's result gather, and the next
    # compile group's lowering/compile all overlap chunk k's device
    # compute.  0 = fully synchronous (bit-for-bit the pre-pipeline
    # execution order — the debugging/A-B escape hatch); scores are
    # identical at every depth.
    pipeline_depth: int = 2
    # donate each chunk's per-launch dynamic-parameter buffers to XLA.
    # Default off, with the measured reason recorded: these programs'
    # outputs (per-task scores, nc x folds) can never alias the donated
    # inputs, so XLA reports the donation unusable and ignores it — the
    # pipeline already caps allocator pressure by dropping each chunk's
    # staged buffers at dispatch (they free the moment the execution
    # consumes them).  The knob exists for backends/families where the
    # aliasing does bind.
    donate_chunk_buffers: bool = False
    # convergence-sorted chunking: when a family exposes a difficulty
    # proxy (GLM: larger C / smaller alpha converges slower), big compile
    # groups are sorted by it and split into ~8 narrower launches so the
    # easy launches early-exit instead of paying the slowest candidate's
    # lockstep iterations.  Same compiled program, same cv_results_
    # order; False restores single-width unsorted chunking.
    sort_candidates: bool = True
    # span tracing (obs/): record host-side spans of the search into
    # the in-memory ring buffer.  None defers to the SST_TRACE env var;
    # True records (export later via obs.export.export_chrome_trace);
    # a string records AND writes a Perfetto/chrome://tracing-loadable
    # trace to that path after each fit.  Off is bit-exact with
    # untraced behavior; on is budgeted <2% overhead (obs/trace.py,
    # enforced by test).
    trace: Any = None
    # tracer ring-buffer capacity (events) while this search records
    trace_buffer_size: int = 65536
    # fold fit + NaN-health + scoring into ONE compiled launch per chunk
    # (models never reach the host; XLA fuses the scoring epilogue into
    # the solver).  Timing contract (sklearn _search.py fit/score time
    # columns): the FIRST chunk of each compile group runs as separate
    # fit/score launches, plus one extra WARM score launch that measures
    # the steady-state score cost per task; later fused chunks attribute
    # that measured cost out of their single-launch wall, so
    # mean_score_time is an estimate calibrated per compile group, never
    # a silent 0.0 (single-chunk groups simply run unfused and report
    # exact split timings).  Set False to restore separate launches
    # everywhere.  Applies to the wide score path only (custom scorers
    # keep separate launches).
    fuse_fit_score: bool = True
    # chunk-loop strategy (parallel/taskgrid.resolve_chunk_loop):
    # "per_chunk" dispatches one launch per chunk — the default, the
    # resumable/faultable baseline, and the fallback for a scanned
    # segment that OOMs.  "scan" rolls a compile group's chunk loop
    # into the program via lax.scan (carry buffers donated by XLA
    # across scan steps), so an entire scan segment — a whole group,
    # or a whole halving rung including its on-device top_k
    # elimination — executes as ONE launch.  Requires the fused
    # fit+score path (fuse_fit_score, wide scoring); searches that
    # cannot fuse fall back to per_chunk and record the reason in
    # search_report["chunkloop"].  None defers to SST_CHUNK_LOOP.
    chunk_loop: Optional[str] = None
    # shared-prefix search graphs (search/prefix.py): treat a Pipeline
    # candidate as a DAG, not an atom — group candidates by a content
    # digest of their transformer-chain params, compute each DISTINCT
    # prefix once per fold on device, cache the transformed design
    # matrix in the DataPlane (normal tenant/byte accounting), and fan
    # the suffix candidates over the cached matrices through the
    # existing chunk/scan machinery: an O(candidates) preprocessing
    # bill becomes O(distinct prefixes).  Bit-exact with the atomic
    # path by construction (same ops, same order — pinned by test).
    # False is the exact escape hatch: every candidate runs as one
    # atomic program, byte-identical to pre-prefix behavior.  Searches
    # that cannot stage (non-Pipeline families, task-batched finals,
    # sharded/streamed data) fall back atomically and record the
    # reason in search_report["prefix"].  None defers to
    # SST_PREFIX_REUSE (1/0), then True.
    prefix_reuse: Optional[bool] = None
    # force the nested per-(candidate, fold) score path even when every
    # scorer exposes a task-batched core — the A/B control arm
    # (tools/score_ab.py).  None/False keeps the wide path; the
    # SST_NESTED_SCORE env var is the process-wide spelling.
    nested_score: bool = False
    # ---- fault tolerance (parallel/faults.py LaunchSupervisor) ----
    # transient device errors retry with exponential backoff + jitter;
    # budgets are per launch AND per search (a flapping device must not
    # retry forever).
    max_launch_retries: int = 2
    max_search_retries: int = 16
    retry_backoff_s: float = 0.5
    retry_backoff_mult: float = 2.0
    retry_jitter_frac: float = 0.25
    # watchdog: a launch whose blocking wait exceeds this many seconds
    # fails the search with a clean LaunchTimeoutError naming the chunk
    # and compile group (completed chunks stay resumable) instead of
    # hanging the gather thread forever.  None/0 disables the watchdog
    # (no wait threads are spawned).
    launch_timeout_s: Optional[float] = None
    # heartbeat-aware watchdog (requires heartbeat=True below): a
    # SCANNED launch whose in-flight beats stop arriving for this many
    # seconds is declared HUNG with the last-beat step index stamped
    # into the LaunchTimeoutError, the fault event and the flight
    # bundle — intra-launch liveness instead of a whole-segment
    # wall-clock budget.  Launches with no live heartbeat segment
    # (per-chunk items, heartbeat off) keep the launch_timeout_s
    # behavior unchanged.  None/0 disables the heartbeat mode.
    heartbeat_timeout_s: Optional[float] = None
    # deterministic fault injection for tests/drills: "transient@3,oom@5"
    # style spec (see faults.FaultPlan).  None defers to SST_FAULT_PLAN.
    fault_plan: Any = None
    # ---- device data plane (parallel/dataplane.py) ----
    # byte budget of the session-scoped device-array cache (X/y, fold
    # masks, tiled masks) shared by every search in the process: uploads
    # happen once per content+sharding and are reused across chunks,
    # compile groups, calibration and subsequent searches (the
    # TPU-native sc.broadcast, made persistent).  0 disables the plane
    # and restores per-search device_put.
    dataplane_bytes: int = 256 * 2 ** 20
    # ---- launch geometry (parallel/taskgrid.plan_geometry) ----
    # "auto": per-group chunk widths chosen by power-of-two bucketing
    # over a measured cost model (n_launches x overhead + padded_lanes
    # x lane_cost), recorded in search_report["geometry"] and pinned
    # into the checkpoint journal so resume replays identical chunk
    # ids.  "fixed": the legacy width rule (pad-to-shards capped by
    # max_tasks_per_batch), bit-compatible with pre-planner runs.
    geometry_mode: str = "auto"
    # manual cost-model overrides (seconds); None uses the process
    # model's measured/default values.  Useful for deterministic
    # geometry in tests and for operators who know their launch costs.
    geometry_overhead_s: Optional[float] = None
    geometry_lane_cost_s: Optional[float] = None
    # ---- persistent AOT program store (parallel/programstore.py) ----
    # directory of the versioned artifact store: compiled search
    # programs are jax.export-serialized there and a later process
    # (bench cold runs, checkpoint-resume restarts, fleet workers)
    # loads them instead of re-tracing — with the geometry plan cache
    # and cost-model state persisted alongside, so a fresh process
    # plans the same chunk widths and its first chunk launches without
    # compiling anything.  None defers to SST_PROGRAM_STORE_DIR; unset
    # disables the store (the in-process and persistent-XLA caches
    # still apply).
    program_store_dir: Optional[str] = None
    # prewarm manifest (written by TpuSession.write_prewarm_manifest):
    # a session constructed with this set loads the manifest's
    # artifacts into memory at init, so the first search's programs
    # resolve without touching disk mid-pipeline.  None defers to
    # SST_PREWARM_MANIFEST; a missing file is skipped, never an error.
    prewarm_manifest: Optional[str] = None
    # store byte budget: oldest artifacts evict beyond it.  None defers
    # to SST_PROGRAM_STORE_BYTES (default 512 MiB); 0 disables the
    # store entirely.
    program_store_bytes: Optional[int] = None
    # ---- multi-tenant search service (serve/executor.py) ----
    # tenant identity of searches run under this config: concurrent
    # searches submitted to one TpuSession fair-share the device by
    # tenant (deficit round-robin over per-tenant chunk queues).  None
    # defers to SST_TENANT, then "default".
    tenant: Optional[str] = None
    # fair-share weight of this config's tenant: a weight-3 tenant is
    # granted 3x the dispatched task share of a weight-1 tenant while
    # both have chunks queued.  None defers to SST_TENANT_WEIGHT, then
    # 1.0.
    tenant_weight: Optional[float] = None
    # admission control: how many searches may run concurrently in the
    # session's executor; beyond it submissions queue (up to
    # max_queued_searches) and then reject with a clean AdmissionError.
    max_concurrent_searches: int = 8
    # bounded submission queue: searches waiting for a concurrency slot
    # beyond this count are rejected at submit() time.
    max_queued_searches: int = 16
    # per-tenant cap on chunks in flight (dispatched, not yet
    # finalized) across ALL of the tenant's concurrent searches; the
    # scheduler skips a capped tenant until a chunk completes.
    # 0 = unbounded (the per-search pipeline_depth still bounds each
    # search on its own).
    tenant_max_inflight: int = 0
    # deficit-round-robin quantum in cost units (one unit = one real
    # (candidate x fold) task of a chunk): per scheduling round each
    # tenant accumulates quantum x tenant_weight of dispatch credit.
    scheduler_quantum: int = 64
    # per-tenant byte quota in the device data plane: a tenant over its
    # quota evicts its OWN least-recently-used resident arrays, never
    # another tenant's (parallel/dataplane.py).  0 = no per-tenant
    # quota (the global dataplane_bytes budget still applies).
    dataplane_tenant_bytes: int = 0
    # ---- adaptive search (search/halving.py) ----
    # successive-halving lane reclamation: re-plan each rung's
    # SURVIVING candidates into narrower chunks (plan_geometry over the
    # survivor sizes, width-affine to already-compiled widths priced by
    # the cost model's measured compile wall), so eliminated candidates
    # retire their lanes instead of riding along as padding.  False
    # pins every rung to the rung-0 chunk widths — the A/B control arm
    # and the "survivors ride along" baseline; cv_results_ is identical
    # either way (widths are pure geometry, never scores).
    halving_replan: bool = True
    # lower bound on a re-planned rung's chunk width (rounded up to the
    # task-shard multiple, capped by the HBM bound): keeps late rungs
    # from degrading into matmul-starved slivers on wide meshes.
    # 0 = no floor beyond the shard multiple.
    min_rung_width: int = 0
    # ---- device-memory ledger (parallel/memledger.py) ----
    # HBM accounting: model every launch's device footprint from its
    # abstract shapes, reconcile against jax memory_stats at launch
    # boundaries, render search_report["memory"], and cap planned
    # chunk widths to the HBM budget below.  False is the exact-no-op
    # escape hatch: reports and cv_results_ are byte-identical to the
    # pre-ledger engine (no "memory" block, no sampling, no ceiling).
    memory_ledger: bool = True
    # per-device byte budget the geometry planner fits chunks into:
    # widths are capped so (broadcast residents + the chunk's modeled
    # dyn/mask/output bytes) x the ledger's learned safety margin stay
    # under it — chunks that would not fit are never launched, and OOM
    # bisection becomes the fallback instead of the discovery
    # mechanism.  None defers to SST_HBM_BUDGET_BYTES, then a fraction
    # (obs.memory.DEFAULT_HBM_FRACTION) of the detected device memory;
    # backends with no measurable limit (XLA:CPU) default to 0 = no
    # ceiling.  0 disables the ceiling explicitly.
    hbm_budget_bytes: Optional[int] = None
    # ---- fleet telemetry (obs/telemetry.py + obs/fleet.py) ----
    # localhost metrics endpoint: the session serves Prometheus text at
    # /metrics and the JSON snapshot at /snapshot.json on this port
    # (127.0.0.1 only).  None disables telemetry entirely — an exact
    # no-op, like the tracer — deferring to SST_TELEMETRY_PORT; 0 binds
    # an ephemeral port (read it back from session.fleet_endpoint.port,
    # or point tools/fleet_top.py at it).
    telemetry_port: Optional[int] = None
    # sliding-window span (seconds) the telemetry SLO series cover
    # (per-tenant queue-wait p50/p95, throughput, shares, device
    # occupancy) and the sampler thread's poll period.
    telemetry_window_s: float = 120.0
    telemetry_interval_s: float = 0.5
    # flight recorder: directory black-box bundles dump to on FATAL
    # faults, watchdog timeouts, first OOM recovery, cancellations and
    # program-store quarantines.  None defers to SST_FLIGHT_DIR; unset
    # disables dumping (the bounded in-memory event ring still
    # records).
    flight_dir: Optional[str] = None
    # in-flight device heartbeats (obs/heartbeat.py): thread a
    # jax.debug.callback beacon into the scanned chunk loop's step body
    # (and a cheap host-side beat into per-chunk dispatches) so
    # SearchFuture.progress() reports intra-segment steps_done/ETA,
    # the heartbeat_timeout_s watchdog sees liveness per scan step,
    # and search_report grows a "heartbeat" block.  Off (the default)
    # is an exact no-op: no callback is traced into the program — its
    # presence joins the program cache key, so on/off never alias —
    # and cv_results_/search_report stay byte-identical.  None defers
    # to SST_HEARTBEAT.
    heartbeat: Optional[bool] = None
    # ---- search doctor (obs/attribution.py + obs/runlog.py) ----
    # critical-path attribution: decompose each search's measured wall
    # into pinned cause lanes (compile/stage/compute/gather/queue
    # wait/faults/padding/memory narrowing) rendered as
    # search_report["attribution"] with a one-line verdict.  False is
    # the exact-no-op escape hatch: no block, reports and cv_results_
    # byte-identical to the pre-doctor engine.
    attribution: bool = True
    # run history + regression sentinel: persist every search's
    # attribution/geometry/cost-model record into the run log and
    # compare against the stored baseline for the same (family,
    # structure digest, env fingerprint) key.  False disables both
    # even when a directory is configured — an exact no-op.
    runlog: bool = True
    # run-log directory (ProgramStore-style layout: records live under
    # v<format>/<env_digest>/).  None defers to SST_RUNLOG_DIR; unset
    # disables the run log and the sentinel.
    runlog_dir: Optional[str] = None
    # run-log byte budget: oldest records prune beyond it.  None
    # defers to SST_RUNLOG_BYTES, then the 32 MiB default; <= 0
    # disables the run log.
    runlog_bytes: Optional[int] = None
    # the sentinel's relative noise band: a watched lane (wall /
    # compile / queue wait / padding) must grow beyond baseline x
    # (1 + frac) — and by more than an absolute 50 ms floor — before
    # a regression is flagged.
    runlog_noise_frac: float = 0.25
    # ---- self-protecting service (serve/executor.py + search/grid.py) ----
    # wall-clock deadline (seconds) a search may spend from submit to
    # finish.  For executor-submitted searches the clock starts at
    # submit time (queue wait counts); solo fits start it at fit().
    # None disables the deadline.  On expiry: partial_results decides.
    search_deadline_s: Optional[float] = None
    # what a deadline or a persistent degradable fault does to the
    # search: "raise" (default — SearchDeadlineError / the fault
    # propagates, exact pre-protection behavior) or "best_effort"
    # (return cv_results_ with un-run candidates carrying sklearn-exact
    # error_score semantics and a search_report["protection"] block
    # naming every shed/quarantined candidate).
    partial_results: str = "raise"
    # admission control mode for executor submits: "static" (default —
    # only the max_concurrent/max_queued slot check, exact PR-12
    # behavior) or "predictive" (additionally price the search's
    # ledger-modeled HBM footprint against hbm_budget_bytes and its
    # queue-wait forecast against search_deadline_s, rejecting with a
    # machine-readable AdmissionError before any device work).
    admission_mode: str = "static"
    # poison-candidate quarantine: when partial_results="best_effort",
    # a candidate whose chunk has bottomed out to a single lane and
    # still faults FATAL this many times is quarantined to error_score
    # instead of killing the search.  Ignored under "raise".
    quarantine_fatal_k: int = 3
    # ---- cross-search launch fusion (serve/executor.py + parallel/pipeline.py) ----
    # coalesce same-program chunks from different concurrent searches
    # into one wide device launch (results scattered back per tenant,
    # bit-identical to each member's solo launch).  None defers to
    # SST_FUSION, then True.  False is the exact escape hatch: the
    # scheduler dispatches every chunk solo, byte-identical reports.
    fusion: Optional[bool] = None
    # how long (milliseconds) the dispatch loop holds a fusable chunk
    # at the head of the queue waiting for a same-program peer from
    # another search before launching it solo.  None defers to
    # SST_FUSION_WINDOW_MS, then 5.0.
    fusion_window_ms: Optional[float] = None
    # cap on a fused launch's total candidate width (real lanes across
    # all members, before padding).  None defers to
    # SST_FUSION_MAX_WIDTH, then 0 = bounded only by the member plans'
    # own width caps.
    fusion_max_width: Optional[int] = None
    # ---- out-of-core data plane (search/stream.py + sparse/csr.py) ----
    # how the dataset reaches the device: "device" (default — X is
    # densified and device-resident for the whole search, exact
    # pre-streaming behavior), "stream" (X stays on the host; sample
    # shards stream through the stage/compute overlap and per-shard
    # partial statistics fold on device — families advertising
    # supports_stream only), or "sparse" (scipy CSR X rides the BCOO
    # bridge end to end, no densify — families advertising
    # supports_sparse only).  None defers to SST_DATA_MODE, then
    # "device".
    data_mode: Optional[str] = None
    # target host->device bytes per streamed sample shard.  The stream
    # planner clamps this against hbm_budget_bytes (residency = budget
    # minus the program footprint, double-buffered) so shard width is a
    # planning decision, never OOM trial-and-error.  None defers to
    # SST_STREAM_SHARD_BYTES, then 64 MiB.
    stream_shard_bytes: Optional[int] = None
    # ---- crash-safe service (serve/journal.py) ----
    # durable submission journal: every executor submission and state
    # transition appends a checksummed, fsynced record here, the
    # lease file fences concurrent owners, and a restarted session
    # recovers non-terminal searches via TpuSession.recover().  None
    # defers to SST_SERVICE_JOURNAL_DIR; unset disables the journal
    # entirely — an exact no-op: zero writes, byte-identical reports
    # and cv_results_.
    service_journal_dir: Optional[str] = None
    # how stale the lease's heartbeat stamp may grow before a restarted
    # process may fence a silent owner and take the journal over.  A
    # LIVE owner with a fresh stamp always wins (ServiceLeaseError for
    # the newcomer).  None defers to SST_SERVICE_LEASE_TIMEOUT_S, then
    # 30 seconds.
    service_lease_timeout_s: Optional[float] = None

    def resolve_devices(self):
        return list(self.devices) if self.devices is not None else jax.devices()

    def resolved_cache_dir(self) -> Optional[str]:
        """The persistent compilation cache directory, honoring both
        spellings (`compilation_cache_dir` preferred)."""
        return self.compilation_cache_dir or self.compile_cache_dir


def build_mesh(config: Optional[TpuConfig] = None) -> Mesh:
    """Build a ("task", "data") mesh from the visible devices.

    On the single-chip machine this is a trivial 1x1 mesh; on a v5e-8 slice it
    is 8x1 by default (all chips fan out over tasks), or 4x2/2x4/1x8 when
    `n_data_shards` asks for in-fit data parallelism.
    """
    config = config or TpuConfig()
    with get_tracer().span("build_mesh"):
        devices = config.resolve_devices()
        n = len(devices)
        nd = max(1, config.n_data_shards)
        if n % nd != 0:
            raise ValueError(
                f"n_data_shards={nd} does not divide device count {n}")
        nt = config.n_task_shards or (n // nd)
        if nt * nd != n:
            raise ValueError(
                f"mesh {nt}x{nd} != {n} devices; set "
                "n_task_shards/n_data_shards so their product equals "
                "the device count")
        dev_array = np.asarray(devices).reshape(nt, nd)
        return Mesh(dev_array, axis_names=(TASK_AXIS, DATA_AXIS))


def replicate(mesh: Mesh, *arrays):
    """Place arrays fully replicated over the mesh — the TPU-native
    `sc.broadcast`.  One transfer per device over ICI; no BitTorrent, no
    pickle (reference: grid_search.py X_bc = sc.broadcast(X))."""
    sharding = NamedSharding(mesh, P())
    with get_tracer().span("device_put.replicate", n_arrays=len(arrays)):
        out = tuple(jax.device_put(a, sharding) for a in arrays)
    return out[0] if len(out) == 1 else out


def shard_leading(mesh: Mesh, *arrays, axis: str = TASK_AXIS):
    """Shard the leading axis of each array across `axis` — the analog of
    sc.parallelize(indexed_param_grid, n): each device owns a contiguous
    stripe of the task grid."""
    sharding = NamedSharding(mesh, P(axis))
    with get_tracer().span("device_put.shard", n_arrays=len(arrays),
                           axis=axis):
        out = tuple(jax.device_put(a, sharding) for a in arrays)
    return out[0] if len(out) == 1 else out


def pad_to_multiple(n: int, k: int) -> int:
    return int(math.ceil(n / k) * k) if k > 1 else n


def task_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(TASK_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def device_get_tree(x):
    """`jax.device_get` that also works under multi-controller JAX.

    In a multi-process cluster (jax.distributed, SURVEY §5.8) the
    engine's launch outputs are globally sharded over a mesh spanning
    processes, so a plain device_get would raise on the non-addressable
    shards; process_allgather replicates them across hosts first (one
    XLA all-gather over the cluster's transport — the analog of Spark's
    collect() back to the driver, except every host gets the result).
    Single-process: plain device_get, zero overhead."""
    if jax.process_count() == 1:
        with get_tracer().span("device_get"):
            return jax.device_get(x)
    from jax.experimental import multihost_utils

    def one(a):
        if isinstance(a, jax.Array) and not a.is_fully_addressable:
            return np.asarray(
                multihost_utils.process_allgather(a, tiled=True))
        return jax.device_get(a)

    with get_tracer().span("device_get.allgather"):
        return jax.tree_util.tree_map(one, x)
