"""The project model — what sstlint knows about THIS codebase.

sstlint is project-native by design: instead of generic heuristics it
carries an explicit map of the engine's concurrency and interface
contracts — which named locks exist (discovered from the
``named_lock``/``named_rlock`` factory calls in the source), which
shared containers each lock owns, which ``search_report`` blocks are
produced where, and which env knobs are deliberately config-field-less.
Tests point a :class:`Project` at fixture trees with their own maps;
the CLI uses :meth:`Project.default` for the real repository layout.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

__all__ = ["BlockSpec", "EscapeHatch", "Producer", "Project",
           "SharedState"]


@dataclasses.dataclass(frozen=True)
class SharedState:
    """A container mutated by more than one thread, and the lock that
    owns it.  ``name`` is a module-global variable; ``cls``/``attrs``
    cover instance attributes of a class; ``taint_key`` additionally
    guards local variables derived from a subscript/``setdefault`` of
    that literal key (e.g. the staged-chunk id set living inside a
    plan dict)."""

    relpath: str
    lock: str
    name: str = ""
    cls: str = ""
    attrs: Tuple[str, ...] = ()
    taint_key: str = ""


@dataclasses.dataclass(frozen=True)
class Producer:
    """One place a report block's keys are written.

    ``kind``:
      - "dict-keys": every string key of every dict literal inside the
        function ``qualname`` of ``relpath``;
      - "subscript-var": every literal key stored via
        ``<var>["key"] = ...`` anywhere in ``relpath``.
    """

    kind: str
    relpath: str
    target: str            # qualname (dict-keys) or var name (subscript-var)


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One pinned ``search_report`` sub-block: the schema constant in
    the metrics module vs. the producers that render it."""

    block: str             # report key ("pipeline", "dataplane", ...)
    schema_attr: str       # constant name in the metrics module
    producers: Tuple[Producer, ...]


@dataclasses.dataclass(frozen=True)
class EscapeHatch:
    """One documented byte-parity escape hatch: a knob whose off/
    default state is CLAIMED (README/docstrings) to reproduce the
    pre-feature engine exactly.  The claim is only as good as the
    parity test that pins it, so every registered hatch names one:
    ``parity_test`` is ``"tests/test_x.py::test_name"`` and the
    ``escape-hatch-untested`` rule fails when it stops resolving.
    Claim lines in README/docstrings naming an unregistered knob are
    ``escape-hatch-unregistered`` findings."""

    name: str              # short registry name ("fusion", ...)
    knob: str              # TpuConfig field the claim is about
    parity_test: str       # "tests/test_x.py::test_name"
    claim: str = ""        # what "off" is claimed to reproduce


@dataclasses.dataclass
class Project:
    """Paths + contract map for one lintable tree."""

    root: Path                          # repo root
    package: Path                       # package dir to lint
    readme: Optional[Path] = None
    docs_api: Optional[Path] = None
    metrics_path: Optional[Path] = None   # obs/metrics.py (import-light)
    spans_path: Optional[Path] = None     # obs/spans.py (import-light)
    #: utils/keycheck.py — the cache-key surface registry + runtime
    #: recorder the keyflow rules load import-light
    keycheck_path: Optional[Path] = None
    #: utils/journalspec.py — the versioned journal record registry
    journalspec_path: Optional[Path] = None
    #: tests/ dir escape-hatch parity-test pointers resolve against
    tests_dir: Optional[Path] = None
    #: every documented byte-parity escape hatch, with its pinning test
    escape_hatches: Tuple["EscapeHatch", ...] = ()
    #: (lock-prefix, lock-prefix) pairs allowed to nest across modules
    allowed_cross_module: Tuple[Tuple[str, str], ...] = ()
    shared_state: Tuple[SharedState, ...] = ()
    blocks: Tuple[BlockSpec, ...] = ()
    #: modules/functions on the launch path, where broad handlers must
    #: stay taxonomy-aware (relpaths, or "relpath::funcname")
    launch_paths: Tuple[str, ...] = ()
    #: env vars deliberately WITHOUT a TpuConfig field, with the reason
    env_field_exceptions: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    #: env var name prefix the knob audit owns
    env_prefix: str = "SST_"
    #: relpaths excluded from source rules (the lock shim itself, ...)
    exclude: Tuple[str, ...] = ()

    @classmethod
    def default(cls, root) -> "Project":
        """The real spark_sklearn_tpu layout and contract map."""
        root = Path(root).resolve()
        pkg = root / "spark_sklearn_tpu"
        return cls(
            root=root,
            package=pkg,
            readme=root / "README.md",
            docs_api=root / "docs" / "API.md",
            metrics_path=pkg / "obs" / "metrics.py",
            spans_path=pkg / "obs" / "spans.py",
            keycheck_path=pkg / "utils" / "keycheck.py",
            journalspec_path=pkg / "utils" / "journalspec.py",
            tests_dir=root / "tests",
            escape_hatches=(
                EscapeHatch(
                    "fusion", "fusion",
                    "tests/test_fusion.py::"
                    "test_fusion_off_block_shape_and_parity",
                    claim="`0` reproduces the pre-fusion engine "
                          "exactly"),
                EscapeHatch(
                    "prefix_reuse", "prefix_reuse",
                    "tests/test_prefix.py::"
                    "test_shared_matches_atomic_exact",
                    claim="`0` is the bit-exact atomic escape hatch"),
                EscapeHatch(
                    "heartbeat", "heartbeat",
                    "tests/test_heartbeat.py::"
                    "test_parity_and_cache_separation",
                    claim="default off = exact no-op (key and traced "
                          "program byte-identical)"),
                EscapeHatch(
                    "memory_ledger", "memory_ledger",
                    "tests/test_memory.py::test_ledger_off_exact_noop",
                    claim="False is the byte-identical pre-ledger "
                          "escape hatch"),
                EscapeHatch(
                    "attribution", "attribution",
                    "tests/test_doctor.py::"
                    "test_attribution_off_is_absent_and_byte_identical",
                    claim="attribution=False is a byte-identical "
                          "escape hatch"),
                EscapeHatch(
                    "runlog", "runlog",
                    "tests/test_doctor.py::"
                    "test_runlog_off_never_touches_disk",
                    claim="runlog=False is a byte-identical escape "
                          "hatch"),
                EscapeHatch(
                    "service_journal", "service_journal_dir",
                    "tests/test_service_journal.py::"
                    "test_default_off_is_exact_noop",
                    claim="unset = exact no-op (zero writes, zero "
                          "reads)"),
                EscapeHatch(
                    "protection", "partial_results",
                    "tests/test_protection.py::"
                    "test_no_block_and_exact_when_off",
                    claim="all-default = byte-identical "
                          "protection-off escape hatch"),
                EscapeHatch(
                    "chunk_loop", "chunk_loop",
                    "tests/test_chunkloop.py::"
                    "test_scan_matches_per_chunk_exact",
                    claim="per_chunk is the resumable/faultable "
                          "baseline scan must match exactly"),
                EscapeHatch(
                    "pipeline_depth", "pipeline_depth",
                    "tests/test_pipeline.py::test_family_matrix_parity",
                    claim="0 = fully synchronous, bit-for-bit the "
                          "pre-pipeline execution order"),
                EscapeHatch(
                    "fuse_fit_score", "fuse_fit_score",
                    "tests/test_score_parity.py::"
                    "test_logreg_multimetric_binary",
                    claim="False restores separate fit/score launches "
                          "everywhere"),
                EscapeHatch(
                    "sort_candidates", "sort_candidates",
                    "tests/test_sorted_chunking.py::"
                    "test_scores_match_and_iterations_drop",
                    claim="False restores single-width unsorted "
                          "chunking; same cv_results_ order either "
                          "way"),
                # surfaced by the escape-hatch audit itself: both were
                # long-standing README/docstring parity claims with
                # tests but no registration
                EscapeHatch(
                    "geometry_fixed", "geometry_mode",
                    "tests/test_geometry.py::"
                    "test_report_and_auto_vs_fixed_exact_parity",
                    claim='"fixed" restores the legacy width rule '
                          "bit-for-bit"),
                EscapeHatch(
                    "runlog_dir", "runlog_dir",
                    "tests/test_doctor.py::"
                    "test_runlog_off_never_touches_disk",
                    claim="no configured directory = exact no-op (no "
                          "store, no records, byte-identical reports)"),
            ),
            allowed_cross_module=(),
            shared_state=(
                # dataplane: process-wide transfer totals + the plane
                SharedState("parallel/dataplane.py",
                            "dataplane._TOTALS_LOCK", name="_TOTALS"),
                SharedState("parallel/dataplane.py",
                            "dataplane._PLANE_LOCK", name="_PLANE"),
                SharedState("parallel/dataplane.py",
                            "dataplane.DataPlane._lock", cls="DataPlane",
                            attrs=("_entries", "_bytes", "_tile_programs",
                                   "hits", "misses", "evictions",
                                   "bytes_uploaded", "bytes_tiled",
                                   "bytes_derived", "byte_budget")),
                SharedState("parallel/dataplane.py",
                            "dataplane.StagingRing._lock",
                            cls="StagingRing", attrs=("_rings",)),
                # the process ledger: totals, records and the
                # persistent-cache event counters, fed by jax's
                # monitoring callbacks on whichever thread builds
                SharedState("obs/process.py",
                            "process.ProcessLedger._lock",
                            cls="ProcessLedger",
                            attrs=("totals", "cache_events", "wait_s",
                                   "blocking_s", "calls", "fits",
                                   "_builds", "_union_s")),
                # faults: the supervisor's recovery bookkeeping
                SharedState("parallel/faults.py",
                            "faults.LaunchSupervisor._lock",
                            cls="LaunchSupervisor",
                            attrs=("faults", "_retries_used",
                                   "_sticky_oom", "_oom_dumped",
                                   "_sticky_fatal", "_fatal_counts",
                                   "_fatal_dumped")),
                # taskgrid: the geometry plan cache + cost model
                SharedState("parallel/taskgrid.py",
                            "taskgrid._PLAN_CACHE_LOCK",
                            name="_PLAN_CACHE"),
                SharedState("parallel/taskgrid.py",
                            "taskgrid.GeometryCostModel._lock",
                            cls="GeometryCostModel",
                            attrs=("launch_overhead_s", "lane_cost_s",
                                   "compile_wall_s", "n_observations")),
                # grid: per-plan staged-chunk id sets
                SharedState("search/grid.py", "grid.stage_lock",
                            taint_key="staged_ids"),
                # grid: the cross-search program cache, hit by every
                # concurrent search's worker + compile threads
                SharedState("search/grid.py", "grid._PROGRAM_CACHE_LOCK",
                            name="_PROGRAM_CACHE"),
                SharedState("search/grid.py", "grid._PROGRAM_CACHE_LOCK",
                            name="_PROGRAM_CACHE_FAMILY_COUNTS"),
                # serve: the fair-share executor's scheduler state
                SharedState("serve/executor.py",
                            "serve.SearchExecutor._lock",
                            cls="SearchExecutor",
                            attrs=("_tenants", "_active", "_pending",
                                   "_workers", "_rr", "_seq",
                                   "_last_handle", "_cost_by_tenant",
                                   "_dispatch_log", "_recent_walls",
                                   "_fuse_defer")),
                # dataplane: per-tenant quota/usage accounting
                SharedState("parallel/dataplane.py",
                            "dataplane.DataPlane._lock", cls="DataPlane",
                            attrs=("_tenant_quotas", "_tenant_bytes")),
                # obs/log: the logger cache
                SharedState("obs/log.py", "log._LOGGERS_LOCK",
                            name="_LOGGERS"),
                # obs/telemetry: the fleet-telemetry aggregator, hit by
                # every note_* hook (dispatch loop, gather threads,
                # supervisor recovery) plus the sampler thread
                SharedState("obs/telemetry.py",
                            "telemetry.TelemetryService._lock",
                            cls="TelemetryService",
                            attrs=("enabled", "_enable_count",
                                   "window_s", "interval_s",
                                   "_t_enabled", "_we_enabled_tracer",
                                   "_thread", "_tenants", "_device_busy",
                                   "_sched_busy",
                                   "_sched_dispatches_total",
                                   "_faults_by_class",
                                   "_faults_by_action", "_h2d",
                                   "_h2d_window", "_ps_events",
                                   "_regression",
                                   "_admission", "_admission_reasons",
                                   "_protection",
                                   "_fusion", "_fusion_borrowed",
                                   "_fusion_donated", "_recovery",
                                   "_providers", "_polls",
                                   "_n_samples")),
                # obs/telemetry: the always-on flight-recorder ring
                SharedState("obs/telemetry.py",
                            "telemetry.FlightRecorder._lock",
                            cls="FlightRecorder",
                            attrs=("_ring", "_n_dumps", "_n_records")),
                # parallel/memledger: the device-memory ledger, hit by
                # the pipeline's launch-boundary hook (gather thread),
                # the telemetry sampler, the geometry planner and the
                # supervisor's OOM forensics
                SharedState("parallel/memledger.py",
                            "memledger.MemoryLedger._lock",
                            cls="MemoryLedger",
                            attrs=("_active", "_measured",
                                   "watermark_bytes",
                                   "peak_modeled_bytes",
                                   "safety_margin", "n_samples",
                                   "n_oom", "_devices", "_groups",
                                   "_compiled")),
                # faults: injected-stall bookkeeping for the heartbeat
                # watchdog drill
                SharedState("parallel/faults.py",
                            "faults.LaunchSupervisor._lock",
                            cls="LaunchSupervisor",
                            attrs=("_hb_stall_keys",)),
                # obs/heartbeat: the in-flight beacon hub, hit by the
                # device callback (runtime thread), the dispatch loop's
                # register/complete hooks, the watchdog's staleness
                # polls and every progress()/snapshot reader
                SharedState("obs/heartbeat.py",
                            "heartbeat.HeartbeatHub._lock",
                            cls="HeartbeatHub",
                            attrs=("_ring", "_next_token", "_by_token",
                                   "_live_by_key", "_done",
                                   "_beats_total", "_chunk_beats_total",
                                   "_segments_total",
                                   "_capped_dropped")),
                # obs/runlog: the persistent run-history store, hit by
                # the doctor's end-of-fit append and by any concurrent
                # session sharing the process-wide active log
                SharedState("obs/runlog.py",
                            "runlog.RunLog._lock",
                            cls="RunLog",
                            attrs=("_seq", "_counts")),
                # serve/journal: the durable service WAL, appended by
                # the submit path, worker threads and the shutdown
                # drain (always OUTSIDE the executor's lock)
                SharedState("serve/journal.py",
                            "journal.ServiceJournal._lock",
                            cls="ServiceJournal",
                            attrs=("_seq", "_counts")),
            ),
            blocks=(
                BlockSpec("pipeline", "PIPELINE_BLOCK_SCHEMA", (
                    Producer("dict-keys", "parallel/pipeline.py",
                             "ChunkPipeline.report"),
                    Producer("subscript-var", "search/grid.py", "pr"),
                )),
                BlockSpec("dataplane", "DATAPLANE_BLOCK_SCHEMA", (
                    Producer("dict-keys", "parallel/dataplane.py",
                             "report_block"),
                )),
                BlockSpec("geometry", "GEOMETRY_BLOCK_SCHEMA", (
                    Producer("dict-keys", "parallel/taskgrid.py",
                             "GeometryPlan.report_block"),
                )),
                BlockSpec("faults", "FAULTS_BLOCK_SCHEMA", (
                    Producer("dict-keys", "parallel/faults.py",
                             "LaunchSupervisor.__init__"),
                    Producer("subscript-var", "search/grid.py",
                             "faults"),
                )),
                BlockSpec("scheduler", "SCHEDULER_BLOCK_SCHEMA", (
                    Producer("dict-keys", "serve/executor.py",
                             "report_block"),
                    Producer("dict-keys", "serve/executor.py",
                             "SearchExecutor.search_block"),
                )),
                BlockSpec("halving", "HALVING_BLOCK_SCHEMA", (
                    Producer("dict-keys", "search/halving.py",
                             "_render_halving_block"),
                )),
                BlockSpec("memory", "MEMORY_BLOCK_SCHEMA", (
                    Producer("dict-keys", "parallel/memledger.py",
                             "report_block"),
                )),
                BlockSpec("attribution", "ATTRIBUTION_BLOCK_SCHEMA", (
                    Producer("dict-keys", "obs/attribution.py",
                             "attribution_block"),
                )),
                BlockSpec("streaming", "STREAMING_BLOCK_SCHEMA", (
                    Producer("dict-keys", "parallel/taskgrid.py",
                             "StreamPlan.report_block"),
                    Producer("dict-keys", "search/stream.py",
                             "_streaming_counters"),
                )),
                BlockSpec("telemetry", "TELEMETRY_SNAPSHOT_SCHEMA", (
                    Producer("dict-keys", "obs/telemetry.py",
                             "TelemetryService.snapshot"),
                )),
                BlockSpec("protection", "PROTECTION_BLOCK_SCHEMA", (
                    Producer("dict-keys", "parallel/faults.py",
                             "protection_block"),
                )),
                BlockSpec("chunkloop", "CHUNKLOOP_BLOCK_SCHEMA", (
                    Producer("dict-keys", "search/grid.py",
                             "chunkloop_block"),
                )),
                BlockSpec("prefix", "PREFIX_BLOCK_SCHEMA", (
                    Producer("dict-keys", "search/prefix.py",
                             "prefix_block"),
                )),
                BlockSpec("heartbeat", "HEARTBEAT_BLOCK_SCHEMA", (
                    Producer("dict-keys", "obs/heartbeat.py",
                             "heartbeat_block"),
                )),
                BlockSpec("process", "PROCESS_BLOCK_SCHEMA", (
                    Producer("dict-keys", "obs/process.py",
                             "ProcessLedger.report"),
                )),
                BlockSpec("recovery", "RECOVERY_BLOCK_SCHEMA", (
                    Producer("dict-keys", "obs/telemetry.py",
                             "TelemetryService._recovery_block"),
                )),
            ),
            launch_paths=(
                "parallel/faults.py",
                "parallel/pipeline.py",
                "serve/executor.py",
                "search/grid.py::_dispatch",
                "search/grid.py::submit_precompile",
                "search/grid.py::resolve_fused",
                "search/grid.py::exec_fused_range",
                "search/grid.py::attempt",
                "search/grid.py::guarded_launch",
                "search/grid.py::guarded_wait",
                "search/grid.py::host_eval",
            ),
            env_field_exceptions={
                "SST_LOCKCHECK": (
                    "process-wide test-harness toggle; the lock shim "
                    "must exist before any TpuConfig is constructed"),
                "SST_LOCKCHECK_HOLD_S": (
                    "tuning companion of SST_LOCKCHECK; same "
                    "pre-config lifetime"),
                "SST_KEYCHECK": (
                    "process-wide test-harness toggle (key-flow "
                    "recorder twin of SST_LOCKCHECK); read per note() "
                    "call, before any TpuConfig exists"),
            },
            exclude=(),
        )
