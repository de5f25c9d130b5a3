"""Session bootstrap — reference util.py / SURVEY §3.5 parity.

The reference's `createLocalSparkSession(appName)` launches an in-process
JVM (reference: python/spark_sklearn/util.py).  On TPU there is nothing to
launch for single-host — `jax.devices()` just works — so the "session" is a
TpuConfig + Mesh pair; multi-host adds one `jax.distributed.initialize`
call (the control-plane analog of Spark's driver bootstrap; data-plane
collectives ride ICI/DCN via XLA — SURVEY §5.8).
"""

from __future__ import annotations

import os
import time
from typing import Optional

from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.parallel.mesh import TpuConfig, build_mesh

logger = get_logger(__name__)


class TpuSession:
    """Holds the mesh + config a process uses for searches and fleets.

    A session points jax's persistent compilation cache at the resolved
    directory (`parallel.pipeline.resolve_compile_cache_dir`:
    `JAX_COMPILATION_CACHE_DIR`, else `TpuConfig.compilation_cache_dir`,
    else the fixed in-checkout default) at construction, so every search
    in the process — and every LATER process sharing the directory —
    amortizes the python->HLO->binary walk (the session-level analog of
    a Spark cluster reusing its deployed jars)."""

    def __init__(self, config: Optional[TpuConfig] = None,
                 appName: str = "spark-sklearn-tpu"):
        from spark_sklearn_tpu.parallel.pipeline import (
            enable_persistent_cache)
        self.appName = appName
        self.config = config or TpuConfig()
        if getattr(self.config, "trace", None):
            # a session asking for tracing turns the recorder on for its
            # whole lifetime (per-search enable would lose inter-search
            # host work from the timeline)
            get_tracer().enable(
                max_events=getattr(self.config, "trace_buffer_size", None))
        with get_tracer().span("session.init", appName=appName):
            self.mesh = build_mesh(self.config)
            self.compile_cache_dir = enable_persistent_cache(self.config)
            # size the device data plane (parallel/dataplane.py) now:
            # every search this session runs shares the same resident
            # X/y/mask uploads — the session-lifetime sc.broadcast
            from spark_sklearn_tpu.parallel.dataplane import plane_for
            self.dataplane = plane_for(self.config)
            # persistent AOT program store (parallel/programstore.py):
            # activate it now and prewarm from the manifest, so the
            # first search's programs — and the launch-geometry plans
            # that select them — are resident before any chunk stages
            from spark_sklearn_tpu.parallel import (
                programstore as _programstore)
            self.programstore = _programstore.activate_store(self.config)
            self._prewarm_summary = {}
            manifest = _programstore.resolve_manifest(self.config)
            if self.programstore is not None and manifest and \
                    os.path.isfile(manifest):
                self._prewarm_summary = self.prewarm(manifest)
            # persistent run history (obs/runlog.py): the search
            # doctor's cross-run regression sentinel appends one
            # attribution record per fit and compares against the
            # stored baseline for the same (family, structure, env)
            from spark_sklearn_tpu.obs import runlog as _runlog
            self.runlog = _runlog.activate_runlog(self.config)
            # parse the fault-injection plan NOW so a typo in
            # TpuConfig(fault_plan=...) / SST_FAULT_PLAN fails loudly at
            # session construction, not halfway through a long search
            from spark_sklearn_tpu.parallel.faults import FaultPlan
            self.fault_plan = FaultPlan.resolve(self.config)
            # the multi-tenant search service (serve/executor.py): the
            # session owns ONE fair-share executor; submit() routes
            # searches through it.  Construction is thread-free — the
            # sst-dispatch loop and worker threads only exist once a
            # search is actually submitted
            from spark_sklearn_tpu.serve import SearchExecutor
            self.executor = SearchExecutor(self.config, appName)
            # the crash-safe service layer (serve/journal.py): durable
            # submission WAL + heartbeat lease on the journal dir.
            # Default OFF — no TpuConfig(service_journal_dir) /
            # SST_SERVICE_JOURNAL_DIR means no object, zero writes, the
            # exact no-op.  A second LIVE owner of the directory raises
            # ServiceLeaseError HERE, at construction, never mid-search
            from spark_sklearn_tpu.serve import journal as _svc_journal
            self.journal = _svc_journal.activate_service_journal(
                self.config, owner=f"{appName}:{os.getpid()}")
            self._recovery_pending = {}
            self._restart_t0 = None
            if self.journal is not None:
                self.executor.attach_journal(self.journal)
            # fleet telemetry (obs/telemetry.py + obs/fleet.py):
            # default OFF — no thread, no socket, hooks early-out.
            # TpuConfig(telemetry_port) / SST_TELEMETRY_PORT turns on
            # the process-wide aggregator, registers this session's
            # scheduler/dataplane/programstore providers, and serves
            # Prometheus + JSON snapshots on localhost
            self.telemetry = None
            self.fleet_endpoint = None
            self._telemetry_owned = False
            self._telemetry_providers = {}
            self._init_telemetry()
            # the journal scan runs AFTER telemetry init so its
            # note_recovery counters (and the crash-marker bundle's
            # embedded snapshot) land in an enabled service; the lease
            # itself was already fenced/acquired above
            if self.journal is not None:
                self._bootstrap_recovery()
        # structured logging channel (never stdout: the session has no
        # legacy print contract)
        logger.info("TpuSession %r: mesh=%s, cache_dir=%r", appName,
                    dict(self.mesh.shape),
                    self.compile_cache_dir,
                    appName=appName, n_devices=self.mesh.size)
        logger.info(
            "data plane: %s (geometry_mode=%s)",
            "disabled" if self.dataplane is None else
            f"budget={self.dataplane.byte_budget // 2 ** 20} MiB",
            getattr(self.config, "geometry_mode", "auto"))
        logger.info(
            "program store: %s",
            "disabled" if self.programstore is None else
            f"{self.programstore.directory} "
            f"(prewarmed {self._prewarm_summary.get('loaded', 0)} "
            "artifact(s))")
        logger.info(
            "run log: %s",
            "disabled" if self.runlog is None else
            f"{self.runlog.directory} (env={self.runlog.env_digest})")
        logger.info(
            "service journal: %s",
            "disabled" if self.journal is None else
            f"{self.journal.directory} "
            f"({len(self._recovery_pending)} non-terminal entr"
            f"{'y' if len(self._recovery_pending) == 1 else 'ies'}, "
            + ("fenced stale lease"
               if (self.journal.lease_info or {}).get("taken_over")
               else "clean lease") + ")")
        from spark_sklearn_tpu.obs import memory as _obs_memory
        from spark_sklearn_tpu.parallel import memledger as _memledger
        self.memledger = _memledger.ledger_for(self.config)
        if self.memledger is not None:
            budget = _obs_memory.resolve_hbm_budget(self.config)
            if budget:
                why = f"{budget // 2 ** 20} MiB"
            elif getattr(self.config, "hbm_budget_bytes", None) == 0 \
                    or os.environ.get(
                        "SST_HBM_BUDGET_BYTES", "").strip() == "0":
                why = "no ceiling — disabled by configuration"
            else:
                why = "no ceiling — no measurable device limit"
            logger.info("memory ledger: on (hbm_budget=%s)", why,
                        hbm_budget_bytes=budget)
        else:
            logger.info("memory ledger: disabled (memory_ledger=False)")
        logger.info(
            "fault supervisor: max_launch_retries=%d "
            "max_search_retries=%d backoff=%.2fs timeout=%s "
            "fault_plan=%d injection(s)",
            getattr(self.config, "max_launch_retries", 2),
            getattr(self.config, "max_search_retries", 16),
            getattr(self.config, "retry_backoff_s", 0.5),
            getattr(self.config, "launch_timeout_s", None),
            len(self.fault_plan))

    def _bootstrap_recovery(self) -> None:
        """Scan the journal at startup: count what this restart owes,
        stamp the time-to-recover clock, and — when the lease was
        fenced from a dead owner — dump the crash-marker flight bundle
        BEFORE recovery overwrites the scene."""
        from spark_sklearn_tpu.obs import telemetry as _telemetry
        from spark_sklearn_tpu.parallel import faults as _faults
        journal = self.journal
        entries = journal.entries()
        self._recovery_pending = journal.nonterminal()
        if self._recovery_pending:
            # the clock resubmit() stops on its first success: the
            # operator-facing time-to-recover
            self._restart_t0 = time.monotonic()
        info = journal.lease_info or {}
        _telemetry.note_recovery("journal_entries", len(entries))
        _telemetry.note_recovery("nonterminal_found",
                                 len(self._recovery_pending))
        if info.get("taken_over"):
            _telemetry.note_recovery("lease_takeovers")
            _telemetry.note_recovery("unclean_shutdowns")
            # no flight dir configured still gets a marker: the journal
            # directory itself is the fallback dump target
            _telemetry.flight_recorder().dump(
                "crash-marker",
                flight_dir=_telemetry.resolve_flight_dir(self.config)
                or journal.directory,
                config=self.config,
                context=_faults.crash_marker_context(
                    self._recovery_pending, info))

    def _init_telemetry(self) -> None:
        from spark_sklearn_tpu.obs import fleet as _fleet
        from spark_sklearn_tpu.obs import telemetry as _telemetry
        port = _fleet.resolve_telemetry_port(self.config)
        if port is None:
            return
        svc = _telemetry.get_telemetry()
        svc.enable(
            window_s=getattr(self.config, "telemetry_window_s", None),
            interval_s=getattr(self.config, "telemetry_interval_s",
                               None))
        self.telemetry = svc
        self._telemetry_owned = True
        # this session's own provider callables, remembered so stop()
        # (and the unwind below) tears down exactly these — never a
        # later session's registration under the same name
        self._telemetry_providers = {
            "scheduler": self.executor.telemetry_gauges}
        if self.dataplane is not None:
            plane = self.dataplane

            def _plane_gauges():
                return {**plane.stats(),
                        "tenant_bytes": {
                            str(t): b for t, b in
                            plane.tenant_usage_all().items()}}

            self._telemetry_providers["dataplane"] = _plane_gauges
        if self.programstore is not None:
            self._telemetry_providers["programstore"] = \
                self.programstore.counts
        if getattr(self.config, "memory_ledger", True):
            # the device-memory ledger's gauges (per-device pressure,
            # modeled peak, watermark) — the sampler keeps the
            # /metrics pressure series current between searches
            from spark_sklearn_tpu.parallel import (
                memledger as _memledger)
            self._telemetry_providers["memory"] = \
                _memledger.get_ledger().gauges
        try:
            for name, fn in self._telemetry_providers.items():
                svc.register_provider(name, fn)
            self.fleet_endpoint = _fleet.FleetEndpoint(
                port, service=svc).start()
        except BaseException:
            # a failed endpoint bind (port in use) must not leave the
            # process-global service enabled with a live sampler bound
            # to this half-built session — unwind to the exact no-op
            self._teardown_telemetry()
            raise
        logger.info(
            "fleet telemetry: window=%.0fs interval=%.2fs endpoint=%s",
            svc.window_s, svc.interval_s, self.fleet_endpoint.url,
            url=self.fleet_endpoint.url)

    def _teardown_telemetry(self) -> None:
        """Release this session's telemetry: drop ONE enable reference
        (refcounted — another telemetry-enabled session keeps the
        shared service alive) and unregister exactly the providers this
        session registered (identity-checked, so a later session's
        same-name registrations survive)."""
        svc = self.telemetry
        self.telemetry = None
        self._telemetry_owned = False
        if svc is None:
            return
        svc.disable()
        for name, fn in getattr(self, "_telemetry_providers",
                                {}).items():
            svc.unregister_provider(name, expected=fn)
        self._telemetry_providers = {}

    def telemetry_snapshot(self) -> dict:
        """The fleet-telemetry snapshot (schema pinned in
        ``obs.metrics.TELEMETRY_SNAPSHOT_SCHEMA``): per-tenant
        queue-wait p50/p95 / throughput / share over the sliding
        window, device occupancy, scheduler queue depth, data-plane and
        program-store gauges, fault totals and flight-recorder state.
        The zeroed ``enabled: False`` shape when telemetry is off."""
        from spark_sklearn_tpu.obs import telemetry as _telemetry
        return _telemetry.get_telemetry().snapshot()

    @property
    def n_devices(self) -> int:
        return self.mesh.size

    # -- multi-tenant serving (serve/executor.py) ------------------------
    def submit(self, search, X, y=None, **fit_params):
        """Submit a search to the session's fair-share executor and
        return a :class:`~spark_sklearn_tpu.serve.SearchFuture`
        (``result()`` / ``cancel()`` / ``progress()``).

        Concurrent submissions interleave their chunk launches on the
        device under deficit-round-robin fair share over tenants
        (``TpuConfig(tenant, tenant_weight)``), with admission control
        (``max_concurrent_searches`` / ``max_queued_searches`` ->
        :class:`~spark_sklearn_tpu.serve.AdmissionError`) and
        per-tenant data-plane byte quotas on top.  Every search's
        ``cv_results_`` is bit-exact with its solo ``fit``; a single
        submitted search short-circuits to the solo dispatch path."""
        return self.executor.submit(search, X, y,
                                    fit_params=fit_params)

    def attach(self, search):
        """Bind a search estimator to this session: its ``fit`` becomes
        sugar for ``submit(...).result()`` — identical results, routed
        through the session's executor so it fair-shares the device
        with concurrently-submitted searches.  Returns the search for
        chaining."""
        search._sst_session = self
        return search

    # -- crash recovery (serve/journal.py) -------------------------------
    def recover(self):
        """What the service journal still owes: a
        :class:`~spark_sklearn_tpu.serve.RecoveryReport` listing every
        journaled search whose last transition is non-terminal (a
        previous process was SIGKILLed mid-flight), plus the lease
        verdict (fenced takeover vs clean start).  The empty report
        when no journal is configured.

        Recovery is two-phase by design: the journal records data
        FINGERPRINTS, not data, so the caller re-binds X/y and passes
        each entry to :meth:`resubmit`."""
        from spark_sklearn_tpu.serve import journal as _svc_journal
        if self.journal is None:
            return _svc_journal.RecoveryReport()
        with get_tracer().span("session.recover"):
            self._recovery_pending = self.journal.nonterminal()
            info = self.journal.lease_info or {}
            entries = []
            for handle in sorted(self._recovery_pending):
                rec = self._recovery_pending[handle]
                entries.append(_svc_journal.RecoveryEntry(
                    handle=handle,
                    tenant=str(rec.get("tenant", "")),
                    weight=float(rec.get("weight", 1.0) or 1.0),
                    family=str(rec.get("family", "")),
                    structure_digest=str(
                        rec.get("structure_digest", "")),
                    data_fingerprint=str(
                        rec.get("data_fingerprint", "")),
                    checkpoint_dir=str(rec.get("checkpoint_dir", "")),
                    state=str(rec.get("state", "")),
                    config=dict(rec.get("config") or {})))
            return _svc_journal.RecoveryReport(
                entries=tuple(entries),
                taken_over=bool(info.get("taken_over")),
                unclean=bool(info.get("unclean")),
                journal_dir=self.journal.directory)

    def resubmit(self, entry, search, X, y=None, **fit_params):
        """Re-admit one recovered search through the NORMAL admission
        path and return its
        :class:`~spark_sklearn_tpu.serve.SearchFuture`.

        ``entry`` is a :class:`~spark_sklearn_tpu.serve.RecoveryEntry`
        from :meth:`recover` (or its journal handle string).  The
        re-bound data's blake2b fingerprint is verified against the
        journaled one FIRST — a mismatch raises
        :class:`~spark_sklearn_tpu.serve.RecoveryDataMismatchError`
        before any admission or device work, because resuming a
        checkpoint journal against different data would silently blend
        two datasets' partial results.  With the same checkpoint
        directory the resumed search replays its per-search journal,
        so the recovered ``cv_results_`` is bit-exact vs the uncrashed
        run."""
        from spark_sklearn_tpu.obs import telemetry as _telemetry
        from spark_sklearn_tpu.serve import journal as _svc_journal
        if self.journal is None:
            raise ValueError(
                "no service journal: construct the session with "
                "TpuConfig(service_journal_dir=...)")
        handle = entry if isinstance(entry, str) else entry.handle
        rec = self._recovery_pending.get(handle)
        if rec is None:
            raise KeyError(
                f"no non-terminal journal entry {handle!r} "
                "(recover() lists what this session owes)")
        expected = str(rec.get("data_fingerprint", ""))
        got = _svc_journal.data_fingerprint(X, y)
        if expected and got != expected:
            _telemetry.note_recovery("mismatch")
            raise _svc_journal.RecoveryDataMismatchError(
                f"recovered search {handle!r}: re-bound data does not "
                f"match the journaled fingerprint (expected "
                f"{expected[:12]}, got {got[:12]})",
                handle=handle, expected=expected, got=got)
        ckpt = str(rec.get("checkpoint_dir", "") or "")
        cfg = getattr(search, "config", None)
        if ckpt and not getattr(cfg, "checkpoint_dir", None) \
                and not getattr(self.config, "checkpoint_dir", None):
            # the recovered search must replay ITS checkpoint journal:
            # carry the journaled directory onto the resubmission when
            # neither the search nor the session names one
            import dataclasses as _dc
            base = cfg if cfg is not None else self.config
            try:
                search.config = _dc.replace(base, checkpoint_dir=ckpt)
            except TypeError:
                pass
        fut = self.executor.submit(search, X, y, fit_params=fit_params,
                                   recovered_from=handle)
        # retire the journaled entry, linked to its successor — the
        # successor's own WAL lifecycle carries the work from here
        self.journal.record_transition(
            handle, "recovered", qualify=False,
            successor=self.journal.qualify(fut.handle_id))
        self._recovery_pending.pop(handle, None)
        if self._restart_t0 is not None:
            # first successful resubmit stops the restart clock
            _telemetry.note_recovery(
                "recovered",
                time_to_recover_s=time.monotonic() - self._restart_t0)
            self._restart_t0 = None
        else:
            _telemetry.note_recovery("recovered")
        return fut

    def executor_stats(self) -> dict:
        """The executor's live state: active/pending search counts and
        per-tenant queue/in-flight/dispatched-cost tallies."""
        return self.executor.stats()

    def dataplane_stats(self) -> dict:
        """Cumulative hit/miss/byte counters of the session's device
        data plane (empty dict when ``dataplane_bytes=0`` disabled
        it)."""
        return {} if self.dataplane is None else self.dataplane.stats()

    def programstore_stats(self) -> dict:
        """Cumulative counters + disk state of the session's persistent
        AOT program store (empty dict when no store is configured)."""
        if self.programstore is None:
            return {}
        return {**self.programstore.counts(),
                **self.programstore.disk_stats()}

    def prewarm(self, manifest) -> dict:
        """Load the AOT program artifacts a manifest declares (path or
        parsed dict — see
        :meth:`~spark_sklearn_tpu.parallel.programstore.ProgramStore.
        prewarm`) into the store's memory cache, so the declared
        (family, grid-shape) programs resolve without disk IO when the
        first search requests them.  No-op (with a log line) when the
        session has no program store."""
        if self.programstore is None:
            logger.info("prewarm skipped: no program store configured "
                        "(TpuConfig.program_store_dir)")
            return {}
        return self.programstore.prewarm(manifest)

    def write_prewarm_manifest(self, path: Optional[str] = None) -> str:
        """Record every store artifact this process served or published
        — what the finished searches actually used — as a prewarm
        manifest for the next session's
        ``TpuConfig(prewarm_manifest=...)``.  Default path: the
        configured ``prewarm_manifest``."""
        if self.programstore is None:
            raise ValueError(
                "no program store: construct the session with "
                "TpuConfig(program_store_dir=...)")
        from spark_sklearn_tpu.parallel.programstore import (
            resolve_manifest)
        target = path or resolve_manifest(self.config)
        if not target:
            raise ValueError(
                "no manifest path: pass one, or construct the session "
                "with TpuConfig(prewarm_manifest=...)")
        return self.programstore.write_manifest(target)

    def export_trace(self, path: Optional[str] = None) -> str:
        """Write the tracer's current buffer as a Chrome trace-event
        JSON (default path: ``TpuConfig.trace`` when it is a string)
        and return the written path."""
        from spark_sklearn_tpu.obs.export import export_chrome_trace
        target = path or (self.config.trace
                          if isinstance(self.config.trace, str) else None)
        if not target:
            raise ValueError(
                "no export path: pass one, or construct the session "
                "with TpuConfig(trace='out.json')")
        return export_chrome_trace(target)

    def stop(self):
        """Shut the session's search executor down (reference API
        symmetry: SparkSession.stop).  Running searches finish, the
        waiting line cancels, new submissions raise AdmissionError.
        A session-owned telemetry endpoint and sampler stop too."""
        self.executor.shutdown()
        if self.journal is not None:
            # AFTER executor shutdown, so the pending line's "shed"
            # transitions land before the clean-shutdown record
            self.journal.release_lease(clean=True)
        if self.fleet_endpoint is not None:
            self.fleet_endpoint.stop()
            self.fleet_endpoint = None
        if self._telemetry_owned:
            self._teardown_telemetry()

    def __repr__(self):
        return (f"TpuSession(appName={self.appName!r}, "
                f"mesh={dict(self.mesh.shape)})")


def createLocalTpuSession(appName: str = "spark-sklearn-tpu",
                          config: Optional[TpuConfig] = None) -> TpuSession:
    """Drop-in analog of the reference's createLocalSparkSession."""
    return TpuSession(config=config, appName=appName)


# alias so reference-style imports keep working
createLocalSparkSession = createLocalTpuSession


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap: one call per host before building the mesh
    (SURVEY §7.3 #6 — everything else is 'same code, bigger mesh').

    With no arguments, defers entirely to jax.distributed's environment
    auto-detection (TPU pod metadata / cluster env vars)."""
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
