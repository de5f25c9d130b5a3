"""Observability subsystem — tracing, metrics, structured logging.

The performance story of this engine (pipelined chunk launches, AOT
compile-ahead, persistent compile caches) lives or dies on being able to
*see* where wall-clock goes — the executor-timeline problem of
distributed-Spark ML (arXiv:1612.01437) and the per-stage-visibility
problem of MPMD pipeline schedulers (arXiv:2412.14374).  Four pieces:

  - ``obs.trace``   — a low-overhead, thread-aware span tracer recording
    into a bounded in-memory ring buffer (documented <2% overhead
    budget, enforced by test; exactly zero recorded work when disabled);
  - ``obs.export``  — Chrome trace-event JSON export: load the file in
    Perfetto (https://ui.perfetto.dev) or ``chrome://tracing`` to see
    the stage/dispatch/compute/gather threads, compile-group boundaries
    and per-launch chunk spans on a shared timeline;
  - ``obs.metrics`` — a registry of named counters/gauges/histograms
    behind ``search_report``: the report's schema is pinned in ONE
    place (``SEARCH_REPORT_SCHEMA``) instead of hand-assembled dicts;
  - ``obs.log``     — a structured logger the ``verbose > 0`` paths
    route through; its stdout-parity emit preserves sklearn's
    ``[CV i/n] END ...`` line format byte-for-byte;
  - ``obs.telemetry`` + ``obs.fleet`` — fleet telemetry for the
    multi-tenant serving path: a process-wide sampler aggregating
    per-tenant SLO series (queue-wait p50/p95, throughput, share),
    device occupancy and fault counters across searches, a localhost
    Prometheus/JSON endpoint owned by the session
    (``TpuConfig(telemetry_port)`` / ``SST_TELEMETRY_PORT``), and an
    always-on flight recorder that dumps a correlated black-box bundle
    to ``SST_FLIGHT_DIR`` on FATAL faults, watchdog timeouts, OOMs,
    cancellations and store quarantines;
  - ``obs.heartbeat`` — in-flight device telemetry for the scanned
    chunk loop: a ``jax.debug.callback`` beacon in the scan step body
    feeds a process-global ``HeartbeatHub`` (live progress/ETA, the
    heartbeat-aware watchdog, the ``search_report["heartbeat"]``
    block), enabled with ``TpuConfig(heartbeat=True)`` /
    ``SST_HEARTBEAT`` — off is an exact no-op.

  - ``obs.process`` — the process ledger: what a process pays once
    (the import by third-party root, the first call, the first fits,
    every program traced, lowered, compiled or loaded, the seconds a
    dispatching thread waited for a build), recorded always and
    bounded; ``search_report["process"]`` and
    ``obs.process_report()``.  The program's one set of jax-monitoring
    listeners lives there.

Enable tracing per search with ``TpuConfig(trace=True)`` (record only)
or ``TpuConfig(trace="out.json")`` (record + export), or process-wide
with the ``SST_TRACE`` environment variable (``1`` or a path).
"""

from spark_sklearn_tpu.obs.trace import (
    Tracer,
    current_correlation,
    get_tracer,
    search_tracing,
    set_correlation,
)
from spark_sklearn_tpu.obs.export import chrome_trace_events, export_chrome_trace
from spark_sklearn_tpu.obs.metrics import (
    SEARCH_REPORT_SCHEMA,
    MetricsRegistry,
    schema_markdown,
    search_registry,
)
from spark_sklearn_tpu.obs.log import StructuredLogger, get_logger
from spark_sklearn_tpu.obs.process import process_report
from spark_sklearn_tpu.obs.telemetry import (
    FlightRecorder,
    TelemetryService,
    flight_recorder,
    get_telemetry,
)

#: obs.fleet re-exports resolve lazily (PEP 562): fleet pulls in
#: http.server, which every `import spark_sklearn_tpu` would otherwise
#: pay at startup with telemetry off — against the zero-cold-start
#: objective.  The session imports fleet only when telemetry_port is
#: actually configured.
_FLEET_EXPORTS = ("FleetEndpoint", "prometheus_text",
                  "resolve_telemetry_port")


def __getattr__(name):
    if name in _FLEET_EXPORTS:
        from spark_sklearn_tpu.obs import fleet
        return getattr(fleet, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "Tracer",
    "current_correlation",
    "get_tracer",
    "search_tracing",
    "set_correlation",
    "chrome_trace_events",
    "export_chrome_trace",
    "MetricsRegistry",
    "SEARCH_REPORT_SCHEMA",
    "search_registry",
    "schema_markdown",
    "StructuredLogger",
    "get_logger",
    "process_report",
    "FlightRecorder",
    "TelemetryService",
    "flight_recorder",
    "get_telemetry",
    "FleetEndpoint",
    "prometheus_text",
    "resolve_telemetry_port",
]
