"""Device-memory ledger — HBM accounting for every launch the engine plans.

The reference ran many sklearn candidates inside FIXED per-executor
memory; this engine runs them inside fixed HBM — and until this module
it was blind to that budget.  The geometry planner picked chunk widths
from a time-only cost model, the dataplane LRU budgeted itself against
a config number with no view of real headroom, and device memory
exhaustion was *discovered* by catching ``RESOURCE_EXHAUSTED`` and
bisecting (``parallel/faults.py``).  The :class:`MemoryLedger` closes
that gap from both ends:

  - **model** — :func:`model_group_footprint` prices each compile
    group's per-chunk device footprint analytically from the same
    abstract shapes the program store keys on (per-candidate dynamic
    params, the task-batched tiled fold masks, score/health outputs;
    all linear in the chunk width), and :func:`precompile-time
    <note_compiled>` XLA ``memory_analysis`` readings (argument/
    output/temp bytes) ride along where the backend exposes them;
  - **measure** — the ledger samples
    :func:`~spark_sklearn_tpu.obs.memory.device_memory_stats` at launch
    boundaries (``parallel/pipeline.py``) and via the PR 8 telemetry
    sampler, keeping a process high-water mark and the model-vs-
    measured error.  Backends without allocator stats (XLA:CPU) run
    ledger-only with ``measured: False`` — nothing raises, nothing is
    sampled per launch after the first probe;
  - **act** — :func:`width_cap` turns the resolved HBM budget
    (``TpuConfig.hbm_budget_bytes`` / ``SST_HBM_BUDGET_BYTES``, default
    a fraction of detected device memory) into a per-group chunk-width
    ceiling for ``taskgrid.plan_geometry``, so chunks that would not
    fit are never launched — OOM bisection becomes the fallback, not
    the discovery mechanism — and :meth:`MemoryLedger.observe_oom`
    trains a safety margin from the bisections that still happen, so
    the model's blind spots (XLA scratch, fusion temps) tighten the
    ceiling instead of repeating.

Observable everywhere an operator looks: ``search_report["memory"]``
(schema pinned in ``obs.metrics.MEMORY_BLOCK_SCHEMA``), per-device
pressure in the telemetry snapshot and the ``/metrics`` Prometheus
families, ``memory.sample``/``memory.footprint`` trace events, modeled-
vs-budget bytes on every OOM fault event, and a full ledger snapshot
stamped into every flight-recorder bundle — an OOM postmortem finally
shows *what was resident and why*.  ``TpuConfig(memory_ledger=False)``
is the exact-no-op escape hatch: reports and ``cv_results_`` are
byte-identical to the pre-ledger engine.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from spark_sklearn_tpu.obs import memory as _obs_memory
from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.utils.locks import named_lock

__all__ = [
    "MemoryLedger",
    "dataset_nbytes",
    "get_ledger",
    "ledger_for",
    "model_group_footprint",
    "note_compiled",
    "note_launch_boundary",
    "report_block",
    "width_cap",
]

#: bound on the per-group footprint / compiled-analysis records the
#: ledger keeps for forensics (a long-lived session cycling many
#: searches must not grow without bound)
_MAX_RECORDS = 256

#: the safety margin's ceiling: beyond 8x the model is not a model any
#: more and the operator should size the budget explicitly
_MAX_MARGIN = 8.0

#: bytes of score output per (candidate x fold) task per scorer:
#: one f32 test cell (+ one train cell when requested) — the health
#: flags and iteration scalars are noise next to it
_SCORE_CELL_BYTES = 4


def dataset_nbytes(X) -> int:
    """True host bytes of a dataset for footprint pricing.

    Dense arrays report ``nbytes``; CSR-like matrices (scipy sparse,
    ``sparse.csr.CSRMatrix``) report the sum of their component arrays
    — nnz-proportional, NOT ``n x d``.  scipy sparse matrices have no
    ``nbytes`` attribute at all, so the old ``getattr(X, "nbytes", 0)``
    spelling priced them at ZERO, and any dense-equivalent pricing
    would over-reject by orders of magnitude; both are wrong for
    predictive admission (pinned by test_sparse_path.py)."""
    if X is None:
        return 0
    nb = getattr(X, "nbytes", None)
    if nb is not None and isinstance(X, np.ndarray):
        return int(nb)
    if hasattr(X, "indptr") and hasattr(X, "data"):
        total = 0
        for part in (getattr(X, "data", None),
                     getattr(X, "indices", None),
                     getattr(X, "indptr", None)):
            if part is not None:
                total += int(np.asarray(part).nbytes)
        return total
    if nb is not None:
        return int(nb)
    try:
        return int(np.asarray(X).nbytes)
    except (TypeError, ValueError):
        return 0


def model_group_footprint(dynamic_params: Dict[str, np.ndarray],
                          width: int, n_folds: int, *,
                          task_batched: bool, n_samples: int,
                          mask_itemsize: int = 4, n_scorers: int = 1,
                          return_train: bool = False,
                          dtype_itemsize: int = 4,
                          workspace: Optional[Dict[str, int]] = None
                          ) -> Dict[str, Any]:
    """One compile group's modeled per-chunk device bytes at ``width``.

    Everything is linear in the width, derived from the same abstract
    shapes ``precompile`` builds its ``ShapeDtypeStruct`` signature
    from:

      - ``dyn_bytes`` — the staged dynamic-parameter buffers (repeated
        per fold on the task-batched layout; the all-static ``_pad``
        operand when a group has no dynamic params);
      - ``mask_bytes`` — the task-batched tiled fold masks, the
        dominant per-chunk resident on wide launches (``width x
        n_folds x n_samples``); non-task-batched families consume the
        base masks already counted in the broadcast residents;
      - ``out_bytes`` — per-task score cells (+ train cells) and
        health flags the launch materializes.

      - ``workspace_bytes`` — what the family says its launch holds
        besides (``Family.launch_workspace``: ``fixed_bytes`` whatever
        the width, ``per_candidate_bytes`` a candidate): for the kernel
        duals one candidate's Gram matrix and its copies, the dual's
        state and the cached pair decisions.  0 for a family that
        prices none.

    Returns the breakdown plus ``per_candidate_bytes`` (the slope the
    width ceiling divides by), ``fixed_bytes`` (the family's workspace
    that no width changes) and ``chunk_bytes`` (the total at ``width``).
    Other model-pytree and XLA temp bytes are deliberately NOT modeled
    here — they are backend/fusion-dependent; the ledger's safety
    margin (trained by observed OOMs) and the precompile-time
    ``memory_analysis`` readings cover them.
    """
    width = int(width)
    n_folds = max(1, int(n_folds))
    repeat = n_folds if task_batched else 1
    dyn_per_cand = 0
    for arr in dynamic_params.values():
        arr = np.asarray(arr)
        tail = int(np.prod(arr.shape[1:], dtype=np.int64)) \
            if arr.ndim > 1 else 1
        dyn_per_cand += arr.dtype.itemsize * tail * repeat
    if not dynamic_params and not task_batched:
        # the all-static group's `_pad` candidate-axis operand
        dyn_per_cand = int(dtype_itemsize)
    mask_per_cand = (n_folds * int(n_samples) * int(mask_itemsize)
                     if task_batched else 0)
    out_per_cand = n_folds * (
        int(n_scorers) * (2 if return_train else 1) * _SCORE_CELL_BYTES
        + 1)  # + per-task health flag
    workspace = workspace or {}
    ws_fixed = int(workspace.get("fixed_bytes", 0))
    ws_per_cand = int(workspace.get("per_candidate_bytes", 0))
    per_cand = dyn_per_cand + mask_per_cand + out_per_cand + ws_per_cand
    out = {
        "dyn_bytes": dyn_per_cand * width,
        "mask_bytes": mask_per_cand * width,
        "out_bytes": out_per_cand * width,
        "per_candidate_bytes": per_cand,
        "chunk_bytes": per_cand * width + ws_fixed,
    }
    if workspace:
        # only where a family prices one: other searches' records (and
        # reports) keep their keys
        out["workspace_bytes"] = ws_fixed + ws_per_cand * width
        out["fixed_bytes"] = ws_fixed
    return out


def width_cap(budget_bytes: int, resident_bytes: int,
              per_candidate_bytes: int, n_task_shards: int,
              max_width: int, margin: float = 1.0) -> Optional[int]:
    """The widest shard-multiple chunk whose modeled footprint
    (resident broadcast set + ``width x per_candidate_bytes``, scaled
    by the ledger's safety ``margin``) fits ``budget_bytes``.

    ``None`` when no budget applies; never below ``n_task_shards`` —
    the minimum launchable width.  A minimum-width chunk whose model
    still exceeds the budget is *planned* anyway (there is no narrower
    program) and left to the supervisor's bisection/host fallback."""
    if not budget_bytes or per_candidate_bytes <= 0:
        return None
    margin = max(1.0, float(margin))
    avail = budget_bytes - float(resident_bytes) * margin
    w = int(avail // (per_candidate_bytes * margin))
    w -= w % max(1, int(n_task_shards))
    return max(int(n_task_shards), min(int(max_width), w))


class MemoryLedger:
    """Process-global HBM accounting shared by every search.

    Activation is refcounted per running search (the dataplane /
    telemetry pattern): the pipeline's launch-boundary hook early-outs
    unless at least one ledger-enabled search is active, so
    ``TpuConfig(memory_ledger=False)`` stays an exact no-op.  All
    mutable state lives under one named lock; device sampling runs
    outside it."""

    def __init__(self):
        self._lock = named_lock("memledger.MemoryLedger._lock")
        self._active = 0
        #: None = never probed; True/False after the first sample —
        #: unmeasurable backends (XLA:CPU) skip per-launch sampling
        self._measured: Optional[bool] = None
        self.watermark_bytes = 0
        self.peak_modeled_bytes = 0
        self.safety_margin = 1.0
        self.n_samples = 0
        self.n_oom = 0
        self._devices: List[Dict[str, Any]] = []
        self._groups: deque = deque(maxlen=_MAX_RECORDS)
        self._compiled: Dict[str, Dict[str, Any]] = {}

    # -- lifecycle -------------------------------------------------------
    @property
    def active(self) -> bool:
        return self._active > 0

    def activate(self) -> "MemoryLedger":
        with self._lock:
            self._active += 1
        return self

    def deactivate(self) -> None:
        with self._lock:
            self._active = max(0, self._active - 1)

    def reset(self) -> None:
        """Drop accumulated state (test isolation)."""
        with self._lock:
            self._measured = None
            self.watermark_bytes = 0
            self.peak_modeled_bytes = 0
            self.safety_margin = 1.0
            self.n_samples = 0
            self.n_oom = 0
            self._devices = []
            self._groups.clear()
            self._compiled.clear()

    # -- measurement -----------------------------------------------------
    def sample(self, force: bool = False) -> List[Dict[str, Any]]:
        """One reconciliation tick: read every device's allocator
        stats (outside the lock), advance the watermark, and record a
        ``memory.sample`` span carrying the fleet's in-use bytes.
        With ``force=False`` a backend probed unmeasurable is skipped
        (the per-launch hook's cheap path); the telemetry sampler
        passes ``force=True`` so ledger-only gauges stay current."""
        with self._lock:
            if not force and self._measured is False:
                return self._devices
        with get_tracer().span("memory.sample") as sp:
            stats = _obs_memory.device_memory_stats()
            measured = any(r["measured"] for r in stats)
            in_use = max((r["bytes_in_use"] for r in stats), default=0)
            sp.set(bytes_in_use=int(in_use), measured=bool(measured),
                   n_devices=len(stats))
        with self._lock:
            self._measured = measured
            self._devices = stats
            self.n_samples += 1
            if in_use > self.watermark_bytes:
                self.watermark_bytes = int(in_use)
        return stats

    @property
    def measured(self) -> bool:
        with self._lock:
            return bool(self._measured)

    # -- model -----------------------------------------------------------
    def note_group(self, record: Dict[str, Any]) -> None:
        """Register one compile group's modeled footprint (the engine
        calls this once per (group, width) as geometry resolves) and
        advance the modeled peak.  ``record`` carries the
        :func:`model_group_footprint` breakdown plus the group/width
        identity and the search's resident broadcast bytes."""
        footprint = int(record.get("chunk_bytes", 0)) \
            + int(record.get("resident_bytes", 0))
        with self._lock:
            self._groups.append(dict(record))
            if footprint > self.peak_modeled_bytes:
                self.peak_modeled_bytes = footprint
        get_tracer().instant(
            "memory.footprint",
            group=record.get("group"), width=record.get("width"),
            chunk_bytes=int(record.get("chunk_bytes", 0)),
            modeled_bytes=footprint,
            capped=bool(record.get("capped", False)))

    def note_compiled(self, label: str, analysis: Dict[str, Any]) -> None:
        """Record an XLA ``memory_analysis`` reading taken at
        precompile time (argument/output/temp/code bytes for one AOT
        program) — ground truth for the parts the shape model cannot
        see, keyed by the compile label for postmortems."""
        with self._lock:
            if len(self._compiled) >= _MAX_RECORDS:
                self._compiled.pop(next(iter(self._compiled)))
            self._compiled[str(label)] = dict(analysis)

    def observe_oom(self, modeled_bytes: int, budget_bytes: int) -> float:
        """Fold one observed OOM back into the safety margin.

        A launch the model said fits (``modeled <= budget``) that still
        exhausted the device proves the model underestimates by at
        least ``budget / modeled`` — future width ceilings scale by the
        learned margin so the same chunk is never planned again.  An
        OOM with no budget (ceiling off) or an over-budget model just
        nudges the margin up.  Returns the new margin."""
        with self._lock:
            self.n_oom += 1
            if modeled_bytes > 0 and budget_bytes > 0 \
                    and modeled_bytes <= budget_bytes:
                implied = 1.25 * budget_bytes / modeled_bytes
                self.safety_margin = min(
                    _MAX_MARGIN, max(self.safety_margin, implied))
            else:
                self.safety_margin = min(
                    _MAX_MARGIN, self.safety_margin * 1.25)
            return self.safety_margin

    # -- views -----------------------------------------------------------
    def counters(self) -> Dict[str, Any]:
        """Cheap per-search baseline (snapshot before / render after)."""
        with self._lock:
            return {
                "n_samples": self.n_samples,
                "watermark_bytes": self.watermark_bytes,
                "n_oom": self.n_oom,
            }

    def gauges(self) -> Dict[str, Any]:
        """The telemetry sampler's provider view: per-device pressure
        plus the modeled state.  Samples the devices itself (the
        sampler thread polls providers outside every lock)."""
        stats = self.sample(force=True)
        with self._lock:
            return {
                "measured": bool(self._measured),
                "watermark_bytes": self.watermark_bytes,
                "modeled_peak_bytes": self.peak_modeled_bytes,
                "safety_margin": round(self.safety_margin, 4),
                "n_oom_observed": self.n_oom,
                "pressure_frac_max": round(
                    max((_obs_memory.pressure(r) for r in stats),
                        default=0.0), 6),
                "devices": {
                    str(r["id"]): {
                        "bytes_in_use": r["bytes_in_use"],
                        "peak_bytes_in_use": r["peak_bytes_in_use"],
                        "bytes_limit": r["bytes_limit"],
                        "pressure_frac": round(
                            _obs_memory.pressure(r), 6),
                    } for r in stats},
            }

    def snapshot(self) -> Dict[str, Any]:
        """The full ledger state — stamped into every flight-recorder
        bundle so an OOM postmortem shows what was resident and why."""
        with self._lock:
            return {
                "active_searches": self._active,
                "measured": bool(self._measured),
                "watermark_bytes": self.watermark_bytes,
                "modeled_peak_bytes": self.peak_modeled_bytes,
                "safety_margin": round(self.safety_margin, 4),
                "n_samples": self.n_samples,
                "n_oom_observed": self.n_oom,
                "devices": [dict(r) for r in self._devices],
                "groups": [dict(g) for g in self._groups],
                "compiled": {k: dict(v)
                             for k, v in self._compiled.items()},
            }


_LEDGER = MemoryLedger()


def get_ledger() -> MemoryLedger:
    """The process-global ledger every hook reports to."""
    return _LEDGER


def ledger_for(config) -> Optional[MemoryLedger]:
    """The ledger a search should use under ``config`` — ``None`` when
    ``TpuConfig(memory_ledger=False)`` disabled it (the byte-identical
    pre-ledger escape hatch)."""
    if not getattr(config, "memory_ledger", True):
        return None
    return _LEDGER


# -- module-level hook spellings (what the producers call) -----------------

def note_launch_boundary() -> None:
    """Pipeline hook: reconcile model vs reality at a launch boundary.
    Exact no-op unless a ledger-enabled search is active; after the
    first probe, unmeasurable backends (XLA:CPU) early-out too."""
    if _LEDGER.active:
        _LEDGER.sample()


def note_compiled(label: str, exe: Any) -> None:
    """Pipeline precompile hook: harvest the compiled executable's XLA
    ``memory_analysis`` (where the backend provides one) into the
    ledger.  Never raises — the analysis is forensics, not control."""
    if not _LEDGER.active:
        return
    analyze = getattr(exe, "memory_analysis", None)
    if analyze is None:
        return
    try:
        ma = analyze()
    except (RuntimeError, NotImplementedError, TypeError, ValueError):
        return
    if ma is None:
        return
    rec = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            rec[field] = int(v)
    if rec:
        _LEDGER.note_compiled(label, rec)


def snapshot_counters(ledger: Optional[MemoryLedger]) -> Dict[str, Any]:
    """Baseline snapshot for per-search deltas (``search_report
    ["memory"]``)."""
    return ledger.counters() if ledger is not None else {}


def report_block(ledger: MemoryLedger, before: Dict[str, Any],
                 ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The rendered ``search_report["memory"]`` block (schema pinned in
    ``obs.metrics.MEMORY_BLOCK_SCHEMA``): this search's modeled
    footprints and budget next to the process watermark.  ``ctx`` is
    the engine's per-search accumulator (group records, resident
    bytes, resolved budget, the search-start measured baseline)."""
    counters = ledger.counters()
    groups = list(ctx.get("groups", ()))
    resident = int(ctx.get("resident_bytes", 0))
    # each group record pairs its chunk bytes with the resident set
    # that was live when it was planned (a halving rung's compacted
    # residents differ from the last rung's), so the peak is the max
    # of footprints that actually coexisted — matching the ledger's
    # own note_group accounting
    peak_modeled = max(
        (int(g.get("chunk_bytes", 0)) + int(g.get("resident_bytes", 0))
         for g in groups), default=resident)
    measured = ledger.measured
    watermark = int(counters.get("watermark_bytes", 0))
    baseline = int(ctx.get("measured_baseline_bytes", 0))
    error_frac = 0.0
    if measured and watermark > baseline and peak_modeled > 0:
        used = watermark - baseline
        error_frac = round(abs(peak_modeled - used) / used, 6)
    return {
        "enabled": True,
        "measured": measured,
        "budget_bytes": int(ctx.get("budget_bytes", 0)),
        "device_limit_bytes": int(ctx.get("device_limit_bytes", 0)),
        "safety_margin": round(ledger.safety_margin, 4),
        "peak_modeled_bytes": int(peak_modeled),
        "resident_bytes": resident,
        "watermark_bytes": watermark,
        "model_error_frac": error_frac,
        "n_samples": int(counters.get("n_samples", 0))
        - int(before.get("n_samples", 0)),
        "groups": groups,
    }
