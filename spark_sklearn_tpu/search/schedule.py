"""The launch schedule of one compiled search.

``BaseSearchTPU._run_groups`` (search/grid.py) hands a search's compile
groups here.  :func:`plan_groups` plans them on the host: the convergence
sort, a family's launch layout and tiles, the memory ledger's width caps,
the launch geometry (with a halving rung's re-plan or pin), whole-run
rounding and the checkpoint journal's check of the geometry, one
:class:`GroupPlan` a group.  A :class:`LaunchSchedule` then turns every
plan's live chunks into ``LaunchItem``s and runs them through the chunk
pipeline under the fault supervisor: the shared-prefix stage, mask
tiling, operand staging, the fused, unfused and calibration launches,
scanned segments, OOM bisection and the cells each launch writes.  Its
:meth:`~LaunchSchedule.close` lets go of every device buffer the
schedule and its plans hold, so a finished search frees them without
waiting for Python's cycle collector.

The traced programs are built in search/grid.py (``_build_programs``,
``_build_scan``, ``_prefix_program``), beside the cross-search program
cache whose keys the key-flow lint proves.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from spark_sklearn_tpu import serve as _serve
from spark_sklearn_tpu.obs import heartbeat as _heartbeat
from spark_sklearn_tpu.obs import process as _process
from spark_sklearn_tpu.obs import telemetry as _telemetry
from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs.trace import get_tracer
from spark_sklearn_tpu.parallel import dataplane as _dataplane
from spark_sklearn_tpu.parallel import faults as _faults
from spark_sklearn_tpu.parallel import memledger as _memledger
from spark_sklearn_tpu.parallel import mesh as mesh_lib
from spark_sklearn_tpu.parallel import ownership as _ownership
from spark_sklearn_tpu.parallel import programstore as _pstore
from spark_sklearn_tpu.parallel.pipeline import (
    ChunkPipeline, FuseSpec, LaunchItem, persistent_cache_counts)
from spark_sklearn_tpu.parallel.taskgrid import (
    GeometryMismatchError, GeometryPlan, freeze, geometry_cost_model,
    pad_chunk, plan_geometry, plan_scan_segments, resolve_chunk_loop,
    split_range)
from spark_sklearn_tpu.search import grid as _grid
from spark_sklearn_tpu.search import prefix as _prefix
from spark_sklearn_tpu.search.launch import LaunchResult, record_stats
from spark_sklearn_tpu.utils import keycheck as _keycheck
from spark_sklearn_tpu.utils.locks import named_lock

logger = get_logger("spark_sklearn_tpu.search")

__all__ = ["GroupPlan", "LaunchSchedule", "plan_groups"]


@dataclasses.dataclass(eq=False)
class GroupPlan:
    """One compile group's launch plan, and the one statement of the
    launch-layout rule.

    A family's launch layout (``Family.launch_layout``) orders the
    group's candidates so that a launch's candidates come in runs of
    `run` that share work (SVC's four C of one gamma share ONE kernel
    matrix), and adds static facts that tell the launch so and key its
    program.  The facts hold for a launch made of whole runs only: any
    other launch (a width under one run, a bisected range, a fused
    launch whose seams cut a run) runs the program of `static_plain`,
    the static without them, at its price."""

    gi: int
    group: Any
    nc: int
    static: Dict[str, Any]
    static_plain: Dict[str, Any]
    sorted_chunks: bool = False
    sorted_cap: Optional[int] = None
    laid_out: bool = False
    run: int = 1
    prefix: Optional[str] = None
    #: set by plan_groups: the width, the ledger's cap, the chunks as
    #: (lo, hi, chunk_id, journalled record) and how many still launch
    nc_batch: int = 0
    cap: Optional[int] = None
    chunks: List[tuple] = dataclasses.field(default_factory=list)
    n_live: int = 0
    mem_chunk_bytes: int = 0
    #: what the launches hold, let go of by release()
    data_dev: Optional[Dict[str, Any]] = None
    programs: Dict[Any, Any] = dataclasses.field(default_factory=dict)
    scan_programs: Dict[Any, Any] = dataclasses.field(
        default_factory=dict)
    w_task_dev: Any = None
    aot_submitted: bool = False
    aot_future: Any = None
    fused_call: Any = None
    #: the calibrated warm score cost per padded lane (set before any
    #: fused chunk finalizes; 0.0 when recovery lost the calibration)
    score_s_per_lane: Optional[float] = None
    calibration_lost: bool = False
    staged_ids: set = dataclasses.field(default_factory=set)

    def __post_init__(self):
        # staged chunks are noted by the stage thread and by a retry's
        self._lock = named_lock("schedule.GroupPlan._lock")

    def whole_runs(self, lo, *sizes) -> bool:
        """Whether rows cut from the group's candidates at `lo` in
        consecutive pieces of `sizes` are whole runs of its layout
        (`whole_runs(width)`: a launch of `width` candidates is)."""
        return all(n % self.run == 0 for n in (lo,) + sizes)

    def static_for(self, whole: bool) -> Dict[str, Any]:
        """The static of a launch that is (or is not) whole runs."""
        return self.static if whole else self.static_plain

    def whole_width(self, width: int, n_task_shards: int,
                    limit: Optional[int] = None) -> int:
        """`width` as whole runs of shard stripes: rounded up (from at
        most the group's candidates) where that stays within `limit`,
        else down; a width already whole runs, or under one stripe of
        runs, stays as it is.  No `limit`: a ceiling, only rounded
        down."""
        if width % self.run == 0:
            return width
        step = math.lcm(self.run, n_task_shards)
        if limit is not None:
            up = mesh_lib.pad_to_multiple(min(width, self.nc), step)
            if up <= limit:
                return up
        return width - width % step or width

    def note_staged(self, chunk_ids) -> None:
        """Once the group's last live chunk has staged, drop the tiled
        fit masks (each payload keeps its own) so one group's masks
        never outlive its launches.  A set of ids: a transient retry
        re-stages and must not count twice."""
        with self._lock:
            self.staged_ids.update(chunk_ids)
            if len(self.staged_ids) >= self.n_live:
                self.w_task_dev = None

    def release(self) -> None:
        """Let go of everything the plan's launches hold."""
        self.data_dev = self.w_task_dev = None
        self.aot_future = self.fused_call = None
        self.programs = {}
        self.scan_programs = {}


def _reorder(group, order):
    """The group's candidates in another order (cv_results_ is written
    through candidate_indices, so its order stays)."""
    group.candidate_indices = np.asarray(group.candidate_indices)[order]
    group.dynamic_params = {k: np.asarray(v)[order]
                            for k, v in group.dynamic_params.items()}


def _new_plan(gi, group, static, *, family, meta, config, n_folds,
              n_task_shards, max_cand_per_batch, prefix):
    """The group's plan before the geometry: its candidates sorted by
    convergence or laid out for the family's launch."""
    nc = group.n_candidates
    # convergence-sorted chunking: a lockstep launch runs its slowest
    # lane's iterations, so a group with a difficulty proxy (GLM: larger
    # C converges slower) is sorted by it and cut into narrower launches
    # of ONE program, and easy launches early-exit at their own count
    sorted_chunks = False
    proxy_hook = getattr(family, "convergence_proxy", None)
    if proxy_hook is not None and config.sort_candidates:
        proxy = proxy_hook(group.dynamic_params, static)
        if proxy is not None:
            proxy = np.asarray(proxy)
            if len(proxy) >= getattr(family, "min_sort_candidates", 32) \
                    and np.unique(proxy).size > 1:
                _reorder(group, np.argsort(proxy, kind="stable"))
                sorted_chunks = True
    # a launch layout (Family.launch_layout): an order the family's
    # task-batched launch can use, applied as the sort above is, and
    # static facts that tell the launch so and key its program
    layout = None if sorted_chunks else family.launch_layout(
        group.dynamic_params, static, meta, n_folds)
    static_plain, run = static, 1
    if layout is not None:
        order, facts, run = layout
        _reorder(group, order)
        static = {**static, **facts}
    sorted_cap = None
    if sorted_chunks:
        # ~8 difficulty-graded launches per group, or one a run of
        # equal proxies (bounded below by the task-shard multiple so
        # sharding stays uniform)
        sorted_cap = min(
            mesh_lib.pad_to_multiple(nc, n_task_shards),
            max_cand_per_batch,
            max(n_task_shards,
                mesh_lib.pad_to_multiple(
                    _grid._sorted_launch_width(
                        proxy, -(-nc // _grid._SORTED_LAUNCHES)),
                    n_task_shards)))
    return GroupPlan(
        gi=gi, group=group, nc=nc, static=static,
        static_plain=static_plain, sorted_chunks=sorted_chunks,
        sorted_cap=sorted_cap, laid_out=layout is not None,
        run=int(run), prefix=prefix)


def _choose_geometry(rung, rung_keys, geo_kwargs, n_task_shards,
                     max_cand_per_batch):
    """The launch geometry: the full grid's plan (plan-cache included),
    or a halving rung's re-plan or pin of the survivors' widths."""
    if rung is None or rung.itr == 0:
        return plan_geometry(reuse=True, **geo_kwargs)
    # a later rung's geometry is search-local, priced by the previous
    # rungs' measured timelines, so it bypasses the plan cache.  With
    # lane reclamation on, widths shrink to the survivors (affine to
    # compiled widths); off, they stay pinned to rung 0's.  Widths are
    # pure geometry: cv_results_ is identical either way.
    with get_tracer().span("geometry.replan", iter=rung.itr,
                           replan=bool(rung.replan)):
        if rung.replan:
            geo = plan_geometry(
                reuse=False, min_width=rung.min_rung_width,
                preferred=[rung.last_widths.get(k) for k in rung_keys],
                **geo_kwargs)
            return dataclasses.replace(geo, source="halving-replan")
        geo = plan_geometry(reuse=False, **geo_kwargs)
        pinned = []
        for gg, k in zip(geo.groups, rung_keys):
            base_w = rung.base_widths.get(k)
            if base_w is not None and base_w % n_task_shards == 0 \
                    and base_w <= max_cand_per_batch:
                gg = dataclasses.replace(
                    gg, width=int(base_w),
                    n_chunks=-(-gg.n_candidates // int(base_w)))
            pinned.append(gg)
        return dataclasses.replace(geo, groups=pinned,
                                   source="halving-pinned")


def _journal_geometry(ckpt, geo, plans, n_task_shards,
                      max_cand_per_batch):
    """The geometry a checkpointed search runs: the journalled plan on
    resume (a structurally different one is a hard error, never a
    silent mix of chunk ids), else the new plan, journalled; the same
    for the shared-prefix grouping."""
    journalled = ckpt.get_meta("geometry_plan")
    if journalled is not None:
        jplan = GeometryPlan.from_dict(journalled)
        if jplan.signature() != geo.signature():
            raise GeometryMismatchError(
                "checkpoint was written under a different launch "
                "geometry (journalled per-group (n_candidates, "
                f"sorted) = {jplan.signature()}, current = "
                f"{geo.signature()}); resuming would mix chunk "
                "ids across geometries.  Delete "
                f"{ckpt.path!r} or restore the original "
                "sort_candidates/grid configuration.")
        # the journalled widths must still be shard multiples within
        # the CURRENT bound (a resume on a smaller mesh, or after
        # lowering max_tasks_per_batch, would break that silently)
        bad = [g.width for g in jplan.groups
               if g.width % n_task_shards != 0
               or g.width > max_cand_per_batch]
        if bad:
            raise GeometryMismatchError(
                f"journalled chunk widths {bad} are invalid "
                f"under the current configuration (task shards="
                f"{n_task_shards}, max width per launch="
                f"{max_cand_per_batch}); the checkpoint was "
                "written on a different mesh or "
                "max_tasks_per_batch.  Delete "
                f"{ckpt.path!r} or restore the original "
                "configuration.")
        # replay: chunk ids, so resume hits, match the original run
        geo = dataclasses.replace(jplan, source="journal")
    else:
        ckpt.put_meta("geometry_plan", geo.to_dict())
    # the prefix grouping journals beside the geometry, and a resume
    # whose digests drifted fails like any other geometry drift.  Atomic
    # searches journal none (their checkpoints stay byte-compatible), so
    # an atomic checkpoint may resume under shared staging: its chunks
    # are bit-exact either way
    px_cur = [p.prefix for p in plans]
    px_journalled = ckpt.get_meta("prefix_plan")
    if px_journalled is not None:
        if list(px_journalled) != list(px_cur):
            raise GeometryMismatchError(
                "checkpoint was written under a different "
                "shared-prefix grouping (journalled per-group "
                f"digests = {px_journalled}, current = "
                f"{px_cur}); resuming would mix prefix-staged "
                "and atomic chunk results.  Delete "
                f"{ckpt.path!r} or restore the original grid/"
                "prefix_reuse configuration.")
    elif any(d is not None for d in px_cur):
        ckpt.put_meta("prefix_plan", px_cur)
    return geo


def _note_rung(rung, geo, rung_keys, n_folds):
    """Rung bookkeeping: remember rung-0 widths (the pin/affinity
    anchors) and account the lanes this rung's re-plan reclaimed vs.
    running the SAME survivors at rung-0 widths."""
    for gg, k in zip(geo.groups, rung_keys):
        rung.base_widths.setdefault(k, int(gg.width))
        rung.last_widths[k] = int(gg.width)
    rung_rec = rung.current
    if rung_rec is None:
        return
    rung_rec["widths"] = [int(g.width) for g in geo.groups]
    rung_rec["n_launches_planned"] = int(
        sum(g.n_chunks for g in geo.groups))
    rung_rec["cost_observations"] = int(
        geo.cost_model.get("n_observations", 0))
    if rung.itr > 0:
        base_lanes = act_lanes = 0
        for gg, k in zip(geo.groups, rung_keys):
            bw = rung.base_widths.get(k, gg.width)
            base_lanes += (-(-gg.n_candidates // bw)) * bw * n_folds
            act_lanes += gg.n_chunks * gg.width * n_folds
        reclaimed = max(0, base_lanes - act_lanes)
        rung_rec["lanes_reclaimed"] = int(reclaimed)
        rung_rec["padding_saved_frac"] = round(
            reclaimed / base_lanes, 6) if base_lanes else 0.0
        rung.lanes_reclaimed_total += int(reclaimed)


def plan_groups(groups, *, base_params, family, meta, config, n_folds,
                n_task_shards, max_cand_per_batch, fit_masks, n_scorers,
                return_train, dtype, metrics, prefixes=None,
                fusion_on=False, chunk_loop="per_chunk", rung=None,
                ckpt=None, ledger=None, mem_ctx=None, resident_bytes=0):
    """Plan a search's compile groups on the host: one
    :class:`GroupPlan` a group, and the launch geometry.

    The waste-aware geometry (``taskgrid.plan_geometry``) chooses every
    group's chunk width over the measured cost model.  Where the memory
    ledger has a budget (`ledger`, `mem_ctx`; `resident_bytes` the
    search's resident broadcast), each group's modeled footprint caps
    its width, so OOM bisection stays the fallback.  The plan is pinned
    into the checkpoint journal (`ckpt`) so a resumed search replays the
    same chunk ids.  `prefixes` are the groups' shared-prefix digests
    (None: atomic); `rung` a halving rung."""
    prefixes = prefixes or [None] * len(groups)
    plans = [
        _new_plan(gi, group, {**base_params, **group.static_params},
                  family=family, meta=meta, config=config,
                  n_folds=n_folds, n_task_shards=n_task_shards,
                  max_cand_per_batch=max_cand_per_batch, prefix=dg)
        for gi, (group, dg) in enumerate(zip(groups, prefixes))]
    n_samples = int(fit_masks.shape[1])
    budget = 0
    if ledger is not None and mem_ctx is not None:
        budget = int(mem_ctx.get("budget_bytes", 0))
    # what a launch works through a tile at a time, sized to what the
    # budget leaves (Family.launch_tiles): facts of every program's key
    tiles_hook = getattr(family, "launch_tiles", None)
    for p in plans if tiles_hook is not None else ():
        tiles = tiles_hook(
            p.static, meta, n_folds, p.nc, n_samples=n_samples,
            budget_bytes=budget, resident_bytes=resident_bytes,
            return_train=return_train)
        if tiles:
            p.static = {**p.static, **tiles}
            p.static_plain = {**p.static_plain, **tiles}
    mem_kw = dict(
        task_batched=hasattr(family, "fit_task_batched"),
        n_samples=n_samples, mask_itemsize=int(fit_masks.dtype.itemsize),
        n_scorers=n_scorers, return_train=return_train,
        dtype_itemsize=int(np.dtype(dtype).itemsize))

    def footprint(plan, width=None, model_width=1):
        """The ledger's model of a launch of the group at `width`, with
        what the family says it holds besides its arguments: a width
        that is not whole runs is priced without the layout's facts."""
        ws = family.launch_workspace(
            n_samples, meta, n_folds, int(np.dtype(dtype).itemsize),
            static=plan.static_for(width is None
                                   or plan.whole_runs(width)))
        return _memledger.model_group_footprint(
            plan.group.dynamic_params, model_width, n_folds,
            **({**mem_kw, "workspace": ws} if ws else mem_kw))

    def width_cap(plan, width=None):
        fp1 = footprint(plan, width)
        return _memledger.width_cap(
            budget, resident_bytes + fp1.get("fixed_bytes", 0),
            fp1["per_candidate_bytes"], n_task_shards,
            max_cand_per_batch, ledger.safety_margin)

    capped = ledger is not None and bool(budget)
    for p in plans if capped else ():
        cap = width_cap(p)
        if cap is not None and cap % p.run:
            # the widest whole runs under the cap, else the cap of a
            # launch without the layout
            cap = p.whole_width(cap, n_task_shards)
            if cap % p.run:
                cap = width_cap(p, cap)
        p.cap = cap
    geo_kwargs = dict(
        sizes=[p.nc for p in plans],
        sorted_caps=[p.sorted_cap for p in plans],
        n_folds=n_folds, n_task_shards=n_task_shards,
        max_width=max_cand_per_batch,
        mode=getattr(config, "geometry_mode", "auto"),
        cost_model=geometry_cost_model(),
        overhead_override=getattr(config, "geometry_overhead_s", None),
        lane_cost_override=getattr(config, "geometry_lane_cost_s", None),
        width_caps=[p.cap for p in plans] if capped else None,
        # under cross-search fusion a padded lane is fillable by a peer,
        # so it prices at half the solo waste
        fusion_lane_discount=0.5 if fusion_on else 0.0,
        # both join the plan key: widths are loop-mode-invariant, and a
        # prefix-staged plan must never alias an atomic one
        chunk_loop=chunk_loop, prefix=[p.prefix for p in plans])
    #: per-group structure identity ACROSS rungs: the static params
    #: minus the budgeted resource
    rung_keys = None
    if rung is not None:
        rung_keys = [freeze({k: v for k, v in p.static.items()
                             if k != rung.resource}) for p in plans]
    geo = _choose_geometry(rung, rung_keys, geo_kwargs, n_task_shards,
                           max_cand_per_batch)
    # a laid-out group's chunks are whole runs only where its width is
    # a multiple of the run; under one run it stays, and its launches
    # run without the layout
    whole = list(geo.groups)
    for i, (p, gg) in enumerate(zip(plans, geo.groups)):
        if gg.width % p.run:
            w = p.whole_width(gg.width, n_task_shards, limit=min(
                max_cand_per_batch, p.cap or max_cand_per_batch))
            whole[i] = dataclasses.replace(
                gg, width=int(w), n_chunks=-(-gg.n_candidates // w))
    if whole != list(geo.groups):
        geo = dataclasses.replace(geo, groups=whole)
    if ckpt is not None:
        geo = _journal_geometry(ckpt, geo, plans, n_task_shards,
                                max_cand_per_batch)
    metrics.put("geometry", geo.report_block())
    if rung is not None:
        _note_rung(rung, geo, rung_keys, n_folds)

    cid_ns = f"{rung.ns}:" if rung is not None else ""
    for plan, gg in zip(plans, geo.groups):
        plan.nc_batch = int(gg.width)
        # resume state resolved up front: which chunk calibrates and
        # which fuse must be known before dispatch
        for lo in range(0, plan.nc, plan.nc_batch):
            hi = min(lo + plan.nc_batch, plan.nc)
            # sorted and laid-out chunks write cells through a PERMUTED
            # index set, so the id carries the order; a halving rung
            # prefixes its namespace ("r2:...")
            chunk_id = cid_ns + f"{plan.gi}:{lo}:{hi}" + (
                ":s" if plan.sorted_chunks else
                ":k" if plan.laid_out else "")
            rec = ckpt.get(chunk_id) if ckpt is not None else None
            if rec is not None and return_train and \
                    rec.get("train") is None:
                rec = None  # written without train scores: recompute
            plan.chunks.append((lo, hi, chunk_id, rec))
        plan.n_live = sum(1 for c in plan.chunks if c[3] is None)

    if ledger is not None and mem_ctx is not None:
        # every (group, width) footprint, as search_report["memory"]
        # renders it and OOM events report it against the budget
        mem_ctx["resident_bytes"] = resident_bytes
        for plan, gg in zip(plans, geo.groups):
            fp = footprint(plan, plan.nc_batch, plan.nc_batch)
            rec = {"group": cid_ns + str(plan.gi),
                   "width": int(plan.nc_batch),
                   "capped": bool(getattr(gg, "capped", False)),
                   "resident_bytes": int(resident_bytes), **fp}
            plan.mem_chunk_bytes = int(fp["chunk_bytes"])
            ledger.note_group(rec)
            mem_ctx["groups"].append(rec)
    return plans, geo


@dataclasses.dataclass
class _FirstChunk:
    """What a group's first live chunk's separate fit, score and
    calibration items hand one another: the fitted models on the
    device, the cells a host recovery produced, and the fit wall."""

    models: Any = None
    host: Any = None
    t_fit: Any = None


def _member_rows(group, lo, hi):
    return {k: np.asarray(arr[lo:hi])
            for k, arr in group.dynamic_params.items()}


class LaunchSchedule:
    """The chunked launch schedule of one compiled search (or one rung
    of a halving search), executed through the pipelined chunk executor
    (parallel/pipeline.py).

    Every chunk of every compile group becomes one (or, for a group's
    first chunk, two or three) `LaunchItem`s: host staging of chunk k+1,
    the result gather of chunk k-1, and the next compile group's
    lowering/compile all overlap chunk k's device compute at
    `config.pipeline_depth >= 1`; depth 0 runs the identical item
    sequence synchronously (the bit-for-bit escape hatch).  Scores are
    independent of the depth — only host work is reordered.

    The arguments are ``_run_groups``' own; the schedule reads and writes
    the search (`search`) only through its report, its verbose lines and
    its protection, heartbeat and memory contexts."""

    def __init__(self, search, *, groups, base_params, family, meta,
                 scorers, scorer_names, data_dev, fit_dev, test_dev,
                 train_sc_dev, test_unw_dev, train_unw_dev, sw_blind,
                 fit_masks, mesh, config, n_task_shards, task_shard,
                 max_cand_per_batch, n_folds, dtype, return_train,
                 test_scores, train_scores, fit_times, score_times, ckpt,
                 fit_failed, candidates, host_eval=None, data_fp=None):
        self.search = search
        self.groups, self.base_params = groups, base_params
        self.family, self.meta, self.config = family, meta, config
        self.scorers, self.scorer_names = scorers, scorer_names
        self.data_dev, self.fit_dev = data_dev, fit_dev
        #: the scoring masks every score, fused and scan program takes
        self.score_ops = (test_dev, train_sc_dev, test_unw_dev,
                          train_unw_dev)
        self.sw_blind, self.fit_masks, self.mesh = sw_blind, fit_masks, mesh
        self.n_task_shards, self.task_shard = n_task_shards, task_shard
        self.max_cand_per_batch = max_cand_per_batch
        self.n_folds, self.dtype = n_folds, dtype
        self.return_train = return_train
        self.test_scores, self.train_scores = test_scores, train_scores
        self.fit_times, self.score_times = fit_times, score_times
        self.ckpt, self.fit_failed = ckpt, fit_failed
        self.candidates, self.host_eval = candidates, host_eval
        self.data_fp = data_fp
        self.plans: List[GroupPlan] = []
        self.metrics = metrics = search._search_metrics

        #: successive-halving rung owner (search/halving.py): this call
        #: is one rung of a search whose rungs share the pipeline, the
        #: registry and the baselines
        self.rung = rung = _ownership.current_owner(search, kind="rung")
        self.cid_ns = f"{rung.ns}:" if rung is not None else ""
        # tiled-mask and prefix labels share the broadcast masks' rung
        # namespace: the rung barrier demotes the previous rung's only
        rung_data = rung is not None and rung.resource == "n_samples"
        self.tiled_label = (f"mask.{rung.ns}." if rung_data
                            else "mask.") + "fit.tiled"
        self.px_label = f"prefix.{rung.ns}." if rung_data else "prefix."
        self.task_batched = hasattr(family, "fit_task_batched")
        if config.n_data_shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self.tb_mask_shard = NamedSharding(
                mesh, P(mesh_lib.TASK_AXIS, mesh_lib.DATA_AXIS))
        else:
            self.tb_mask_shard = task_shard
        # the session's device data plane tiles the masks on device and
        # caches the all-static pad operand
        self.plane = _dataplane.plane_for(config)
        # the device-memory ledger: the per-search accumulator was
        # initialized by _fit_compiled_impl
        self.ledger = _memledger.ledger_for(config)
        self.mem_ctx = getattr(search, "_memory_ctx", None) \
            if self.ledger is not None else None
        # the executor binding (serve/executor.py) of a search submitted
        # to a TpuSession: its items route through the session's shared
        # dispatch queue, its uploads are charged to its tenant
        self.binding = binding = _serve.current_binding()
        self.tenant = binding.tenant if binding is not None else None
        # self-protection (deadline shed / quarantine / degradation):
        # None when off, and every guarded path is the plain engine's
        self.pctx = getattr(search, "_protection_ctx", None)
        # the score of work a protected search never ran: sklearn's
        # numeric error_score, or NaN under error_score='raise' (shed
        # cells are declared in the protection block, not failed fits)
        self.errval = (np.nan if isinstance(search.error_score, str)
                       else search.error_score)
        self.best_effort = str(getattr(config, "partial_results", "raise")
                               or "raise") == "best_effort"
        self._fm_fp = None

        # the wide score path: every registry scorer is model views plus
        # a metric core, the views computed ONCE a launch over the flat
        # task axis.  Scorers without a core, and SST_NESTED_SCORE=1
        # (the A/B control arm; "0"/"off" must NOT force it), keep the
        # nested path
        nested_env = os.environ.get(
            "SST_NESTED_SCORE", "").strip().lower() in (
                "1", "true", "on", "yes")
        self.all_cores = all(hasattr(fn, "core")
                             for fn in scorers.values()) and not nested_env
        # fused launch (default): fit + NaN-health + scoring in ONE
        # program a chunk; a group's first live chunk runs separate fit
        # and score launches plus a warm calibration score launch
        self.fused_mode = self.all_cores and config.fuse_fit_score
        # chunk_loop="scan" rolls a group's chunks into ONE lax.scan
        # launch of the fused body, so it needs the fused path
        self.chunk_loop = resolve_chunk_loop(config)
        self.scan_mode = (self.chunk_loop == "scan") and self.fused_mode
        self.cl_state = _grid.chunkloop_block(
            metrics.struct("chunkloop"), mode=self.chunk_loop,
            enabled=self.scan_mode,
            score_attribution="folded" if self.scan_mode
            else "calibrated")
        if self.chunk_loop == "scan" and not self.fused_mode:
            self.cl_state["fallbacks"].append("unfused-score-path")
        self._init_prefix()
        self._init_heartbeat()
        # cross-search launch fusion (serve/executor.py): an executor-
        # submitted search's steady-state fused chunks offer a FuseSpec
        # to same-program chunks of OTHER searches; first-chunk items
        # and scanned segments never fuse
        self.fusion_on = (self.fused_mode and binding is not None
                          and not self.scan_mode
                          and _serve.resolve_fusion(config))
        # THIS search's program store (None: a store-less search never
        # resolves programs through a store an earlier search activated)
        self.store = _pstore.activate_store(config)
        # quarantine armed: the first-chunk items carry an isolate hook
        # too, so a poison candidate in ANY chunk is quarantined alone
        self.quarantine_armed = (
            self.pctx is not None and self.best_effort
            and int(getattr(config, "quarantine_fatal_k", 3) or 0) > 0)
        self.resident_bytes = 0
        self.pipe = None
        self._oom_lock = named_lock("schedule.LaunchSchedule._oom_lock")
        self._oom_trained: set = set()

    # ------------------------------------------------------------------
    # search-wide context
    # ------------------------------------------------------------------
    def _init_prefix(self):
        """Shared-prefix search graphs (search/prefix.py): each DISTINCT
        transformer chain computed once a fold (:meth:`stage_prefixes`),
        the suffix candidates fanned over the cached matrices.
        Ineligible searches run atomic and record the reason."""
        px_on = _prefix.resolve_prefix_reuse(self.config)
        self.px_state = _prefix.prefix_block(
            self.metrics.struct("prefix"),
            mode="shared" if px_on else "atomic", enabled=False)
        reason = None
        if px_on:
            reason = _prefix.prefix_fallback_reason(
                self.family, all_cores=self.all_cores,
                n_data_shards=int(self.config.n_data_shards),
                x_dev=self.data_dev.get("X"))
            if reason is None and self.plane is None:
                # the derived-buffer cache IS the data plane; without
                # it there is nowhere resident to fan suffixes over
                reason = "dataplane-disabled"
            if reason is None and self.data_fp is None:
                reason = "no-x-fingerprint"
            if reason is not None \
                    and reason not in self.px_state["fallbacks"]:
                self.px_state["fallbacks"].append(reason)
        self.px_stage = px_on and reason is None

    def _init_heartbeat(self):
        """In-flight heartbeats (obs/heartbeat.py) of the scanned
        steps; `hb_on` gates EVERY heartbeat touch, so off is an exact
        no-op."""
        hb_ctx = getattr(self.search, "_hb_ctx", None)
        binding, config = self.binding, self.config
        self.hb_on = hb_on = hb_ctx is not None \
            and _heartbeat.resolve_heartbeat(config)
        self.hb_scope = hb_ctx["scope"] if hb_on else ""
        self.hb_handle = binding.handle.id \
            if (hb_on and binding is not None) else ""
        self.hb_tenant = binding.tenant \
            if (hb_on and binding is not None) else ""
        self.hb_overhead_s = self.hb_lane_cost_s = 0.0
        if hb_on:
            # the geometry cost model's prior prices a scan step for the
            # ETA blend (config overrides win, like plan_geometry's)
            snap = geometry_cost_model().snapshot()
            overhead = getattr(config, "geometry_overhead_s", None)
            self.hb_overhead_s = float(
                snap.get("launch_overhead_s", 0.0)) \
                if overhead is None else overhead
            lane_cost = getattr(config, "geometry_lane_cost_s", None)
            self.hb_lane_cost_s = float(snap.get("lane_cost_s", 0.0)) \
                if lane_cost is None else lane_cost

    def fit_masks_fp(self):
        """The fold masks' content digest, hashed once per search (the
        plane's tiled-mask keys need it; fit_masks never mutates)."""
        if self._fm_fp is None:
            self._fm_fp = _dataplane.fingerprint(self.fit_masks)
        return self._fm_fp

    def budget_bytes(self) -> int:
        return int(self.mem_ctx.get("budget_bytes", 0)) \
            if self.mem_ctx is not None else 0

    def plan_data(self, plan):
        """The launch data dict: prefix-staged plans swap the raw X for
        their cached per-fold transformed matrices
        (``data_d["X_folds"]``, (F, n, d')); atomic plans share the
        search-wide broadcast dict."""
        return plan.data_dev or self.data_dev

    # ------------------------------------------------------------------
    # the schedule: plan, stage the prefixes, run the pipeline, report
    # ------------------------------------------------------------------
    def run(self):
        """Plan the groups, then run every live chunk's launches."""
        with get_tracer().span("fit.plan", n_groups=len(self.groups)):
            self._plan()
        if self.px_stage:
            self.stage_prefixes()
        rung = self.rung
        cache0 = persistent_cache_counts()
        builds0 = _grid._program_build_count()
        if rung is not None:
            # whole-search baselines: the final pipeline block's
            # n_compiles / persistent-cache deltas cover every rung
            if rung.cache0 is None:
                rung.cache0, rung.builds0 = cache0, builds0
            cache0, builds0 = rung.cache0, rung.builds0
        if rung is not None and rung.pipeline is not None:
            # the rungs of one halving search share ONE pipeline, whose
            # compile thread stays warm and whose report covers them all
            self.pipe = rung.pipeline
        else:
            # multi-controller runs gather through a cross-process
            # collective, which only the synchronous schedule issues in
            # the same order on every process: depth 0
            depth = self.config.pipeline_depth \
                if jax.process_count() == 1 else 0
            self.pipe = ChunkPipeline(depth, verbose=self.search.verbose,
                                      heartbeat=self.hb_on)
            if rung is not None:
                rung.pipeline = self.pipe
        # every LaunchItem runs under the fault supervisor: transient
        # retry, OOM bisection through the hooks below, a watchdog and
        # fault injection — identical at every pipeline depth
        supervisor = _faults.LaunchSupervisor(
            self.config, faults=self.metrics.struct("faults"),
            ckpt=self.ckpt, verbose=self.search.verbose,
            memory_info=self.oom_info if self.ledger is not None
            else None,
            # later rungs accumulate into the shared faults struct
            # instead of zeroing the earlier rungs' recovery record
            reset_faults=(rung is None or rung.itr == 0))
        items = self.items()
        if self.binding is not None:
            # executor wrapping sits UNDER the supervisor: a routed
            # launch that fails recovers on THIS search's threads
            n_live_total = sum(p.n_live for p in self.plans)
            if rung is not None:
                # progress() spans the whole halving search
                rung.planned_total += n_live_total
                n_live_total = rung.planned_total
            self.binding.executor.note_planned(self.binding.handle,
                                               n_live_total)
            items = self.binding.executor.wrap_items(self.binding.handle,
                                                     items)
        resumed0 = int(self.metrics.data.get("n_chunks_resumed", 0))
        try:
            self.pipe.run(supervisor.wrap(items))
        except Exception as exc:
            # under partial_results='best_effort' a persistent non-memory
            # fault stops the search WITHOUT killing it
            if self.pctx is None or not self.best_effort \
                    or getattr(exc, "_sst_cancelled", False) \
                    or _faults.is_oom(exc):
                raise
            self.shed_rest(exc)
        finally:
            with get_tracer().span("fit.report"):
                self.report(cache0, builds0, resumed0)

    def close(self):
        """Let go of every device buffer the schedule and its plans
        hold, so they free with their last holder, not at the cycle
        collector's next run."""
        for plan in self.plans:
            plan.release()
        self.plans = []
        self.data_dev = self.fit_dev = None
        self.score_ops = ()
        self.pipe = self.host_eval = None

    def _plan(self):
        groups = self.groups
        prefixes = None
        if self.px_stage:
            # groups differing only in final-step statics share a
            # digest, and so the cached transformed matrix
            prefixes = _prefix.group_prefix_digests(
                groups, self.base_params, self.family)
            if all(d is None for d in prefixes):
                self.px_stage = False
                prefixes = None
                self.px_state["fallbacks"].append("undigestable-prefix")
        if self.ledger is not None:
            # the resident broadcast, one count a distinct buffer;
            # leaf-wise, so a BCOO operand prices its components
            seen = set()
            for dev_arr in list(self.data_dev.values()) + [
                    self.fit_dev, *self.score_ops]:
                if id(dev_arr) in seen:
                    continue
                seen.add(id(dev_arr))
                for leaf in jax.tree_util.tree_leaves(dev_arr):
                    self.resident_bytes += int(getattr(leaf, "nbytes", 0))
        self.plans, _ = plan_groups(
            groups, base_params=self.base_params, family=self.family,
            meta=self.meta, config=self.config, n_folds=self.n_folds,
            n_task_shards=self.n_task_shards,
            max_cand_per_batch=self.max_cand_per_batch,
            fit_masks=self.fit_masks, n_scorers=len(self.scorers),
            return_train=self.return_train, dtype=self.dtype,
            metrics=self.metrics, prefixes=prefixes,
            fusion_on=self.fusion_on, chunk_loop=self.chunk_loop,
            rung=self.rung, ckpt=self.ckpt, ledger=self.ledger,
            mem_ctx=self.mem_ctx, resident_bytes=self.resident_bytes)

    def report(self, cache0, builds0, resumed0):
        """The search's pipeline and scheduler blocks (the chunkloop and
        prefix blocks are the registry's own structs, kept current in
        place); the measured walls fed back to the geometry cost
        model."""
        rung, metrics, pipe = self.rung, self.metrics, self.pipe
        metrics.put("scheduler", _serve.report_block(self.binding))
        # the compile thread traces under this search's jax config: join
        # it before returning (a halving rung only drains it)
        if rung is None:
            pipe.close()
        else:
            pipe.drain()
        pr = pipe.report()
        cache1 = persistent_cache_counts()
        pr["persistent_cache_hits"] = cache1["hits"] - cache0["hits"]
        pr["persistent_cache_misses"] = cache1["misses"] - cache0["misses"]
        # distinct traced-program constructions (program-cache misses)
        total_builds = _grid._program_build_count() - builds0
        pr["n_compiles"] = total_builds
        metrics.put("pipeline", pr)
        # the measured launch overhead and lane cost feed the geometry
        # cost model (a halving search's at every rung, over its slice);
        # n_builds normalizes the compile lane PER PROGRAM
        launches = pr.get("launches") or []
        if rung is not None:
            new_launches = launches[rung.launches_seen:]
            rung.launches_seen = len(launches)
            nb = total_builds - int(getattr(rung, "builds_observed", 0))
            rung.builds_observed = total_builds
            geometry_cost_model().observe(new_launches, n_builds=nb)
            rung_rec = rung.current
            if rung_rec is not None:
                rung_rec["n_chunks_resumed"] = int(
                    metrics.data.get("n_chunks_resumed", 0)) - resumed0
                wall = float(pr.get("wall_s", 0.0))
                rung_rec["pipe_wall_s"] = round(
                    max(0.0, wall - rung.prev_pipe_wall), 4)
                rung.prev_pipe_wall = wall
                # the rung's end in the shared launch timeline
                rung_rec["launches_end"] = len(launches)
        else:
            geometry_cost_model().observe(launches, n_builds=total_builds)
        # the plan cache and cost model beside the AOT artifacts: a fresh
        # process plans the SAME widths without re-measuring
        if self.store is not None:
            from spark_sklearn_tpu.parallel.taskgrid import (
                export_plan_state)
            self.store.save_plan_state(export_plan_state())

    def shed_rest(self, exc):
        """Every candidate still missing cells declared shed and written
        to error_score: the search returns declared-partial results."""
        pctx = self.pctx
        left = np.flatnonzero(~pctx["done"])
        self._write_error_cells(left)
        pctx["done"][left] = True
        pctx["shed"].append({
            "reason": "fault", "chunk": None,
            "candidates": [int(i) for i in left],
            "error": f"{type(exc).__name__}: {exc}"[:300]})
        _telemetry.note_protection("shed", len(left))
        logger.warning(
            "persistent fault under partial_results='best_effort' "
            "(%r): %d candidate(s) shed to error_score, the search "
            "returns declared-partial results", exc, len(left))

    # ------------------------------------------------------------------
    # stage 1 — shared-prefix compute (search/prefix.py)
    # ------------------------------------------------------------------
    def stage_prefixes(self):
        """One launch per DISTINCT transformer-chain digest with live
        chunks, vectorized over folds, its (F, n, d') matrix cached in
        the DataPlane as a derived buffer.  Completion is journaled with
        an npz payload, so kill-resume re-UPLOADS a finished prefix."""
        from spark_sklearn_tpu.utils import checkpoint as _ckpt_mod
        ckpt, px_state = self.ckpt, self.px_state
        t_px0 = time.perf_counter()
        distinct: Dict[str, List[GroupPlan]] = {}
        for plan in self.plans:
            if plan.prefix is not None and plan.n_live > 0:
                distinct.setdefault(plan.prefix, []).append(plan)
        x_sharding = getattr(self.data_dev["X"], "sharding", None)
        base_no_x = {k: v for k, v in self.data_dev.items() if k != "X"}
        n_computed = n_resumed = n_reused = 0
        px_bytes = 0
        ck_dir = os.path.dirname(ckpt.path) if ckpt is not None else None
        with get_tracer().span("prefix.stage", n_distinct=len(distinct)):
            for dg, dplans in distinct.items():
                tf_jit = _grid._prefix_program(
                    self.family, dplans[0].static, dg, self.meta,
                    self.mesh)
                aval = jax.eval_shape(tf_jit, self.data_dev, self.fit_dev)
                nbytes = (int(np.prod(aval.shape))
                          * np.dtype(aval.dtype).itemsize)
                key_parts = (dg, self.fit_masks_fp(), self.data_fp,
                             _dataplane._sharding_key(x_sharding))
                kp_fp = _ckpt_mod.fingerprint(*key_parts)
                npz_path = (os.path.join(ck_dir, f"prefix_{kp_fp}")
                            if ck_dir is not None else None)
                ck_meta = (ckpt.get_meta(f"prefix:{kp_fp}")
                           if ckpt is not None else None)
                how = {}
                xt_dev, cache_hit = self.plane.derived(
                    key_parts, functools.partial(
                        self._make_prefix, tf_jit, x_sharding,
                        ck_meta is not None and npz_path is not None,
                        npz_path, how),
                    nbytes, label=self.px_label + "xt",
                    tenant=self.tenant)
                if cache_hit:
                    n_reused += 1
                elif how.get("src") == "resumed":
                    n_resumed += 1
                else:
                    n_computed += 1
                    jax.block_until_ready(xt_dev)
                    if ckpt is not None and npz_path is not None:
                        _ckpt_mod.save_pytree(npz_path, np.asarray(xt_dev))
                        ckpt.put_meta(f"prefix:{kp_fp}",
                                      {"path": npz_path})
                px_bytes += nbytes
                for p in dplans:
                    p.data_dev = {**base_no_x, "X_folds": xt_dev}
        px_state["enabled"] = True
        n_cand_px = sum(p.nc for ps in distinct.values() for p in ps)
        px_state["n_candidates_total"] += n_cand_px
        px_state["n_prefixes_distinct"] += len(distinct)
        px_state["n_prefix_launches"] += n_computed
        px_state["n_prefix_reused"] += n_reused
        px_state["n_prefix_resumed"] += n_resumed
        px_state["recompute_saved"] += max(0, n_cand_px - n_computed)
        px_state["bytes_cached"] += px_bytes
        px_state["prefix_wall_s"] += round(time.perf_counter() - t_px0, 6)

    def _make_prefix(self, tf_jit, x_sharding, journalled, npz_path, how):
        if journalled:
            from spark_sklearn_tpu.utils import checkpoint as _ckpt_mod
            try:
                host = _ckpt_mod.load_pytree(npz_path)
                how["src"] = "resumed"
                return _dataplane.upload(np.asarray(host), x_sharding,
                                         label=self.px_label + "xt")
            # a missing or torn payload is not an error: the recompute
            # below is bit-exact with what it held
            # sstlint: disable=swallowed-exception
            except Exception:
                how.pop("src", None)
        how["src"] = "computed"
        return tf_jit(self.data_dev, self.fit_dev)

    # ------------------------------------------------------------------
    # programs, masks and operands of a launch
    # ------------------------------------------------------------------
    def programs(self, plan, width=None, whole=True):
        """The group's jitted programs, built on first need so fully
        resumed groups never trace; at `width` (None: the group's own),
        and from the static without the layout's facts unless the
        launch is `whole` runs."""
        nc_batch = width or plan.nc_batch
        key = (nc_batch, not whole)
        progs = plan.programs.get(key)
        if progs is None:
            progs = plan.programs[key] = _grid._build_programs(
                self.family, self.meta, self.config, self.mesh,
                self.scorers, static=plan.static_for(whole),
                nc_batch=nc_batch, n_folds=self.n_folds,
                prefix=plan.prefix, all_cores=self.all_cores,
                fused_mode=self.fused_mode, sw_blind=self.sw_blind,
                return_train=self.return_train,
                task_shard=self.task_shard, store=self.store)
        return progs

    def scan_program(self, plan, n_steps, topk_k=0, hb=False):
        """ONE jitted program executing `n_steps` chunks of the group as
        a `lax.scan` (``grid._build_scan``)."""
        key = (int(n_steps), int(topk_k)) + (("hb",) if hb else ())
        prog = plan.scan_programs.get(key)
        if prog is None:
            prog = plan.scan_programs[key] = _grid._build_scan(
                self.programs(plan)["fused_body"], self.family, self.meta,
                self.config, self.mesh, self.scorers, static=plan.static,
                nc_batch=plan.nc_batch, nc=int(plan.nc),
                n_folds=self.n_folds, n_steps=n_steps, topk_k=topk_k,
                score0=self.scorer_names[0], errval=self.errval,
                prefix=plan.prefix, sw_blind=self.sw_blind,
                return_train=self.return_train, hb=hb)
        return prog

    def group_masks(self, plan, width=None):
        """The fit-mask device buffer of a launch of the group.
        Task-batched families take the fold masks tiled to the launch
        width: under the data plane a cached ON-DEVICE broadcast of the
        resident masks, else host-tiled.  At the group's own width
        (`width` None) the buffer is memoized on the plan."""
        if not self.task_batched:
            return self.fit_dev
        if width is None and plan.w_task_dev is not None:
            return plan.w_task_dev
        lanes_w = width or plan.nc_batch
        if self.plane is not None:
            w = self.plane.tiled(
                self.fit_masks, self.fit_dev, lanes_w, self.tb_mask_shard,
                label=self.tiled_label, fp=self.fit_masks_fp(),
                tenant=self.tenant)
        else:
            w = _dataplane.upload(
                np.tile(self.fit_masks, (lanes_w, 1)),
                self.tb_mask_shard, label=self.tiled_label)
        if width is None:
            plan.w_task_dev = w
        return w

    def stage_operands(self, plan, arrays, lo, hi, width, label):
        """What a launch of `width` candidates (None: the group's own
        width) takes: rows [lo, hi) of every dynamic parameter in
        `arrays` padded to the width and uploaded under `label`, the
        `_pad` operand of an all-static group, and the fit masks at
        that width.  Shared by the per-chunk stage, the bisection's
        relaunch and the cross-search fused launch."""
        mask_width, width = width, width or plan.nc_batch
        repeat = self.n_folds if self.task_batched else 1
        dyn = {k: _dataplane.upload(pad_chunk(arr, lo, hi, width, repeat),
                                    self.task_shard, label=label)
               for k, arr in arrays.items()}
        if not dyn and not self.task_batched:
            # all-static group: vmap still needs a batched operand
            dyn["_pad"] = (
                self.plane.zeros(width, self.dtype, self.task_shard,
                                 tenant=self.tenant)
                if self.plane is not None else
                _dataplane.upload(np.zeros(width, dtype=self.dtype),
                                  self.task_shard, label="dyn.pad"))
        return dyn, self.group_masks(plan, mask_width)

    def stage_chunk(self, plan, lo, hi, chunk_id):
        dyn, w = self.stage_operands(plan, plan.group.dynamic_params, lo,
                                     hi, None, "dyn")
        plan.note_staged([chunk_id])
        return dyn, w

    def submit_precompile(self, plan):
        """AOT-lower/compile the group's fused program on the compile
        thread so the group boundary does not stall the device (the
        executable is bit-identical to the jit path's; a failure only
        means the jit path compiles at first dispatch)."""
        if plan.aot_submitted or self.pipe.depth == 0 \
                or not self.fused_mode or self.scan_mode \
                or plan.n_live < 2:
            return
        plan.aot_submitted = True
        try:
            progs = self.programs(plan)
            nc_batch = plan.nc_batch
            lanes = nc_batch * self.n_folds
            rows = lanes if self.task_batched else nc_batch
            dyn_spec = {k: jax.ShapeDtypeStruct(
                (rows,) + arr.shape[1:], arr.dtype, sharding=self.task_shard)
                for k, arr in plan.group.dynamic_params.items()}
            if not dyn_spec and not self.task_batched:
                dyn_spec["_pad"] = jax.ShapeDtypeStruct(
                    (nc_batch,), self.dtype, sharding=self.task_shard)
            if self.task_batched:
                w_spec = jax.ShapeDtypeStruct(
                    (lanes,) + self.fit_masks.shape[1:],
                    self.fit_masks.dtype, sharding=self.tb_mask_shard)
            else:
                w_spec = self.fit_dev
            plan.aot_future = self.pipe.submit_precompile(
                progs["fused"], dyn_spec, self.plan_data(plan), w_spec,
                *self.score_ops, label=f"fused group {plan.gi}")
        # sstlint: disable=launch-except-taxonomy — AOT compile-ahead
        # is an optimization only: any failure here means the jit
        # path compiles at first dispatch, exactly as it always did
        except Exception as exc:   # AOT is an optimization only
            logger.debug("fused precompile submission failed: %r", exc)

    def resolve_fused(self, plan):
        """The callable for this group's fused chunks: the AOT
        executable when the compile thread produced one, the plain jit
        program otherwise (identical results either way)."""
        if plan.fused_call is not None:
            return plan.fused_call
        jit_fn = self.programs(plan)["fused"]
        call = jit_fn
        fut, plan.aot_future = plan.aot_future, None
        if fut is not None:
            try:
                exe = _process.join_build(
                    fut, get_tracer(), where="dispatch",
                    label=f"fused group {plan.gi}")
                call = functools.partial(_call_aot, [exe], jit_fn)
            # sstlint: disable=launch-except-taxonomy — consuming a
            # failed AOT future: the plain jit program below is the
            # sanctioned identical-results fallback
            except Exception as exc:
                logger.debug("fused precompile failed (%r); "
                             "falling back to jit", exc)
        plan.fused_call = call
        return call

    # ------------------------------------------------------------------
    # OOM recovery: bisected relaunch + per-candidate host bottom-out
    # (hooks consumed by the launch supervisor, parallel/faults.py)
    # ------------------------------------------------------------------
    def host_fused_range(self, plan, lo, hi, sup, chunk_id):
        """Candidates [lo, hi) of the plan's group on the host — sklearn
        `_fit_and_score` per (candidate, fold) with exact error_score
        semantics — shaped like the fused gather."""
        idx = plan.group.candidate_indices[lo:hi]
        sup.record_host_fallback(f"{chunk_id}[{lo}:{hi}]", plan.gi,
                                 len(idx) * self.n_folds)
        return LaunchResult.host_fill(*self.host_eval(idx), hi - lo,
                                      self.n_folds)

    def exec_fused_halves(self, plan, lo, hi, sup, chunk_id):
        lo_, mid, hi_ = split_range(lo, hi)
        return LaunchResult.merge(
            self.exec_fused_range(plan, lo_, mid, sup, chunk_id),
            self.exec_fused_range(plan, mid, hi_, sup, chunk_id))

    def _relaunch(self, plan, lo, hi, width, sup, key):
        dyn, w = self.stage_operands(plan, plan.group.dynamic_params, lo,
                                     hi, width, "dyn.recover")
        n = hi - lo
        out = self.programs(plan, width, plan.whole_runs(lo, n))["fused"](
            dyn, self.plan_data(plan), w, *self.score_ops)
        out = sup.wait_ready(out, key=key, group=plan.gi)
        return out.to_host(n, self.n_folds)

    def exec_fused_range(self, plan, lo, hi, sup, chunk_id):
        """Relaunch candidates [lo, hi) as one fused program at the
        narrowest padded width, recursing on further OOMs down to single
        candidates and finally the host path: a host-side LaunchResult
        of hi - lo rows, bit-identical to the full-width launch's (vmap
        lanes are independent).  A range that cuts a run of a launch
        layout, whose lanes are not, runs the program without the
        layout's facts."""
        n = hi - lo
        width = max(self.n_task_shards,
                    mesh_lib.pad_to_multiple(n, self.n_task_shards))
        key = f"{chunk_id}[{lo}:{hi}]"
        try:
            return sup.call(
                functools.partial(self._relaunch, plan, lo, hi, width,
                                  sup, key),
                key=key, group=plan.gi, n_real=n)
        except Exception as exc:
            if _faults.is_oom(exc):
                if n <= 1:
                    return self.host_fused_range(plan, lo, hi, sup,
                                                 chunk_id)
                sup.record_bisection(key, plan.gi)
                return self.exec_fused_halves(plan, lo, hi, sup, chunk_id)
            # poison-candidate quarantine (armed under best_effort only):
            # FATAL ranges split like OOM, and a single lane that still
            # faults K times is quarantined to error_score
            if not getattr(sup, "quarantine_k", 0) \
                    or getattr(exc, "_sst_cancelled", False) \
                    or _faults.classify_error(exc) != _faults.FATAL:
                raise
            if n > 1:
                sup.record_bisection(key, plan.gi,
                                     fault_class=_faults.FATAL)
                return self.exec_fused_halves(plan, lo, hi, sup, chunk_id)
            n_faults = sup.note_fatal(key)
            if n_faults < sup.quarantine_k:
                return self.exec_fused_range(plan, lo, hi, sup, chunk_id)
            sup.record_quarantine(key, plan.gi, exc, n_faults)
            if self.pctx is not None:
                self.pctx["quarantined"].append({
                    "key": key, "group": int(plan.gi),
                    "candidates": [
                        int(i) for i in
                        plan.group.candidate_indices[lo:hi]],
                    "error": f"{type(exc).__name__}: {exc}"[:300],
                    "n_faults": int(n_faults)})
            fill = {s: np.full((n, self.n_folds), self.errval)
                    for s in self.scorer_names}
            return LaunchResult.host_fill(
                fill, fill if self.return_train else {}, n, self.n_folds)

    def bisect_fused(self, plan, lo, hi, chunk_id, sup):
        if hi - lo <= 1:
            return self.host_fused_range(plan, lo, hi, sup, chunk_id)
        sup.record_bisection(chunk_id, plan.gi)
        return self.exec_fused_halves(plan, lo, hi, sup, chunk_id)

    def bisect_fit(self, plan, lo, hi, chunk_id, st, sup):
        res = self.bisect_fused(plan, lo, hi, chunk_id, sup)
        # the score item consumes the recovered cells instead of
        # launching (same contract as the OOM host fallback)
        st.host = (res.test, res.train)
        return res

    def bisect_score(self, plan, lo, hi, chunk_id, sup):
        res = self.bisect_fused(plan, lo, hi, chunk_id, sup)
        return res.test, res.train

    def oom_info(self, key, group):
        """OOM forensics: an OOM fault event carries the chunk's modeled
        bytes beside the budget, and the FIRST OOM a chunk (bisected
        sub-ranges "id[lo:hi]" are their chunk's) trains the ledger's
        safety margin."""
        plans = self.plans
        plan = plans[group] if 0 <= group < len(plans) else None
        modeled = int(self.resident_bytes) + (
            int(plan.mem_chunk_bytes) if plan is not None else 0)
        budget = self.budget_bytes()
        base_key = key.split("[", 1)[0]
        with self._oom_lock:
            fresh = base_key not in self._oom_trained
            if fresh:
                self._oom_trained.add(base_key)
        if fresh:
            self.ledger.observe_oom(modeled, budget)
        return {"modeled_bytes": modeled, "budget_bytes": budget}

    # ------------------------------------------------------------------
    # cross-search launch fusion (the executor's FusedLaunch seam): equal
    # keys mean the SAME compiled fused program on the SAME resident
    # buffers, so the members' rows concatenated and re-padded once are
    # the bisection relaunch's shape, bit-identical lane for lane
    # ------------------------------------------------------------------
    def make_fuse_spec(self, plan, lo, hi, chunk_id):
        group, config = plan.group, self.config
        fkey = (
            "sst-fuse-v1", self.family.name, freeze(plan.static),
            freeze(self.meta), int(self.n_folds),
            bool(config.bf16_matmul), _grid._mesh_desc(self.mesh),
            tuple(sorted(self.scorers)), tuple(sorted(self.sw_blind)),
            bool(self.return_train), bool(self.sw_blind),
            str(np.dtype(self.dtype)), int(self.n_task_shards),
            bool(self.task_batched), tuple(sorted(group.dynamic_params)),
            self.fit_masks_fp(), plan.prefix,
            # device-buffer identities, stable while the schedule holds
            # them; the plane's dedup makes equal content equal objects
            tuple(id(leaf) for leaf in
                  jax.tree_util.tree_leaves(self.plan_data(plan))),
            id(self.fit_dev), *map(id, self.score_ops))
        _keycheck.note(
            "fuse_spec", fkey,
            fields={"bf16_matmul": bool(config.bf16_matmul)},
            detail=self.family.name)
        # a fused width may exceed a solo chunk's bound; its ceiling is
        # the ledger's cap (0: none — an over-wide OOM still recovers)
        return FuseSpec(key=fkey, n=hi - lo,
                        shard=int(self.n_task_shards),
                        max_width=int(plan.cap) if plan.cap else 0,
                        rows=functools.partial(_member_rows, group, lo, hi),
                        run=functools.partial(self.run_fused, plan),
                        slice_out=self.slice_member)

    def run_fused(self, plan, specs):
        total = sum(int(s.n) for s in specs)
        width = max(self.n_task_shards,
                    mesh_lib.pad_to_multiple(total, self.n_task_shards))
        member_rows = [s.rows() for s in specs]
        dyn, w = self.stage_operands(
            plan, {k: np.concatenate([np.asarray(r[k])
                                      for r in member_rows])
                   for k in sorted(member_rows[0])},
            0, total, width, "dyn.fuse")
        # members are whole chunks: the seams fall between runs where
        # every member is whole runs
        whole = plan.whole_runs(0, *(int(s.n) for s in specs))
        return self.programs(plan, width, whole)["fused"](
            dyn, self.plan_data(plan), w, *self.score_ops)

    def slice_member(self, out, off, n):
        return out.slice(off, n, self.n_folds)

    # ------------------------------------------------------------------
    # cells and report of a launch
    # ------------------------------------------------------------------
    def write_cells(self, plan, idx, lo, hi, chunk_id, te, tr, t_fit,
                    t_score, count_launch=True):
        # the launch wall is charged to the chunk's REAL candidates, so
        # the fit-time cells sum to the device wall
        n_folds, metrics = self.n_folds, self.metrics
        n_real = (hi - lo) * n_folds
        self.fit_times[idx, :] = t_fit / n_real
        self.score_times[idx, :] = t_score / n_real
        for s in self.scorer_names:
            self.test_scores[s][idx, :] = np.asarray(te[s])[:hi - lo]
            if self.return_train:
                self.train_scores[s][idx, :] = np.asarray(tr[s])[:hi - lo]
        if count_launch:
            # a scan segment counts its one launch in its finalize
            metrics.counter("n_launches").inc()
        metrics.gauge("fit_wall_s").add(t_fit)
        metrics.gauge("score_wall_s").add(t_score)
        lanes_launch = plan.nc_batch * n_folds
        metrics.histogram("padding_waste").observe(
            (lanes_launch - n_real) / lanes_launch)
        rec = self.per_group_rec(plan)
        if count_launch:
            rec["n_launches"] += 1
        rec["fit_wall_s"] += t_fit
        rec["score_wall_s"] += t_score
        if self.search.verbose > 1:
            self.search._print_task_end_lines(
                self.candidates, idx, n_folds, self.scorer_names,
                self.test_scores, self.train_scores, self.return_train,
                (t_fit + t_score) / n_real, self.fit_failed)
        if self.ckpt is not None:
            self.ckpt.put(chunk_id, {
                "test": {s: self.test_scores[s][idx, :].tolist()
                         for s in self.scorer_names},
                "train": ({s: self.train_scores[s][idx, :].tolist()
                           for s in self.scorer_names}
                          if self.return_train else None),
                "fit_t": t_fit / n_real,
                "score_t": t_score / n_real,
                "failed": self.fit_failed[idx, :].tolist()})
        if self.pctx is not None:
            # degradation never overwrites written cells
            self.pctx["done"][idx] = True

    def per_group_rec(self, plan):
        pg = self.metrics.struct("per_group")
        # rung-namespaced: a halving search's rungs share the registry
        key = self.cid_ns + str(plan.gi) if self.rung is not None \
            else plan.gi
        return pg.setdefault(key, {
            "static_params": repr(plan.group.static_params),
            "n_launches": 0, "fit_wall_s": 0.0, "score_wall_s": 0.0,
            "score_path": ("scan-fused" if self.scan_mode else
                           "wide-fused" if self.fused_mode else
                           "wide" if self.all_cores else "nested"),
            **({"n_features": int(self.meta["n_features"])}
               if "n_features" in self.meta else {})})

    def record_launch(self, plan, res, lanes, idx):
        """One launch's solver stats into the report: what the family's
        traced hook reported and its host hook knows of the launch."""
        if not res.stats:
            return
        self.metrics.series("lanes_per_launch").append(int(lanes))
        facts = self.family.launch_facts(plan.static, self.meta,
                                         lanes // self.n_folds,
                                         self.n_folds)
        record_stats(self.metrics, {**facts, **res.stats}, idx,
                     len(self.candidates), self.n_folds,
                     self.per_group_rec(plan))

    def replay_chunk(self, idx, rec):
        """Write a journalled chunk's cells back."""
        for s in self.scorer_names:
            self.test_scores[s][idx, :] = np.asarray(rec["test"][s])
            if self.return_train:
                self.train_scores[s][idx, :] = np.asarray(rec["train"][s])
        self.fit_times[idx, :] = rec["fit_t"]
        self.score_times[idx, :] = rec["score_t"]
        if rec.get("failed") is not None:
            self.fit_failed[idx, :] |= np.asarray(rec["failed"], bool)
        self.metrics.counter("n_chunks_resumed").inc()
        if self.pctx is not None:
            self.pctx["done"][idx] = True

    def _write_error_cells(self, idx):
        """Un-run candidates: sklearn's error_score with ZERO times,
        declared in the protection block, not failed fits."""
        for s in self.scorer_names:
            self.test_scores[s][idx, :] = self.errval
            if self.return_train:
                self.train_scores[s][idx, :] = self.errval
        self.fit_times[idx, :] = 0.0
        self.score_times[idx, :] = 0.0

    def shed_chunk(self, idx, chunk_id):
        """True when the search deadline expired and this chunk was shed
        to error_score (best_effort); raises under
        partial_results='raise'."""
        pctx = self.pctx
        if pctx is None or pctx["t_deadline"] is None \
                or time.perf_counter() < pctx["t_deadline"]:
            return False
        elapsed = time.perf_counter() - pctx["t_start"]
        deadline_s = float(self.config.search_deadline_s)
        if not self.best_effort:
            raise _faults.SearchDeadlineError(
                deadline_s, elapsed, n_remaining=int((~pctx["done"]).sum()))
        if not pctx["deadline_hit"]:
            pctx["deadline_hit"] = True
            _telemetry.note_protection("deadline_hit")
            logger.warning(
                "search deadline %.3gs expired after %.3fs: "
                "shedding the remaining chunks to error_score "
                "(partial_results='best_effort')",
                deadline_s, elapsed, chunk=chunk_id)
        self._write_error_cells(idx)
        pctx["done"][idx] = True
        pctx["shed"].append({
            "reason": "deadline", "chunk": chunk_id,
            "candidates": [int(i) for i in idx]})
        _telemetry.note_protection("shed", len(idx))
        return True

    def live_chunks(self, plan):
        """The plan's chunks that still have to launch, as (lo, hi,
        chunk_id); a journalled chunk is replayed and an expired one shed
        on the way."""
        for lo, hi, chunk_id, rec in plan.chunks:
            idx = plan.group.candidate_indices[lo:hi]
            if rec is not None:
                self.replay_chunk(idx, rec)
            elif not self.shed_chunk(idx, chunk_id):
                yield lo, hi, chunk_id

    # ------------------------------------------------------------------
    # the per-chunk items
    # ------------------------------------------------------------------
    def items(self):
        """Yield this search's LaunchItems in dispatch order, on the
        dispatching thread: the group-level work between yields overlaps
        the dispatched launches' device compute."""
        plans = self.plans
        for pi, plan in enumerate(plans):
            if self.scan_mode:
                yield from self.scan_plan_items(plan)
                continue
            # compile-ahead: this group's fused program and the next's
            self.submit_precompile(plan)
            if pi + 1 < len(plans):
                self.submit_precompile(plans[pi + 1])
            for live_seen, (lo, hi, chunk_id) in enumerate(
                    self.live_chunks(plan), 1):
                if self.fused_mode and live_seen > 1:
                    # steady state: ONE fused launch per chunk
                    yield self._fused_item(plan, lo, hi, chunk_id)
                else:
                    # a group's first live chunk (or the never-fused
                    # path): separate fit and score launches, plus a warm
                    # calibration score launch where later chunks fuse
                    yield from self._unfused_items(
                        plan, lo, hi, chunk_id,
                        calibrate=self.fused_mode
                        and live_seen < plan.n_live)

    def _gather(self, n, out):
        return out.to_host(n, self.n_folds)

    def _fused_item(self, plan, lo, hi, chunk_id):
        return LaunchItem(
            key=chunk_id, kind="fused", group=plan.gi,
            n_tasks=(hi - lo) * self.n_folds,
            stage=functools.partial(self.stage_chunk, plan, lo, hi,
                                    chunk_id),
            launch=functools.partial(self.launch_fused, plan),
            gather=functools.partial(self._gather, hi - lo),
            finalize=functools.partial(self.finish_fused, plan, lo, hi,
                                       chunk_id),
            bisect=functools.partial(self.bisect_fused, plan, lo, hi,
                                     chunk_id),
            fuse=(self.make_fuse_spec(plan, lo, hi, chunk_id)
                  if self.fusion_on else None))

    def launch_fused(self, plan, payload):
        dyn, w = payload
        return self.resolve_fused(plan)(dyn, self.plan_data(plan), w,
                                        *self.score_ops)

    def finish_fused(self, plan, lo, hi, chunk_id, host, tm):
        idx = plan.group.candidate_indices[lo:hi]
        lanes = plan.nc_batch * self.n_folds
        wall = tm.dispatch_s + tm.compute_s + tm.gather_s
        # the group's calibrated score cost of the PADDED lanes is the
        # score time, an estimate; the rest of the wall is fit
        t_score = min((plan.score_s_per_lane or 0.0) * lanes, wall)
        self.fit_failed[idx, :] |= host.bad
        self.record_launch(plan, host, lanes, idx)
        self.write_cells(plan, idx, lo, hi, chunk_id, host.test,
                         host.train, wall - t_score, t_score)

    def _unfused_items(self, plan, lo, hi, chunk_id, calibrate):
        idx = plan.group.candidate_indices[lo:hi]
        n_real = (hi - lo) * self.n_folds
        st = _FirstChunk()
        partial = functools.partial
        armed = self.quarantine_armed
        yield LaunchItem(
            key=chunk_id + ":fit", kind="fit", group=plan.gi,
            n_tasks=n_real,
            stage=partial(self.stage_chunk, plan, lo, hi, chunk_id),
            launch=partial(self.launch_fit, plan, st),
            gather=partial(self._gather, hi - lo),
            finalize=partial(self.finish_fit, plan, idx, st),
            host_fallback=partial(self.host_fit, idx, st),
            bisect=(partial(self.bisect_fit, plan, lo, hi, chunk_id, st)
                    if armed else None))
        yield LaunchItem(
            key=chunk_id + ":score", kind="score", group=plan.gi,
            n_tasks=n_real, launch=partial(self.launch_score, plan, st),
            gather=partial(self.gather_score, st),
            finalize=partial(self.finish_score, plan, lo, hi, chunk_id,
                             st, calibrate),
            host_fallback=partial(self.host_score, idx, st),
            bisect=(partial(self.bisect_score, plan, lo, hi, chunk_id)
                    if armed else None))
        if calibrate:
            # a SECOND, warm score launch measures the score cost later
            # fused chunks attribute out of their wall; counted in
            # n_launches and score_wall_s, in no candidate's cells
            yield LaunchItem(
                key=chunk_id + ":calibrate", kind="calibrate",
                group=plan.gi, n_tasks=n_real,
                launch=partial(self.launch_calibration, plan, st),
                finalize=partial(self.finish_calibration, plan),
                host_fallback=partial(self.host_calibration, plan, st))

    def launch_fit(self, plan, st, payload):
        dyn, w = payload
        models, stats = self.programs(plan)["fit"](
            dyn, self.plan_data(plan), w)
        st.models = models
        return LaunchResult({}, {}, _grid._models_health(models), stats)

    def finish_fit(self, plan, idx, st, host, tm):
        if host.bad is not None:
            self.fit_failed[idx, :] |= host.bad
        self.record_launch(plan, host, plan.nc_batch * self.n_folds, idx)
        st.t_fit = tm.dispatch_s + tm.compute_s

    def host_fit(self, idx, st):
        # the whole chunk degrades to the host; the score item consumes
        # the stashed cells
        st.host = self.host_eval(idx)
        return LaunchResult.host_fill(*st.host, len(idx), self.n_folds)

    def launch_score(self, plan, st, payload):
        if st.host is not None:
            return None   # chunk recovered on the host
        return self.programs(plan)["score"](
            st.models, self.plan_data(plan), *self.score_ops)

    def gather_score(self, st, out):
        if out is None and st.host is not None:
            host, st.host = st.host, None
            return host
        te, tr = out
        return (mesh_lib.device_get_tree(te),
                mesh_lib.device_get_tree(tr))

    def host_score(self, idx, st):
        if st.host is not None:
            host, st.host = st.host, None
            return host
        st.models = None
        return self.host_eval(idx)

    def finish_score(self, plan, lo, hi, chunk_id, st, calibrate, host,
                     tm):
        te, tr = host
        t_score = tm.dispatch_s + tm.compute_s + tm.gather_s
        if not calibrate:
            st.models = None
        self.write_cells(plan, plan.group.candidate_indices[lo:hi], lo,
                         hi, chunk_id, te, tr, st.t_fit, t_score)

    def launch_calibration(self, plan, st, payload):
        models, st.models = st.models, None
        if models is None:
            # recovered on the host: no device models to calibrate with
            plan.calibration_lost = True
            return None
        return self.programs(plan)["score"](
            models, self.plan_data(plan), *self.score_ops)

    def host_calibration(self, plan, st):
        st.models = None
        plan.calibration_lost = True
        return None

    def finish_calibration(self, plan, host, tm):
        if plan.calibration_lost:
            # lost to recovery: later fused chunks attribute no score
            plan.calibration_lost = False
            plan.score_s_per_lane = 0.0
            return
        wall = tm.dispatch_s + tm.compute_s
        # per PADDED lane, as fused chunks scale it back up
        plan.score_s_per_lane = wall / (plan.nc_batch * self.n_folds)
        self.metrics.counter("n_launches").inc()
        self.metrics.gauge("score_wall_s").add(wall)
        rec = self.per_group_rec(plan)
        rec["n_launches"] += 1
        rec["score_wall_s"] += wall
        rec["score_s_per_task_calibrated"] = round(
            plan.score_s_per_lane, 7)

    # ------------------------------------------------------------------
    # the scanned segments (chunk_loop="scan")
    # ------------------------------------------------------------------
    def scan_plan_items(self, plan):
        """The plan's live chunks as scan-segment LaunchItems, a
        segment's member chunks stacked along a leading step axis and
        run as ONE `lax.scan` launch.  Segments are priced against the
        memory ledger (taskgrid.plan_scan_segments); an OOM that still
        slips through runs that segment per chunk (the bisect hook)."""
        gi, group, n_folds = plan.gi, plan.group, self.n_folds
        nc_batch = plan.nc_batch
        lanes = nc_batch * n_folds
        repeat = n_folds if self.task_batched else 1
        live = list(self.live_chunks(plan))
        if not live:
            return
        # the rung's elimination runs on device only where the carry's
        # rows are the whole rung: one group, one scorer, no resumed or
        # shed chunk, one segment; else sklearn's host _top_k
        topk_k = 0
        rung = self.rung
        if rung is not None and len(self.plans) == 1 \
                and len(self.scorer_names) == 1 \
                and len(live) == len(plan.chunks):
            k = int(getattr(rung, "keep_next", 0) or 0)
            if 0 < k < int(plan.nc):
                topk_k = k
        carry_bytes = (int(plan.nc) + 1) * n_folds * 4 if topk_k else 0
        # per-step stacked bytes: the operand rows and the outputs (the
        # model working set is step-reused, priced in reserved_bytes)
        chunk_dyn_bytes = sum(
            nc_batch * repeat * int(np.prod(arr.shape[1:], dtype=int))
            * int(arr.dtype.itemsize)
            for arr in group.dynamic_params.values())
        out_bytes = nc_batch * n_folds * (
            len(self.scorer_names) * (2 if self.return_train else 1)
            * int(np.dtype(self.dtype).itemsize) + 1) + 8
        seg_plan = plan_scan_segments(
            len(live), chunk_bytes=chunk_dyn_bytes + out_bytes,
            carry_bytes=carry_bytes, budget_bytes=self.budget_bytes(),
            reserved_bytes=int(self.resident_bytes)
            + int(plan.mem_chunk_bytes))
        if seg_plan.capped:
            topk_k = 0   # the carry cannot cross launches
            self.cl_state["fallbacks"].append(
                f"segment-capped:{self.cid_ns}{gi}")
        self.cl_state["n_segments"] += seg_plan.n_segments
        partial = functools.partial
        for si, (slo, shi) in enumerate(seg_plan.segments()):
            members = live[slo:shi]
            n_steps = len(members)
            seg_key = self.cid_ns + f"{gi}:scan{si}"
            seg_topk = topk_k if n_steps == len(live) else 0
            # a step's cost estimate, seeding the heartbeat's ETA blend
            hb_est = self.hb_overhead_s / max(1, n_steps) \
                + self.hb_lane_cost_s * lanes
            yield LaunchItem(
                key=seg_key, kind="scan", group=gi,
                n_tasks=sum((hi - lo) * n_folds for lo, hi, _ in members),
                n_chunks=n_steps,
                stage=partial(self.stage_segment, plan, members, si,
                              seg_key, hb_est),
                launch=partial(self.launch_segment, plan, n_steps,
                               seg_topk),
                gather=partial(self.gather_segment, members, seg_topk),
                finalize=partial(self.finish_segment, plan, members,
                                 seg_key),
                bisect=partial(self.bisect_segment, plan, members,
                               seg_key))

    def stage_segment(self, plan, members, si, seg_key, hb_est):
        from jax.sharding import NamedSharding, PartitionSpec as P
        # a leading step axis; each step keeps the task sharding
        scan_shard = NamedSharding(self.mesh, P(None, mesh_lib.TASK_AXIS))
        nc_batch, n_steps = plan.nc_batch, len(members)
        repeat = self.n_folds if self.task_batched else 1
        with get_tracer().span("chunkloop.segment", group=plan.gi,
                               n_chunks=n_steps):
            dyn = {}
            for k, arr in plan.group.dynamic_params.items():
                rows = np.stack([pad_chunk(arr, lo, hi, nc_batch, repeat)
                                 for lo, hi, _ in members])
                dyn[k] = _dataplane.upload(rows, scan_shard,
                                           label="dyn.scan")
            if not dyn and not self.task_batched:
                dyn["_pad"] = _dataplane.upload(
                    np.zeros((n_steps, nc_batch), dtype=self.dtype),
                    scan_shard, label="dyn.scan.pad")
            # per-step candidate POSITIONS for the top-k carry (padded
            # lanes hit the dump row); staged with or without top-k
            idx_rows = np.full((n_steps, nc_batch), int(plan.nc),
                               np.int32)
            for i, (lo, hi, _) in enumerate(members):
                idx_rows[i, :hi - lo] = np.arange(lo, hi, dtype=np.int32)
            idx_st = _dataplane.upload(
                idx_rows, mesh_lib.replicated_sharding(self.mesh),
                label="dyn.scan.idx")
            w = self.group_masks(plan)
            plan.note_staged([cid for _, _, cid in members])
            # registered before dispatch, so a launch that never beats
            # still shows up stale to the watchdog
            tok = None
            if self.hb_on:
                tok = _heartbeat.get_hub().register_segment(
                    seg_key, group=plan.gi, segment=si, n_steps=n_steps,
                    scope=self.hb_scope, handle=self.hb_handle,
                    tenant=self.hb_tenant, est_step_s=hb_est)
            return dyn, idx_st, w, tok

    def launch_segment(self, plan, n_steps, seg_topk, payload):
        dyn, idx_st, w, tok = payload
        with get_tracer().span("chunkloop.scan", group=plan.gi,
                               n_chunks=n_steps, topk=seg_topk):
            # the heartbeat token is a RUNTIME operand
            hb_ops = () if tok is None else (np.asarray(tok, np.int32),)
            return self.scan_program(
                plan, n_steps, seg_topk, hb=tok is not None)(
                    dyn, idx_st, self.plan_data(plan), w,
                    *self.score_ops, *hb_ops)

    def gather_segment(self, members, seg_topk, out):
        ys, surv = out
        ys_h = ys.to_host()
        chunks = [ys_h.step(i).slice(0, hi - lo, self.n_folds)
                  for i, (lo, hi, _) in enumerate(members)]
        surv_h = (np.asarray(mesh_lib.device_get_tree(surv))
                  if seg_topk else None)
        return {"chunks": chunks, "survivors": surv_h}

    def bisect_segment(self, plan, members, seg_key, sup):
        # OOM on the scanned segment: each member relaunches through the
        # fused bisection, and the rung's elimination reverts to the host
        sup.record_bisection(seg_key, plan.gi)
        self.cl_state["fallbacks"].append(
            f"oom-per-chunk:{self.cid_ns}{plan.gi}")
        chunks = [self.exec_fused_range(plan, lo, hi, sup, cid)
                  for lo, hi, cid in members]
        return {"chunks": chunks, "survivors": None}

    def finish_segment(self, plan, members, seg_key, host, tm):
        n_folds, cl_state = self.n_folds, self.cl_state
        if self.hb_on:
            # after success AND after the per-chunk fallback
            _heartbeat.get_hub().complete_segment(seg_key)
        lanes = plan.nc_batch * n_folds
        wall = tm.dispatch_s + tm.compute_s + tm.gather_s
        total_real = sum((hi - lo) * n_folds for lo, hi, _ in members)
        for (lo, hi, chunk_id), res in zip(members, host["chunks"]):
            idx = plan.group.candidate_indices[lo:hi]
            n_real = (hi - lo) * n_folds
            # the segment wall splits by real lanes, scoring folded into
            # fit: time columns are estimates, scores exact
            t_fit = wall * n_real / max(1, total_real)
            self.fit_failed[idx, :] |= res.bad
            self.record_launch(plan, res, lanes, idx)
            self.write_cells(plan, idx, lo, hi, chunk_id, res.test,
                             res.train, t_fit, 0.0, count_launch=False)
        self.metrics.counter("n_launches").inc()
        self.per_group_rec(plan)["n_launches"] += 1
        cl_state["n_chunks_scanned"] += len(members)
        cl_state["segment_lengths"].append(len(members))
        cl_state["n_launches_saved"] += len(members) - 1
        surv = host.get("survivors")
        if surv is not None and self.rung is not None:
            # device positions -> rung candidate indices, in sklearn
            # _top_k order, for halving's next rung
            self.rung.device_survivors = np.asarray(
                plan.group.candidate_indices)[np.asarray(surv, int)]
            cl_state["rung_topk_device"] += 1


def _call_aot(exe, jit_fn, *args):
    """A group's fused launch through its AOT executable (`exe`, a
    one-item list), or through the jit program once the executable
    refused the arguments."""
    if exe[0] is not None:
        try:
            return exe[0](*args)
        except (TypeError, ValueError):
            # aval/sharding mismatch only: drop to jit forever (runtime
            # failures propagate: the jit program would only fail again)
            exe[0] = None
    return jit_fn(*args)
