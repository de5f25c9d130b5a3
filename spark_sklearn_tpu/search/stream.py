"""Streaming-fold data plane — ``data_mode="stream"`` (SURVEY §7.4).

The reference's answer to "X does not fit" was Spark's: leave the data
partitioned on the cluster and ship the *model* search to it.  This
engine's device tier had only the opposite move — ship ALL of X to the
accelerator (replicated, or sample-sharded over the mesh) — so a
dataset bigger than HBM simply could not ride the compiled path on one
chip.  The streaming-fold tier closes that gap analytically instead of
by trial-and-error:

  - **plan** — :func:`~spark_sklearn_tpu.parallel.taskgrid.
    plan_stream_shards` sizes uniform sample shards from the resolved
    HBM budget minus the modeled resident program footprint (the PR 10
    ledger's pricing: sparse rows enter nnz-proportionally), so the
    shard width is a *planning decision* journaled next to the launch
    geometry — an OOM bisection on the streamed path is a bug, not a
    discovery mechanism;
  - **pipeline** — each shard's host slice + upload runs on the
    :class:`~spark_sklearn_tpu.parallel.pipeline.ChunkPipeline` stage
    thread, overlapping the PREVIOUS shard's device compute; the data
    plane's content fingerprints dedup re-uploads, so a shard crossing
    host->device twice in one pass is a bug;
  - **fold** — families expose per-shard, per-fold fit statistics that
    are candidate-independent and additive (``stream_fit_partial``);
    the engine folds them on device in shard order, journals the
    accumulator after every shard (a kill mid-stream resumes exactly
    like a chunk kill), then vmaps ``stream_fit_finalize`` over each
    chunk's candidates — for families whose statistics are exact sums
    (the discrete NB family), the streamed fit IS the in-core fit,
    bit for bit;
  - **score** — a second pass streams the same shards through the
    ordinary ``predict``, accumulating the default scorer's sufficient
    statistics (accuracy's hit/weight sums; r2's weighted moments), so
    ``cv_results_`` matches the in-core engine without the test folds
    ever being resident at once.

Knobs: ``TpuConfig.data_mode`` / ``SST_DATA_MODE`` pick the tier
("device" default, "stream", "sparse"); ``TpuConfig.
stream_shard_bytes`` / ``SST_STREAM_SHARD_BYTES`` cap the per-shard
slab the planner targets before the budget shrinks it.
"""

from __future__ import annotations

import base64
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from spark_sklearn_tpu.models.base import NotCompiledError
from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs.trace import get_tracer

logger = get_logger("search.stream")

__all__ = [
    "DATA_MODES",
    "check_stream_supported",
    "resolve_data_mode",
    "resolve_shard_bytes",
    "run_stream",
]

DATA_MODES = ("device", "stream", "sparse")

#: default shard slab the planner targets when neither the config knob
#: nor the env mirror speaks — small enough that even a modest HBM
#: budget double-buffers it, big enough to amortize dispatch overhead
DEFAULT_SHARD_BYTES = 64 << 20


def resolve_data_mode(config) -> str:
    """The search's data tier: ``TpuConfig.data_mode`` wins, then the
    ``SST_DATA_MODE`` env mirror, then ``"device"`` (the byte-identical
    legacy path)."""
    mode = getattr(config, "data_mode", None)
    if mode is None:
        mode = os.environ.get("SST_DATA_MODE", "").strip().lower() or None
    if mode is None:
        return "device"
    mode = str(mode).strip().lower()
    if mode not in DATA_MODES:
        raise ValueError(
            f"data_mode={mode!r} is not a data tier; expected one of "
            f"{DATA_MODES}")
    return mode


def resolve_shard_bytes(config) -> int:
    """Target host bytes per streamed sample shard:
    ``TpuConfig.stream_shard_bytes`` wins, then
    ``SST_STREAM_SHARD_BYTES``, then 64 MiB."""
    v = getattr(config, "stream_shard_bytes", None)
    if v is None:
        env = os.environ.get("SST_STREAM_SHARD_BYTES", "").strip()
        v = int(env) if env else None
    if v is None:
        return DEFAULT_SHARD_BYTES
    v = int(v)
    if v <= 0:
        raise ValueError(
            f"stream_shard_bytes={v} must be a positive byte count")
    return v


def check_stream_supported(family, scoring, config) -> None:
    """Fail fast (clear refusal, never a silent densified fallback)
    when this search cannot run the streaming-fold tier."""
    if not getattr(family, "supports_stream", False):
        raise NotCompiledError(
            f"data_mode='stream' requires a family implementing the "
            f"streaming-fold protocol (stream_fit_partial/"
            f"stream_fit_finalize); {family.name} does not.  Use "
            "data_mode='device' or backend='host'.")
    if scoring is not None:
        raise NotCompiledError(
            "data_mode='stream' scores through the family's default "
            f"scorer only (accuracy / r2); scoring={scoring!r} is not "
            "streamable.  Use data_mode='device' or backend='host'.")
    if getattr(family, "default_scorer", None) is not None:
        raise ValueError(
            f"data_mode='stream' cannot stream {family.name}'s custom "
            "default scorer; use data_mode='device'.")
    if int(getattr(config, "n_data_shards", 1) or 1) > 1:
        raise ValueError(
            "data_mode='stream' and n_data_shards>1 are alternative "
            "answers to the same problem (X larger than one chip); "
            "pick one.")


# ---------------------------------------------------------------------------
# journal (de)serialization: accumulator pytrees as base64 leaves
# ---------------------------------------------------------------------------

def _pack_tree(tree) -> List[Dict[str, Any]]:
    """Device/host pytree -> JSON-safe leaf records, in tree order.
    f32/f64 bytes round-trip exactly, so a resumed accumulator is
    bit-identical to the one the killed run folded."""
    import jax
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.ascontiguousarray(np.asarray(leaf))
        out.append({"shape": list(arr.shape), "dtype": str(arr.dtype),
                    "b64": base64.b64encode(arr.tobytes()).decode()})
    return out


def _unpack_tree(packed, like):
    """Inverse of :func:`_pack_tree`; ``like`` (same structure) donates
    the treedef.  Returns None on any structural mismatch — the caller
    then treats the journal entry as absent."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(like)
    if packed is None or len(packed) != len(leaves):
        return None
    new = []
    for rec, leaf in zip(packed, leaves):
        try:
            arr = np.frombuffer(
                base64.b64decode(rec["b64"]),
                dtype=np.dtype(str(rec["dtype"])))
            arr = arr.reshape([int(s) for s in rec["shape"]])
        except (KeyError, TypeError, ValueError):
            return None
        want = np.asarray(leaf)
        if arr.shape != want.shape or arr.dtype != want.dtype:
            return None
        new.append(arr)
    return jax.tree_util.tree_unflatten(treedef, new)


def _zeros_like_shapes(shapes):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _streaming_counters(plan, n_live: int) -> Dict[str, Any]:
    """The initial ``search_report["streaming"]`` block (schema pinned
    in ``obs.metrics.STREAMING_BLOCK_SCHEMA``): the journaled plan's
    facts plus zeroed pass counters ``run_stream`` advances in place."""
    return {
        **plan.report_block(),
        "fit_shards_streamed": 0,
        "score_shards_streamed": 0,
        "fit_shards_resumed": 0,
        "score_shards_resumed": 0,
        "h2d_bytes": 0,
        "n_live_chunks": int(n_live),
    }


def _pad_rows(arr: np.ndarray, lo: int, hi: int, rows: int) -> np.ndarray:
    """Host row slice [lo, hi) padded to ``rows`` with ZERO rows (zero
    weight rows contribute exactly 0.0 to every partial sum, so the
    uniform shard shape costs nothing in exactness)."""
    sl = arr[lo:hi]
    if hi - lo == rows:
        return np.ascontiguousarray(sl)
    out = np.zeros((rows,) + arr.shape[1:], arr.dtype)
    out[: hi - lo] = sl
    return out


def _pad_mask(m: np.ndarray, lo: int, hi: int, rows: int) -> np.ndarray:
    """(n_folds, n) mask column slice padded with zero-weight columns."""
    sl = m[:, lo:hi]
    if hi - lo == rows:
        return np.ascontiguousarray(sl)
    out = np.zeros((m.shape[0], rows), m.dtype)
    out[:, : hi - lo] = sl
    return out


# ---------------------------------------------------------------------------
# the streamed search runner
# ---------------------------------------------------------------------------

def run_stream(search, *, groups, base_params, family, meta, scorer_names,
               data, fit_masks, test_sc_masks, train_sc_masks, repl,
               config, n_task_shards, max_cand_per_batch, n_folds, dtype,
               return_train, test_scores, train_scores, fit_times,
               score_times, ckpt, fit_failed, candidates):
    """Run every compile group's chunks through the streaming-fold data
    plane instead of :meth:`_run_groups`'s resident-X launches.

    Two shard passes over the host dataset: a FIT pass folding each
    family's additive per-fold statistics on device (journaled per
    shard), a finalize step vmapping each chunk's candidates over the
    folded statistics, then a SCORE pass streaming the same shards
    through ``predict`` into the default scorer's sufficient
    statistics.  Shard upload (stage thread) overlaps the previous
    shard's compute at ``pipeline_depth >= 1``; depth 0 is the
    synchronous bit-identical escape hatch."""
    import jax
    import jax.numpy as jnp

    from spark_sklearn_tpu.obs import memory as _obs_memory
    from spark_sklearn_tpu.parallel import dataplane as _dataplane
    from spark_sklearn_tpu.parallel import memledger as _memledger
    from spark_sklearn_tpu.parallel.pipeline import ChunkPipeline, LaunchItem
    from spark_sklearn_tpu.parallel.taskgrid import (
        GeometryMismatchError, pad_chunk, plan_stream_shards)
    from spark_sklearn_tpu.search.scorers import EPS

    # n_task_shards is part of the _run_groups lane geometry; the
    # streamed programs take fully-replicated operands, so on a wider
    # task mesh they simply run replicated (correct, if redundant) —
    # no reshard, no error
    del n_task_shards

    tracer = get_tracer()
    metrics = search._search_metrics
    plane = _dataplane.plane_for(config)
    ledger = _memledger.ledger_for(config)
    from spark_sklearn_tpu import serve as _serve
    binding = _serve.current_binding()
    tenant = binding.tenant if binding is not None else None
    dp_before = _dataplane.snapshot_counters(plane)
    is_cls = bool(family.is_classifier)
    n_samples = int(next(iter(data.values())).shape[0])

    def _put(arr, label):
        if plane is not None:
            return plane.put(arr, repl, label=label, tenant=tenant)
        return _dataplane.upload(arr, repl, label=label)

    # -- chunk geometry (fixed-width: the stream tier's launch count is
    # -- dominated by n_shards, so the waste-aware planner buys nothing)
    plans = []
    for gi, group in enumerate(groups):
        nc = int(group.n_candidates)
        width = max(1, min(nc, int(max_cand_per_batch)))
        static = {**base_params, **group.static_params}
        chunks = []
        for lo in range(0, nc, width):
            hi = min(lo + width, nc)
            chunks.append((lo, hi, f"st:{gi}:{lo}:{hi}"))
        plans.append({"gi": gi, "group": group, "static": static,
                      "nc": nc, "width": width, "chunks": chunks})

    # -- resume completed chunks (same record shape as write_cells')
    live: List[tuple] = []          # (plan, lo, hi, chunk_id)
    for plan in plans:
        group = plan["group"]
        for lo, hi, chunk_id in plan["chunks"]:
            rec = ckpt.get(chunk_id) if ckpt is not None else None
            if rec is not None and return_train \
                    and rec.get("train") is None:
                rec = None
            idx = group.candidate_indices[lo:hi]
            if rec is not None:
                for s in scorer_names:
                    test_scores[s][idx, :] = np.asarray(rec["test"][s])
                    if return_train:
                        train_scores[s][idx, :] = np.asarray(
                            rec["train"][s])
                fit_times[idx, :] = rec["fit_t"]
                score_times[idx, :] = rec["score_t"]
                if rec.get("failed") is not None:
                    fit_failed[idx, :] |= np.asarray(rec["failed"], bool)
                metrics.counter("n_chunks_resumed").inc()
            else:
                live.append((plan, lo, hi, chunk_id))

    # -- analytic shard plan: budget minus the modeled resident program
    # -- footprint (chunk operands + accumulators + finalized models),
    # -- all priced before the first upload
    row_bytes = 0
    for v in data.values():
        v = np.asarray(v)
        row_bytes += v.dtype.itemsize * int(
            np.prod(v.shape[1:], dtype=np.int64))
    n_mask_ops = 2 + (1 if return_train else 0)   # fit + test (+ train)
    row_bytes += n_mask_ops * n_folds * fit_masks.dtype.itemsize

    def _struct_rows(rows):
        d_s = {k: jax.ShapeDtypeStruct((rows,) + np.asarray(v).shape[1:],
                                       np.asarray(v).dtype)
               for k, v in data.items()}
        w_s = jax.ShapeDtypeStruct((n_folds, rows), fit_masks.dtype)
        return d_s, w_s

    def make_partial(static):
        def partial(data_s, fw_s):
            return family.stream_fit_partial(static, data_s, fw_s, meta)
        return partial

    reserved = 0
    for plan in plans:
        fp = _memledger.model_group_footprint(
            plan["group"].dynamic_params, plan["width"], n_folds,
            task_batched=False, n_samples=0,
            mask_itemsize=int(fit_masks.dtype.itemsize),
            n_scorers=len(scorer_names), return_train=return_train,
            dtype_itemsize=int(np.dtype(dtype).itemsize))
        plan["partial"] = make_partial(plan["static"])
        d1, w1 = _struct_rows(1)
        acc_shapes = jax.eval_shape(plan["partial"], d1, w1)
        plan["acc_shapes"] = acc_shapes
        acc_bytes = sum(
            int(np.prod(s.shape, dtype=np.int64))
            * np.dtype(s.dtype).itemsize
            for s in jax.tree_util.tree_leaves(acc_shapes))
        # a chunk's finalized models stay resident for the score pass:
        # price one fold's model pytree x (width x n_folds) tasks
        one_stats = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            acc_shapes)
        dyn1 = {k: jax.ShapeDtypeStruct((), np.asarray(v).dtype)
                for k, v in plan["group"].dynamic_params.items()}
        try:
            model_shapes = jax.eval_shape(
                lambda dn, st: family.stream_fit_finalize(
                    dn, plan["static"], st, meta), dyn1, one_stats)
            model_bytes = sum(
                int(np.prod(s.shape, dtype=np.int64))
                * np.dtype(s.dtype).itemsize
                for s in jax.tree_util.tree_leaves(model_shapes))
        except Exception as exc:
            # pricing only — the real finalize traces (and raises)
            # below; an unpriceable model just doesn't shrink the shard
            logger.debug(
                "stream plan: model footprint eval_shape failed (%r); "
                "pricing finalized models at 0 bytes", exc)
            model_bytes = 0
        n_chunks = len(plan["chunks"])
        reserved += int(fp["chunk_bytes"]) + acc_bytes \
            + model_bytes * plan["width"] * n_folds * n_chunks

    budget = 0
    mem_ctx = getattr(search, "_memory_ctx", None)
    if ledger is not None and mem_ctx is not None:
        budget = int(mem_ctx.get("budget_bytes", 0))
    else:
        budget = int(_obs_memory.resolve_hbm_budget(config, None))

    t_plan0 = time.perf_counter()
    plan_sh = plan_stream_shards(
        n_samples, row_bytes, resolve_shard_bytes(config),
        budget_bytes=budget, reserved_bytes=reserved)
    tracer.record_span(
        "stream.plan", t_plan0, time.perf_counter(),
        n_shards=plan_sh.n_shards, shard_rows=plan_sh.shard_rows,
        row_bytes=plan_sh.row_bytes, capped=plan_sh.capped)
    if ckpt is not None:
        journalled = ckpt.get_meta("stream_plan")
        if journalled is not None:
            from spark_sklearn_tpu.parallel.taskgrid import StreamPlan
            jplan = StreamPlan.from_dict(journalled)
            if jplan.signature() != plan_sh.signature():
                raise GeometryMismatchError(
                    "checkpoint was written under a different stream-"
                    "shard geometry (journalled (n_samples, shard_rows, "
                    f"n_shards) = {jplan.signature()}, current = "
                    f"{plan_sh.signature()}); per-shard journal entries "
                    "are only addressable under the geometry that wrote "
                    f"them.  Delete {ckpt.path!r} or restore the "
                    "original stream_shard_bytes / HBM budget.")
            plan_sh = jplan
        else:
            ckpt.put_meta("stream_plan", plan_sh.to_dict())

    rows = int(plan_sh.shard_rows)
    n_shards = int(plan_sh.n_shards)
    if ledger is not None and mem_ctx is not None:
        rec = {"group": "stream", "width": int(rows),
               "capped": bool(plan_sh.capped),
               "resident_bytes": int(reserved),
               "chunk_bytes": int(2 * rows * row_bytes),
               "dyn_bytes": 0, "mask_bytes": 0, "out_bytes": 0,
               "per_candidate_bytes": 0}
        ledger.note_group(rec)
        mem_ctx["groups"].append(rec)

    stream_block = _streaming_counters(plan_sh, len(live))

    if not live:
        metrics.put("streaming", stream_block)
        return

    live_plans = [p for p in plans
                  if any(pl is p for pl, *_ in live)]

    # -- per-group device programs -------------------------------------
    def _tree_add(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    add_jit = jax.jit(_tree_add)

    for plan in live_plans:
        static = plan["static"]
        plan["partial_jit"] = jax.jit(plan["partial"])
        plan["acc"] = _zeros_like_shapes(plan["acc_shapes"])

        def make_fin(static=static, width=plan["width"]):
            def fin(dyn, stats):
                def one_cand(dyn_c):
                    def one_fold(stats_f):
                        return family.stream_fit_finalize(
                            dyn_c, static, stats_f, meta)
                    return jax.vmap(one_fold)(stats)
                models = jax.vmap(one_cand)(dyn)
                bad = None
                for leaf in jax.tree_util.tree_leaves(models):
                    if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                        continue
                    b = jnp.isnan(leaf).any(
                        axis=tuple(range(2, leaf.ndim)))
                    bad = b if bad is None else (bad | b)
                if bad is None:
                    bad = jnp.zeros((width, n_folds), bool)
                return models, bad
            return fin

        plan["fin_jit"] = jax.jit(make_fin())

        def make_score(static=static):
            def score_shard(models, Xs, ys, te_m, tr_m):
                def one_cand(model_c):
                    def one_fold(model_f, te_w, tr_w):
                        pred = family.predict(model_f, static, Xs, meta)
                        out = {}
                        if is_cls:
                            ok = (pred == ys).astype(te_w.dtype)
                            out["num_te"] = jnp.sum(te_w * ok)
                            out["den_te"] = jnp.sum(te_w)
                            if return_train:
                                out["num_tr"] = jnp.sum(tr_w * ok)
                                out["den_tr"] = jnp.sum(tr_w)
                        else:
                            err = ys - pred
                            out["ssr_te"] = jnp.sum(te_w * err * err)
                            out["s0_te"] = jnp.sum(te_w)
                            out["s1_te"] = jnp.sum(te_w * ys)
                            out["s2_te"] = jnp.sum(te_w * ys * ys)
                            if return_train:
                                out["ssr_tr"] = jnp.sum(tr_w * err * err)
                                out["s0_tr"] = jnp.sum(tr_w)
                                out["s1_tr"] = jnp.sum(tr_w * ys)
                                out["s2_tr"] = jnp.sum(tr_w * ys * ys)
                        return out
                    return jax.vmap(one_fold)(model_c, te_m, tr_m)
                return jax.vmap(one_cand)(models)
            return score_shard

        plan["score_jit"] = jax.jit(make_score())

    # -- pipeline ------------------------------------------------------
    depth = config.pipeline_depth if jax.process_count() == 1 else 0
    pipe = ChunkPipeline(depth, verbose=search.verbose)
    walls = {"fit": 0.0, "score": 0.0}

    def shard_bounds(j):
        lo = j * rows
        return lo, min(lo + rows, n_samples)

    # -- FIT pass ------------------------------------------------------
    # resume: the highest contiguous journaled shard's accumulators
    start_shard = 0
    if ckpt is not None:
        j = 0
        rec = None
        while j < n_shards:
            r = ckpt.get(f"st:fit:{j}")
            if r is None:
                break
            rec = r
            j += 1
        if rec is not None and j > 0:
            restored = {}
            ok = True
            for plan in live_plans:
                acc = _unpack_tree(
                    rec.get("accs", {}).get(str(plan["gi"])),
                    plan["acc"])
                if acc is None:
                    ok = False
                    break
                restored[plan["gi"]] = acc
            if ok:
                start_shard = j
                for plan in live_plans:
                    plan["acc"] = jax.tree_util.tree_map(
                        jnp.asarray, restored[plan["gi"]])
                stream_block["fit_shards_resumed"] = int(j)
            else:
                logger.warning(
                    "streamed fit journal is structurally stale; "
                    "refolding from shard 0", chunk="st:fit")

    def fit_items():
        for j in range(start_shard, n_shards):
            lo, hi = shard_bounds(j)

            def stage(j=j, lo=lo, hi=hi):
                payload = {
                    k: _put(_pad_rows(np.asarray(v), lo, hi, rows),
                            f"stream.data.{k}.s{j}")
                    for k, v in data.items()}
                payload["__fw__"] = _put(
                    _pad_mask(fit_masks, lo, hi, rows),
                    f"stream.mask.fit.s{j}")
                return payload

            def launch(payload):
                fw = payload.pop("__fw__")
                outs = []
                for plan in live_plans:
                    part = plan["partial_jit"](payload, fw)
                    plan["acc"] = add_jit(plan["acc"], part)
                    outs.append(plan["acc"])
                return outs

            def gather(out):
                if ckpt is None:
                    return None
                return {str(plan["gi"]): _pack_tree(acc)
                        for plan, acc in zip(live_plans, out)}

            def finalize(host, tm, j=j):
                walls["fit"] += tm.dispatch_s + tm.compute_s \
                    + tm.gather_s
                metrics.counter("n_launches").inc()
                stream_block["fit_shards_streamed"] += 1
                if ckpt is not None and host is not None:
                    ckpt.put(f"st:fit:{j}", {"accs": host})

            yield LaunchItem(
                key=f"st:fit:{j}", kind="stream_fit", group=0,
                n_tasks=len(live_plans), stage=stage, launch=launch,
                gather=gather, finalize=finalize)

    t0 = time.perf_counter()
    pipe.run(fit_items())
    tracer.record_span("stream.fit_pass", t0, time.perf_counter(),
                       n_shards=n_shards - start_shard, shard_rows=rows)

    # -- finalize: one cheap launch per live chunk ---------------------
    t0 = time.perf_counter()
    models = {}
    for plan, lo, hi, chunk_id in live:
        group = plan["group"]
        width = plan["width"]
        dyn = {k: _dataplane.upload(
                   pad_chunk(np.asarray(arr), lo, hi, width, 1),
                   repl, label="stream.dyn")
               for k, arr in group.dynamic_params.items()}
        if not dyn:
            dyn["_pad"] = _dataplane.upload(
                np.zeros(width, dtype=dtype), repl, label="stream.dyn")
        mdl, bad = plan["fin_jit"](dyn, plan["acc"])
        idx = group.candidate_indices[lo:hi]
        fit_failed[idx, :] |= np.asarray(bad)[: hi - lo]
        models[chunk_id] = mdl
        metrics.counter("n_launches").inc()
    walls["fit"] += time.perf_counter() - t0
    tracer.record_span("stream.finalize", t0, time.perf_counter(),
                       n_chunks=len(live))

    # -- SCORE pass ----------------------------------------------------
    saccs = {}
    for plan, lo, hi, chunk_id in live:
        te_like = jnp.zeros((plan["width"], n_folds), fit_masks.dtype)
        if is_cls:
            keys = ["num_te", "den_te"] + (
                ["num_tr", "den_tr"] if return_train else [])
        else:
            keys = ["ssr_te", "s0_te", "s1_te", "s2_te"] + (
                ["ssr_tr", "s0_tr", "s1_tr", "s2_tr"]
                if return_train else [])
        saccs[chunk_id] = {k: te_like for k in keys}

    score_start = 0
    if ckpt is not None:
        j = 0
        rec = None
        while j < n_shards:
            r = ckpt.get(f"st:score:{j}")
            if r is None:
                break
            rec = r
            j += 1
        if rec is not None and j > 0:
            restored = {}
            ok = True
            for plan, lo, hi, chunk_id in live:
                acc = _unpack_tree(
                    rec.get("accs", {}).get(chunk_id), saccs[chunk_id])
                if acc is None:
                    ok = False
                    break
                restored[chunk_id] = acc
            if ok:
                score_start = j
                for cid, acc in restored.items():
                    saccs[cid] = jax.tree_util.tree_map(
                        jnp.asarray, acc)
                stream_block["score_shards_resumed"] = int(j)
            else:
                logger.warning(
                    "streamed score journal is structurally stale; "
                    "rescoring from shard 0", chunk="st:score")

    def score_items():
        for j in range(score_start, n_shards):
            lo, hi = shard_bounds(j)

            def stage(j=j, lo=lo, hi=hi):
                payload = {
                    "X": _put(_pad_rows(np.asarray(data["X"]),
                                        lo, hi, rows),
                              f"stream.data.X.s{j}"),
                    "y": _put(_pad_rows(np.asarray(data["y"]),
                                        lo, hi, rows),
                              f"stream.data.y.s{j}"),
                    "te": _put(_pad_mask(test_sc_masks, lo, hi, rows),
                               f"stream.mask.test.s{j}"),
                    "tr": _put(_pad_mask(train_sc_masks, lo, hi, rows),
                               f"stream.mask.train.s{j}")
                    if return_train else None,
                }
                return payload

            def launch(payload):
                te_m = payload["te"]
                tr_m = payload["tr"] if return_train else te_m
                outs = []
                for plan, lo_, hi_, chunk_id in live:
                    part = plan["score_jit"](
                        models[chunk_id], payload["X"], payload["y"],
                        te_m, tr_m)
                    saccs[chunk_id] = add_jit(saccs[chunk_id], part)
                    outs.append(saccs[chunk_id])
                return outs

            def gather(out):
                if ckpt is None:
                    return None
                return {chunk_id: _pack_tree(acc)
                        for (plan, lo_, hi_, chunk_id), acc
                        in zip(live, out)}

            def finalize(host, tm, j=j):
                walls["score"] += tm.dispatch_s + tm.compute_s \
                    + tm.gather_s
                metrics.counter("n_launches").inc()
                stream_block["score_shards_streamed"] += 1
                if ckpt is not None and host is not None:
                    ckpt.put(f"st:score:{j}", {"accs": host})

            yield LaunchItem(
                key=f"st:score:{j}", kind="stream_score", group=0,
                n_tasks=len(live), stage=stage, launch=launch,
                gather=gather, finalize=finalize)

    t0 = time.perf_counter()
    pipe.run(score_items())
    pipe.close()
    tracer.record_span("stream.score_pass", t0, time.perf_counter(),
                       n_shards=n_shards - score_start, shard_rows=rows)

    # -- reduce sufficient statistics to cv_results_ cells -------------
    sname = scorer_names[0]
    eps = np.asarray(EPS, fit_masks.dtype)
    total_real = sum((hi - lo) * n_folds for _, lo, hi, _ in live)
    fit_t = walls["fit"] / max(1, total_real)
    score_t = walls["score"] / max(1, total_real)
    metrics.gauge("fit_wall_s").add(walls["fit"])
    metrics.gauge("score_wall_s").add(walls["score"])

    def _reduce(acc, side):
        if is_cls:
            num = np.asarray(acc[f"num_{side}"])
            den = np.asarray(acc[f"den_{side}"])
            return num / (den + eps)
        ssr = np.asarray(acc[f"ssr_{side}"])
        s0 = np.asarray(acc[f"s0_{side}"])
        s1 = np.asarray(acc[f"s1_{side}"])
        s2 = np.asarray(acc[f"s2_{side}"])
        ybar = s1 / (s0 + eps)
        sstot = s2 - 2.0 * ybar * s1 + ybar * ybar * s0
        return 1.0 - ssr / np.maximum(sstot, eps)

    for plan, lo, hi, chunk_id in live:
        idx = plan["group"].candidate_indices[lo:hi]
        acc = {k: np.asarray(v) for k, v in saccs[chunk_id].items()}
        te = _reduce(acc, "te")[: hi - lo]
        test_scores[sname][idx, :] = te
        if return_train:
            tr = _reduce(acc, "tr")[: hi - lo]
            train_scores[sname][idx, :] = tr
        fit_times[idx, :] = fit_t
        score_times[idx, :] = score_t
        if ckpt is not None:
            ckpt.put(chunk_id, {
                "test": {sname: test_scores[sname][idx, :].tolist()},
                "train": ({sname: train_scores[sname][idx, :].tolist()}
                          if return_train else None),
                "fit_t": fit_t, "score_t": score_t,
                "failed": fit_failed[idx, :].tolist()})

    dp_after = _dataplane.snapshot_counters(plane)
    stream_block["h2d_bytes"] = int(
        dp_after.get("total_bytes", 0) - dp_before.get("total_bytes", 0))
    metrics.put("streaming", stream_block)
