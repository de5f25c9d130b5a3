"""GridSearchCV / RandomizedSearchCV — the flagship feature.

Drop-in replacements for the reference's `spark_sklearn.GridSearchCV(sc,
estimator, param_grid)` (reference: python/spark_sklearn/grid_search.py) and
for sklearn's own search estimators.  API compatibility notes:

  - ``GridSearchCV(estimator, param_grid, ...)`` — sklearn-style; ALSO
    accepts the reference's legacy ``GridSearchCV(sc, estimator, param_grid)``
    calling convention: if the first positional argument has no
    ``get_params``, it is treated as a legacy Spark context and ignored (the
    mesh plays its role).
  - ``cv_results_`` schema matches sklearn's `_format_results`
    (sklearn/model_selection/_search.py:1208-1290): `params`, masked
    `param_*` arrays, `split{i}_test_*`, `mean/std/rank_test_*`,
    `mean/std_fit_time`, `mean/std_score_time`, optional train scores.
  - `best_index_/best_params_/best_score_/best_estimator_/refit_time_`,
    `multimetric_`, `n_splits_`, `scorer_` follow sklearn
    (_search.py:1148-1202).

Execution: two tiers (SURVEY §7.0).
  Tier A (compiled): estimator family recognised by the registry -> the
    (candidates x folds) grid becomes nested `vmap` axes of one jitted
    program per compile group, sharded over the mesh "task" axis; the dataset
    is device_put replicated (the TPU-native `sc.broadcast`).
  Tier B (host): any other estimator -> real `clone(est).set_params(**p)
    .fit(...)` via sklearn `_fit_and_score` fanned out with joblib — full
    sklearn generality, exactly the reference's per-task semantics.
"""

from __future__ import annotations

import functools
import numbers
import time
import warnings
from collections import OrderedDict, defaultdict
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from sklearn.base import BaseEstimator, MetaEstimatorMixin, clone, is_classifier
from sklearn.callback import CallbackSupportMixin
from sklearn.callback._callback_support import callback_management_context
from sklearn.model_selection import ParameterGrid, ParameterSampler, check_cv
from sklearn.utils import Bunch
from sklearn.utils.metadata_routing import (
    MetadataRouter,
    MethodMapping,
    _raise_for_params,
    _routing_enabled,
    process_routing,
)
from sklearn.utils.metaestimators import available_if
from sklearn.utils.validation import _check_method_params, check_is_fitted

from spark_sklearn_tpu.models.base import (
    CANDIDATE_AXIS, NotCompiledError, resolve_family)
from spark_sklearn_tpu.parallel import mesh as mesh_lib
from spark_sklearn_tpu.parallel import ownership as _ownership
from spark_sklearn_tpu.parallel.mesh import TpuConfig, build_mesh
from spark_sklearn_tpu.parallel.taskgrid import build_compile_groups
from spark_sklearn_tpu.search.scorers import (
    BINARY_ONLY_SCORERS,
    CLASSIFICATION_SCORERS,
    build_view,
    resolve_scoring,
)
from spark_sklearn_tpu.utils import keycheck as _keycheck
from spark_sklearn_tpu.utils.locks import named_lock, named_rlock
from spark_sklearn_tpu.utils.native import fold_masks
from spark_sklearn_tpu.obs.log import get_logger
from spark_sklearn_tpu.obs.metrics import search_registry
from spark_sklearn_tpu.obs import process as _process
from spark_sklearn_tpu.obs.trace import get_tracer, search_tracing
from spark_sklearn_tpu.parallel import faults as _faults


import contextlib as _contextlib

logger = get_logger("spark_sklearn_tpu.search")
_nullcontext = _contextlib.nullcontext


def _freeze(obj):
    """Strict hashable view for program-cache keys (shared helper in
    parallel/taskgrid.py); raises TypeError for unkeyable values."""
    from spark_sklearn_tpu.parallel.taskgrid import freeze
    return freeze(obj, strict=True)


#: cross-search cache of jitted callables, LRU-ordered (oldest first).
#: Values are (callable, family_tag); jitted callables pin XLA executables
#: and device constants, so the bound is per-family as well as global — a
#: long-lived process cycling many shapes of ONE family can at worst evict
#: its own older programs, never another family's entire working set.
#: CONCURRENT searches (serve/executor.py) hit this cache from several
#: worker threads, so every read-modify-write runs under the rlock;
#: program construction itself stays outside it (builds may take the
#: programstore's own locks, and two racing builders just keep the
#: first-inserted program).
_PROGRAM_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_PROGRAM_CACHE_MAX = 128
_PROGRAM_CACHE_MAX_PER_FAMILY = 32
_PROGRAM_CACHE_FAMILY_COUNTS: Dict[Any, int] = defaultdict(int)
_PROGRAM_CACHE_LOCK = named_rlock("grid._PROGRAM_CACHE_LOCK")


def _cache_evict(fam=None):
    """Drop the least-recently-used entry (of `fam` if given, else global)."""
    with _PROGRAM_CACHE_LOCK:
        victim = None
        if fam is not None:
            victim = next((k for k, (_, f) in _PROGRAM_CACHE.items()
                           if f == fam), None)
        if victim is None:
            victim = next(iter(_PROGRAM_CACHE))
        _, vfam = _PROGRAM_CACHE.pop(victim)
        _PROGRAM_CACHE_FAMILY_COUNTS[vfam] -= 1
        if _PROGRAM_CACHE_FAMILY_COUNTS[vfam] <= 0:
            del _PROGRAM_CACHE_FAMILY_COUNTS[vfam]
#: launches per compile group under convergence-sorted chunking — enough
#: grading that easy launches early-exit well below max_iter, few enough
#: that each launch stays matmul-wide
_SORTED_LAUNCHES = 8


def _sorted_launch_width(proxy, graded):
    """Candidates a launch of a convergence-sorted group holds: `graded`
    (the group over `_SORTED_LAUNCHES`), or a whole run of equal proxies
    where the sorted `proxy` is runs of one length that is no shorter.
    Lanes of one proxy value stop together (the boosters' proxy is
    `n_estimators` itself, a handful of distinct counts), so a launch a
    run idles no lane, and a cut inside a run buys no grading: it only
    pays the launch's fixed cost again.  A group whose proxies all
    differ is runs of one candidate, and keeps `graded`."""
    runs = np.unique(proxy, return_counts=True)[1]
    run = int(runs[0])
    return run if run >= graded and np.all(runs == run) else graded


def _cached_program(key, build, store_parts=None, store=None,
                    check_fields=None):
    """Cross-search cache of jitted callables.

    The fit/score programs are built from per-search closures, so without
    this every search re-traces and re-lowers programs jax has already
    compiled (~0.7 s per search at bench scale even with a warm persistent
    compile cache — the XLA binary is cached, the python->jaxpr->HLO walk
    is not).  Keyed by everything the closures capture; jax.jit's own
    cache below handles shapes/dtypes.  Unkeyable captures (e.g. a fresh
    user lambda) just skip the cache.

    Eviction is LRU with per-family program accounting (keys are
    ("fit"|"score"|..., family, ...) tuples): a family at its cap evicts
    its own LRU entry, the global cap evicts the overall LRU entry.

    ``check_fields`` names the call site's EFFECTIVE trace inputs (the
    config-derived values that alter what ``build`` traces) for the
    ``SST_KEYCHECK=1`` runtime recorder (utils/keycheck.py): each must
    flow into ``key``, so two calls agreeing on the key but disagreeing
    on a field are two distinct traced artifacts aliasing one cache
    slot — reported as a key collision by the conftest hook.

    ``store_parts`` (a deterministic ``(kind, family_name, *structure)``
    tuple) additionally routes the program through ``store`` — THIS
    SEARCH's persistent AOT store (parallel/programstore.py), resolved
    by the caller from its own config so a store-less search never
    consults a store some earlier search activated: the cached value
    becomes a :class:`~spark_sklearn_tpu.parallel.programstore.
    StoredProgram` that resolves serialized artifacts instead of
    re-tracing, and ``n_compiles`` then counts signatures that actually
    traced (store misses) rather than cache builds.
    """
    if store_parts is None:
        store = None
    try:
        k = _freeze(key)
    except TypeError:
        _count_build()
        return build()
    if store is not None:
        # store-backed and plain programs are distinct cache residents:
        # a later store-less search must not consult the store through
        # a stale proxy (nor the reverse)
        k = (k, "__programstore__", store.directory)
    _keycheck.note(
        "program_cache", k, fields=check_fields,
        detail=str(key[0]) if isinstance(key, tuple) and key else "")
    with _PROGRAM_CACHE_LOCK:
        hit = _PROGRAM_CACHE.get(k)
        if hit is not None:
            _PROGRAM_CACHE.move_to_end(k)
    if hit is not None:
        if store is not None:
            # a deactivate/re-activate cycle minted a fresh store
            # object for the same directory: repoint the cached proxy
            # so traffic lands on the store whose counters/manifest
            # this search reports (outside the cache lock: rebind
            # takes the store's own)
            rebind = getattr(hit[0], "rebind", None)
            if rebind is not None:
                rebind(store)
        return hit[0]
    fam = key[1] if isinstance(key, tuple) and len(key) > 1 else None
    # build OUTSIDE the lock: tracing/wrapping may take programstore
    # locks, and a slow build must not stall every concurrent search's
    # cache lookups.  Two racing builders of the same key are benign —
    # the first insert wins below and the loser's identical program is
    # dropped (its _count_build still ran: both really traced).
    fn = build()
    if store is not None:
        from spark_sklearn_tpu.parallel import programstore as _ps
        wrapped = _ps.maybe_wrap(fn, store, store_parts,
                                 on_trace=_count_build)
        if wrapped is fn:     # store-unkeyable: legacy accounting
            _count_build()
        fn = wrapped
    else:
        _count_build()
    with _PROGRAM_CACHE_LOCK:
        raced = _PROGRAM_CACHE.get(k)
        if raced is not None:
            _PROGRAM_CACHE.move_to_end(k)
            return raced[0]
        if _PROGRAM_CACHE_FAMILY_COUNTS.get(fam, 0) >= \
                _PROGRAM_CACHE_MAX_PER_FAMILY:
            _cache_evict(fam)
        elif len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_MAX:
            _cache_evict()
        _PROGRAM_CACHE[k] = (fn, fam)
        _PROGRAM_CACHE_FAMILY_COUNTS[fam] += 1
    return fn


#: count of traced-program constructions (program-cache misses; with a
#: program store active, store-resolution misses) — the search_report's
#: n_compiles.  Store resolution may run on the compile thread while
#: the dispatch thread builds, hence the lock.
_PROGRAM_BUILDS = 0
_BUILDS_LOCK = named_lock("grid._BUILDS_LOCK")


def _count_build() -> None:
    global _PROGRAM_BUILDS
    with _BUILDS_LOCK:
        _PROGRAM_BUILDS += 1


def _program_build_count() -> int:
    with _BUILDS_LOCK:
        return _PROGRAM_BUILDS


@jax.jit
def _models_health(models):
    """(nc_batch, n_folds) True where any inexact model leaf went NaN —
    the compiled-tier analog of est.fit raising.  inf is NOT flagged:
    families use inf sentinels legitimately (e.g. tree split
    thresholds)."""
    bad = None
    for leaf in jax.tree_util.tree_leaves(models):
        if not jnp.issubdtype(leaf.dtype, jnp.inexact):
            continue
        b = jnp.isnan(leaf).any(axis=tuple(range(2, leaf.ndim)))
        bad = b if bad is None else (bad | b)
    return bad


def chunkloop_block(state, *, mode="per_chunk", enabled=False,
                    score_attribution="calibrated"):
    """Normalize the ``search_report["chunkloop"]`` block in place
    (schema pinned in ``obs.metrics.CHUNKLOOP_BLOCK_SCHEMA``).

    The state dict is the registry's own ``metrics.struct("chunkloop")``
    object, so the scan-path finalizers (and halving's elimination
    accounting) mutate the same dict this function returns — a halving
    search's rungs accumulate into one whole-search block.  Emitted for
    BOTH loop modes: a per-chunk search reports the zeroed
    ``enabled=False`` shape, so the report schema never changes.
    """
    defaults = {
        "mode": mode,
        "enabled": bool(enabled),
        "n_segments": 0,
        "n_chunks_scanned": 0,
        "n_launches_saved": 0,
        "segment_lengths": [],
        "fallbacks": [],
        "rung_topk_device": 0,
        "rung_topk_host": 0,
        "score_attribution": score_attribution,
    }
    for k, v in defaults.items():
        state.setdefault(k, v)
    state["mode"] = mode
    state["enabled"] = bool(enabled)
    state["score_attribution"] = score_attribution
    return state


def _mesh_desc(mesh):
    """The mesh as a program-store key part: everything in a store key
    must repr identically across processes, so the mesh becomes its
    topology and device ids."""
    return ("mesh", tuple(sorted(dict(mesh.shape).items())),
            tuple(int(d.id) for d in np.asarray(mesh.devices).flat))


def _build_programs(family, meta, config, mesh, scorers, *, static,
                    nc_batch, n_folds, prefix, all_cores, fused_mode,
                    sw_blind, return_train, task_shard, store):
    """A compile group's jitted programs for a launch of `nc_batch`
    candidates under `static`, through the cross-search program cache:
    the fit program (models and launch stats), the score program and,
    with `fused_mode`, the fused fit + health + score program and its
    raw body, which the scan program steps.  jax.jit is lazy, so a
    program a search never calls is never traced.  `prefix` (the
    group's shared-prefix digest, which joins every key) makes them the
    suffix family's programs over the cached per-fold matrices;
    `store` is the search's persistent program store."""
    from spark_sklearn_tpu.search.launch import LaunchResult
    task_batched = hasattr(family, "fit_task_batched")
    px = prefix
    suffix_fam = family.suffix_family() if px else None
    needed_views = frozenset(
        v for fn in scorers.values() for v in getattr(fn, "views", ()))
    # the persistent store's key parts must repr identically across
    # processes: registry names for the family and scorers, the mesh's
    # topology
    score_key = tuple(sorted(scorers.items()))
    mesh_desc = _mesh_desc(mesh)
    store_score_names = tuple(sorted(scorers))
    store_sw_key = tuple(sorted(sw_blind))

    def _fold_data(data_d, Xf):
        return {**{k: v for k, v in data_d.items()
                   if k != "X_folds"}, "X": Xf}

    if task_batched:
        # flatten (candidate x fold) into one leading task axis and
        # let the family turn it into wide-matmul width (candidate-
        # major order: task t = (cand t//n_folds, fold t%n_folds))

        def fit_batch_tb(dyn_t, data_d, w_t,
                         static={**static, "__n_folds__": n_folds,
                                 "__bf16__": config.bf16_matmul}):
            with jax.named_scope("sst.fit"):
                model = family.fit_task_batched(
                    dyn_t, static, data_d, w_t, meta)
                return jax.tree_util.tree_map(
                    lambda l: l.reshape(
                        (nc_batch, n_folds) + l.shape[1:]), model)

    def fit_batch(dyn_arrs, data_d, train_m, static=static):
        def one_cand(dyn_scalars):
            if px:
                # suffix fit: fold f on its own cached transformed
                # matrix, bit-exact with the inline transform
                def one_fold_px(w, Xf):
                    return suffix_fam.fit(
                        dyn_scalars, static,
                        _fold_data(data_d, Xf), w, meta)
                return jax.vmap(one_fold_px)(
                    train_m, data_d["X_folds"])

            def one_fold(w):
                return family.fit(dyn_scalars, static, data_d, w,
                                  meta)
            return jax.vmap(one_fold)(train_m)
        with jax.named_scope("sst.fit"):
            # the candidate axis has a name, so that a family's
            # lockstep loop can ask whether ANY candidate still
            # runs (models/base.py::any_candidate)
            return jax.vmap(one_cand,
                            axis_name=CANDIDATE_AXIS)(dyn_arrs)

    def with_stats(fit_fn):
        # the unfused fit launch's program: the models and, from
        # the hook the fused body calls, the launch's stats
        @functools.wraps(fit_fn)    # the program keeps its name
        def fit_program(*args):
            models = fit_fn(*args)
            return models, family.launch_stats(models, static,
                                               meta)
        return fit_program

    def score_batch_wide(models, data_d, test_m, train_m, test_u,
                         train_u, static=static):
        leaf = jax.tree_util.tree_leaves(models)[0]
        ncb, nf = leaf.shape[0], leaf.shape[1]
        n_tasks = ncb * nf
        flat = jax.tree_util.tree_map(
            lambda l: l.reshape((n_tasks,) + l.shape[2:]), models)
        views = {}
        if px:
            # suffix views: fold f's models score on fold f's
            # cached matrix, candidates over folds as the fit
            # vmaps them.  (Flat over tasks with a gather
            # X_folds[t % nf] a task, XLA:TPU materialized the
            # (T, n, d') operand it was meant to fuse away:
            # 6.6 GB at 60 tasks of 70 000 x 784; compiled for a
            # v5e the launch's scratch is 7.17 GB that way and
            # 0.04 GB this way, PERF.md, PR 33.)
            xf = data_d["X_folds"]
            for name in needed_views:
                views[name] = jax.tree_util.tree_map(
                    lambda v: v.reshape((n_tasks,) + v.shape[2:]),
                    jax.vmap(lambda m_c, name=name: jax.vmap(
                        lambda m, Xf: build_view(
                            name, suffix_fam, m, static,
                            _fold_data(data_d, Xf), meta))(
                                m_c, xf))(models))
        else:
            wide = getattr(family, "views_task_batched", None)
            if wide is not None:
                views = dict(wide(flat, static, data_d, meta,
                                  needed_views))
            for name in needed_views:
                if name not in views:
                    views[name] = jax.vmap(
                        lambda m, name=name: build_view(
                            name, family, m, static, data_d,
                            meta))(flat)

        y = data_d.get("y")
        # fold masks are indexed per task (t % n_folds,
        # candidate-major flattening) instead of tiled to (T, n):
        # the gather fuses into the reduction cores, where a tile
        # would materialize ncb copies of every mask buffer
        fold_idx = jnp.arange(n_tasks, dtype=jnp.int32) % nf

        def one_task(view_t, fi):
            wte, wtr = test_m[fi], train_m[fi]
            wteu, wtru = test_u[fi], train_u[fi]
            te = {s: fn.core(view_t, y,
                             wteu if s in sw_blind else wte, meta)
                  for s, fn in scorers.items()}
            tr = ({s: fn.core(view_t, y,
                              wtru if s in sw_blind else wtr, meta)
                   for s, fn in scorers.items()}
                  if return_train else {})
            return te, tr

        te, tr = jax.vmap(one_task)(views, fold_idx)
        return (jax.tree_util.tree_map(
                    lambda a: a.reshape(ncb, nf), te),
                jax.tree_util.tree_map(
                    lambda a: a.reshape(ncb, nf), tr))

    def score_batch_nested(models, data_d, test_m, train_m, test_u,
                           train_u, static=static):
        def one_cand(model_c):
            def one_fold(model, w_test, w_train, w_test_u,
                         w_train_u):
                te = {s: fn(family, model, static, data_d, meta,
                            w_test_u if s in sw_blind else w_test)
                      for s, fn in scorers.items()}
                tr = ({s: fn(family, model, static, data_d, meta,
                             w_train_u if s in sw_blind
                             else w_train)
                       for s, fn in scorers.items()}
                      if return_train else {})
                return te, tr
            return jax.vmap(one_fold)(
                model_c, test_m, train_m, test_u, train_u)
        return jax.vmap(one_cand)(models)

    score_core = score_batch_wide if all_cores \
        else score_batch_nested

    @functools.wraps(score_core)    # the program keeps its name
    def score_batch(*args):
        with jax.named_scope("sst.score"):
            return score_core(*args)

    fused_jit = None
    if fused_mode:
        fit_core = fit_batch_tb if task_batched else fit_batch

        def fused_batch(dyn_t, data_d, w_fit, test_m, train_m,
                        test_u, train_u):
            models = fit_core(dyn_t, data_d, w_fit)
            with jax.named_scope("sst.score"):
                bad = _models_health(models)
                if bad is None:
                    leaf = jax.tree_util.tree_leaves(models)[0]
                    bad = jnp.zeros(leaf.shape[:2], bool)
                # what the family reports of the launch: named
                # int32 arrays the engine carries without reading
                stats = family.launch_stats(models, static, meta)
                te, tr = score_batch_wide(models, data_d, test_m,
                                          train_m, test_u, train_u)
            return LaunchResult(te, tr, bad, stats)

        fused_jit = _cached_program(
            ("fused", family, static, meta, nc_batch, n_folds,
             bool(config.bf16_matmul), mesh, score_key,
             return_train, sw_blind, px),
            lambda: jax.jit(fused_batch),
            store_parts=(
                "fused", family.name, static, meta, nc_batch,
                n_folds, bool(config.bf16_matmul), mesh_desc,
                store_score_names, store_sw_key, return_train,
                px),
            store=store,
            check_fields={
                "bf16_matmul": bool(config.bf16_matmul),
                "mesh": mesh_desc})
    # separate fit/score programs: every chunk's on the non-fused path,
    # a group's first live chunk's on the fused path (the calibration)
    if task_batched:
        # the mesh joins the in-memory key as mesh_desc joins the store
        # key: a search on a re-built mesh must not reuse a program
        # whose store proxy was keyed to the old one
        fit_jit = _cached_program(
            ("fit_tb", family, static, meta, nc_batch, n_folds,
             bool(config.bf16_matmul), mesh),
            lambda: jax.jit(with_stats(fit_batch_tb)),
            store_parts=(
                "fit_tb", family.name, static, meta, nc_batch,
                n_folds, bool(config.bf16_matmul), mesh_desc),
            store=store,
            check_fields={
                "bf16_matmul": bool(config.bf16_matmul),
                "mesh": mesh_desc})
    else:
        fit_jit = _cached_program(
            ("fit", family, static, meta, mesh, px),
            lambda: jax.jit(with_stats(fit_batch),
                            out_shardings=(task_shard, None)),
            store_parts=("fit", family.name, static, meta, mesh_desc,
                         px),
            store=store,
            check_fields={"mesh": mesh_desc})
    score_jit = _cached_program(
        ("score", family, static, meta, score_key, return_train,
         sw_blind, bool(all_cores), px, mesh),
        lambda: jax.jit(score_batch),
        store_parts=("score", family.name, static, meta,
                     mesh_desc, store_score_names, store_sw_key,
                     return_train, bool(all_cores), px),
        store=store,
        check_fields={"mesh": mesh_desc})
    return {"fit": fit_jit, "score": score_jit, "fused": fused_jit,
            # the raw (un-jitted) fused body: the scan program
            # wraps it as its lax.scan step function
            "fused_body": fused_batch if fused_mode else None}


def _build_scan(fused_body, family, meta, config, mesh, scorers, *,
                static, nc_batch, nc, n_folds, n_steps, topk_k, score0,
                errval, prefix, sw_blind, return_train, hb=False):
    """ONE jitted program executing `n_steps` chunks of a compile group
    as a `lax.scan` over the stacked chunk axis.  The step function is
    the group's fused body, so every lane computes exactly what its
    solo fused launch would.

    `topk_k > 0` also folds the halving rung's elimination on device: a
    score carry (a row a candidate position, plus a dump row for padded
    lanes) accumulates the `score0` test scores, and the program
    returns the top-k candidate POSITIONS as sklearn's `_top_k` orders
    them.  `hb=True` threads the heartbeat beacon into the step body:
    a jax.debug.callback emits (token, step), the token a RUNTIME
    operand, so ONE compiled program serves every search."""
    from jax import lax
    from spark_sklearn_tpu.obs import heartbeat as _heartbeat

    def scan_batch(dyn_st, idx_st, data_d, w_fit, test_m,
                   train_m, test_u, train_u, hb_tok=None):
        if topk_k:
            carry0 = jnp.full((nc + 1, n_folds),
                              jnp.float32(errval))
        else:
            carry0 = jnp.zeros((), jnp.float32)

        def step(carry, xs):
            if hb:
                dyn_c, idx_c, step_i = xs
            else:
                dyn_c, idx_c = xs
            res = fused_body(
                dyn_c, data_d, w_fit, test_m, train_m,
                test_u, train_u)
            if hb:
                # in-flight beat: fires on jax's callback
                # thread as each scan step executes; unordered
                # (no token threading cost) — the hub takes
                # the max step either way
                jax.debug.callback(
                    _heartbeat.device_beat, hb_tok, step_i,
                    ordered=False)
            if topk_k:
                # mirror the host-side error_score substitution
                # BEFORE the mean, so the device ranking sees
                # the same scores sklearn's _top_k would
                sc = jnp.where(
                    res.bad, jnp.float32(errval),
                    res.test[score0].astype(jnp.float32))
                carry = carry.at[idx_c].set(sc)
            return carry, res

        xs = (dyn_st, idx_st)
        if hb:
            xs = xs + (jnp.arange(n_steps, dtype=jnp.int32),)
        carry, ys = lax.scan(step, carry0, xs)
        if topk_k:
            mean = carry[:nc].mean(axis=1)
            order = jnp.roll(jnp.argsort(mean),
                             jnp.count_nonzero(jnp.isnan(mean)))
            surv = order[-topk_k:].astype(jnp.int32)
        else:
            surv = jnp.zeros((0,), jnp.int32)
        return ys, surv

    # the nan error_score breaks key equality (nan != nan), so the key
    # carries its repr; scan programs skip the persistent store.  The
    # beacon joins the key ONLY when on, so on/off never alias
    score_key = tuple(sorted(scorers.items()))
    return _cached_program(
        ("scan", family, static, meta, nc_batch, n_folds, int(n_steps),
         bool(config.bf16_matmul), mesh, score_key, return_train,
         sw_blind, int(topk_k), nc, repr(float(errval)), prefix)
        + (("hb",) if hb else ()),
        lambda: jax.jit(scan_batch),
        store_parts=None,
        check_fields={
            "bf16_matmul": bool(config.bf16_matmul),
            "heartbeat": bool(hb),
            "mesh": _mesh_desc(mesh)})


def _prefix_program(family, static, digest, meta, mesh):
    """The shared-prefix transform of a compile group's Pipeline, keyed
    on its DIGEST, not the group statics: two groups differing only in
    final-step params share one compiled transform."""
    def build():
        return jax.jit(
            lambda data_d, w_f: family.prefix_transform(static, data_d,
                                                        w_f))
    return _cached_program(
        ("prefix", family, digest, meta, mesh), build,
        check_fields={"prefix_digest": digest})


def _looks_like_estimator(obj) -> bool:
    return hasattr(obj, "get_params") and (
        hasattr(obj, "fit") or hasattr(obj, "predict"))


def _is_multimetric(scorer_names) -> bool:
    return not (len(scorer_names) == 1 and scorer_names[0] == "score")



def _check_refit(search_cv, attr):
    if not search_cv.refit:
        raise AttributeError(
            f"This {type(search_cv).__name__} instance was initialized with "
            f"`refit=False`. {attr} is available only after refitting on "
            "the best parameters. You can refit an estimator manually "
            "using the `best_params_` attribute")


def _search_estimator_has(attr):
    """sklearn's delegation check (_search.py:368): method availability
    mirrors the (best_)estimator and the refit flag."""

    def check(self):
        _check_refit(self, attr)
        if hasattr(self, "best_estimator_"):
            getattr(self.best_estimator_, attr)
            return True
        getattr(self.estimator, attr)
        return True

    return check


class BaseSearchTPU(CallbackSupportMixin, MetaEstimatorMixin, BaseEstimator):
    """Shared engine: candidate generation is the only subclass hook
    (`_get_candidates`), mirroring sklearn's `_run_search` split
    (_search.py:1708/2109).  Callback support follows sklearn's task tree:
    root -> search -> candidate-split-evaluation leaves, plus a
    refit-with-best-params task (sklearn callback module contract)."""

    def __init__(self, estimator, *, scoring=None, n_jobs=None, refit=True,
                 cv=None, verbose=0, error_score=np.nan,
                 return_train_score=False, backend=None,
                 config: Optional[TpuConfig] = None):
        self.estimator = estimator
        self.scoring = scoring
        self.n_jobs = n_jobs
        self.refit = refit
        self.cv = cv
        self.verbose = verbose
        self.error_score = error_score
        self.return_train_score = return_train_score
        self.backend = backend          # None=auto, "tpu"=compiled, "host"
        self.config = config


    @property
    def search_report(self):
        """Per-search execution report (backend, compile groups, launches,
        fit/score wall).  Stored privately so fit() only adds underscore-
        prefixed/suffixed attributes, per sklearn's estimator checks.

        The report is the rendered view of a typed metrics registry —
        its full schema (every key, kind and meaning) is pinned in
        ``spark_sklearn_tpu.obs.metrics.SEARCH_REPORT_SCHEMA`` and
        rendered into ``docs/API.md``.

        Compiled searches additionally carry ``report["pipeline"]`` — the
        chunk scheduler's timeline (parallel/pipeline.py):

          - ``depth``: the pipeline depth the search ran at (0 = the
            synchronous escape hatch);
          - ``launches``: one record per device launch with its
            ``kind`` (fit/score/calibrate/fused) and per-phase walls
            (``stage_s``/``dispatch_s``/``compute_s``/``gather_s``/
            ``finalize_s``);
          - ``stage_wall_s``/``dispatch_wall_s``/``compute_wall_s``/
            ``gather_wall_s``/``finalize_wall_s``: the per-phase sums,
            and ``wall_s`` the run's actual wall — their gap is the
            ``overlap_frac`` (host work hidden behind device compute);
          - ``n_compiles``/``n_precompiled``: how many programs were
            traced this search, and how many of those the compile-ahead
            thread AOT-compiled;
          - ``persistent_cache_hits``/``persistent_cache_misses``: the
            persistent compilation cache's traffic during this search
            (nonzero hits = a previous process already paid the
            compile; see TpuConfig.compilation_cache_dir).
        """
        if not hasattr(self, "_search_report"):
            from sklearn.exceptions import NotFittedError

            # NotFittedError subclasses AttributeError, so hasattr()
            # and legacy `except AttributeError` callers keep working
            raise NotFittedError(
                f"This {type(self).__name__} instance is not fitted yet; "
                "search_report is set by fit(). Call 'fit' with "
                "appropriate arguments first.")
        return self._search_report

    # -- candidate generation -------------------------------------------
    def _get_candidates(self) -> List[Dict[str, Any]]:
        raise NotImplementedError

    def _run_search(self, evaluate_candidates, *, callback_ctx=None):
        """sklearn's extension point (_search.py:1040-1134): subclasses may
        call `evaluate_candidates` any number of times with any candidate
        batches (e.g. successive-halving-style searches); each call returns
        `cv_results_`-shaped results for everything evaluated so far."""
        candidates = self._get_candidates()
        if callback_ctx is None:
            evaluate_candidates(candidates)
            return
        search_ctx = callback_ctx.subcontext(
            task_name="search",
            max_subtasks=len(candidates) * self.n_splits_,
            sequential_subtasks=False,
        ).call_on_fit_task_begin(estimator=self)
        evaluate_candidates(candidates, callback_ctx=search_ctx)
        search_ctx.call_on_fit_task_end(estimator=self)

    # -- sklearn plumbing -----------------------------------------------
    def _check_refit_for_multimetric(self, scorer_names):
        if self.refit is not False and (
            not isinstance(self.refit, str) or self.refit not in scorer_names
        ) and not callable(self.refit):
            # sklearn's exact phrasing (_search.py _check_refit_for_...)
            raise ValueError(
                "For multi-metric scoring, the parameter refit must be set "
                "to a scorer key or a callable to refit an estimator with "
                f"the best parameter setting on the whole data and make the "
                f"best_* attributes available for that metric. If this is "
                f"not needed, refit should be set to False explicitly. "
                f"{self.refit!r} was passed.")

    # -- metadata routing (sklearn 1.4+ contract; installed
    # _search.py get_metadata_routing/_get_routed_params_for_fit) --------
    def _get_scorers(self):
        """sklearn-facing scorer objects, used for routing decisions and
        as `scorer_` (the compiled tier resolves its own device scorers
        separately)."""
        from sklearn.metrics import check_scoring
        from sklearn.metrics._scorer import (
            _check_multimetric_scoring, _MultimetricScorer)

        if callable(self.scoring):
            return self.scoring
        if self.scoring is None or isinstance(self.scoring, str):
            return check_scoring(self.estimator, self.scoring)
        scorers = _check_multimetric_scoring(self.estimator, self.scoring)
        return _MultimetricScorer(
            scorers=scorers, raise_exc=(self.error_score == "raise"))

    def _check_scorers_accept_sample_weight(self):
        """Warn per scorer that cannot consume sample_weight (sklearn's
        pre-routing forwarding rule) and return whether any can."""
        from inspect import signature

        from sklearn.metrics._scorer import _MultimetricScorer

        scorers = self._get_scorers()
        if isinstance(scorers, _MultimetricScorer):
            for name, scorer in scorers._scorers.items():
                if not scorer._accept_sample_weight():
                    warnings.warn(
                        f"The scoring {name}={scorer} does not support "
                        "sample_weight, which may lead to statistically "
                        f"incorrect results when fitting {self} with "
                        "sample_weight. ")
            return scorers._accept_sample_weight()
        if hasattr(scorers, "_accept_sample_weight"):
            accept = scorers._accept_sample_weight()
        else:
            accept = "sample_weight" in signature(scorers).parameters
        if not accept:
            warnings.warn(
                f"The scoring {scorers} does not support sample_weight, "
                "which may lead to statistically incorrect results when "
                f"fitting {self} with sample_weight. ")
        return accept

    def _get_routed_params_for_fit(self, params):
        if _routing_enabled():
            return process_routing(self, "fit", **params)
        params = params.copy()
        groups = params.pop("groups", None)
        routed_params = Bunch(
            estimator=Bunch(fit=params),
            splitter=Bunch(split={"groups": groups}),
            scorer=Bunch(score={}),
        )
        # pre-routing rule: sample_weight forwards to the scorer(s) when
        # present and accepted (any scorer, for multimetric)
        if (params.get("sample_weight") is not None
                and self._check_scorers_accept_sample_weight()):
            routed_params.scorer.score["sample_weight"] = \
                params["sample_weight"]
        return routed_params

    def get_metadata_routing(self):
        router = MetadataRouter(owner=self)
        router.add(
            estimator=self.estimator,
            method_mapping=MethodMapping().add(caller="fit", callee="fit"),
        )
        router.add(
            scorer=self._get_scorers(),
            method_mapping=MethodMapping()
            .add(caller="score", callee="score")
            .add(caller="fit", callee="score"),
        )
        router.add(
            splitter=self.cv,
            method_mapping=MethodMapping().add(caller="fit", callee="split"),
        )
        return router

    def fit(self, X, y=None, **params):
        # a session-attached search (TpuSession.attach) is sugar for
        # submit + wait: fit routes through the session's fair-share
        # executor, sharing the device with any concurrently-submitted
        # searches.  Inside an executor worker (current_binding set)
        # this IS the submitted fit, so it runs the real path below —
        # unattached searches are untouched.
        session = getattr(self, "_sst_session", None)
        if session is not None:
            from spark_sklearn_tpu import serve as _serve
            if _serve.current_binding() is None:
                return session.submit(self, X, y, **params).result()
        # teardown of attached callbacks is guaranteed even when fit
        # raises (sklearn wraps fit the same way via _fit_context)
        with callback_management_context(self):
            # span tracing scoped to this search: recording only when
            # TpuConfig(trace=...)/SST_TRACE asks; exact no-op otherwise
            with search_tracing(self.config) as tracer:
                # the process ledger's two stamps (obs/process.py): the
                # report's "process" block is everything this process
                # paid once, up to the end of this search
                _process.fit_begin()
                try:
                    with tracer.span(
                            "search.fit", search=type(self).__name__,
                            estimator=type(self.estimator).__name__):
                        return self._fit_impl(X, y, params)
                finally:
                    block = _process.fit_end()
                    metrics = getattr(self, "_search_metrics", None)
                    if metrics is not None:
                        metrics.put("process", block)

    def _fit_impl(self, X, y, params):
        estimator = self.estimator
        if self.scoring is None and not hasattr(estimator, "score"):
            # sklearn validates this before any work (BaseSearchCV.fit)
            raise TypeError(
                "If no scoring is specified, the estimator passed should "
                f"have a 'score' method. The estimator {estimator!r} "
                "does not.")
        # multimetric refit misconfiguration must fail BEFORE any other
        # work — even cv validation (sklearn's ordering)
        if isinstance(self.scoring, (list, tuple, set, dict)):
            self._check_refit_for_multimetric(
                list(self.scoring.keys())
                if isinstance(self.scoring, dict) else list(self.scoring))

        with get_tracer().span("fit.prepare"):
            cv = check_cv(self.cv, y, classifier=is_classifier(estimator))
            from spark_sklearn_tpu.sparse.csr import CSRMatrix
            if isinstance(X, CSRMatrix):
                X = X.to_scipy()  # splitters/refit understand scipy CSR
            else:
                import scipy.sparse as _sp
                if _sp.issparse(X) and X.format not in ("csr", "csc"):
                    X = X.tocsr()  # COO/DOK are not sliceable by fold indices
            X_arr = X if hasattr(X, "shape") else np.asarray(X)

            params = _check_method_params(X, params=params)
            routed_params = self._get_routed_params_for_fit(params)

            sw_meta = params.get("sample_weight")
            metadata_callbacks = ({"sample_weight": sw_meta}
                                  if sw_meta is not None else None)
            root_callback_ctx = self._init_callback_context(
                max_subtasks=1 + (self.refit is not False)
            ).call_on_fit_task_begin(
                estimator=self, X=X, y=y, metadata=metadata_callbacks)

            splits = list(cv.split(X_arr, y, **routed_params.splitter.split))
            self.n_splits_ = len(splits)
            if hasattr(cv, "get_n_splits"):
                expected_n_splits = cv.get_n_splits(
                    X_arr, y, **routed_params.splitter.split)
                if expected_n_splits != self.n_splits_:
                    raise ValueError(
                        "cv.split and cv.get_n_splits return "
                        f"inconsistent results. Expected {expected_n_splits} "
                        f"splits, got {self.n_splits_}")

            family = (None if self.backend == "host"
                      else resolve_family(estimator))
            use_compiled = family is not None
            # groups is fine on the compiled path: the splits above already
            # encode it and only fold masks reach the device.  sample_weight is
            # too: it is one multiply into the fold masks.  Any OTHER fit/score
            # param is an arbitrary kwarg that cannot enter a traced fit.
            est_fit_params = dict(routed_params.estimator.fit)
            score_params = dict(routed_params.scorer.score)
            fit_weight = est_fit_params.get("sample_weight")
            score_weight = score_params.get("sample_weight")
            unsupported_compiled = (
                {k for k, v in est_fit_params.items()
                 if k != "sample_weight" and v is not None}
                | {k for k, v in score_params.items()
                   if k != "sample_weight" and v is not None})
            if use_compiled and fit_weight is not None and \
                    getattr(estimator, "class_weight", None) == "balanced" \
                    and np.any(np.asarray(fit_weight) == 0):
                # sklearn's balanced counts are unweighted bincounts over ALL
                # train-fold rows; the compiled tier derives them from the
                # weighted mask's support, which drops zero-weight rows ->
                # reproduce sklearn on the host instead
                unsupported_compiled = unsupported_compiled | {"sample_weight"}
            if use_compiled and fit_weight is not None and not getattr(
                    family, "accepts_sample_weight", True):
                # e.g. Pipelines: sklearn raises on a bare sample_weight (step
                # routing wants "step__sample_weight") — the host path
                # reproduces that contract
                unsupported_compiled = unsupported_compiled | {"sample_weight"}
            if use_compiled and unsupported_compiled:
                if self.backend == "tpu":
                    raise NotCompiledError(
                        f"fit/score params {sorted(unsupported_compiled)} are "
                        "not supported on the compiled path; use "
                        "backend='host'")
                use_compiled = False
            if use_compiled:
                try:
                    resolve_scoring(self.scoring, family)
                except NotCompiledError:
                    if self.backend == "tpu":
                        raise
                    use_compiled = False

        # sklearn's extension point (_search.py evaluate_candidates):
        # _run_search may call evaluate_candidates several times; batches
        # accumulate and each call returns the results-so-far
        acc: Dict[str, Any] = {
            "params": [], "test": None, "train": None,
            "fit_t": [], "score_t": [], "names": None, "results": None,
            "more": {}}

        state = {"use_compiled": use_compiled}

        def _compact_for_rung(splits_used):
            """Row-compact the dataset to the union of a halving rung's
            subsampled fold indices (compiled tier only).

            The fold-mask machinery makes a subsampled rung CORRECT by
            zero-weighting the unused rows, but zero-weight rows still
            multiply — rung 0 of a 1797-row search at n_resources=40
            would pay full-dataset matmuls for every lane.  Slicing
            X/y (and the weights) to the rows any fold actually uses,
            with the split indices remapped, makes the rung's compute
            proportional to its resource; every used row keeps its
            exact value, so the per-cell scores are the same
            computation on the same rows.  Returns None when
            compaction cannot apply (exotic X containers, nothing to
            drop, or a subsample that lost an entire class — the
            compiled class structure must match the full dataset's)."""
            import scipy.sparse as _sp
            if not (isinstance(X_arr, np.ndarray) or _sp.issparse(X_arr)):
                return None
            used = np.unique(np.concatenate(
                [np.concatenate([np.asarray(tr), np.asarray(te)])
                 for tr, te in splits_used]))
            if used.size == 0 or used.size >= X_arr.shape[0]:
                return None
            y_arr = None if y is None else np.asarray(y)
            y_sub = None if y_arr is None else y_arr[used]
            if y_arr is not None and is_classifier(self.estimator) \
                    and np.unique(y_sub).size != np.unique(y_arr).size:
                return None
            splits_c = [(np.searchsorted(used, np.asarray(tr)),
                         np.searchsorted(used, np.asarray(te)))
                        for tr, te in splits_used]
            fw = None if fit_weight is None \
                else np.asarray(fit_weight)[used]
            sw = None if score_weight is None \
                else np.asarray(score_weight)[used]
            return X_arr[used], y_sub, splits_c, fw, sw

        def _dispatch(cands, eval_ctxs, splits_used, rung_compact=False):
            if self.n_splits_ == 0:
                raise ValueError(
                    "No fits were performed. "
                    "Was the CV iterator empty? "
                    "Were there no candidates?")
            if state["use_compiled"]:
                try:
                    X_c, y_c, splits_c = X_arr, y, splits_used
                    fw_c, sw_c = fit_weight, score_weight
                    if rung_compact:
                        sub = _compact_for_rung(splits_used)
                        if sub is not None:
                            X_c, y_c, splits_c, fw_c, sw_c = sub
                    return self._fit_compiled(
                        family, X_c, y_c, cands, splits_c,
                        fit_weight=fw_c, score_weight=sw_c,
                        eval_ctxs=eval_ctxs)
                except NotCompiledError as exc:
                    # The ONE exception traded for the host tier: a
                    # family's (or the engine's) own refusal, raised
                    # host-side from static values.  Everything else — a
                    # jax trace / lowering / compile refusal, a runtime
                    # or device error, an exhausted supervisor, a
                    # watchdog timeout — propagates exactly as under
                    # backend="tpu": a silent sklearn re-run would hide
                    # that the accelerator never produced the scores.
                    if self.backend == "tpu":
                        raise
                    state["use_compiled"] = False  # fall back ONCE
                    # recorded into the host report's faults block so
                    # the refusal stays observable after the compiled
                    # registry is replaced
                    state["fallback_exc"] = exc
                    warnings.warn(
                        f"compiled search path failed ({exc!r}); falling "
                        "back to the host backend", UserWarning)
            # the host path receives the CALLER's X (list, sparse, frame —
            # sklearn estimators may validate its exact type); only the
            # compiled path needs the dense array form
            return self._fit_host(X, y, cands, splits_used, est_fit_params,
                                  score_params, eval_ctxs,
                                  fallback_exc=state.pop(
                                      "fallback_exc", None))

        def evaluate_candidates(candidate_params, cv=None,
                                more_results=None, callback_ctx=None):
            # sklearn's full evaluate_candidates contract
            # (_search.py:829): a subclass `_run_search` (successive
            # halving) may pass a per-call cv — the rung's subsample
            # splitter — and extra result columns (`iter`,
            # `n_resources`) that accumulate into cv_results_.  The
            # parameter deliberately shadows the outer validated cv.
            cands = list(candidate_params)
            if cv is None:
                splits_used = splits
            else:
                splits_used = list(cv.split(
                    X_arr, y, **routed_params.splitter.split))
                if len(splits_used) != self.n_splits_:
                    raise ValueError(
                        f"the per-call cv yielded {len(splits_used)} "
                        f"splits, expected {self.n_splits_}")
            if self.verbose > 0:
                # structured logger, stdout-parity channel: the line is
                # byte-for-byte sklearn's (BaseSearchCV.fit)
                logger.print(
                    f"Fitting {self.n_splits_} folds for each of "
                    f"{len(cands)} candidates, totalling "
                    f"{self.n_splits_ * len(cands)} fits",
                    n_splits=self.n_splits_, n_candidates=len(cands))
            if not cands:
                if not acc["params"]:
                    raise ValueError(
                        "No fits were performed. "
                        "Was the CV iterator empty? "
                        "Were there no candidates?")
                return acc["results"]
            # one leaf context per (candidate, split) pair, candidate-major
            # like the task list (sklearn: "candidate-split-evaluation").
            # Only allocated when callbacks are attached: a 10k-candidate
            # grid must not build 50k context objects for nobody.
            if callback_ctx is not None and \
                    getattr(self, "_skl_callbacks", None):
                eval_ctxs = [
                    callback_ctx.subcontext(
                        task_name="candidate-split-evaluation",
                        task_id=tid)
                    for tid in range(len(cands) * self.n_splits_)]
            else:
                eval_ctxs = None
            (test_scores, train_scores, fit_times, score_times,
             scorer_names, scorer_attr) = _dispatch(
                cands, eval_ctxs, splits_used,
                # a per-call cv is a halving rung's subsample: compact
                # the compiled tier's rows to what the rung uses (the
                # host tier always receives the caller's full X)
                rung_compact=cv is not None)
            if acc["names"] is None:
                acc["names"] = scorer_names
                acc["test"] = {s: [] for s in scorer_names}
                acc["train"] = ({s: [] for s in scorer_names}
                                if self.return_train_score else None)
                self.scorer_ = scorer_attr
            elif scorer_names != acc["names"]:
                raise ValueError(
                    f"inconsistent scorer names across evaluate_candidates "
                    f"calls: {scorer_names} vs {acc['names']}")
            acc["params"].extend(cands)
            for s in scorer_names:
                acc["test"][s].append(test_scores[s])
                if self.return_train_score:
                    acc["train"][s].append(train_scores[s])
            acc["fit_t"].append(fit_times)
            acc["score_t"].append(score_times)
            if more_results:
                for k, v in more_results.items():
                    acc["more"].setdefault(k, []).extend(v)
            with get_tracer().span("fit.results",
                                   n_candidates=len(acc["params"])):
                acc["results"] = self._format_results(
                    acc["params"],
                    {s: np.concatenate(v) for s, v in acc["test"].items()},
                    ({s: np.concatenate(v) for s, v in acc["train"].items()}
                     if self.return_train_score else None),
                    np.concatenate(acc["fit_t"]),
                    np.concatenate(acc["score_t"]), acc["names"],
                    more_results=acc["more"])
            return acc["results"]

        from inspect import signature as _signature
        # the search doctor's wall: timed around the WHOLE candidate
        # loop (every rung for halving), so host orchestration the
        # pipeline never sees is attributable too
        _doctor_t0 = time.perf_counter()
        if "callback_ctx" in _signature(self._run_search).parameters:
            self._run_search(evaluate_candidates,
                             callback_ctx=root_callback_ctx)
        else:
            # custom subclasses predating the callback API
            self._run_search(evaluate_candidates)
        _doctor_wall = time.perf_counter() - _doctor_t0
        # critical-path attribution + run-log sentinel (exact no-op
        # when attribution=False or on the host tier)
        self._doctor_finalize(
            _doctor_wall, _doctor_t0,
            family_name=(family.name if family is not None
                         else type(estimator).__name__),
            structure_parts=(
                type(estimator).__name__, len(acc["params"]),
                self.n_splits_, tuple(getattr(X_arr, "shape", ())),
                str(getattr(self.config, "dtype", ""))))

        if not acc["params"]:
            raise ValueError(
                "No fits were performed. "
                "Was the CV iterator empty? "
                "Were there no candidates?")
        scorer_names = acc["names"]
        self.multimetric_ = _is_multimetric(scorer_names)
        if self.multimetric_:
            self._check_refit_for_multimetric(scorer_names)
        # a string refit only names a metric when scoring is multimetric;
        # single-metric results are keyed "score" regardless (sklearn)

        results = acc["results"]
        self.cv_results_ = results

        refit_metric = (self.refit if self.multimetric_
                        and isinstance(self.refit, str) else "score")
        if self.refit or not self.multimetric_:
            self.best_index_ = self._select_best_index(
                self.refit, refit_metric, results)
            if not callable(self.refit):
                self.best_score_ = results[
                    f"mean_test_{refit_metric}"][self.best_index_]
            self.best_params_ = results["params"][self.best_index_]

        if self.refit:
            # Refit on the "driver", exactly like the reference
            # (grid_search.py: best_estimator_ = clone(base).set_params(
            #  **best_params).fit(X, y)); our native estimators run their own
            # compiled fit here.
            # param VALUES are cloned too, so estimator-valued grid
            # entries (e.g. {"regressor": [LinearRegression()]}) are never
            # fitted in place (sklearn _search.py:1166)
            self.best_estimator_ = clone(estimator).set_params(
                **clone(self.best_params_, safe=False))
            refit_subctx = root_callback_ctx.subcontext(
                task_name="refit-with-best-params")
            t0 = time.perf_counter()
            with refit_subctx.propagate_callback_context(
                    self.best_estimator_), \
                    get_tracer().span("refit",
                                      estimator=type(
                                          self.best_estimator_).__name__):
                refit_subctx.call_on_fit_task_begin(
                    estimator=self, X=X, y=y, metadata=metadata_callbacks)
                if y is not None:
                    self.best_estimator_.fit(
                        X, y, **routed_params.estimator.fit)
                else:
                    self.best_estimator_.fit(
                        X, **routed_params.estimator.fit)
            self.refit_time_ = time.perf_counter() - t0
            refit_subctx.call_on_fit_task_end(
                estimator=self, X=X, y=y, metadata=metadata_callbacks)
            if hasattr(self.best_estimator_, "classes_"):
                self.classes_ = self.best_estimator_.classes_
        if hasattr(X_arr, "shape") and len(getattr(X_arr, "shape", ())) == 2:
            self.n_features_in_ = X_arr.shape[1]
        root_callback_ctx.call_on_fit_task_end(
            estimator=self, X=X, y=y, metadata=metadata_callbacks)
        return self

    def _doctor_finalize(self, wall_s, t0_s, family_name,
                         structure_parts):
        """Search doctor: render ``search_report["attribution"]`` from
        the blocks the search just recorded, then let the run log
        persist the record and judge it against the stored baseline
        (``obs/attribution.py`` + ``obs/runlog.py``).

        Runs AFTER ``_run_search`` returns, so every block the
        analyzer consumes (pipeline, scheduler, faults, memory,
        geometry, halving) is already rendered.  Exact no-op when
        ``TpuConfig.attribution`` is off or the fit never reached the
        compiled tier (no pipeline timeline to decompose) — the
        report stays byte-identical to the pre-doctor shape."""
        if not getattr(self.config, "attribution", True):
            return
        metrics = getattr(self, "_search_metrics", None)
        if metrics is None or "pipeline" not in metrics.data:
            return
        from spark_sklearn_tpu.obs import attribution as _attribution
        from spark_sklearn_tpu.obs import runlog as _runlog
        tracer = get_tracer()
        # the tracer ring is process-global: clip to THIS search's
        # wall window so a previous search's compile/recovery spans
        # cannot leak into these lanes
        t1_s = t0_s + wall_s
        spans = [(name, max(a, t0_s), min(b, t1_s))
                 for name, a, b in _attribution.spans_from_tracer(
                     tracer.events())
                 if a < t1_s and b > t0_s] if len(tracer) else []
        with tracer.span("doctor.analyze", family=family_name):
            block = _attribution.attribution_block(
                metrics.data, wall_s, spans)
            metrics.put("attribution", block)
        digest = _runlog.structure_digest(family_name, *structure_parts)
        with tracer.span("doctor.sentinel", family=family_name):
            _runlog.note_run(metrics.data, family_name, digest,
                             config=self.config)
        logger.info(
            "search doctor: %s", block["verdict"],
            family=family_name, dominant=block["dominant"],
            wall_s=block["wall_s"],
            regression=block["regression"].get("status", "off"))

    @staticmethod
    def _hashable_labels(y):
        """Deterministic bytes for the checkpoint fingerprint: object-dtype
        labels would hash pointer addresses."""
        if y is None:
            return "none"
        y_arr = np.asarray(y)
        if y_arr.dtype == object:
            y_arr = y_arr.astype(str)
        return y_arr

    @staticmethod
    def _densify(X, dtype):
        """Sparse inputs reach the compiled path as dense device arrays
        (XLA has no first-class CSR; the native runtime does the threaded
        decompression — the CSRVectorUDT analog's job).  The host path
        receives sparse X unchanged, like sklearn."""
        import scipy.sparse as sp

        from spark_sklearn_tpu.utils.native import csr_to_dense

        # CSRMatrix was already converted to scipy CSR at the top of fit()
        if sp.issparse(X):
            m = X.tocsr()
            return csr_to_dense(
                m.data, m.indices, m.indptr, m.shape).astype(
                dtype, copy=False)
        return np.asarray(X)

    @staticmethod
    def _select_best_index(refit, refit_metric, results):
        if callable(refit):
            best_index = refit(results)
            if not isinstance(best_index, numbers.Integral):
                raise TypeError("best_index_ returned is not an integer")
            if best_index < 0 or best_index >= len(results["params"]):
                raise IndexError("best_index_ index out of range")
            return best_index
        return results[f"rank_test_{refit_metric}"].argmin()

    # ------------------------------------------------------------------
    # Tier A: compiled path
    # ------------------------------------------------------------------
    def _fit_compiled(self, family, X, y, candidates, splits,
                      fit_weight=None, score_weight=None, eval_ctxs=None):
        config = self.config or TpuConfig()
        if fit_weight is not None and \
                np.any(np.asarray(fit_weight) == 0):
            # 'balanced' may also arrive via the grid itself, not just the
            # estimator (the _fit_impl guard covers only the latter); the
            # compiled balanced counts come from the weighted mask's
            # support, which drops zero-weight rows sklearn would count
            if any(v == "balanced" for c in candidates for k, v in c.items()
                   if k == "class_weight" or k.endswith("__class_weight")):
                raise NotCompiledError(
                    "class_weight='balanced' with zero-valued sample "
                    "weights is not compiled; use backend='host'")
        out = self._fit_compiled_dispatch(
            family, X, y, candidates, splits, config,
            fit_weight=fit_weight, score_weight=score_weight)
        # compiled tasks execute fused inside XLA programs, so per-task
        # hooks fire host-side AFTER the sweep succeeds (begin/end per
        # task, completion-report style — live per-task progress does not
        # exist under fusion).  Firing post-hoc also means a compiled
        # failure that falls back to the host path has fired nothing, so
        # the host tier's _fit_and_score hooks are the only ones seen.
        # X/y passed to hooks are the full replicated arrays — fold
        # slicing exists only as masks on the device.
        if eval_ctxs is not None and getattr(self, "_skl_callbacks", None):
            n_folds = len(splits)
            for t, ctx in enumerate(eval_ctxs):
                train_idx = splits[t % n_folds][0]
                md = ({"sample_weight": np.asarray(fit_weight)[train_idx]}
                      if fit_weight is not None else None)
                ctx.call_on_fit_task_begin(
                    estimator=self, X=X, y=y, metadata=md)
                ctx.call_on_fit_task_end(
                    estimator=self, X=X, y=y, metadata=md)
        return out

    def _fit_compiled_dispatch(self, family, X, y, candidates, splits,
                               config, fit_weight=None, score_weight=None):
        # closed-form linear-algebra families (ridge-type normal equations)
        # amplify f32 rounding through the Gram conditioning to ~1e-4 —
        # far from sklearn's f64 answers.  They advertise wants_float64 and
        # run under a temporarily-enabled x64 mode so sklearn parity and
        # weighted-vs-repeated equivalence hold at sklearn's own 1e-7.
        use_f64 = bool(getattr(family, "wants_float64", False)) and \
            config.dtype is None
        if not use_f64:
            return self._fit_compiled_impl(
                family, X, y, candidates, splits, config,
                fit_weight=fit_weight, score_weight=score_weight)
        prev_x64 = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", True)
        try:
            return self._fit_compiled_impl(
                family, X, y, candidates, splits, config,
                fit_weight=fit_weight, score_weight=score_weight,
                dtype_override=np.float64)
        finally:
            jax.config.update("jax_enable_x64", prev_x64)

    def _prevalidate_candidates(self, candidates):
        """Host-side per-candidate hyperparameter validation (sklearn
        raises InvalidParameterError inside fit(); the compiled solvers
        accept any finite value, so the failure is reproduced here).

        Fast path: sklearn's ``_validate_params`` checks each declared
        param independently against the class's declarative
        ``_parameter_constraints``, so a candidate only needs its CHANGED
        keys re-checked against the owning (sub-)estimator's constraints —
        the unchanged rest was validated once on the base clone.  A
        clone-per-candidate loop (the previous implementation) costs ~1 ms
        per candidate, which at bench scale (1000 candidates) was ~25% of
        the whole warm search.  Candidates that rewire sub-estimators
        (estimator-valued values) fall back to the full clone+validate.
        """
        n_cand = len(candidates)
        failed = np.zeros(n_cand, bool)
        first_exc = None

        def validate_all(cand):
            if hasattr(cand, "_validate_params"):
                cand._validate_params()
            for sub in cand.get_params(deep=True).values():
                if hasattr(sub, "_validate_params") and \
                        hasattr(sub, "get_params"):
                    sub._validate_params()

        from sklearn.utils._param_validation import (
            validate_parameter_constraints)

        base = clone(self.estimator)
        base_exc = None
        try:
            validate_all(base)
        except Exception as exc:
            base_exc = exc
        deep = base.get_params(deep=True)

        def rewires(params):
            return any(
                hasattr(v, "get_params") or (
                    isinstance(v, (list, tuple))
                    and any(hasattr(e, "get_params") for e in v))
                for v in params.values())

        def validate_fast(params):
            """Check only the candidate's changed values against their
            owners' declarative constraints (what _validate_params does
            per key); keys are already known to exist in `deep`."""
            for k, v in params.items():
                if "__" in k:
                    prefix, bare = k.rsplit("__", 1)
                    owner = deep.get(prefix)
                else:
                    owner, bare = base, k
                constraints = getattr(owner, "_parameter_constraints", None)
                if constraints and bare in constraints:
                    validate_parameter_constraints(
                        {bare: constraints[bare]}, {bare: v},
                        caller_name=type(owner).__name__)

        for ci, params in enumerate(candidates):
            # base_exc disables the fast path entirely: a candidate may
            # OVERRIDE the base's invalid value with a valid one, which
            # only the real clone+set_params+validate can decide
            fast = base_exc is None and not rewires(params)
            if fast and any(k not in deep for k in params):
                fast = False           # key may be unknown: let set_params
                                       # produce its own (aborting) error
            cand = None
            if not fast:
                # unknown param KEYS abort the whole search (set_params
                # raises OUTSIDE the try), exactly as before
                cand = clone(self.estimator).set_params(**params)
            exc = None
            try:
                if fast:
                    validate_fast(params)
                else:
                    validate_all(cand)
            except Exception as e:
                exc = e
            if exc is not None:
                failed[ci] = True
                if first_exc is None:
                    first_exc = exc
        return failed, first_exc

    def _fit_compiled_impl(self, family, X, y, candidates, splits, config,
                           fit_weight=None, score_weight=None,
                           dtype_override=None):
        with get_tracer().span("fit.prepare"):
            from sklearn.metrics import check_scoring

            from spark_sklearn_tpu.parallel.pipeline import (
                enable_persistent_cache)
            enable_persistent_cache(config)
            # persistent AOT program store: sessionless fits activate it
            # here (a TpuSession already did at construction) — programs
            # resolve from serialized artifacts instead of re-tracing, and
            # the search publishes what it compiles for the next process
            from spark_sklearn_tpu.parallel import (
                programstore as _programstore)
            pstore = _programstore.activate_store(config)
            ps_before = _programstore.snapshot_counters(pstore)
            # successive-halving rung owner (search/halving.py, attached
            # through the launch-ownership protocol): when set, this
            # evaluate_candidates call is ONE RUNG of a multi-rung search —
            # the report registry, pipeline and counter baselines are
            # shared across rungs so the final search_report covers the
            # whole search, not the last rung
            rung = _ownership.current_owner(self, kind="rung")
            if rung is not None:
                if rung.ps_before is None:
                    rung.ps_before = ps_before
                ps_before = rung.ps_before
            dtype = dtype_override or config.dtype or np.float32
            scorers, _ = resolve_scoring(self.scoring, family)
            scorer_names = list(scorers)

            oracle_proba_dt = self._oracle_proba_dtype(family, X)
            # the pre-densified X (what sklearn estimators would see): the
            # supervisor's per-candidate host fallback fits on THIS, so a
            # bisection that bottoms out reproduces sklearn exactly
            X_host = X
            # data tier (search/stream.py): "device" is the legacy resident
            # path, "stream" folds sample shards through the pipeline,
            # "sparse" keeps a scipy CSR as a device BCOO end to end
            import scipy.sparse as _scipy_sparse

            from spark_sklearn_tpu.search import stream as _stream
            data_mode = _stream.resolve_data_mode(config)
            sparse_op = None
            if data_mode == "sparse" and _scipy_sparse.issparse(X):
                if not getattr(family, "supports_sparse", False):
                    raise NotCompiledError(
                        f"data_mode='sparse' requires a family with BCOO "
                        f"fit/predict programs; {family.name} has none.  "
                        "Use data_mode='device' (densified upload) or "
                        "backend='host'.")
                if config.n_data_shards > 1:
                    raise ValueError(
                        "data_mode='sparse' does not compose with "
                        "n_data_shards>1 (BCOO operands replicate only)")
                from spark_sklearn_tpu.sparse.csr import register_bcoo_export
                register_bcoo_export()
                X = X.tocsr()
                data, meta = family.prepare_data_sparse(X, y, dtype=dtype)
                sparse_op = data["X"]
            else:
                if data_mode == "stream":
                    _stream.check_stream_supported(family, self.scoring,
                                                   config)
                X = self._densify(X, dtype)
                data, meta = family.prepare_data(X, y, dtype=dtype)
            meta["logloss_clip_eps"] = float(np.finfo(oracle_proba_dt).eps)
            if self.scoring is not None:
                self._check_compiled_scoring(family, data, meta)
            n_samples = X.shape[0]
            train_masks, test_masks = fold_masks(
                splits, n_samples, dtype=dtype)
            # families whose validity depends on fold geometry (e.g. KNN's
            # n_neighbors <= smallest train fold) check this in
            # observe_candidates, so both backends raise on the same grids
            meta["min_fold_train_count"] = int(
                np.sum(train_masks > 0, axis=1).min())
            # ... and families that can serve each row's test scores from
            # ONE fold's fit (KNN's neighbour lists): every row is left out
            # of exactly one fold's training rows, as K-fold splits leave it
            meta["held_out_once"] = bool(
                np.all(np.sum(train_masks <= 0, axis=0) == 1))
            # ... and families whose arithmetic is exact on 0/1 masks (the
            # forests' integer histograms) that no sample_weight scales them
            meta["unit_fit_weights"] = fit_weight is None
            n_folds = len(splits)
            n_cand = len(candidates)
            return_train = self.return_train_score

            # sample_weight enters the compiled tier as mask multiplies: the
            # estimator's weights scale the FIT masks, the scorer's weights
            # scale the SCORING masks (sklearn routes the two independently —
            # a scorer that rejects sample_weight scores unweighted even when
            # the fit was weighted)
            fit_masks = train_masks
            if fit_weight is not None:
                fw = np.asarray(fit_weight, dtype=dtype)
                if fw.shape != (n_samples,):
                    raise ValueError(
                        f"sample_weight has shape {fw.shape}, expected "
                        f"({n_samples},)")
                fit_masks = train_masks * fw[None, :]
            if score_weight is not None:
                sw = np.asarray(score_weight, dtype=dtype)
                if sw.shape != (n_samples,):
                    raise ValueError(
                        f"scorer sample_weight has shape {sw.shape}, expected "
                        f"({n_samples},)")
                test_sc_masks = test_masks * sw[None, :]
                train_sc_masks = train_masks * sw[None, :]
            else:
                test_sc_masks = test_masks
                train_sc_masks = train_masks
            # scorers whose sklearn twin rejects sample_weight score unweighted
            # even in a weighted search (_MultimetricScorer forwards per
            # scorer)
            from spark_sklearn_tpu.search.scorers import (
                SAMPLE_WEIGHT_BLIND_FNS)
            sw_blind = frozenset(
                name for name, fn in scorers.items()
                if fn in SAMPLE_WEIGHT_BLIND_FNS)
            need_unweighted = score_weight is not None and bool(sw_blind)

            base_params = family.extract_params(self.estimator)
        # sklearn raises InvalidParameterError inside fit() for
        # out-of-range hyperparameters (LinearSVC C=0, negative alpha...);
        # the compiled solvers accept any finite value, so reproduce the
        # per-candidate failure host-side BEFORE launching: invalid
        # candidates are excluded from the compiled launch entirely (a
        # static value like degree='junk' would crash tracing) and get
        # error_score on every fold with ZERO fit/score times, exactly
        # like a raising est.fit (upstream test_search_cv_timing).
        # set_params stays outside the try: unknown param KEYS abort the
        # whole search, as in sklearn.
        with get_tracer().span("prevalidate", n_candidates=len(candidates)):
            preval_failed, preval_exc = \
                self._prevalidate_candidates(candidates)
        if preval_exc is not None and isinstance(self.error_score, str) \
                and self.error_score == "raise":
            # sklearn raises this exact exception
            raise preval_exc

        with get_tracer().span("fit.plan",
                               n_candidates=len(candidates)):
            launch_index = None
            launch_candidates = candidates
            if preval_failed.any():
                launch_index = np.flatnonzero(~preval_failed)
                launch_candidates = [candidates[i] for i in launch_index]
            if hasattr(family, "observe_candidates"):
                # e.g. tree families need the grid-wide max n_estimators to fix
                # the compiled program's static tree count (valid candidates
                # only — an invalid static value would crash the observation)
                family.observe_candidates(launch_candidates, base_params, meta)
            dyn_names = list(family.dynamic_params)
            groups = build_compile_groups(
                launch_candidates, dyn_names, family.dynamic_params,
                getattr(family, "dynamic_codes", None))
            if launch_index is not None:
                for g in groups:
                    g.candidate_indices = launch_index[
                        np.asarray(g.candidate_indices)]

            mesh = build_mesh(config)
            n_task_shards = mesh.shape[mesh_lib.TASK_AXIS]
            logger.info(
                "compiled search: family=%s, %d candidates x %d folds, "
                "%d compile group(s), mesh=%s", family.name, n_cand, n_folds,
                len(groups), dict(mesh.shape))
            repl = mesh_lib.replicated_sharding(mesh)
            task_shard = mesh_lib.task_sharding(mesh)

            # device data plane: a fingerprint-keyed, sharding-aware LRU of
            # device arrays shared by every search in the process — X/y and
            # the fold masks upload ONCE per content+placement and are
            # reused across chunks, compile groups, calibration and
            # subsequent searches (the persistent sc.broadcast).  Disabled
            # (dataplane_bytes=0) restores per-search device_put.
            from spark_sklearn_tpu.parallel import dataplane as _dataplane
            plane = _dataplane.plane_for(config)
            dp_before = _dataplane.snapshot_counters(plane)
            if rung is not None:
                if rung.dp_before is None:
                    rung.dp_before = dp_before
                dp_before = rung.dp_before
            # device-memory ledger (parallel/memledger.py): model each
            # launch's footprint from its abstract shapes, reconcile
            # against jax memory_stats at launch boundaries, cap planned
            # widths to the HBM budget and render search_report["memory"].
            # Disabled (memory_ledger=False) the report and cv_results_
            # stay byte-identical to the pre-ledger engine.
            from spark_sklearn_tpu.obs import memory as _obs_memory
            from spark_sklearn_tpu.parallel import memledger as _memledger
            ledger = _memledger.ledger_for(config)
            mem_before = _memledger.snapshot_counters(ledger)
            if ledger is not None and (rung is None or rung.itr == 0):
                mem_stats = ledger.sample(force=True)
                self._memory_ctx = {
                    "groups": [],
                    "resident_bytes": 0,
                    "budget_bytes": _obs_memory.resolve_hbm_budget(
                        config, mem_stats),
                    "device_limit_bytes": _obs_memory.
                    detect_device_memory_bytes(mem_stats),
                    "measured_baseline_bytes": max(
                        (r["bytes_in_use"] for r in mem_stats), default=0),
                }
            if rung is not None:
                if rung.mem_before is None:
                    rung.mem_before = mem_before
                mem_before = rung.mem_before
            # in-flight heartbeats (obs/heartbeat.py): allocate ONE hub
            # scope per fit (halving rungs share it) so the report block
            # aggregates exactly this search's segments — cid_ns is empty
            # for plain fits and cannot key the hub.  Off is an exact
            # no-op: no ctx, no block, no beacon traced.
            from spark_sklearn_tpu.obs import heartbeat as _heartbeat
            _hb_enabled = _heartbeat.resolve_heartbeat(config)
            if _hb_enabled and (rung is None or rung.itr == 0):
                self._hb_ctx = {"scope": _heartbeat.get_hub().new_scope()}
            hb_ctx = getattr(self, "_hb_ctx", None) if _hb_enabled else None
            # a search submitted through a session's SearchExecutor charges
            # its broadcast residents to its tenant's data-plane quota
            from spark_sklearn_tpu import serve as _serve
            _binding = _serve.current_binding()
            _tenant = _binding.tenant if _binding is not None else None

        def _bput(v, sharding, label):
            from spark_sklearn_tpu.sparse.csr import SparseOperand
            if isinstance(v, SparseOperand):
                # a sparse operand uploads as its two nnz-proportional
                # components (each content-fingerprinted and accounted
                # separately) and reassembles the device BCOO — upload
                # bytes and plane keys price nnz, never n x d
                return v.to_bcoo(
                    values=_bput(v.values, sharding, label + ".values"),
                    indices=_bput(v.indices, sharding,
                                  label + ".indices"))
            if plane is not None:
                return plane.put(v, sharding, label=label,
                                 tenant=_tenant)
            return _dataplane.upload(v, sharding, label=label)

        with get_tracer().span(
                "device_put.broadcast", n_samples=n_samples,
                n_data_shards=config.n_data_shards):
            if data_mode == "stream":
                # streaming tier: X/y and the masks stay host-side — each
                # sample shard crosses host->device on the pipeline's stage
                # thread inside run_stream, overlapped with the previous
                # shard's compute
                data_dev = {}
                fit_dev = test_dev = train_sc_dev = None
                test_unw_dev = train_unw_dev = None
            elif config.n_data_shards > 1:
                # large-X mode: shard samples over the "data" mesh axis instead
                # of replicating (the TPU-native answer to X not fitting one
                # chip's HBM) — sample-axis reductions inside the families
                # become XLA collectives over ICI automatically.  Sample counts
                # are padded to the shard count with zero-weight rows.
                from jax.sharding import NamedSharding, PartitionSpec as P
                nd = config.n_data_shards
                n_pad = mesh_lib.pad_to_multiple(n_samples, nd)
                if n_pad != n_samples:
                    pad = n_pad - n_samples
                    data = {k: np.concatenate(
                        [v, np.zeros((pad,) + v.shape[1:], v.dtype)])
                        for k, v in data.items()}

                    def _padm(m, pad=pad):
                        return np.concatenate(
                            [m, np.zeros((n_folds, pad), m.dtype)], axis=1)
                    train_sc_aliases_fit = train_sc_masks is fit_masks
                    fit_masks = _padm(fit_masks)
                    test_sc_masks = _padm(test_sc_masks)
                    train_sc_masks = (fit_masks if train_sc_aliases_fit
                                      else _padm(train_sc_masks))
                    if need_unweighted:
                        test_masks = _padm(test_masks)
                        train_masks = _padm(train_masks)
                sample_shard = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
                mask_shard = NamedSharding(mesh, P(None, mesh_lib.DATA_AXIS))
                data_dev = {k: _bput(v, sample_shard, f"data.{k}")
                            for k, v in data.items()}
                put_masks = mask_shard
            else:
                data_dev = {k: _bput(v, repl, f"data.{k}")
                            for k, v in data.items()}
                put_masks = repl
            # one device buffer per DISTINCT mask array: in the unweighted case
            # fit/train-scoring masks are the same object, so they share one
            # upload and one HBM allocation (the plane's content keys make
            # the dedup hold even across separately-built equal arrays).
            # A halving rung's subsampled masks carry a RUNG-SCOPED label
            # ("mask.r1.fit"): the next rung's barrier then demotes exactly
            # the previous rung's buffers — plane keys are shared by
            # content, so a bare "mask." sweep could un-charge a sibling
            # search's live masks under the same tenant
            mask_ns = (f"mask.{rung.ns}." if rung is not None
                       and rung.resource == "n_samples" else "mask.")
            if data_mode != "stream":
                fit_dev = _bput(fit_masks, put_masks, mask_ns + "fit")
                test_dev = _bput(test_sc_masks, put_masks, mask_ns + "test")
                train_sc_dev = (fit_dev if train_sc_masks is fit_masks
                                else _bput(train_sc_masks, put_masks,
                                           mask_ns + "train"))
                if need_unweighted:
                    test_unw_dev = _bput(test_masks, put_masks,
                                         mask_ns + "test_unw")
                    train_unw_dev = _bput(train_masks, put_masks,
                                          mask_ns + "train_unw")
                else:
                    test_unw_dev, train_unw_dev = test_dev, train_sc_dev

        test_scores = {s: np.empty((n_cand, n_folds)) for s in scorer_names}
        train_scores = ({s: np.empty((n_cand, n_folds))
                         for s in scorer_names} if return_train else None)
        fit_times = np.empty((n_cand, n_folds))
        score_times = np.empty((n_cand, n_folds))
        # per-(candidate, fold) fit-failure flags: a compiled fit that
        # diverges to NaN parameters is a failed fit and gets error_score,
        # exactly like a raising est.fit on the host path (SURVEY §5.3:
        # "error_score must be reimplemented explicitly")
        fit_failed = np.zeros((n_cand, n_folds), bool)
        fit_failed[preval_failed, :] = True

        ckpt = self._open_checkpoint(
            config, base_params=base_params, candidates=candidates,
            scorer_names=scorer_names, n_folds=n_folds,
            return_train=return_train, X=X, sparse_op=sparse_op, y=y,
            train_masks=train_masks, fit_weight=fit_weight,
            score_weight=score_weight, rung=rung,
            data_mode=data_mode) if config.checkpoint_dir else None

        profiler_cm = None
        if config.profile_dir:
            import jax.profiler as _prof
            profiler_cm = _prof.trace(config.profile_dir)
            profiler_cm.__enter__()
        debug_ctx = (jax.debug_nans(True) if config.debug_nans
                     else _nullcontext())
        metrics = self._open_search_registry(
            rung, len(groups), n_task_shards, config)

        self._open_protection(config, rung, _binding, preval_failed)

        # bound peak HBM: chunk each compile group so one launch holds at
        # most max_tasks_per_batch (candidate x fold) program instances;
        # every chunk of a group is padded to one uniform width so the
        # group's two jitted programs compile exactly once
        max_tasks = config.max_tasks_per_batch
        hint = getattr(family, "max_tasks_hint", None)
        if hint is not None:
            # families with big per-task workspaces (e.g. SVC kernel and
            # decision caches) bound their own launch width
            max_tasks = min(max_tasks, max(n_folds, hint(n_samples, meta)))
        max_cand_per_batch = max(
            n_task_shards,
            mesh_lib.pad_to_multiple(
                max(1, max_tasks // max(n_folds, 1)),
                n_task_shards))

        host_eval = functools.partial(
            self._host_eval, X_host, y, candidates, splits, scorer_names,
            return_train, fit_weight, score_weight, [])

        if ledger is not None:
            # launch-boundary sampling (pipeline._record) is live only
            # while a ledger-enabled search runs — refcounted so
            # concurrent searches compose and memory_ledger=False
            # stays an exact no-op
            ledger.activate()
        try:
            with debug_ctx:
                if data_mode == "stream":
                    _stream.run_stream(
                        self, groups=groups, base_params=base_params,
                        family=family, meta=meta,
                        scorer_names=scorer_names, data=data,
                        fit_masks=fit_masks,
                        test_sc_masks=test_sc_masks,
                        train_sc_masks=train_sc_masks, repl=repl,
                        config=config, n_task_shards=n_task_shards,
                        max_cand_per_batch=max_cand_per_batch,
                        n_folds=n_folds, dtype=dtype,
                        return_train=return_train,
                        test_scores=test_scores,
                        train_scores=train_scores, fit_times=fit_times,
                        score_times=score_times, ckpt=ckpt,
                        fit_failed=fit_failed, candidates=candidates)
                else:
                    # content fp of host X for the shared-prefix derived
                    # cache key — only worth hashing when the family can
                    # actually stage prefixes (compiled Pipeline with
                    # transformer steps, dense host X)
                    data_fp = None
                    if (hasattr(family, "prefix_digest")
                            and getattr(family, "steps", None)
                            and isinstance(data.get("X"), np.ndarray)):
                        data_fp = _dataplane.fingerprint(data["X"])
                    self._run_groups(
                        groups=groups, base_params=base_params,
                        family=family,
                        meta=meta, scorers=scorers,
                        scorer_names=scorer_names,
                        data_dev=data_dev, fit_dev=fit_dev,
                        test_dev=test_dev, train_sc_dev=train_sc_dev,
                        test_unw_dev=test_unw_dev,
                        train_unw_dev=train_unw_dev,
                        sw_blind=sw_blind,
                        fit_masks=fit_masks, mesh=mesh,
                        config=config, n_task_shards=n_task_shards,
                        task_shard=task_shard,
                        max_cand_per_batch=max_cand_per_batch,
                        n_folds=n_folds,
                        dtype=dtype, return_train=return_train,
                        test_scores=test_scores,
                        train_scores=train_scores,
                        fit_times=fit_times, score_times=score_times,
                        ckpt=ckpt,
                        fit_failed=fit_failed, candidates=candidates,
                        host_eval=host_eval, data_fp=data_fp)
        finally:
            if profiler_cm is not None:
                profiler_cm.__exit__(None, None, None)
            self._put_search_blocks(
                metrics, config, family, plane=plane, dp_before=dp_before,
                pstore=pstore, ps_before=ps_before, ledger=ledger,
                mem_before=mem_before, hb_ctx=hb_ctx)
        self._account_failed_fits(
            groups, candidates, preval_failed, fit_failed, scorer_names,
            test_scores, train_scores, fit_times, score_times, return_train)
        # scorer_ keeps the sklearn-facing objects so .score() works the
        # sklearn way even though CV scoring ran compiled
        if self.scoring is None or isinstance(self.scoring, str):
            scorer_attr = check_scoring(self.estimator, self.scoring)
        else:
            from sklearn.metrics._scorer import _check_multimetric_scoring
            scorer_attr = _check_multimetric_scoring(
                self.estimator, self.scoring)
        return (test_scores, train_scores, fit_times, score_times,
                scorer_names, scorer_attr)

    @staticmethod
    def _oracle_proba_dtype(family, X):
        """The dtype of the probabilities sklearn's twin of `family`
        produces on `X`, where log_loss clips them."""
        # sklearn's log_loss clips probas at THEIR dtype's machine eps
        # (_classification.py log_loss), and the sklearn twin's proba
        # dtype is a per-family fact: on this sklearn nearly every
        # classifier (libsvm, forests, KNN, LogReg, the NB family)
        # produces f64 probas regardless of X dtype; only MLP and LDA
        # preserve the user's X dtype (proba_dtype_rule="input") — the
        # compiled scorer must clip where the oracle clips, not where
        # the engine's compute dtype lands (see scorers.py
        # _neg_log_loss)
        proba_rule = getattr(family, "proba_dtype_rule", "float64")
        # the dtype that matters is the one sklearn's own validation
        # would hand the estimator: float32 stays float32, EVERYTHING
        # else (float64, ints, lists, frames — check_array's numeric
        # rule) becomes float64.  Resolve it after coercion: sparse
        # matrices and ndarrays expose .dtype directly; other inputs
        # (lists, DataFrames) go through np.asarray like sklearn's
        # check_array would
        x_dt = getattr(X, "dtype", None)
        if not isinstance(x_dt, np.dtype):
            # dtype-less inputs resolve WITHOUT copying the dataset:
            # DataFrames promote their column dtypes; lists/tuples
            # resolve from their first row (a float32-ndarray row list
            # stays float32 under np.asarray, everything else becomes
            # float64 under check_array's numeric rule)
            col_dtypes = getattr(X, "dtypes", None)
            if col_dtypes is not None and len(col_dtypes):
                x_dt = np.result_type(*col_dtypes)
            elif isinstance(X, (list, tuple)) and len(X) \
                    and isinstance(X[0], np.ndarray):
                x_dt = X[0].dtype
            elif isinstance(X, (list, tuple)):
                x_dt = np.dtype(np.float64)
            else:
                x_dt = np.asarray(X).dtype
        return np.float64 if (
            proba_rule == "float64" or x_dt != np.float32) else np.float32

    def _check_compiled_scoring(self, family, data, meta):
        """Refuse a `scoring` the compiled tier cannot score as sklearn
        would: labels that never reached the device, a classification
        metric without classes, a binary-only metric on multiclass."""
        if "y" not in data:
            raise ValueError(
                f"scoring={self.scoring!r} needs labels, but none "
                f"reached the device ({family.name} is unsupervised: "
                "y was absent or not numerically encodable; only its "
                "default scorer applies)")
        from spark_sklearn_tpu.search.scorers import (
            compiled_name_for_scorer)

        def _canon(s):
            return s if isinstance(s, str) \
                else compiled_name_for_scorer(s)
        if isinstance(self.scoring, str):
            wanted = [self.scoring]
        elif isinstance(self.scoring, dict):
            # dict values name the metrics; keys are display labels
            wanted = [_canon(s) for s in self.scoring.values()]
        elif isinstance(self.scoring, (list, tuple, set)):
            wanted = [_canon(s) for s in self.scoring]
        else:
            wanted = [_canon(self.scoring)]
        wanted = [s for s in wanted if s is not None]
        if any(s in CLASSIFICATION_SCORERS for s in wanted) and \
                "n_classes" not in meta:
            raise ValueError(
                f"scoring={self.scoring!r} requires a classifier "
                f"family; {family.name} has no class structure")
        if any(s in BINARY_ONLY_SCORERS for s in wanted) and \
                meta.get("n_classes", 2) > 2:
            # sklearn's semantics for these on multiclass (averaging
            # options, undefined-metric warnings) live on the host path
            raise NotCompiledError(
                f"scoring={self.scoring!r} on multiclass targets is "
                "not compiled; use backend='host'")

    def _open_checkpoint(self, config, *, base_params, candidates,
                         scorer_names, n_folds, return_train, X, sparse_op,
                         y, train_masks, fit_weight, score_weight, rung,
                         data_mode):
        """The search's chunk journal, keyed by everything its cells
        depend on: a resumed search only reuses chunks computed under a
        result-identical configuration."""
        from spark_sklearn_tpu.utils.checkpoint import (
            SearchCheckpoint, fingerprint)
        if sparse_op is not None:
            # CSR content enters by its canonical components — a
            # sparse head-slice repr() carries no values, and any
            # dense staging here would defeat the whole tier
            _x_head = sparse_op.values[:4096]
            _x_moments = (
                *sparse_op.signature(),
                float(np.sum(sparse_op.values, dtype=np.float64)),
                float(np.sum(np.square(sparse_op.values,
                                       dtype=np.float64))),
                float(np.sum(sparse_op.indices, dtype=np.float64)))
        else:
            _x_head = X[: min(64, X.shape[0])]
            # whole-dataset moments so ANY changed X row or label
            # set breaks the fingerprint (head rows can collide)
            _x_moments = (
                X.shape, float(np.sum(X, dtype=np.float64)),
                float(np.sum(np.square(X, dtype=np.float64))))
        key = fingerprint(
            type(self.estimator).__name__, base_params, candidates,
            scorer_names, n_folds, return_train,
            # result-affecting config: resuming under a different matmul
            # precision or dtype must not reuse the other run's scores
            (bool(config.bf16_matmul), str(config.dtype)),
            _x_head,
            _x_moments,
            self._hashable_labels(y),
            np.asarray(train_masks),
            # weighted searches must not resume an unweighted run's
            # chunks (and vice versa); arrays go in as bare top-level
            # parts so fingerprint() hashes their bytes (tuples would
            # be repr()'d, which numpy truncates past 1000 elements)
            "fitw",
            np.asarray(fit_weight, np.float64)
            if fit_weight is not None else "none",
            "scw",
            np.asarray(score_weight, np.float64)
            if score_weight is not None else "none",
            # halving rungs are distinct resumable units: the rung
            # index (and its resource) joins the fingerprint even
            # though the candidate set / masks already differ, so
            # two rungs can never alias one journal file
            *(("halving", rung.itr, rung.n_resources)
              if rung is not None else ()),
            # a streamed run's journal holds per-shard accumulator
            # records addressed by the stream geometry — never let a
            # device-mode resume read (or extend) it
            *(("stream",) if data_mode == "stream" else ()))
        _keycheck.note(
            "checkpoint", key,
            fields={"bf16_matmul": bool(config.bf16_matmul),
                    "dtype": str(config.dtype)},
            detail=type(self.estimator).__name__)
        return SearchCheckpoint(config.checkpoint_dir, key)

    def _open_search_registry(self, rung, n_groups, n_task_shards, config):
        """`search_report` as the rendered view of a typed registry whose
        schema lives in obs.metrics.SEARCH_REPORT_SCHEMA (keys
        materialize here in the legacy order).  A halving search's rungs
        share ONE registry: counters (n_launches, walls,
        n_chunks_resumed) accumulate across rungs and the struct blocks
        render the whole search's deltas."""
        if rung is not None and rung.registry is not None:
            metrics = rung.registry
        else:
            metrics = search_registry("tpu")
            if rung is not None:
                rung.registry = metrics
        ncg = metrics.gauge("n_compile_groups")
        if rung is not None:
            # like the counters: the whole search's group total, not
            # the last rung's
            ncg.set(int(ncg.value) + n_groups)
        else:
            ncg.set(n_groups)
        metrics.counter("n_launches")
        metrics.counter("n_chunks_resumed")
        metrics.gauge("fit_wall_s")
        metrics.gauge("score_wall_s")
        metrics.struct("mesh").update(
            {"task": n_task_shards, "data": config.n_data_shards})
        self._search_metrics = metrics
        self._search_report = metrics.data
        return metrics

    def _open_protection(self, config, rung, binding, preval_failed):
        """The self-protection context (deadline shed, quarantine,
        partial-results degradation — parallel/faults.py
        protection_block).  Search-scoped: one ctx spans every halving
        rung, so the deadline covers the WHOLE search; the done mask is
        per-call (each rung owns fresh result arrays).  Protection off:
        None, and every guarded path is untouched."""
        if _faults.protection_enabled(config):
            pctx = getattr(self, "_protection_ctx", None)
            if pctx is None or rung is None or rung.itr == 0:
                t_dl = None
                if getattr(config, "search_deadline_s", None):
                    # the executor stamps the deadline at SUBMIT (queue
                    # wait spends the budget); a sessionless fit starts
                    # the clock here
                    hd = getattr(getattr(binding, "handle", None),
                                 "t_deadline", None)
                    t_dl = hd if hd is not None else (
                        time.perf_counter()
                        + float(config.search_deadline_s))
                pctx = self._protection_ctx = {
                    "t_start": time.perf_counter(),
                    "t_deadline": t_dl,
                    "deadline_hit": False,
                    "shed": [],
                    "quarantined": [],
                }
            # candidates with written cells: prevalidation failures
            # already carry error_score, so degradation never
            # overwrites them
            pctx["done"] = preval_failed.copy()
        else:
            self._protection_ctx = None

    def _host_eval(self, X_host, y, candidates, splits, scorer_names,
                   return_train, fit_weight, score_weight, scorer_cache,
                   cand_indices):
        """Per-candidate host execution for the supervisor's OOM
        bottom-out: real `clone(est).set_params(**p)` fits via
        sklearn `_fit_and_score` — exact sklearn error_score
        semantics — returning (test, train) score dicts shaped
        (len(cand_indices), n_folds) under the compiled scorer
        names; `scorer_cache` keeps the host scorer once built."""
        n_folds = len(splits)
        from sklearn.metrics import check_scoring
        from sklearn.model_selection._validation import (
            _fit_and_score, _warn_or_raise_about_fit_failures)

        if not scorer_cache:
            if self.scoring is None or isinstance(self.scoring, str) \
                    or callable(self.scoring):
                scorer_cache.append(
                    check_scoring(self.estimator, self.scoring))
            else:
                from sklearn.metrics._scorer import (
                    _MultimetricScorer, _check_multimetric_scoring)
                sc = _check_multimetric_scoring(
                    self.estimator, self.scoring)
                if set(sc) != set(scorer_names):
                    # compiled names must address the same cells the
                    # host scorer produces; a mismatch cannot be
                    # recovered into cv_results_
                    raise RuntimeError(
                        "host fallback scorer names "
                        f"{sorted(sc)} do not match compiled names "
                        f"{sorted(scorer_names)}")
                scorer_cache.append(_MultimetricScorer(
                    scorers=sc,
                    raise_exc=(self.error_score == "raise")))
        scorer = scorer_cache[0]
        host_fit_params = ({"sample_weight": fit_weight}
                           if fit_weight is not None else None)
        host_score_params = ({"sample_weight": score_weight}
                             if score_weight is not None else None)
        results = []
        for ci in cand_indices:
            for tr_idx, te_idx in splits:
                results.append(_fit_and_score(
                    clone(self.estimator), X_host, y, scorer=scorer,
                    train=tr_idx, test=te_idx, verbose=0,
                    parameters=candidates[int(ci)],
                    fit_params=host_fit_params,
                    score_params=host_score_params,
                    return_train_score=return_train,
                    return_times=True,
                    error_score=self.error_score))
        _warn_or_raise_about_fit_failures(results, self.error_score)
        n = len(cand_indices)
        te = {s: np.empty((n, n_folds)) for s in scorer_names}
        tr = ({s: np.empty((n, n_folds)) for s in scorer_names}
              if return_train else {})
        for t, res in enumerate(results):
            i, f = divmod(t, n_folds)
            ts = res["test_scores"]
            if not isinstance(ts, dict):
                ts = {s: ts for s in scorer_names}
            for s in scorer_names:
                te[s][i, f] = ts.get(s, np.nan)
            if return_train:
                trs = res.get("train_scores", {})
                if not isinstance(trs, dict):
                    trs = {s: trs for s in scorer_names}
                for s in scorer_names:
                    tr[s][i, f] = trs.get(s, np.nan)
        return te, tr

    def _put_search_blocks(self, metrics, config, family, *, plane,
                           dp_before, pstore, ps_before, ledger, mem_before,
                           hb_ctx):
        """The search's data-plane, program-store, memory, heartbeat and
        protection blocks of `search_report`."""
        from spark_sklearn_tpu.obs import heartbeat as _heartbeat
        from spark_sklearn_tpu.parallel import dataplane as _dataplane
        from spark_sklearn_tpu.parallel import memledger as _memledger
        from spark_sklearn_tpu.parallel import (
            programstore as _programstore)
        # this search's broadcast-cache traffic (hits = arrays
        # reused with zero transfer; bytes_uploaded = cacheable
        # bytes actually shipped; bytes_staged = per-chunk dyn
        # params) — schema in obs.metrics.DATAPLANE_BLOCK_SCHEMA
        mask_tiling = ("n/a" if not hasattr(family, "fit_task_batched")
                       else "device" if plane is not None else "host")
        metrics.put("dataplane", _dataplane.report_block(
            plane, dp_before, mask_tiling=mask_tiling))
        # this search's AOT-store traffic (hits = programs served
        # from serialized artifacts with zero tracing; publishes =
        # artifacts written for the next cold process) — schema in
        # obs.metrics.PROGRAMSTORE_BLOCK_SCHEMA
        metrics.put("programstore", _programstore.report_block(
            pstore, ps_before))
        # this search's device-memory view (modeled per-group
        # footprints, budget/ceiling state, measured watermark) —
        # schema in obs.metrics.MEMORY_BLOCK_SCHEMA.  Rendered
        # ONLY when the ledger is on: off, the report shape is
        # byte-identical to the pre-ledger engine.
        if ledger is not None:
            ledger.deactivate()
            metrics.put("memory", _memledger.report_block(
                ledger, mem_before,
                getattr(self, "_memory_ctx", {}) or {}))
        # this search's in-flight heartbeat view (beats/steps,
        # cadence percentiles, staleness, overhead estimate) —
        # schema in obs.metrics.HEARTBEAT_BLOCK_SCHEMA.  Rendered
        # ONLY when heartbeat is on: off, the report shape is
        # byte-identical to the beacon-less engine.
        if hb_ctx is not None:
            metrics.put("heartbeat", _heartbeat.heartbeat_block(
                hb_ctx["scope"]))
        # the search's protection verdict (deadline/shed/quarantine
        # state) — schema in obs.metrics.PROTECTION_BLOCK_SCHEMA.
        # Rendered ONLY when protection is on: off, the report is
        # byte-identical to the unprotected engine.  A halving
        # search re-puts each rung; the shared ctx accumulates, so
        # the last put covers the whole search.
        pctx_fin = getattr(self, "_protection_ctx", None)
        if pctx_fin is not None:
            metrics.put("protection", _faults.protection_block(
                config, deadline_hit=pctx_fin["deadline_hit"],
                shed=pctx_fin["shed"],
                quarantined=pctx_fin["quarantined"],
                elapsed_s=time.perf_counter()
                - pctx_fin["t_start"]))

    def _account_failed_fits(self, groups, candidates, preval_failed,
                             fit_failed, scorer_names, test_scores,
                             train_scores, fit_times, score_times,
                             return_train):
        """Failed fits' cells, times and warnings, sklearn's way."""
        n_folds = fit_failed.shape[1]
        if preval_failed.any():
            # failed fits never ran: sklearn records 0.0 for their times
            fit_times[preval_failed, :] = 0.0
            score_times[preval_failed, :] = 0.0
            if self.verbose > 1:
                # excluded from every launch -> their END lines (showing
                # error_score, like sklearn's failed fits) print here
                self._print_task_end_lines(
                    candidates, np.flatnonzero(preval_failed), n_folds,
                    scorer_names, test_scores, train_scores, return_train,
                    0.0, fit_failed)

        # failed-fit accounting, sklearn error_score semantics
        # (_warn_or_raise_about_fit_failures): two detectors feed it —
        #   1. NaN hyperparameters (sklearn raises at validation; our
        #      solvers won't blow up, so the chance-level score they
        #      produce must not masquerade as a result).  inf stays legal —
        #      sklearn itself uses C=np.inf for "no penalty".
        #   2. per-(candidate, fold) NaN model parameters detected on
        #      device after each launch (_run_groups): a diverging MLP or
        #      an ill-conditioned solve is a failed fit, not a result.
        # Genuinely non-finite SCORES from finite models pass through,
        # like sklearn's (_format_results warns about those separately).
        for group in groups:
            for arr in group.dynamic_params.values():
                if np.issubdtype(arr.dtype, np.floating):
                    fit_failed[group.candidate_indices[
                        np.isnan(arr)], :] = True
        if fit_failed.any():
            n_bad = int(fit_failed.sum())
            if isinstance(self.error_score, str) and \
                    self.error_score == "raise":
                raise ValueError(
                    f"{n_bad} fits failed with non-finite parameters and "
                    "error_score='raise'")
            if fit_failed.all():
                # sklearn's _warn_or_raise_about_fit_failures raises when
                # EVERY fit failed, even with a numeric error_score (the
                # host tier inherits this from sklearn directly)
                raise ValueError(
                    f"\nAll the {fit_failed.size} fits failed.\n"
                    "It is very likely that your model is misconfigured.\n"
                    "You can try to debug the error by setting "
                    "error_score='raise'.")
            from sklearn.exceptions import FitFailedWarning
            warnings.warn(
                f"\n{n_bad} fits failed out of a total of "
                f"{fit_failed.size}.\nThe score on these train-test "
                "partitions for these parameters will be set to "
                f"{self.error_score}. (cause: non-finite model "
                "parameters or hyperparameters)", FitFailedWarning)
            for s in scorer_names:
                test_scores[s][fit_failed] = self.error_score
                if return_train:
                    train_scores[s][fit_failed] = self.error_score

    def _run_groups(self, *, groups, base_params, family, meta, scorers,
                    scorer_names, data_dev, fit_dev, test_dev, train_sc_dev,
                    test_unw_dev, train_unw_dev, sw_blind,
                    fit_masks, mesh, config, n_task_shards, task_shard,
                    max_cand_per_batch, n_folds, dtype, return_train,
                    test_scores, train_scores, fit_times, score_times, ckpt,
                    fit_failed, candidates, host_eval=None, data_fp=None):
        """Run the search's compile groups as one launch schedule
        (search/schedule.py): plan them, run every live chunk's launches
        through the pipelined chunk executor, and let go of the
        schedule's device buffers however the run ends."""
        from spark_sklearn_tpu.search.schedule import LaunchSchedule
        schedule = LaunchSchedule(
            self, groups=groups, base_params=base_params, family=family,
            meta=meta, scorers=scorers, scorer_names=scorer_names,
            data_dev=data_dev, fit_dev=fit_dev, test_dev=test_dev,
            train_sc_dev=train_sc_dev, test_unw_dev=test_unw_dev,
            train_unw_dev=train_unw_dev, sw_blind=sw_blind,
            fit_masks=fit_masks, mesh=mesh, config=config,
            n_task_shards=n_task_shards, task_shard=task_shard,
            max_cand_per_batch=max_cand_per_batch, n_folds=n_folds,
            dtype=dtype, return_train=return_train,
            test_scores=test_scores, train_scores=train_scores,
            fit_times=fit_times, score_times=score_times, ckpt=ckpt,
            fit_failed=fit_failed, candidates=candidates,
            host_eval=host_eval, data_fp=data_fp)
        try:
            schedule.run()
        finally:
            schedule.close()

    def _print_task_end_lines(self, candidates, idx, n_folds, scorer_names,
                              test_scores, train_scores, return_train,
                              t_task, fit_failed):
        """sklearn's `_fit_and_score` verbose>1 "[CV i/n] END ..." lines,
        emitted post-launch (compiled tasks execute fused, so per-task
        lines appear when their launch completes — same completion-report
        contract as the callback hooks).  Format mirrors the installed
        sklearn/model_selection/_validation.py:892-915.  Cells already
        known to be failed fits print error_score (sklearn prints the
        substituted score, never the garbage the lane computed)."""
        from joblib.logger import short_format_time

        err = self.error_score if not isinstance(self.error_score, str) \
            else np.nan

        def cell(scores, gidx, f):
            return err if fit_failed[gidx, f] else scores[gidx, f]

        for gidx in idx:
            params = candidates[gidx]
            params_msg = ", ".join(
                f"{k}={params[k]}" for k in sorted(params))
            for f in range(n_folds):
                progress_msg = (f" {f + 1}/{n_folds}"
                                if self.verbose > 2 else "")
                result_msg = params_msg + (";" if params_msg else "")
                # scores appear at verbose > 2 only — sklearn's exact
                # gating (_fit_and_score: `if verbose > 2:`)
                if self.verbose > 2 and len(scorer_names) > 1:
                    for s in sorted(scorer_names):
                        result_msg += f" {s}: ("
                        if return_train:
                            result_msg += ("train="
                                           f"{cell(train_scores[s], gidx, f):.3f}, ")
                        result_msg += f"test={cell(test_scores[s], gidx, f):.3f})"
                elif self.verbose > 2:
                    s = scorer_names[0]
                    result_msg += ", score="
                    if return_train:
                        result_msg += (
                            f"(train={cell(train_scores[s], gidx, f):.3f}, "
                            f"test={cell(test_scores[s], gidx, f):.3f})")
                    else:
                        result_msg += f"{cell(test_scores[s], gidx, f):.3f}"
                result_msg += f" total time={short_format_time(t_task)}"
                end_msg = f"[CV{progress_msg}] END "
                end_msg += "." * max(0, 80 - len(end_msg) - len(result_msg))
                end_msg += result_msg
                # stdout-parity channel: byte-for-byte sklearn's
                # _fit_and_score END line (pinned by test_obs.py)
                logger.print(end_msg, candidate=int(gidx), fold=f)

    # ------------------------------------------------------------------
    # Tier B: host fallback (full sklearn generality)
    # ------------------------------------------------------------------
    def _fit_host(self, X, y, candidates, splits, fit_params,
                  score_params=None, eval_ctxs=None, fallback_exc=None):
        from joblib import Parallel, delayed
        from sklearn.metrics import check_scoring
        from sklearn.metrics._scorer import _check_multimetric_scoring
        from sklearn.model_selection._validation import _fit_and_score

        estimator = self.estimator
        if callable(self.scoring):
            # a callable may return a scalar (single metric) or a dict
            # (multimetric, sklearn contract) — discovered from results
            scorer_attr: Any = self.scoring
            scorer_for_fs: Any = self.scoring
            scorer_names = None
        elif self.scoring is None or isinstance(self.scoring, str):
            scorer_obj = check_scoring(estimator, self.scoring)
            scorer_attr = scorer_obj
            scorer_for_fs = scorer_obj
            scorer_names = ["score"]
        else:
            from sklearn.metrics._scorer import _MultimetricScorer
            scorers = _check_multimetric_scoring(estimator, self.scoring)
            scorer_attr = dict(scorers)
            scorer_for_fs = _MultimetricScorer(
                scorers=scorers,
                raise_exc=(self.error_score == "raise"))
            scorer_names = list(scorers)

        n_folds = len(splits)
        tasks = [
            (ci, fi, params, train, test)
            for ci, params in enumerate(candidates)
            for fi, (train, test) in enumerate(splits)
        ]
        metrics = search_registry("host")
        metrics.gauge("n_tasks").set(len(tasks))
        metrics.gauge("n_jobs").set(
            self.n_jobs if self.n_jobs is not None else 1)
        faults = metrics.struct("faults")
        if fallback_exc is not None:
            # the caught exception type that pushed the compiled tier to
            # fall back here (the compiled registry — and its faults
            # journal — was replaced by this host one)
            faults["fallback_exception"] = (
                f"{type(fallback_exc).__name__}: "
                f"{fallback_exc}"[:200])
        self._search_metrics = metrics
        self._search_report = metrics.data

        from inspect import signature as _sig
        _fs_params = _sig(_fit_and_score).parameters

        def run(params, train, test, callback_ctx):
            # caller/callback_ctx exist only on the sklearn callback
            # branch; stock releases reject unknown kwargs
            extra = {}
            if "caller" in _fs_params:
                extra["caller"] = self
            if "callback_ctx" in _fs_params:
                extra["callback_ctx"] = callback_ctx
            return _fit_and_score(
                clone(estimator), X, y, scorer=scorer_for_fs,
                train=train, test=test, verbose=self.verbose,
                parameters=params, fit_params=fit_params or None,
                score_params=score_params or None,
                return_train_score=self.return_train_score,
                return_times=True, error_score=self.error_score,
                **extra)

        ctxs = eval_ctxs if eval_ctxs is not None else [None] * len(tasks)
        n_jobs = self.n_jobs if self.n_jobs is not None else 1
        with get_tracer().span("host.fit_and_score", n_tasks=len(tasks),
                               n_jobs=n_jobs):
            results = Parallel(n_jobs=n_jobs)(
                delayed(run)(params, train, test, ctx)
                for (_, _, params, train, test), ctx in zip(tasks, ctxs))

        # sklearn's own failure accounting: FitFailedWarning with the
        # "n fits failed out of a total of m" format, ValueError when all
        # fits failed (_search.py:1107 _warn_or_raise_about_fit_failures)
        from sklearn.model_selection._validation import (
            _warn_or_raise_about_fit_failures)
        _warn_or_raise_about_fit_failures(results, self.error_score)

        if scorer_names is None:
            # callable scoring: multimetric iff it returned a dict
            scorer_names = ["score"]
            for res in results:
                if isinstance(res["test_scores"], dict):
                    scorer_names = list(res["test_scores"])
                    break

        n_cand = len(candidates)
        test_scores = {s: np.empty((n_cand, n_folds)) for s in scorer_names}
        train_scores = ({s: np.empty((n_cand, n_folds))
                        for s in scorer_names}
                        if self.return_train_score else None)
        fit_times = np.empty((n_cand, n_folds))
        score_times = np.empty((n_cand, n_folds))
        for (ci, fi, _, _, _), res in zip(tasks, results):
            ts = res["test_scores"]
            if not isinstance(ts, dict):
                # scalar: single metric, or error_score from a failed
                # multimetric fit — applies to every metric
                ts = {s: ts for s in scorer_names}
            for s in scorer_names:
                test_scores[s][ci, fi] = ts.get(s, np.nan)
            if self.return_train_score:
                trs = res.get("train_scores", {})
                if not isinstance(trs, dict):
                    trs = {s: trs for s in scorer_names}
                for s in scorer_names:
                    train_scores[s][ci, fi] = trs.get(s, np.nan)
            fit_times[ci, fi] = res["fit_time"]
            score_times[ci, fi] = res["score_time"]
        return (test_scores, train_scores, fit_times, score_times,
                scorer_names, scorer_attr)

    # ------------------------------------------------------------------
    # cv_results_ assembly — sklearn _format_results schema
    # (_search.py:1208-1290)
    # ------------------------------------------------------------------
    def _format_results(self, candidates, test_scores, train_scores,
                        fit_times, score_times, scorer_names,
                        more_results=None):
        from scipy.stats import rankdata

        n_candidates = len(candidates)
        # extra columns from a halving-style _run_search come first,
        # as arrays — sklearn's exact layout (_format_results:
        # `results = dict(more_results or {})`, then np.asarray each)
        results: Dict[str, Any] = {
            k: np.asarray(v) for k, v in (more_results or {}).items()}

        def _store(key_name, array, weights=None, splits=False, rank=False):
            array = np.asarray(array, dtype=np.float64).reshape(
                n_candidates, -1)
            if splits:
                for i in range(array.shape[1]):
                    results[f"split{i}_{key_name}"] = array[:, i]
            array_means = np.average(array, axis=1, weights=weights)
            results[f"mean_{key_name}"] = array_means
            if key_name.startswith(("train_", "test_")) and np.any(
                    ~np.isfinite(array_means)):
                # sklearn's exact wording (_search.py:1237)
                warnings.warn(
                    f"One or more of the {key_name.split('_')[0]} scores "
                    f"are non-finite: {array_means}",
                    category=UserWarning)
            array_stds = np.sqrt(np.average(
                (array - array_means[:, None]) ** 2, axis=1,
                weights=weights))
            results[f"std_{key_name}"] = array_stds
            if rank:
                if np.isnan(array_means).any():
                    rank_arr = rankdata(
                        np.where(np.isnan(array_means), np.inf,
                                 -array_means), method="min")
                else:
                    rank_arr = rankdata(-array_means, method="min")
                results[f"rank_{key_name}"] = rank_arr.astype(np.int32)

        _store("fit_time", fit_times)
        _store("score_time", score_times)

        # masked param arrays, sklearn's exact dtype rule
        # (_search.py _yield_masked_array_for_each_param): dtype inferred
        # from the PRESENT values; strings and nested sequences stay object
        param_results: Dict[str, Dict[int, Any]] = defaultdict(dict)
        for cand_idx, params in enumerate(candidates):
            for name, value in params.items():
                param_results[f"param_{name}"][cand_idx] = value
        for key, param_result in param_results.items():
            param_list = list(param_result.values())
            try:
                arr = np.array(param_list)
            except ValueError:
                arr_dtype = np.dtype(object)
            else:
                arr_dtype = (arr.dtype if arr.dtype.kind != "U"
                             and arr.ndim == 1 else object)
            ma = np.ma.MaskedArray(np.empty(n_candidates, dtype=arr_dtype),
                                   mask=True)
            for index, value in param_result.items():
                ma[index] = value
            results[key] = ma
        results["params"] = list(candidates)

        for s in scorer_names:
            _store(f"test_{s}", test_scores[s], splits=True, rank=True)
            if self.return_train_score:
                _store(f"train_{s}", train_scores[s], splits=True)
        return results

    # -- prediction delegation (sklearn parity: available_if makes these
    # methods conditional, so hasattr() reflects the wrapped estimator and
    # refit state exactly like sklearn's BaseSearchCV) ------------------

    @available_if(_search_estimator_has("score_samples"))
    def score_samples(self, X):
        check_is_fitted(self)
        return self.best_estimator_.score_samples(X)

    @available_if(_search_estimator_has("predict"))
    def predict(self, X):
        check_is_fitted(self)
        return self.best_estimator_.predict(X)

    @available_if(_search_estimator_has("predict_proba"))
    def predict_proba(self, X):
        check_is_fitted(self)
        return self.best_estimator_.predict_proba(X)

    @available_if(_search_estimator_has("predict_log_proba"))
    def predict_log_proba(self, X):
        check_is_fitted(self)
        return self.best_estimator_.predict_log_proba(X)

    @available_if(_search_estimator_has("decision_function"))
    def decision_function(self, X):
        check_is_fitted(self)
        return self.best_estimator_.decision_function(X)

    @available_if(_search_estimator_has("transform"))
    def transform(self, X):
        check_is_fitted(self)
        return self.best_estimator_.transform(X)

    @available_if(_search_estimator_has("inverse_transform"))
    def inverse_transform(self, X):
        check_is_fitted(self)
        return self.best_estimator_.inverse_transform(X)

    def _sk_visual_block_(self):
        # sklearn's diagram repr (_search.py _sk_visual_block_): fitted
        # searches display the refit best_estimator_, unfitted ones the
        # wrapped estimator
        from sklearn.utils._repr_html.estimator import _VisualBlock
        if hasattr(self, "best_estimator_"):
            key, estimator = "best_estimator_", self.best_estimator_
        else:
            key, estimator = "estimator", self.estimator
        return _VisualBlock(
            "parallel", [estimator],
            names=[f"{key}: {estimator.__class__.__name__}"],
            name_details=[str(estimator)])

    def __sklearn_tags__(self):
        # full tag delegation to the wrapped estimator, like sklearn's
        # BaseSearchCV (_search.py:490): estimator_type makes
        # is_classifier(search) follow the inner estimator, pairwise lets
        # cv see precomputed metrics
        tags = super().__sklearn_tags__()
        try:
            from copy import deepcopy

            from sklearn.utils import get_tags
            sub = get_tags(self.estimator)
            tags.estimator_type = sub.estimator_type
            tags.classifier_tags = deepcopy(sub.classifier_tags)
            tags.regressor_tags = deepcopy(sub.regressor_tags)
            tags.input_tags.pairwise = sub.input_tags.pairwise
            tags.input_tags.sparse = sub.input_tags.sparse
            tags.array_api_support = sub.array_api_support
        # sstlint: disable=swallowed-exception — sklearn-version compat
        # shim: tag surfaces moved repeatedly across 1.x; missing
        # attributes simply leave the default tags in place
        except Exception:
            pass
        return tags

    def score(self, X, y=None, **params):
        _check_refit(self, "score")
        if not hasattr(self, "best_estimator_"):
            raise AttributeError(
                f"This {type(self).__name__} instance is not fitted yet; "
                "call fit() first.")
        # metadata routing contract: extra params are rejected unless
        # enable_metadata_routing=True, then routed to the scorer
        _raise_for_params(params, self, "score")
        if _routing_enabled():
            score_params = process_routing(
                self, "score", **params).scorer["score"]
        else:
            score_params = {}
        if callable(self.scoring):
            score = self.scoring(self.best_estimator_, X, y, **score_params)
            # a multimetric callable returns a dict; score() is the refit
            # metric's scalar (sklearn _search.py BaseSearchCV.score)
            if getattr(self, "multimetric_", False):
                score = score[self.refit]
            return score
        if self.scorer_ is not None and not isinstance(self.scorer_, dict):
            return self.scorer_(self.best_estimator_, X, y, **score_params)
        if isinstance(self.scorer_, dict) and isinstance(self.refit, str):
            return self.scorer_[self.refit](
                self.best_estimator_, X, y, **score_params)
        return self.best_estimator_.score(X, y, **score_params)


class GridSearchCV(BaseSearchTPU):
    """Exhaustive search over a parameter grid on a TPU mesh.

    Accepts both calling conventions:
      GridSearchCV(estimator, param_grid, ...)            (sklearn)
      GridSearchCV(sc, estimator, param_grid, ...)        (reference legacy —
        `sc` is accepted and ignored; the JAX mesh replaces the Spark
        cluster.  Reference: grid_search.py GridSearchCV(self, sc, ...).)
    """

    def __init__(self, estimator, param_grid=None, legacy_grid=None, *,
                 scoring=None, n_jobs=None, refit=True, cv=None, verbose=0,
                 error_score=np.nan, return_train_score=False, backend=None,
                 config=None):
        # third positional slot exists only for the reference's legacy
        # (sc, estimator, param_grid) convention; it is an explicit named
        # parameter (not *args) because sklearn's get_params/clone/repr
        # introspect __init__ and reject varargs
        if not _looks_like_estimator(estimator) and \
                _looks_like_estimator(param_grid):
            estimator = param_grid
            param_grid = legacy_grid
            legacy_grid = None
        elif legacy_grid is not None:
            # slot exists only for the legacy (sc, est, grid) convention;
            # a stray third positional (e.g. scoring) must not be swallowed
            raise TypeError(
                f"unexpected positional argument {legacy_grid!r}; pass "
                "scoring/cv/... as keywords")
        if param_grid is None:
            raise TypeError("param_grid is required")
        super().__init__(
            estimator, scoring=scoring, n_jobs=n_jobs, refit=refit, cv=cv,
            verbose=verbose, error_score=error_score,
            return_train_score=return_train_score, backend=backend,
            config=config)
        self.param_grid = param_grid
        self.legacy_grid = legacy_grid

    def _get_candidates(self):
        return list(ParameterGrid(self.param_grid))


class RandomizedSearchCV(BaseSearchTPU):
    """Randomized search: candidates drawn by sklearn's ParameterSampler
    (identical sampling semantics — _search.py:2109), evaluated on the mesh.

    Legacy `(sc, estimator, param_distributions)` convention accepted like
    GridSearchCV."""

    def __init__(self, estimator, param_distributions=None,
                 legacy_distributions=None, *, n_iter=10,
                 scoring=None, n_jobs=None, refit=True, cv=None, verbose=0,
                 random_state=None, error_score=np.nan,
                 return_train_score=False, backend=None, config=None):
        if not _looks_like_estimator(estimator) and \
                _looks_like_estimator(param_distributions):
            estimator = param_distributions
            param_distributions = legacy_distributions
            legacy_distributions = None
        elif legacy_distributions is not None:
            raise TypeError(
                f"unexpected positional argument {legacy_distributions!r}; "
                "pass n_iter/scoring/... as keywords")
        if param_distributions is None:
            raise TypeError("param_distributions is required")
        self.legacy_distributions = legacy_distributions
        super().__init__(
            estimator, scoring=scoring, n_jobs=n_jobs, refit=refit, cv=cv,
            verbose=verbose, error_score=error_score,
            return_train_score=return_train_score, backend=backend,
            config=config)
        self.param_distributions = param_distributions
        self.n_iter = n_iter
        self.random_state = random_state

    def _get_candidates(self):
        return list(ParameterSampler(
            self.param_distributions, self.n_iter,
            random_state=self.random_state))
