"""Gradient boosting and random forest families on binned trees.

Reference counterpart: sklearn's GradientBoostingRegressor and
RandomForestClassifier running whole inside Spark tasks (BASELINE.json
configs[3] and configs[2]).  Exact-CART is replaced by the histogram grower
in ops/trees.py, whose level histograms and routing are ops/tree_hist.py's
(grouped one-hot product kernels on a TPU, segment sums elsewhere); the
boosting/bagging layers are `lax.while_loop`/`vmap` programs:

  - GBDT: a loop over stages (`_boost`), carry the raw scores F on the
    FULL dataset (fold masks only weight the gradients).  A stage is ONE
    tree for a regressor and for two classes (scikit-learn's half-binomial
    loss on the log-odds of class 1), a tree a class on the softmax's
    gradients for three or more.  `n_estimators` is DYNAMIC: the program
    is built for the grid's maximum stage count and a lane stops at its
    own — boosting is prefix-stable (stage t only depends on stages < t,
    and draws `keys[t]` of `split(PRNGKey(random_state), t_max)` whatever
    the count), so one compiled program serves every n_estimators value
    in the grid instead of one compile group per value.  Where a group's
    candidates fall into runs of one length S >= 2 that share
    (`learning_rate`, `subsample`) — the usual learning rate x count grid
    — `launch_layout` orders them run-major and `fit_task_batched` grows
    ONE stage loop a (run, fold) to the run's largest count, keeping F as
    it passes each candidate's count (PR 40): the cell's 15 candidates
    are 25 lanes of 100 stages, 2 500 lane-stages where a lane a
    (candidate, fold) grew 4 375; the engine rounds such a group's width
    to whole runs.  Elsewhere a run is one candidate, as in `fit`: where
    the grid's runs are ragged or of one the engine cuts the group's
    launches where the counts change (`convergence_proxy`), so no lane
    idles, and a launch that cuts a run (a recovery's range, a fuse's
    seam, a width under one run) grows a lane a (candidate, fold).  A
    lane's gradients are real numbers and differ by learning rate, so
    every feature's histograms of every node are built from three
    bfloat16 parts a statistic (`ops/tree_hist.py`); `launch_workspace`
    prices a lane, a laid-out candidate at 1/S of one and its own F.
  - Random forest: ONE forest a fold, grown once a launch and read at every
    `n_estimators` of the launch's candidates (`fit_task_batched`; PR 36).
    `fit` draws `keys = split(PRNGKey(random_state), t_max)` with `t_max`
    the GRID's largest count, and tree `ti` is made from `keys[ti]` and
    its fold's mask alone: Poisson(1) bootstrap weights (the standard
    streaming approximation of sampling with replacement) from `keys[ti]`,
    per-level random feature subsets from `fold_in(keys[ti], 7)`, one-hot
    targets so the variance criterion matches gini up to scaling.  So tree
    `ti` of a fold is bit for bit the same tree whatever count it is grown
    for, the forest of a smaller count is the first trees of a larger
    one's (scikit-learn's own forests nest the same way: tree t's seed is
    the t-th draw of the forest's RNG), and a candidate's votes are the
    float32 sum `acc + 1.0 * pred` over its own first trees in the order
    it always had.  A compile group differs in `n_estimators` alone, so
    any chunk of it may share: the launch runs a `while_loop` to the
    largest count among its candidates over lanes = folds (one tree's
    level histograms a fold live at a time, at each node's own
    `max_features` features, drawn before the histograms: 33.5 MB at
    depth 10 under covtype's 7 of 54, 268 MB where a node may split on
    any; priced by `launch_workspace` whatever the width), and
    keeps a vote accumulator a candidate and fold.  Grading a group's
    launches by tree count would only regrow the shared first trees, so
    the forest families have no `convergence_proxy`: a group is one launch
    wherever memory allows (the boosters keep theirs for grids that are
    not whole runs).  `fit` itself is
    that loop for one fold, read at one count or at a vector of them: the
    direct call and the launch run the same body.  A task axis sharded
    over devices hands XLA's partitioner candidate-major tasks and their
    tiled masks; on the suite's 8-device mesh it grows the forests
    partitioned by fold and keeps the votes by candidate, bit-equal to one
    device.  `BinnedInvariantPipelineFamily` (monotone scalers + a tree
    family) forwards `fit_task_batched`, `launch_layout` and
    `convergence_proxy`: its launch is the bare family's.

Known deviations from sklearn (accuracy-level parity, tested):
  256-bin quantile splits instead of exact; Poisson bootstrap;
  max_depth=None capped at 10 (fixed shapes need a bound).
"""

from __future__ import annotations

import warnings
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import Family, encode_labels, register_family
from spark_sklearn_tpu.ops import tree_hist
from spark_sklearn_tpu.ops.trees import grow_tree

N_BINS = 256
#: of a forest's deepest level of histograms, how many the launch compiled
#: for a v5e holds at once (the kernel's output, and the share of it that
#: the pass over it for the gains keeps beside it), and of a row's sorted
#: bytes (the tables the sorts gather from, the gathered, padded and
#: transposed copies); read off `memory_analysis` of the covtype cell's
#: launches, 5 forests x 3 candidates, with every feature's histograms:
#: 2.04 GB of scratch at depth 10, 0.63 GB at depth 8 and at depth 6
#: alike; with a node's own 7 of 54, 0.63 GB at every depth, the rows'
#: share (PERF.md section 4)
_HIST_COPIES = 1.4
_ROW_COPIES = 5
#: copies of a candidate's votes: the loop's carry and its update, the
#: average, the model's output
_VOTE_COPIES = 4
#: float32 vectors a boosting lane holds a raw score beside its tree: F in
#: the loop's carry and its update, the mean, the gradient, the hessian
#: and the stage's row weights
_STAGE_COPIES = 6
#: copies of a laid-out candidate's kept F: the loop's carry, its update
#: and the model's candidate-major copy
_KEPT_COPIES = 3
#: the key of the static fact `GradientBoostingRegressorFamily.
#: launch_layout` hands the launch: the length S of the runs of
#: candidates that share (learning_rate, subsample)
_STAGE_RUN = "__stage_run__"
#: fixed-shape compiled growers need a static depth bound
MAX_COMPILED_DEPTH = 10


def _prep_codes(X, dtype):
    """Bin edges and the rows' codes, a byte a cell: what goes to the
    device (the grower widens a code where it computes with it)."""
    from spark_sklearn_tpu.utils.native import quantile_bin
    edges, codes = quantile_bin(np.asarray(X, np.float32), N_BINS)
    return edges, np.ascontiguousarray(codes, dtype=np.uint8)


def _own_rows(tree):
    """A grown tree's prediction for the rows it was grown on (fit rows
    and masked-out rows alike: every row is routed), from the node each
    ended in."""
    with jax.named_scope("sst.tree.predict"):
        return tree_hist.take_rows(tree.value, tree.leaf)


def _seed(static):
    rs = static.get("random_state")
    return 0 if rs is None else int(rs)


def _observe_tree_candidates(cls, candidates, base_params, meta):
    """Engine hook body, host-side once per search (shared by the GBDT
    and forest families — they don't share a base class, so the hook is
    a free function that takes the concrete family).

    1. The compiled program always grows the grid's MAX tree count
       (contributions masked per candidate), so the static bound must be
       known before tracing.
    2. The once-per-search depth-fidelity warning (VERDICT r4 next #3):
       a `max_depth` of None or > MAX_COMPILED_DEPTH is truncated by the
       fixed-shape grower (None maps to the family's default bound),
       which can change the model on deep data — that must never happen
       without a visible signal.
    """
    # the base estimator's value only matters where a candidate does not
    # override it — unconditionally including it would grow (and warn
    # about) models the search never fits (e.g. the default
    # n_estimators=100 under a {"n_estimators": [5, 8]} grid)
    base = base_params.get("n_estimators", 100)
    vals = [c.get("n_estimators", base) for c in candidates] or [base]
    meta["max_estimators"] = int(
        max([v for v in vals
             if isinstance(v, (int, np.integer))] or [100]))
    base_md = base_params.get("max_depth", cls._sklearn_default_depth)
    depths = ({c.get("max_depth", base_md) for c in candidates}
              or {base_md})
    truncated = sorted(
        (d for d in depths
         if d is None or (isinstance(d, (int, np.integer))
                          and int(d) > MAX_COMPILED_DEPTH)),
        key=lambda d: (d is not None, d if d is not None else 0))
    if truncated:
        warnings.warn(
            f"compiled {cls.name}: max_depth values {truncated} exceed "
            f"the histogram grower's static bound — integers are capped "
            f"at {MAX_COMPILED_DEPTH} and None (sklearn: unbounded) "
            f"maps to the family default of {cls._default_depth}. The "
            f"fitted model can differ from sklearn's on deep data; "
            f"pass max_depth <= {MAX_COMPILED_DEPTH} for a faithful "
            f"compiled fit, or backend='host' for sklearn's exact "
            f"unbounded CART.",
            UserWarning, stacklevel=2)


def _depth(static, default):
    md = static.get("max_depth", default)
    return default if md is None else min(int(md), MAX_COMPILED_DEPTH)


def _stage_runs(dynamic_params, static):
    """On the host: a booster group's candidates ordered by
    (learning_rate, subsample) as float32 values, then by n_estimators,
    and the run length S where that order is runs of one length S >= 2
    that share the pair; None where the runs are ragged or of one."""
    nc = max((np.size(v) for v in dynamic_params.values()), default=0)
    lr, sub, count = (
        np.broadcast_to(np.asarray(dynamic_params.get(
            name, static.get(name, default)), dtype), (nc,))
        for name, default, dtype in (("learning_rate", 0.1, np.float32),
                                     ("subsample", 1.0, np.float32),
                                     ("n_estimators", 100, np.int64)))
    if nc < 2:
        return None
    counts = np.unique(np.stack([lr, sub], axis=1), axis=0,
                       return_counts=True)[1]
    if counts[0] < 2 or np.any(counts != counts[0]):
        return None
    return np.lexsort((count, sub, lr)), int(counts[0])


def _fixed_rows(dynamic, static, meta):
    """Whether every stage of a booster's lane counts the same rows, the
    fold's training rows: `subsample` is 1.0 and no candidate of the
    search names it (`observe_candidates`).  The stage loop then runs in
    one row order a lane (`tree_hist.row_order`)."""
    return ("subsample" not in dynamic
            and not meta.get("subsample_searched", False)
            and float(static.get("subsample", 1.0)) == 1.0)


def _stage_run(static, n_candidates):
    """The run length a launch of `n_candidates` grows one stage loop a
    fold for: the layout's fact, else 1 — no fact (a direct caller, a
    grid that is not whole runs, a launch the engine knows not to be made
    of whole runs: `Family.launch_layout`) or a width that is no multiple
    of it."""
    S = int((static or {}).get(_STAGE_RUN, 1))
    return S if S > 1 and n_candidates % S == 0 else 1


class GradientBoostingRegressorFamily(Family):
    name = "gradient_boosting_regressor"
    is_classifier = False
    keyed_compatible = False   # consumes binned "codes", not raw "X"
    #: a feature's quantile codes: a monotone scaler in front moves none
    quantile_binned = True
    dynamic_params = {"learning_rate": np.float32,
                      "n_estimators": np.int32,
                      "subsample": np.float32}
    #: max_depth=None caps deeper than GBDT's usual 3
    _default_depth = 3
    #: sklearn's own ctor default (GradientBoosting*: max_depth=3)
    _sklearn_default_depth = 3

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        edges, codes = _prep_codes(X, dtype)
        y = np.asarray(y, dtype)
        data = {"codes": codes, "y": y}
        meta = {"n_features": int(X.shape[1]), "edges": edges,
                "max_estimators": None}
        return data, meta

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        _observe_tree_candidates(cls, candidates, base_params, meta)
        meta["subsample_searched"] = any("subsample" in c
                                         for c in candidates)

    #: per-tree work is large (level histograms over all samples), so
    #: even small grids amortise the extra dispatches
    min_sort_candidates = 4

    @classmethod
    def convergence_proxy(cls, dynamic_params, static):
        """A launch's while_loop runs max-over-lanes(n_estimators)
        stages; sorting by n_estimators makes that max tight per
        launch, and a grid's few distinct counts are whole runs of
        equal proxy: a launch a count, in which no lane idles.  None
        where `launch_layout` applies: a run's candidates share ONE
        stage loop a fold, and a cut by count would grow it again in
        every launch."""
        if _stage_runs(dynamic_params, static) is not None:
            return None
        return dynamic_params.get("n_estimators")

    @classmethod
    def launch_layout(cls, dynamic_params, static, meta, n_folds):
        """The group's candidates run-major, and the run length S: where
        they fall into runs of one length S >= 2 that share
        (learning_rate, subsample), a run's candidates differ in
        n_estimators alone, and one stage loop a fold read at each of
        their counts is each one's model (`fit_task_batched`).  None
        where the runs are ragged or of one."""
        runs = _stage_runs(dynamic_params, static)
        if runs is None:
            return None
        order, S = runs
        return order, {_STAGE_RUN: S}, S

    # -- the loss: squared error on y -------------------------------------
    @classmethod
    def _start(cls, data, train_w, meta):
        """Every row's raw score before the first stage: the weighted
        mean of the fold's training targets."""
        y = data["y"]
        wsum = jnp.sum(train_w) + 1e-12
        return jnp.full(y.shape, jnp.sum(train_w * y) / wsum, jnp.float32)

    @classmethod
    def _mean(cls, F):
        """The loss's mean function of the raw scores."""
        return F

    @classmethod
    def _grad_hess(cls, mean, data):
        """d loss / dF and d2 loss / dF2 a row (and, for several trees a
        stage, a column): 0.5 (F - y)^2 here."""
        return mean - data["y"], jnp.ones(mean.shape, jnp.float32)

    @classmethod
    def _boost(cls, dynamic, static, data, train_w, meta):
        """The stage loop.  Carries the raw scores F on the FULL data
        set, ``(n,)`` for one tree a stage and ``(n, k)`` for a tree a
        class; a stage takes the loss's gradient and hessian at F, grows
        its trees on them over the fold's (subsampled) training rows and
        adds ``learning_rate`` x each row's leaf value.  A lane is a run
        of ``(C,)`` counts (`fit_task_batched`; one count is a run of
        one): it stops at the largest and keeps F as it passes each.
        Returns the kept F, ``(C,) + F.shape``, and the stages each
        count ran.

        Where every stage counts the fold's training rows (`_fixed_rows`)
        the loop runs in the lane's `tree_hist.row_order`, made once: F,
        the targets and the weights are put in it before the loop and the
        kept F back after it, and no tree sorts or gathers its rows at
        level 0 or sorts them back.  A tree a class shares it."""
        codes = data["codes"]
        n = codes.shape[0]
        depth = _depth(static, cls._default_depth)
        t_max = int(meta.get("max_estimators")
                    or static.get("n_estimators", 100))
        lr = jnp.asarray(dynamic.get(
            "learning_rate", static.get("learning_rate", 0.1)), jnp.float32)
        n_est = jnp.atleast_1d(jnp.asarray(dynamic.get(
            "n_estimators", static.get("n_estimators", 100)), jnp.int32))
        subsample = jnp.asarray(dynamic.get(
            "subsample", static.get("subsample", 1.0)), jnp.float32)
        min_leaf = float(static.get("min_samples_leaf", 1))
        key = jax.random.PRNGKey(_seed(static))
        fixed = _fixed_rows(dynamic, static, meta)

        # in the caller's order: its sums keep their bits
        F = cls._start(data, train_w, meta)
        order = tree_hist.row_order(codes, train_w) if fixed else None
        rows = data
        if order is not None:
            with jax.named_scope("sst.tree.partition"):
                F, train_w = order.rows(F), order.rows(train_w)
                rows = {**data, **{k: order.rows(data[k])
                                   for k in ("y", "y1h") if k in data}}

        # while_loop with a per-lane trip count: a run stops growing
        # trees past its largest n_estimators
        keys = jax.random.split(key, t_max)
        n_lim = jnp.minimum(n_est, t_max)
        n_top, lim_top = jnp.max(n_est), jnp.max(n_lim)

        def grow(g_c, h_c, w_t):
            return grow_tree(codes, g_c[:, None], h_c, w_t, depth, N_BINS,
                             min_child_weight=min_leaf, reg_lambda=1e-6,
                             order=order)

        def one_stage(carry):
            t, F, kept = carry
            k_t = keys[t]
            with jax.named_scope("sst.boost.gradient"):
                mean = cls._mean(F)
                # at subsample 1.0 every draw is below it
                w_t = train_w if fixed else train_w * (
                    jax.random.uniform(k_t, (n,)) < subsample).astype(
                    jnp.float32)
                G, H = cls._grad_hess(mean, rows)
            if F.ndim == 1:
                delta = _own_rows(grow(G, H, w_t))[:, 0]
            else:                                   # a tree a class
                trees_k = jax.vmap(lambda g_c, h_c: grow(g_c, h_c, w_t),
                                   in_axes=(1, 1))(G, H)
                delta = jax.vmap(lambda tr: _own_rows(tr)[:, 0],
                                 in_axes=0, out_axes=1)(trees_k)   # (n, k)
            with jax.named_scope("sst.boost.update"):
                live = (t < n_top).astype(jnp.float32)
                F = F + lr * live * delta
                # F of every count this stage completes
                done = (n_lim == t + 1).reshape((-1,) + (1,) * F.ndim)
                return t + 1, F, jnp.where(done, F[None], kept)

        t_end, F, kept = jax.lax.while_loop(
            lambda c: c[0] < lim_top, one_stage,
            (jnp.asarray(0, jnp.int32), F,
             jnp.broadcast_to(F, n_lim.shape + F.shape)))
        if order is not None:
            with jax.named_scope("sst.tree.partition"):
                kept = order.back(kept, axis=1)
        # a lane's own stages, of those the loop ran (under the lanes'
        # vmap the loop's counter is the launch's largest count).  Read
        # off the counter, the counts reach the launch's statistics when
        # the loop has ended: on a mesh of devices their reductions over
        # the tasks are collectives, and XLA:CPU starts independent
        # collectives in any order (eight virtual devices waited in the
        # loop's and in the statistics' at once, and for good)
        return kept, jnp.minimum(n_lim, t_end)

    @classmethod
    def _model(cls, F, n_iter):
        """A candidate's model from its raw scores after its stages."""
        return {"pred": F, "n_iter": n_iter}

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        F, n_iter = cls._boost(dynamic, static, data, train_w, meta)
        return cls._model(F[0], n_iter[0])

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        """A launch's (candidate x fold) tasks, candidate-major: ONE
        stage loop a (run, fold) at the run's learning_rate and
        subsample, to its largest count, each candidate's model built
        from F as the loop passed its own count — the same stages, stage
        for stage, as its one-candidate `fit`.  A run is S candidates
        where `static` carries `launch_layout`'s fact and the launch is
        made of whole runs (`_stage_run`), else one.  The folds' masks
        are the first candidate's rows of `train_w` (every candidate's
        are the same)."""
        n_folds = int(static["__n_folds__"])
        nc = train_w.shape[0] // n_folds
        folds = train_w[:n_folds]
        cand = {k: jnp.reshape(v, (nc, n_folds) + jnp.shape(v)[1:])[:, 0]
                for k, v in dynamic.items()}
        S = _stage_run(static, nc)
        runs = {k: v.reshape((nc // S, S) + v.shape[1:])[:, 0]
                for k, v in cand.items() if k != "n_estimators"}
        runs["n_estimators"] = jnp.broadcast_to(jnp.asarray(cand.get(
            "n_estimators", static.get("n_estimators", 100)), jnp.int32),
            (nc,)).reshape(nc // S, S)
        F, n_iter = jax.vmap(lambda d: jax.vmap(
            lambda w: cls._boost(d, static, data, w, meta))(folds),
            axis_size=nc // S)(runs)            # (runs, folds, S, ...)

        def tasks(leaf):
            return jnp.moveaxis(leaf, 2, 1).reshape(
                (nc * n_folds,) + leaf.shape[3:])
        return jax.vmap(cls._model)(tasks(F), tasks(n_iter))

    # -- what a launch reports, and what it holds --------------------------
    @classmethod
    def _trees_per_stage(cls, meta):
        return 1

    @classmethod
    def launch_stats(cls, models, static, meta):
        """The default's lockstep stages (maximum and sum over tasks),
        each task's own count, and what the launch executed: every lane
        it grew (a task, or a run of S candidates a fold where the launch
        is laid out), padding included, carried through the launch's
        largest count, and the sorts of its rows into node order (a
        lane's order once where `_fixed_rows`, a tree's at level 0
        otherwise, and at levels 5 and 10)."""
        stats = super().launch_stats(models, static, meta)
        stages = models["n_iter"].astype(jnp.int32)     # (candidates, folds)
        lanes = stages.size // _stage_run(static, stages.shape[0])
        # from the default's maximum: a second reduction over the tasks
        # would be a second collective on a mesh of devices
        steps = stats["solver_iters"] * lanes
        trees = steps * cls._trees_per_stage(meta)
        stats["trees"] = stages.reshape(-1)
        stats["tree_steps"] = steps
        stats["tree_slots"] = trees
        stats["trees_grown"] = trees
        depth = _depth(static, cls._default_depth)
        stats["tree_levels"] = trees * depth
        stats["row_sorts"] = tree_hist.row_sorts(
            depth, trees, lanes, _fixed_rows({}, static, meta))
        return stats

    @classmethod
    def launch_facts(cls, static, meta, n_candidates, n_folds):
        """A stage's trees may split on any feature.  Where every stage
        counts the fold's training rows (`_fixed_rows`) a fold's runs (the
        launch's candidates, or its runs of S) and a stage's trees read
        the fold's one order, so its histograms' masks serve them all."""
        runs, trees = 1, 1
        if _fixed_rows({}, static, meta):
            runs = n_candidates // _stage_run(static, n_candidates)
            trees = cls._trees_per_stage(meta)
        return {"hist_features": int(meta["n_features"]),
                "hist_bytes": cls._hist_bytes(static, meta),
                "mask_lanes": tree_hist.lanes_per_mask(runs, trees)}

    @classmethod
    def _hist_bytes(cls, static, meta):
        """Bytes of one tree's deepest level of histograms: every
        feature, the hessian and the one gradient."""
        return tree_hist.level_histogram_bytes(
            _depth(static, cls._default_depth), meta["n_features"], 2,
            N_BINS)

    @classmethod
    def launch_workspace(cls, n_samples, meta, n_folds, itemsize=4, *,
                         static, row_sets=1):
        """What a launch holds besides its arguments, for the memory
        ledger.  A lane is a (candidate, fold) of its own, with its own
        order of the rows after every sort: a tree of it holds what a
        forest's holds by row (leaf values and statistics, `_ROW_COPIES`
        of the sorted copy of codes and the three-part statistics) or,
        where that is more, `_HIST_COPIES` of its deepest level's
        histograms; a tree a class where a stage grows as many.  Beside
        the trees `_STAGE_COPIES` float32 vectors a raw score: F in the
        loop's carry and its update, the mean, gradient, hessian and the
        stage's row weights.  Where the group is laid out in runs of S
        (`launch_layout`'s fact in `static`) a lane is a run's, so a
        candidate is 1/S of one and `_KEPT_COPIES` of its own F; the
        ledger gives a width that is no multiple of S the price without
        the fact, as the launch runs it.

        Where every stage counts the fold's training rows (`_fixed_rows`)
        the rows stay in one order a fold: a tree holds one copy of a
        row's kernels' bytes but its codes, which are its fold's order's
        (the runs and trees of a fold share their code tiles), and sorts
        none, and the launch holds a fold's order
        (`tree_hist.order_row_bytes` a row)."""
        trees = cls._trees_per_stage(meta)
        n = int(n_samples)
        static = static or {}
        fixed = _fixed_rows({}, static, meta)
        row_bytes = tree_hist.row_bytes(meta["n_features"], 2,
                                        integer_stats=False,
                                        own_codes=not fixed)
        row = (2 * 2 * 4                        # leaf values, stats
               + (1 if fixed else _ROW_COPIES) * row_bytes)
        tree = max(int(_HIST_COPIES * cls._hist_bytes(static, meta)),
                   n * row)
        stage = _STAGE_COPIES * 4 * n * trees
        lane = trees * tree + stage
        S = int(static.get(_STAGE_RUN, 1))
        if S > 1:
            lane = lane // S + _KEPT_COPIES * 4 * n * trees
        orders = n_folds * n * tree_hist.order_row_bytes(
            meta["n_features"]) if fixed else 0
        return {"fixed_bytes": orders, "per_candidate_bytes": n_folds * lane}

    @classmethod
    def predict(cls, model, static, X, meta):
        # the search scores on the training X: cached full-dataset preds
        # (every stage adds its trees' leaf values at the node each row
        # ended in, `_own_rows`: no tree is walked a second time)
        return model["pred"]

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"n_features_in_": meta["n_features"]}


class GradientBoostingClassifierFamily(GradientBoostingRegressorFamily):
    """Log-loss boosting as scikit-learn runs it: for two classes ONE
    tree a stage on the log-odds of class 1 (half-binomial loss: raw score
    F, p = sigmoid(F), g = p - y, h = p (1 - p), F0 the prior's log-odds;
    ``_gb.py``: ``n_trees_per_iteration_ = 1 if n_classes <= 2``), for
    more a tree a class on the softmax's."""
    name = "gradient_boosting_classifier"
    is_classifier = True
    #: sklearn's staged decision/proba arrays are float64 regardless of X
    proba_dtype_rule = "float64"

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        edges, codes = _prep_codes(X, dtype)
        classes, y_enc = encode_labels(y)
        k = len(classes)
        data = {"codes": codes, "y": y_enc,
                "y1h": np.eye(k, dtype=np.float32)[y_enc]}
        meta = {"n_features": int(X.shape[1]), "edges": edges,
                "n_classes": int(k), "classes": classes,
                "max_estimators": None}
        return data, meta

    @classmethod
    def _trees_per_stage(cls, meta):
        k = int(meta["n_classes"])
        return 1 if k == 2 else k

    @classmethod
    def _start(cls, data, train_w, meta):
        y1h = data["y1h"]
        n, k = y1h.shape
        wsum = jnp.sum(train_w) + 1e-12
        prior = jnp.clip(
            (train_w[:, None] * y1h).sum(0) / wsum, 1e-6, 1 - 1e-6)
        if cls._trees_per_stage(meta) == 1:     # the log-odds of class 1
            return jnp.full((n,), jnp.log(prior[1] / (1.0 - prior[1])),
                            jnp.float32)
        return jnp.broadcast_to(jnp.log(prior)[None, :], (n, k)).astype(
            jnp.float32) + jnp.zeros((n, k), jnp.float32)

    @classmethod
    def _mean(cls, F):
        return jax.nn.sigmoid(F) if F.ndim == 1 else jax.nn.softmax(F, axis=1)

    @classmethod
    def _grad_hess(cls, mean, data):
        y = data["y1h"][:, 1] if mean.ndim == 1 else data["y1h"]
        return mean - y, mean * (1.0 - mean)

    @classmethod
    def _model(cls, F, n_iter):
        pred = (F > 0) if F.ndim == 1 else jnp.argmax(F, axis=1)
        return {"pred": pred.astype(jnp.int32), "logits": F,
                "n_iter": n_iter}

    @classmethod
    def decision(cls, model, static, X, meta):
        # the raw scores: for two classes the log-odds, a 1-D margin (the
        # scorers' contract)
        return model["logits"]

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        p = cls._mean(model["logits"])
        return jnp.stack([1.0 - p, p], axis=1) if p.ndim == 1 else p


class RandomForestClassifierFamily(Family):
    name = "random_forest_classifier"
    is_classifier = True
    keyed_compatible = False   # consumes binned "codes", not raw "X"
    #: a feature's quantile codes: a monotone scaler in front moves none
    quantile_binned = True
    #: sklearn's vote-averaged probas are float64 regardless of X
    proba_dtype_rule = "float64"
    dynamic_params = {"n_estimators": np.int32}
    _default_depth = 10
    #: sklearn's own ctor default (RandomForest*: max_depth=None,
    #: i.e. unbounded — the compiled cap always applies, so a default
    #: forest search gets the fidelity warning)
    _sklearn_default_depth = None

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        edges, codes = _prep_codes(X, dtype)
        classes, y_enc = encode_labels(y)
        k = len(classes)
        data = {"codes": codes, "y": y_enc,
                "y1h": np.eye(k, dtype=np.float32)[y_enc]}
        meta = {"n_features": int(X.shape[1]), "edges": edges,
                "n_classes": int(k), "classes": classes,
                "max_estimators": None}
        return data, meta

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        _observe_tree_candidates(cls, candidates, base_params, meta)

    @classmethod
    def _max_features(cls, static, d):
        mf = static.get("max_features", "sqrt")
        if mf in ("sqrt", "auto"):
            return max(1, int(np.sqrt(d)))
        if mf == "log2":
            return max(1, int(np.log2(d)))
        if mf is None:
            return d
        if isinstance(mf, float):
            return max(1, int(mf * d))
        return int(mf)

    @classmethod
    def _targets(cls, data):
        return data["y1h"]

    @classmethod
    def _n_estimators(cls, dynamic, static):
        return jnp.asarray(dynamic.get(
            "n_estimators", static.get("n_estimators", 100)), jnp.int32)

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        """One fold's forest, read at `n_estimators`: the model of one
        count (a direct call), or of each of a vector of counts `(C,)`,
        its leaves led by `(C,)` (`fit_task_batched`).  Grown to the
        largest count; tree `ti`'s votes go to the accumulator of every
        count above `ti`."""
        n_est = cls._n_estimators(dynamic, static)
        counts = n_est.reshape(-1)
        codes = data["codes"]
        t = cls._targets(data)                          # (n, n_out)
        n, d = codes.shape
        n_out = t.shape[1]
        depth = _depth(static, cls._default_depth)
        t_max = int(meta.get("max_estimators")
                    or static.get("n_estimators", 100))
        bootstrap = bool(static.get("bootstrap", True))
        min_leaf = float(static.get("min_samples_leaf", 1))
        mf = cls._max_features(static, d)
        key = jax.random.PRNGKey(_seed(static))

        # while_loop (not scan/vmap) over trees: level histograms are the
        # memory hot spot, one tree's workspace stays live.  Tree `ti` is
        # drawn from `keys[ti]` and the fold's mask, whatever the count
        # it is grown for: the forest of a smaller count is the first
        # trees of a larger one's.  The bound is no fold's own, so under
        # the folds' vmap the loop's counter and the draws stay one
        keys = jax.random.split(key, t_max)
        n_lim = jnp.minimum(counts, t_max)              # (C,)
        n_trees = jnp.max(n_lim)

        def one_tree(carry):
            ti, acc = carry
            k_t = keys[ti]
            with jax.named_scope("sst.tree.bootstrap"):
                if bootstrap:
                    w_t = train_w * jax.random.poisson(
                        k_t, 1.0, (n,)).astype(jnp.float32)
                else:
                    w_t = train_w
            # squared loss from F=0: grad = -target, hess = 1 -> leaf
            # value = weighted mean target (class distribution / mean y)
            tree = grow_tree(codes, -t, jnp.ones((n,), jnp.float32), w_t,
                             depth, N_BINS, min_child_weight=min_leaf,
                             reg_lambda=1e-9,
                             feat_mask_key=jax.random.fold_in(k_t, 7),
                             max_features=mf, n_out=n_out,
                             integer_stats=cls._integer_stats(meta))
            pred = _own_rows(tree)                      # (n, n_out)
            live = (ti < counts).astype(jnp.float32)
            return ti + 1, acc + live[:, None, None] * pred

        acc0 = jnp.zeros((counts.shape[0], n, n_out), jnp.float32)
        _, acc = jax.lax.while_loop(
            lambda c: c[0] < n_trees, one_tree,
            (jnp.asarray(0, jnp.int32), acc0))
        out = cls._finalize(
            acc / jnp.maximum(n_lim.astype(jnp.float32), 1.0)[
                :, None, None])
        out["n_iter"] = n_lim   # a task's own trees, for launch accounting
        if n_est.ndim == 0:
            out = jax.tree_util.tree_map(lambda leaf: leaf[0], out)
        return out

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        """A launch's (candidate x fold) tasks, candidate-major, as ONE
        forest a fold read at every candidate's count: `fit` a fold, with
        the launch's counts.  The candidates of a compile group differ in
        `n_estimators` alone, so whatever chunk of it a launch holds, its
        forests are theirs to share; the folds' masks are the first
        candidate's rows of `train_w` (every candidate's are the same).
        A padded lane repeats the chunk's last candidate, so it raises no
        bound."""
        n_folds = int(static["__n_folds__"])
        counts = jnp.broadcast_to(
            cls._n_estimators(dynamic, static),
            train_w.shape[:1]).reshape(-1, n_folds)[:, 0]
        models = jax.vmap(
            lambda w: cls.fit({"n_estimators": counts}, static, data, w,
                              meta), out_axes=1)(train_w[:n_folds])
        return jax.tree_util.tree_map(
            lambda leaf: leaf.reshape((-1,) + leaf.shape[2:]), models)

    @classmethod
    def _finalize(cls, avg):
        return {"proba": avg,
                "pred": jnp.argmax(avg, axis=-1).astype(jnp.int32)}

    @classmethod
    def _integer_stats(cls, meta):
        """Whether a row's statistics are small integers: the fold's 0/1
        mask (no sample_weight: the engine's word) times a bootstrap
        count, times a one-hot class."""
        return bool(meta.get("unit_fit_weights", False))

    @classmethod
    def _n_stats(cls, meta):
        """Statistics a row carries into a histogram: its hessian and a
        gradient an output (a class of the one-hot target, or y)."""
        return 1 + int(meta.get("n_classes", 1))

    @classmethod
    def launch_stats(cls, models, static, meta):
        """The default's lockstep trees (maximum and sum over tasks),
        each task's own, and what the launch executed: one forest a fold
        grown to the launch's largest tree count, a level at a time."""
        stats = super().launch_stats(models, static, meta)
        trees = models["n_iter"].astype(jnp.int32)      # (candidates, folds)
        grown = jnp.max(trees) * trees.shape[1]
        stats["trees"] = trees.reshape(-1)
        stats["trees_grown"] = grown
        stats["tree_slots"] = grown
        stats["tree_levels"] = grown * _depth(static, cls._default_depth)
        return stats

    @classmethod
    def _hist_features(cls, static, meta):
        """Features a node's histograms hold: its own `max_features`
        where that is a subset (`grow_tree` builds no others), every one
        otherwise."""
        d = int(meta["n_features"])
        return min(cls._max_features(static, d), d)

    @classmethod
    def _hist_bytes(cls, static, meta):
        """Bytes of one lane's deepest level of histograms."""
        return tree_hist.level_histogram_bytes(
            _depth(static, cls._default_depth), meta["n_features"],
            cls._n_stats(meta), N_BINS,
            slots=cls._hist_features(static, meta))

    @classmethod
    def launch_facts(cls, static, meta, n_candidates, n_folds):
        # a forest's trees draw their own rows: each its own code tiles
        return {"hist_bytes": cls._hist_bytes(static, meta),
                "hist_features": cls._hist_features(static, meta),
                "mask_lanes": tree_hist.lanes_per_mask(1)}

    @classmethod
    def launch_workspace(cls, n_samples, meta, n_folds, itemsize=4, *,
                         static, row_sets=1):
        """What a launch holds besides its arguments, for the memory
        ledger.  Whatever its width, a forest a fold: the larger of
        `_HIST_COPIES` of its deepest level's histograms (the kernel's
        blocks and the gains' share beside them) and what the sorts hold
        by row (a tree's leaf distributions, the statistics, and
        `_ROW_COPIES` of the sorted copy of codes and statistics): the
        compiler gives the histograms the sorts' space.  A candidate:
        `_VOTE_COPIES` of its votes by fold and row."""
        n_stats = cls._n_stats(meta)
        row = (2 * n_stats * 4                 # leaf values, stats
               + _ROW_COPIES * tree_hist.row_bytes(
                   meta["n_features"], n_stats, cls._integer_stats(meta)))
        forest = max(int(_HIST_COPIES * cls._hist_bytes(static, meta)),
                     int(n_samples) * row)
        votes = _VOTE_COPIES * (n_stats - 1) * 4 * int(n_samples)
        return {"fixed_bytes": n_folds * forest,
                "per_candidate_bytes": n_folds * votes}

    @classmethod
    def predict(cls, model, static, X, meta):
        return model["pred"]

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        p = jnp.maximum(model["proba"], 0.0)
        return p / jnp.maximum(p.sum(axis=1, keepdims=True), 1e-12)

    @classmethod
    def decision(cls, model, static, X, meta):
        if meta.get("n_classes") == 2:
            # scorer contract: binary decision is a 1-D margin
            return model["proba"][:, 1] - model["proba"][:, 0]
        return model["proba"]

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"classes_": meta.get("classes"),
                "n_features_in_": meta["n_features"]}


class RandomForestRegressorFamily(RandomForestClassifierFamily):
    name = "random_forest_regressor"
    is_classifier = False

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        edges, codes = _prep_codes(X, dtype)
        y = np.asarray(y, dtype)
        data = {"codes": codes, "y": y,
                "y_target": y.reshape(len(y), 1)}
        meta = {"n_features": int(X.shape[1]), "edges": edges,
                "max_estimators": None}
        return data, meta

    @classmethod
    def _max_features(cls, static, d):
        mf = static.get("max_features", 1.0)   # sklearn regressor default
        if isinstance(mf, float) and mf == 1.0:
            return d                            # int 1 means ONE feature
        return RandomForestClassifierFamily._max_features.__func__(
            cls, static, d)

    @classmethod
    def _targets(cls, data):
        return data["y_target"]

    @classmethod
    def _integer_stats(cls, meta):
        return False             # a row's gradient is its target

    @classmethod
    def _finalize(cls, avg):
        return {"pred": avg[..., 0]}

    @classmethod
    def predict(cls, model, static, X, meta):
        return model["pred"]


register_family(
    GradientBoostingRegressorFamily,
    "sklearn.ensemble._gb.GradientBoostingRegressor",
    "sklearn.ensemble.GradientBoostingRegressor",
)
register_family(
    GradientBoostingClassifierFamily,
    "sklearn.ensemble._gb.GradientBoostingClassifier",
    "sklearn.ensemble.GradientBoostingClassifier",
)
register_family(
    RandomForestClassifierFamily,
    "sklearn.ensemble._forest.RandomForestClassifier",
    "sklearn.ensemble.RandomForestClassifier",
)
register_family(
    RandomForestRegressorFamily,
    "sklearn.ensemble._forest.RandomForestRegressor",
    "sklearn.ensemble.RandomForestRegressor",
)
