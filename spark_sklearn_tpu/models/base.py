"""Tier-A estimator family protocol and registry.

The reference runs `clone(estimator).set_params(**p).fit(X[train], y[train])`
as arbitrary host Python inside each Spark task (reference: grid_search.py ->
sklearn _fit_and_score).  A TPU cannot run arbitrary Python; instead each
supported estimator *family* re-expresses fit/predict/score as pure JAX
functions with fixed shapes:

    fit(dynamic, static, data, train_w, meta)  -> model pytree
    predict(model, static, X, meta)            -> encoded predictions
    decision(model, static, X, meta)           -> scores/logits (optional)

- `dynamic`: dict of scalar hyperparameters that batch under vmap (C, alpha..)
- `static`:  dict of trace-shaping hyperparameters (penalty, hidden sizes..)
- `train_w`: per-sample weight mask (ragged CV folds -> fixed shapes,
  SURVEY §7.3 #2)
- `meta`:    host-side data facts (n_classes, classes_, feature means...)

The registry maps BOTH sklearn estimator classes and our own native
estimators to a family, so a user's existing `sklearn.linear_model.
LogisticRegression` instance is dispatched to the compiled path with no code
change — the same drop-in contract the reference had.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Type

import numpy as np

_FAMILIES_BY_CLASSNAME: Dict[str, Any] = {}


class NotCompiledError(ValueError):
    """A family's (or the engine's) own refusal: this estimator
    configuration, scorer or data mode has no compiled program — "...
    is not compiled; use backend='host'".  It is raised host-side,
    from static values, and is the ONLY exception `backend=None` trades
    for a run on the host tier; under `backend="tpu"` it propagates.
    Anything else that leaves the compiled path — a jax trace, lowering
    or compile refusal, a runtime or device error — is a failure and
    propagates under every backend setting."""


def register_family(family, *qualified_names: str):
    """Register a family under fully-qualified estimator class names
    (e.g. "sklearn.linear_model._logistic.LogisticRegression")."""
    for qn in qualified_names:
        _FAMILIES_BY_CLASSNAME[qn] = family
    return family


def _qualname(cls: Type) -> str:
    return f"{cls.__module__}.{cls.__name__}"


def resolve_family(estimator) -> Optional[Any]:
    """Find the Tier-A family for an estimator instance, or None (-> Tier B).

    Matching is by qualified class name, then by bare class name with module
    prefix "sklearn." — robust to sklearn's private-module shuffling.
    """
    cls = type(estimator)
    qn = _qualname(cls)
    if qn == "sklearn.pipeline.Pipeline":
        from spark_sklearn_tpu.models.pipeline import make_pipeline_family
        return make_pipeline_family(estimator)
    if qn in _FAMILIES_BY_CLASSNAME:
        return _FAMILIES_BY_CLASSNAME[qn]
    # tolerate sklearn's private-module shuffling, but ONLY for sklearn
    # classes — a third-party class that happens to be named
    # "LogisticRegression" must not silently get the compiled fit
    if qn.startswith("sklearn."):
        for known, fam in _FAMILIES_BY_CLASSNAME.items():
            if known.startswith("sklearn.") and \
                    known.split(".")[-1] == cls.__name__:
                return fam
    return None


class Family:
    """Base class for Tier-A families (documentation of the protocol)."""

    name: str = "base"
    #: dynamic (vmap-batchable) hyperparameter names -> numpy dtype
    dynamic_params: Dict[str, Any] = {}
    #: True for classifiers (label-encode y, default scorer = accuracy)
    is_classifier: bool = False

    #: families whose fit consumes the standard {"X", "y"[, "y1h"]} data
    #: dict; tree families (binned "codes" + grid-dependent meta) opt out
    #: of dispatchers that synthesise that dict (the keyed fleet)
    keyed_compatible: bool = True

    #: True when fit/predict tolerate data["X"] as a BCOO device operand
    #: (matmuls in operator form, no dense-only ops on X) AND the family
    #: implements `prepare_data_sparse` — consumed by the engine's
    #: `data_mode="sparse"` tier
    supports_sparse: bool = False

    #: True when the family implements the streaming-fold protocol
    #: (`stream_fit_partial` / `stream_fit_finalize`): per-fold fit
    #: statistics that are candidate-independent, additive over sample
    #: shards, and exactly reconstruct the in-core fit — consumed by the
    #: engine's `data_mode="stream"` tier
    supports_stream: bool = False

    @classmethod
    def has_per_task_fit(cls) -> bool:
        """True when the family implements the per-task `fit` (some, like
        SVC, only provide the task-batched form and cannot be composed by
        dispatchers that need one fit per vmap lane)."""
        return getattr(cls.fit, "__func__", cls.fit) is not \
            Family.fit.__func__

    # --- host side -------------------------------------------------------
    @classmethod
    def extract_params(cls, estimator) -> Dict[str, Any]:
        """estimator instance -> full param dict (host)."""
        return dict(estimator.get_params(deep=False))

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        """-> (data: dict of arrays ready for device, meta: dict of host
        facts).  Called once per search, not per candidate."""
        raise NotImplementedError

    @classmethod
    def prepare_data_sparse(cls, X, y, dtype=np.float32):
        """Sparse twin of `prepare_data`: `X` is a scipy CSR matrix and
        the returned data dict carries it as a
        `sparse.csr.SparseOperand` under "X" (the engine uploads its
        components and reassembles a device BCOO).  Host-side input
        validation (finiteness, sign checks) runs on `X.data` — never on
        a densified form.  Only meaningful with `supports_sparse`."""
        raise NotImplementedError

    # --- streaming-fold protocol (data_mode="stream") ---------------------
    # Per-fold fit statistics must be candidate-independent within one
    # compile group (static params may enter; dynamic ones may not) and
    # additive over row shards: the engine folds
    #   acc <- stream_fit_accumulate(acc, stream_fit_partial(shard))
    # on device in shard order, then vmaps stream_fit_finalize over the
    # chunk's candidates.  Scoring streams through the ordinary
    # `predict` on each shard.
    @classmethod
    def stream_fit_partial(cls, static, data, fit_w, meta):
        """One shard's per-fold fit statistics.  `data` holds the
        shard's row slices (same keys as `prepare_data`'s dict);
        `fit_w` is the (n_folds, shard_rows) fit-mask slice.  Returns a
        pytree whose leaves carry a leading fold axis and sum exactly
        across shards."""
        raise NotImplementedError

    @classmethod
    def stream_fit_finalize(cls, dynamic, static, stats, meta):
        """Folded statistics (one fold's slice, no fold axis) + one
        candidate's dynamic params -> the same model pytree `fit`
        returns.  The engine vmaps candidates x folds around this."""
        raise NotImplementedError

    # --- device side (pure, jit/vmap-safe) -------------------------------
    @classmethod
    def build_fit_data(cls, Xg, yg, meta):
        """Device-side data dict for a single-group fit (the keyed fleet's
        analog of prepare_data, traced under vmap).  `yg` is None for
        unsupervised fits; classifiers receive already-encoded labels.
        Families whose loss consumes extra keys (MLPRegressor's
        "y_target") override this so the contract lives with the family.
        """
        import jax
        import jax.numpy as jnp

        if yg is None:
            return {"X": Xg}
        if cls.is_classifier:
            yi = yg.astype(jnp.int32)
            return {"X": Xg, "y": yi,
                    "y1h": jax.nn.one_hot(yi, meta["n_classes"],
                                          dtype=Xg.dtype)}
        return {"X": Xg, "y": yg.astype(Xg.dtype)}

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        raise NotImplementedError

    @classmethod
    def predict(cls, model, static, X, meta):
        raise NotImplementedError

    @classmethod
    def decision(cls, model, static, X, meta):
        """Margins/logits for log-loss & AUC scorers; optional."""
        raise NotImplementedError

    # --- what a launch reports about its solver --------------------------
    # Two hooks, one traced and one on the host, keyed by the ``stat``
    # names of ``obs.metrics.LAUNCH_STATS``: that table declares how each
    # combines across a bisected chunk and the ``search_report`` series
    # it feeds, and the engine (search/launch.py) carries the values
    # there unread.  A new solver counter is an entry here, a row there.
    @classmethod
    def launch_stats(cls, models, static, meta) -> Dict[str, Any]:
        """Traced, once inside a launch's program, on the fitted models
        of all its (candidate, fold) tasks: named int32 arrays, a scalar
        each or (for a "per_candidate" stat) one entry a task in
        candidate-major order.  Default: nothing where the model has no
        ``n_iter_exec`` / ``n_iter`` leaf (no iterative solver), else the
        launch's lockstep maximum and the sum over its lanes."""
        import jax.numpy as jnp

        it = None
        if isinstance(models, dict):
            # prefer the solver's true executed count over a
            # sklearn-facing rescale (FISTA reports n_iter on the
            # caller's max_iter axis but runs a larger internal budget)
            it = models.get("n_iter_exec", models.get("n_iter"))
        if it is None:
            return {}
        return {"solver_iters": jnp.max(it).astype(jnp.int32),
                "solver_iters_sum": jnp.sum(it).astype(jnp.int32)}

    @classmethod
    def launch_facts(cls, static, meta, n_candidates: int,
                     n_folds: int) -> Dict[str, int]:
        """On the host: what is known of a launch of ``n_candidates``
        (padding included) without running it, under the "fact" stats'
        names.  Recorded only for a launch that reports solver stats.
        Default: nothing."""
        return {}

    @classmethod
    def launch_workspace(cls, n_samples: int, meta, n_folds: int,
                         itemsize: int = 4, *, static=None,
                         row_sets: int = 1) -> Dict[str, int]:
        """On the host, once a compile group (`static` is the group's):
        what a launch holds on the device besides its arguments, for the
        memory ledger (``memledger.model_group_footprint(workspace=)``):
        ``fixed_bytes`` whatever the launch's width and
        ``per_candidate_bytes`` a candidate, all its folds.  `row_sets`
        is how many matrices of rows the launch's fits read: one, or one
        a fold behind a Pipeline's transformers.  Default: nothing is
        priced."""
        return {}

    @classmethod
    def launch_layout(cls, dynamic_params, static, meta, n_folds: int):
        """On the host, once a compile group, before its chunks are cut:
        an order of the group's candidates that the family's task-batched
        launch can use, and the static facts that say so.  `dynamic_params`
        holds the group's numpy arrays, one entry a candidate.  Returns
        ``(order, facts, run)`` — the engine permutes the group's
        candidates by `order` (``cv_results_`` is written through their
        indices, so its order does not move; a checkpoint's chunk ids say
        that a layout cut them) and `facts` join the group's ``static``,
        and with it the program's key — or None: the candidates stay as
        they came.  `run`: the group in that order is whole runs of `run`
        candidates, and the facts hold for a launch of whole runs.  The
        engine hands them to no other launch: a recovery's range or a
        cross-search fuse that cuts a run gets the program built without
        them.  Default: None."""
        return None

    # --- interop ---------------------------------------------------------
    @classmethod
    def sklearn_attrs(cls, model, static, meta) -> Dict[str, Any]:
        """Fitted-attribute dict (coef_, intercept_, classes_...) used by
        Converter and by refit write-back."""
        raise NotImplementedError


#: the name of the candidate axis in the engine's per-task fit launch
#: (``search/grid.py``: ``vmap(one_candidate, axis_name=CANDIDATE_AXIS)``)
CANDIDATE_AXIS = "sst_candidates"


def any_candidate(flag):
    """Traced: whether `flag` holds in ANY lane of the launch's candidate
    axis, one value for all of them — or the lane's own flag where no
    such axis is bound (a direct ``family.fit``, the keyed fleet).

    A ``lax.while_loop`` whose condition comes through here is not
    batched over candidates by ``vmap``, so neither is what the loop
    carries and no candidate changes (an epoch counter, a PRNG key, the
    minibatch they draw): JAX batches EVERY carried value of a loop
    whose condition is batched."""
    import jax
    import jax.numpy as jnp

    try:
        jax.lax.axis_size(CANDIDATE_AXIS)
    except NameError:       # the axis is not bound here
        return flag
    return jax.lax.pmax(flag.astype(jnp.int32), CANDIDATE_AXIS) > 0


def encode_labels(y):
    """Host-side label encoding shared by all classifier families."""
    classes, y_enc = np.unique(y, return_inverse=True)
    return classes, y_enc.astype(np.int32)


def class_weight_multiplier(mask, y_enc, meta, class_weight):
    """Per-sample weight multipliers for `class_weight` (traced).

    mask: (..., n) fold masks (possibly many tasks batched on leading
    axes); y_enc: (n,) encoded labels.  Returns a same-shape multiplier.

    - dict {label: weight}: fold-independent lookup (host-built table).
    - "balanced": sklearn's n_train / (n_classes * bincount(y_train)),
      computed per fold from the mask's support (mask > 0), exactly the
      train-fold counts compute_class_weight sees on the host path.
    """
    import jax
    import jax.numpy as jnp

    if class_weight is None:
        return None
    k = meta["n_classes"]
    y1h = jax.nn.one_hot(y_enc, k, dtype=mask.dtype)         # (n, k)
    if isinstance(class_weight, str):
        if class_weight != "balanced":
            raise NotCompiledError(
                f"class_weight={class_weight!r} is not compiled; use "
                "backend='host'")
        ind = (mask > 0).astype(mask.dtype)                  # (..., n)
        cnt = ind @ y1h                                      # (..., k)
        n_eff = jnp.sum(ind, axis=-1, keepdims=True)         # (..., 1)
        per_class = n_eff / (k * jnp.maximum(cnt, 1.0))      # (..., k)
        return per_class @ y1h.T                             # (..., n)
    if isinstance(class_weight, dict):
        classes = list(meta["classes"])
        cw = np.ones(k, np.float64)
        for label, weight in class_weight.items():
            hits = [i for i, c in enumerate(classes) if c == label]
            if not hits:
                # sklearn raises its own wording on the host path
                raise ValueError(
                    f"class_weight key {label!r} is not a class label")
            cw[hits[0]] = weight
        arr = jnp.asarray(cw, mask.dtype)
        return jnp.broadcast_to(arr[y_enc], mask.shape)
    raise NotCompiledError(
        f"class_weight={class_weight!r} is not compiled; use "
        "backend='host'")


def apply_class_weight(mask, y_enc, meta, class_weight):
    """mask with `class_weight` multiplied in (identity when None)."""
    mult = class_weight_multiplier(mask, y_enc, meta, class_weight)
    return mask if mult is None else mask * mult
