"""JAX scorer registry for the compiled (Tier A) search path.

The reference passes sklearn scorer objects into `_fit_and_score` on CPU
executors (reference: grid_search.py -> sklearn scorers).  Inside a jitted
program a scorer must be a pure function over fixed-shape arrays, with the
test fold expressed as a weight mask.  Every scorer here matches the sklearn
metric of the same name on dense inputs (oracle-tested in
tests/test_scorers.py).

Weighted-mask convention: `w` is 1.0 on the fold's samples, 0.0 elsewhere;
all means are weighted means over `w`.

Every metric is split into a **view requirement** and a **metric core**:
views are the model's outputs on the dataset ("pred", "decision",
"proba") and cores are pure reductions `core(views, y, w, meta)`.  The
split is what lets the search engine compute each view ONCE per launch
for ALL (candidate x fold) tasks — for linear families a single wide
matmul (`views_task_batched`) instead of one matvec per task per scorer —
and share it across every scorer in a multimetric search.  The public
callables keep the legacy per-task signature
`(family, model, static, data, meta, w)` for direct use and tests.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import NotCompiledError

EPS = 1e-12

#: view name -> per-task builder (the generic path; families may batch
#: these over the task axis themselves via `views_task_batched`)
VIEW_BUILDERS: Dict[str, Callable] = {}


def _wsum(w):
    return jnp.sum(w) + EPS


def _feats(data):
    """Families that pre-transform the dataset (binned trees) carry their
    own representation; predict implementations know which they expect."""
    return data["X"] if "X" in data else data["codes"]


def build_view(name, family, model, static, data, meta):
    return VIEW_BUILDERS[name](family, model, static, data, meta)


VIEW_BUILDERS["pred"] = lambda family, model, static, data, meta: \
    family.predict(model, static, _feats(data), meta)
VIEW_BUILDERS["decision"] = lambda family, model, static, data, meta: \
    family.decision(model, static, _feats(data), meta)
VIEW_BUILDERS["proba"] = lambda family, model, static, data, meta: \
    family.predict_proba(model, static, _feats(data), meta)


def _scorer(*views):
    """Wrap a metric core into the legacy per-task scorer callable while
    exposing `.views` / `.core` for the engine's task-batched path."""
    def deco(core):
        def fn(family, model, static, data, meta, w):
            v = {name: build_view(name, family, model, static, data, meta)
                 for name in views}
            return core(v, data["y"], w, meta)
        fn.views = views
        fn.core = core
        fn.__name__ = core.__name__
        fn.__doc__ = core.__doc__
        return fn
    return deco


@_scorer("pred")
def _accuracy(v, y, w, meta):
    return jnp.sum(w * (v["pred"] == y)) / _wsum(w)


@_scorer("proba")
def _neg_log_loss(v, y, w, meta):
    proba = v["proba"]
    # sklearn's log_loss clips to [eps, 1-eps] at the PROBA DTYPE's
    # machine eps (_classification.py _log_loss) — and the dtype that
    # matters is the ORACLE's (libsvm/forest/KNN probas are always f64;
    # LogReg/MLP/NB preserve the user's X dtype), which the engine
    # resolves per family into meta["logloss_clip_eps"].  An f32-proba
    # oracle charges a confidently-wrong sample -log(1.19e-7) ~ 15.9
    # where an f64 one charges ~36; with saturating families (NB) that
    # difference dominated the whole score.
    # fallback for direct/legacy callers whose meta came straight from
    # prepare_data: f64 eps, the pre-round-5 behavior (the engine path
    # always sets the per-family key)
    eps = meta.get("logloss_clip_eps") or float(np.finfo(np.float64).eps)
    p = jnp.clip(proba[jnp.arange(proba.shape[0]), y], eps, 1.0 - eps)
    return -(jnp.sum(w * -jnp.log(p)) / _wsum(w))


def _binary_counts(pred, y, w, positive=1):
    tp = jnp.sum(w * ((pred == positive) & (y == positive)))
    fp = jnp.sum(w * ((pred == positive) & (y != positive)))
    fn = jnp.sum(w * ((pred != positive) & (y == positive)))
    return tp, fp, fn


@_scorer("pred")
def _f1(v, y, w, meta):
    tp, fp, fn = _binary_counts(v["pred"], y, w)
    return 2 * tp / jnp.maximum(2 * tp + fp + fn, EPS)


@_scorer("pred")
def _precision(v, y, w, meta):
    tp, fp, fn = _binary_counts(v["pred"], y, w)
    return tp / jnp.maximum(tp + fp, EPS)


@_scorer("pred")
def _recall(v, y, w, meta):
    tp, fp, fn = _binary_counts(v["pred"], y, w)
    return tp / jnp.maximum(tp + fn, EPS)


@_scorer("pred")
def _f1_macro(v, y, w, meta):
    pred = v["pred"]
    k = meta["n_classes"]

    def per_class(c):
        tp = jnp.sum(w * ((pred == c) & (y == c)))
        fp = jnp.sum(w * ((pred == c) & (y != c)))
        fn = jnp.sum(w * ((pred != c) & (y == c)))
        return 2 * tp / jnp.maximum(2 * tp + fp + fn, EPS)

    return jnp.mean(jax.vmap(per_class)(jnp.arange(k)))


@_scorer("pred")
def _balanced_accuracy(v, y, w, meta):
    """Macro-average recall over classes present in the fold (sklearn
    semantics: classes absent from y_true drop out of the mean)."""
    pred = v["pred"]
    k = meta["n_classes"]

    def per_class(c):
        support = jnp.sum(w * (y == c))
        tp = jnp.sum(w * ((pred == c) & (y == c)))
        rec = tp / jnp.maximum(support, EPS)
        return rec, (support > 0).astype(rec.dtype)

    recalls, present = jax.vmap(per_class)(jnp.arange(k))
    return jnp.sum(recalls * present) / jnp.maximum(jnp.sum(present), 1.0)


@_scorer("pred")
def _explained_variance(v, y, w, meta):
    err = y - v["pred"]
    ebar = jnp.sum(w * err) / _wsum(w)
    var_err = jnp.sum(w * (err - ebar) ** 2) / _wsum(w)
    ybar = jnp.sum(w * y) / _wsum(w)
    var_y = jnp.sum(w * (y - ybar) ** 2) / _wsum(w)
    return 1.0 - var_err / jnp.maximum(var_y, EPS)


@_scorer("pred")
def _neg_msle(v, y, w, meta):
    # sklearn RAISES on negative targets/predictions; inside a compiled
    # program we return NaN instead, which surfaces through the
    # non-finite-score warning rather than silently scoring a clamp
    pred = v["pred"]
    invalid = jnp.sum(w * ((y < 0) | (pred < 0)).astype(w.dtype)) > 0
    ly = jnp.log1p(jnp.maximum(y, 0.0))
    lp = jnp.log1p(jnp.maximum(pred, 0.0))
    val = -(jnp.sum(w * (ly - lp) ** 2) / _wsum(w))
    return jnp.where(invalid, jnp.nan, val)


@_scorer("decision")
def _roc_auc(v, y, w, meta):
    """Weighted binary AUC via the rank/Mann-Whitney statistic."""
    s = v["decision"]
    y = y.astype(s.dtype)
    order = jnp.argsort(s)
    s_s, y_s, w_s = s[order], y[order], w[order]
    # weighted rank = cumulative weight; ties handled approximately (exact
    # tie-averaging needs segment means — acceptable for continuous margins)
    cw = jnp.cumsum(w_s) - 0.5 * w_s
    pos = jnp.sum(w_s * y_s)
    neg = jnp.sum(w_s * (1.0 - y_s))
    rank_pos = jnp.sum(w_s * y_s * cw)
    return (rank_pos - 0.5 * pos * pos) / jnp.maximum(pos * neg, EPS)


@_scorer("pred")
def _r2(v, y, w, meta):
    pred = v["pred"]
    ybar = jnp.sum(w * y) / _wsum(w)
    ss_res = jnp.sum(w * (y - pred) ** 2)
    ss_tot = jnp.sum(w * (y - ybar) ** 2)
    return 1.0 - ss_res / jnp.maximum(ss_tot, EPS)


def _neg_mse_core(v, y, w, meta):
    return -(jnp.sum(w * (y - v["pred"]) ** 2) / _wsum(w))


_neg_mse = _scorer("pred")(_neg_mse_core)


@_scorer("pred")
def _neg_rmse(v, y, w, meta):
    return -jnp.sqrt(-_neg_mse_core(v, y, w, meta))


@_scorer("pred")
def _neg_mae(v, y, w, meta):
    return -(jnp.sum(w * jnp.abs(y - v["pred"])) / _wsum(w))


@_scorer("pred")
def _neg_median_ae(v, y, w, meta):
    # weighted median via sorting on |err| with mask-weights; when the
    # cumulative weight hits exactly half (even-sized unweighted folds),
    # average the two middle errors the way np.median does
    err = jnp.abs(y - v["pred"])
    order = jnp.argsort(err)
    e_s, w_s = err[order], w[order]
    cw = jnp.cumsum(w_s)
    half = 0.5 * jnp.sum(w_s)
    n = err.shape[0]
    idx_lo = jnp.clip(jnp.searchsorted(cw, half), 0, n - 1)
    idx_hi = jnp.clip(jnp.searchsorted(cw, half, side="right"), 0, n - 1)
    lo, hi = e_s[idx_lo], e_s[idx_hi]
    return -jnp.where(cw[idx_lo] == half, 0.5 * (lo + hi), lo)


@_scorer("pred")
def _max_error(v, y, w, meta):
    return -jnp.max(w * jnp.abs(y - v["pred"]))


SCORERS: Dict[str, Callable] = {
    "accuracy": _accuracy,
    "balanced_accuracy": _balanced_accuracy,
    "explained_variance": _explained_variance,
    "neg_mean_squared_log_error": _neg_msle,
    "neg_log_loss": _neg_log_loss,
    "f1": _f1,
    "f1_macro": _f1_macro,
    "precision": _precision,
    "recall": _recall,
    "roc_auc": _roc_auc,
    "r2": _r2,
    "neg_mean_squared_error": _neg_mse,
    "neg_root_mean_squared_error": _neg_rmse,
    "neg_mean_absolute_error": _neg_mae,
    "neg_median_absolute_error": _neg_median_ae,
    "max_error": _max_error,        # legacy sklearn name
    "neg_max_error": _max_error,    # sklearn >= 1.6 name
}


#: scorers that need label/class structure (meta["n_classes"]) — consulted
#: by the engine's pre-sweep validation so mismatches fail clearly
CLASSIFICATION_SCORERS = {
    "accuracy", "balanced_accuracy", "neg_log_loss", "f1", "f1_macro",
    "precision", "recall", "roc_auc",
}
#: binary-only compiled implementations (multiclass variants live on the
#: host path with sklearn's averaging semantics)
BINARY_ONLY_SCORERS = {"f1", "precision", "recall", "roc_auc"}

#: compiled impls whose sklearn twin does NOT accept sample_weight; the
#: engine scores these with unweighted masks even in a weighted search,
#: mirroring _MultimetricScorer's per-scorer forwarding
SAMPLE_WEIGHT_BLIND_FNS = frozenset({_max_error})


#: make_scorer(_score_func, sign) -> compiled scorer name; consulted so
#: user-built `make_scorer(accuracy_score)`-style objects (with default
#: kwargs) stay on the compiled path instead of de-optimizing to host
_SCORE_FUNC_TABLE = {
    ("accuracy_score", 1): "accuracy",
    ("balanced_accuracy_score", 1): "balanced_accuracy",
    ("recall_score", 1): "recall",
    ("precision_score", 1): "precision",
    ("f1_score", 1): "f1",
    ("roc_auc_score", 1): "roc_auc",
    ("log_loss", -1): "neg_log_loss",
    ("r2_score", 1): "r2",
    ("explained_variance_score", 1): "explained_variance",
    ("mean_squared_error", -1): "neg_mean_squared_error",
    ("root_mean_squared_error", -1): "neg_root_mean_squared_error",
    ("mean_absolute_error", -1): "neg_mean_absolute_error",
    ("median_absolute_error", -1): "neg_median_absolute_error",
    ("mean_squared_log_error", -1): "neg_mean_squared_log_error",
    ("max_error", -1): "max_error",
}


def compiled_name_for_scorer(obj):
    """Map a sklearn make_scorer object with default kwargs to the
    equivalent compiled scorer name, or None when it has no compiled
    twin (custom kwargs, custom callables, pos_label overrides...)."""
    from sklearn.metrics._scorer import _Scorer
    if not isinstance(obj, _Scorer):
        return None
    if getattr(obj, "_kwargs", None):
        return None
    fn_name = getattr(getattr(obj, "_score_func", None), "__name__", None)
    sign = getattr(obj, "_sign", 1)
    name = _SCORE_FUNC_TABLE.get((fn_name, sign))
    return name if name in SCORERS else None


def resolve_scoring(scoring, family):
    """scoring arg -> ordered {name: jax scorer}.  None uses the estimator
    default (accuracy / r2) like sklearn's check_scoring."""
    if scoring is None:
        default = getattr(family, "default_scorer", None)
        if default is not None:   # e.g. KMeans: -inertia
            return {"score": default}, "score"
        name = "accuracy" if family.is_classifier else "r2"
        return {"score": SCORERS[name]}, "score"
    if isinstance(scoring, str):
        if scoring not in SCORERS:
            raise NotCompiledError(
                f"scoring={scoring!r} has no compiled implementation; "
                f"available: {sorted(SCORERS)} (or use backend='host')")
        return {"score": SCORERS[scoring]}, "score"
    obj_name = compiled_name_for_scorer(scoring)
    if obj_name is not None:
        return {"score": SCORERS[obj_name]}, "score"
    if isinstance(scoring, (list, tuple, set)):
        # sklearn's contract: list/tuple scoring must be unique metric-name
        # STRINGS (_check_multimetric_scoring rejects objects in lists) —
        # keep that behavior rather than canonicalizing objects here
        out = {}
        for s in scoring:
            if not isinstance(s, str) or s not in SCORERS:
                raise NotCompiledError(
                    f"scoring entry {s!r} not compiled (list scoring takes "
                    "unique metric-name strings); use backend='host'")
            out[s] = SCORERS[s]
        return out, None
    if isinstance(scoring, dict):
        out = {}
        for name, s in scoring.items():
            if not isinstance(s, str):
                s = compiled_name_for_scorer(s)
            if s is None or s not in SCORERS:
                raise NotCompiledError(
                    f"multimetric entry {name}={scoring[name]!r} not "
                    "compiled; use backend='host'")
            out[name] = SCORERS[s]
        return out, None
    raise NotCompiledError(
        f"scoring spec {scoring!r} is not compiled; use backend='host'")
