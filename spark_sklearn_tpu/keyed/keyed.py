"""KeyedEstimator / KeyedModel — per-key model fleets.

Reference: python/spark_sklearn/keyed_models.py — a pyspark.ml Estimator that
fits one sklearn estimator per key group of a DataFrame and stores the
fitted, *pickled* estimator inside a DataFrame column; transform joins on the
keys and applies per-row Python UDFs (call stack SURVEY §3.2).

TPU-native redesign: models live as **stacked parameter pytrees** with a
leading key axis when the estimator maps to a compiled family — one `vmap`
over keys replaces the per-key executor loop, and transform is one batched
gather + predict instead of a join shipping pickles.  Estimators outside the
registry fall back to per-key host fits (full sklearn generality, same as
the reference's semantics minus Spark).

API mirrors the reference's Params:
  KeyedEstimator(sklearnEstimator=, keyCols=, xCol=, yCol=, outputCol=,
                 estimatorType=)   with estimatorType in
  {"predictor", "transformer", "clusterer"} (inferred when yCol is given).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import pandas as pd

from sklearn.base import BaseEstimator, clone


def _stack_x(col) -> np.ndarray:
    """Column of vectors/scalars -> 2-D float array."""
    first = col.iloc[0]
    if np.isscalar(first) or (hasattr(first, "shape") and
                              np.asarray(first).ndim == 0):
        return np.asarray(col, dtype=np.float64)[:, None]
    return np.stack([np.asarray(v, dtype=np.float64) for v in col])


def bucket_len(m: int, floor: int = 8) -> int:
    """Pad length for a group of m rows: next power of two (>= floor).

    Bucketed padding bounds the waste at 2x per group, so one huge key
    among thousands of small ones costs O(G_small * L_small + L_big)
    memory instead of the O(G * L_max) a single global pad would
    (SURVEY §3.2 redesign note; the round-1 fleet padded globally).
    Shared by the keyed fleets and gapply's compiled segment path.
    """
    L = floor
    while L < m:
        L *= 2
    return L


def run_bucketed(mats, encs, y_dtype, fit_one, launch=None):
    """The bucketed-fleet launcher shared by keyed fleets and gapply.

    mats: per-group (m_i, d) float32 arrays; encs: matching (m_i,) target
    arrays, or None for target-less fits (transformer steps, gapply
    segment funcs — `fit_one` then takes (Xg, wg) instead of
    (Xg, yg, wg)).  Each group is zero-padded to its bucket length, each
    bucket runs as one jit(vmap(fit_one)) program, and the stacked result
    pytrees are concatenated on the group axis.  `launch` overrides the
    per-bucket callable (callers that reuse a cached jit across calls).

    Returns (order, stacked): order[j] = index into `mats` of stacked
    row j.
    """
    import jax
    import jax.numpy as jnp

    if launch is None:
        launch = jax.jit(jax.vmap(fit_one))

    buckets: Dict[int, list] = {}
    for i, m in enumerate(mats):
        buckets.setdefault(bucket_len(len(m)), []).append(i)

    d = mats[0].shape[1]
    order, stacked = [], []
    for L in sorted(buckets):
        idxs = buckets[L]
        Xs = np.zeros((len(idxs), L, d), np.float32)
        ws = np.zeros((len(idxs), L), np.float32)
        ys = None if encs is None else np.zeros((len(idxs), L), y_dtype)
        for j, gi in enumerate(idxs):
            m = len(mats[gi])
            Xs[j, :m] = mats[gi]
            ws[j, :m] = 1.0
            if ys is not None:
                ys[j, :m] = encs[gi]
        args = [jnp.asarray(Xs)]
        if ys is not None:
            args.append(jnp.asarray(ys))
        args.append(jnp.asarray(ws))
        stacked.append(launch(*args))
        order.extend(idxs)
    if jax.tree_util.tree_leaves(stacked[0]):
        models = jax.tree_util.tree_map(
            lambda *leaves: jnp.concatenate(leaves, axis=0), *stacked)
    else:
        models = stacked[0]   # stateless result (e.g. Normalizer step)
    return order, models


class KeyedEstimator(BaseEstimator):
    """Fits one estimator per distinct key of a DataFrame.

    >>> ke = KeyedEstimator(sklearnEstimator=LinearRegression(),
    ...                     keyCols=["user"], xCol="x", yCol="y")
    >>> model = ke.fit(df)          # df: pandas DataFrame
    >>> model.transform(df2)        # adds model.outputCol per-key predictions
    """

    _TYPES = ("predictor", "transformer", "clusterer")

    def __init__(self, sklearnEstimator=None,
                 keyCols: Sequence[str] = ("key",),
                 xCol: str = "features", yCol: Optional[str] = None,
                 outputCol: str = "output",
                 estimatorType: Optional[str] = None):
        self.outputCol = outputCol
        if sklearnEstimator is None:
            raise ValueError("sklearnEstimator must be provided")
        if not hasattr(sklearnEstimator, "fit"):
            raise ValueError("sklearnEstimator must implement fit()")
        if yCol is not None and not hasattr(sklearnEstimator, "predict"):
            raise ValueError(
                "supervised (yCol given) requires a predictor estimator")
        self.sklearnEstimator = sklearnEstimator
        self.keyCols = list(keyCols)
        self.xCol = xCol
        self.yCol = yCol
        if estimatorType is None:
            estimatorType = "predictor" if yCol is not None else (
                "clusterer" if hasattr(sklearnEstimator, "predict")
                and not hasattr(sklearnEstimator, "transform")
                else "transformer")
        if estimatorType not in self._TYPES:
            raise ValueError(
                f"estimatorType must be one of {self._TYPES}, "
                f"got {estimatorType!r}")
        if yCol is not None and estimatorType != "predictor":
            raise ValueError(
                "estimatorType must be 'predictor' when yCol is given")
        # transform-time requirements checked up front (the reference's
        # Param validation equivalent): predictor/clusterer apply predict,
        # transformer applies transform — transductive estimators like
        # DBSCAN (no predict) cannot serve as keyed clusterers
        needed = ("transform" if estimatorType == "transformer"
                  else "predict")
        if not hasattr(sklearnEstimator, needed):
            raise ValueError(
                f"estimatorType={estimatorType!r} requires an estimator "
                f"with a {needed}() method; "
                f"{type(sklearnEstimator).__name__} has none")
        self.estimatorType = estimatorType

    def fit(self, df: pd.DataFrame) -> "KeyedModel":
        missing = [c for c in self.keyCols + [self.xCol] if c not in df]
        if self.yCol is not None and self.yCol not in df:
            missing.append(self.yCol)
        if missing:
            raise KeyError(f"DataFrame is missing columns: {missing}")

        work = df.reset_index(drop=True)   # positional index for gathers
        keys, slices = [], []
        for key, pdf in work.groupby(self.keyCols, sort=True):
            if not isinstance(key, tuple):
                key = (key,)
            keys.append(key)
            slices.append(pdf)

        if self.estimatorType == "transformer":
            fleet, host_pairs = self._fit_transformer_fleet(
                work, keys, slices)
        else:
            fleet, host_pairs = self._fit_family_fleet(work, keys, slices)

        models: Optional[Dict[tuple, Any]] = None
        if host_pairs:
            models = {}
            for key, pdf in host_pairs:
                X = _stack_x(pdf[self.xCol])
                est = clone(self.sklearnEstimator)
                if self.yCol is not None:
                    est.fit(X, np.asarray(pdf[self.yCol]))
                else:
                    est.fit(X)
                models[key] = est
        return KeyedModel(
            keyCols=self.keyCols, xCol=self.xCol, yCol=self.yCol,
            outputCol=self.outputCol,
            estimatorType=self.estimatorType, models=models, fleet=fleet)

    _bucket_len = staticmethod(bucket_len)

    def _fit_family_fleet(self, work, keys, slices):
        """The TPU-native per-key fleet: keys become vmap axes.

        Groups are padded to per-bucket maxima with zero sample weights
        (same fixed-shape trick as CV fold masks), each bucket's keys are
        fitted by one jitted vmapped program, and the fleet lives as ONE
        stacked parameter pytree with a leading key axis (bucket results
        are concatenated — model shapes depend on d/k, never on group
        length) — replacing the reference's pickled-estimator-per-row
        DataFrame column (reference: keyed_models.py stores cloudpickled
        sklearn models; SURVEY §3.2).

        Returns (fleet | None, host_pairs): keys the compiled path cannot
        serve — no compiled family, too few rows for the estimator, or a
        classifier key lacking some of the global classes (per-key
        classes_ semantics) — are returned for per-key host fits instead
        of failing the whole fleet to the host loop.
        """
        from spark_sklearn_tpu.models.base import resolve_family

        pairs = list(zip(keys, slices))
        if not pairs:
            return None, pairs
        family = resolve_family(self.sklearnEstimator)
        if family is None or not family.has_per_task_fit() or \
                not getattr(family, "keyed_compatible", True):
            return None, pairs

        X_all = _stack_x(work[self.xCol]).astype(np.float32)
        unsupervised = self.yCol is None
        y_all = None if unsupervised else np.asarray(work[self.yCol])
        try:
            _, meta = family.prepare_data(X_all, y_all)
        except Exception as exc:
            # unsupported data shape/labels for the compiled family —
            # fall back to per-key host fits, but leave a trace of why
            # instead of a silent swallow
            from spark_sklearn_tpu.obs.log import get_logger
            get_logger(__name__).debug(
                "keyed fleet: prepare_data rejected the stacked data "
                "(%r); using per-key host fits", exc)
            return None, pairs
        static = family.extract_params(self.sklearnEstimator)
        min_needed = (family.min_group_size(static)
                      if hasattr(family, "min_group_size") else 1)

        if unsupervised:
            enc = None   # no targets: _fit_bucketed uses 2-arg fit_one
        elif family.is_classifier:
            lookup = {v: i for i, v in enumerate(meta["classes"])}
            enc = np.array([lookup[v] for v in y_all], np.float64)
        else:
            enc = np.asarray(y_all, np.float64)

        eligible, host_pairs = [], []
        for key, pdf in pairs:
            if len(pdf) < min_needed:
                # too few rows for this estimator on the compiled path
                # (e.g. fewer samples than n_clusters) — host fit raises
                # per key the way sklearn would
                host_pairs.append((key, pdf))
            elif not unsupervised and family.is_classifier and \
                    len(set(enc[pdf.index.to_numpy()])) < meta["n_classes"]:
                # per-key classes_ semantics: a key whose group lacks some
                # of the global classes must be fitted over its OWN label
                # set, which only the host loop does
                host_pairs.append((key, pdf))
            else:
                eligible.append((key, pdf))
        if not eligible:
            return None, host_pairs

        if unsupervised:
            def fit_one(Xg, wg):
                return family.fit(
                    {}, static, family.build_fit_data(Xg, None, meta),
                    wg, meta)
        else:
            def fit_one(Xg, yg, wg):
                return family.fit(
                    {}, static, family.build_fit_data(Xg, yg, meta),
                    wg, meta)

        y_dtype = np.int32 if (not unsupervised and family.is_classifier) \
            else np.float32
        try:
            fleet_keys, models = self._fit_bucketed(
                eligible, X_all, enc, y_dtype, fit_one)
        except Exception as exc:
            import warnings
            warnings.warn(
                f"compiled keyed fleet failed ({exc!r}); falling back to "
                "per-key host fits", UserWarning)
            return None, host_pairs + eligible
        return dict(
            kind="family", family=family, models=models, meta=meta,
            static=static,
            key_index={k: i for i, k in enumerate(fleet_keys)}), host_pairs

    def _fit_bucketed(self, eligible, X_all, enc, y_dtype, fit_one):
        """Adapter over the module-level `run_bucketed` launcher: slices
        per-key group matrices/targets out of the full arrays and maps the
        launcher's order back to keys.  Returns (keys_in_fleet_order,
        stacked_models)."""
        mats = [X_all[pdf.index.to_numpy()] for _, pdf in eligible]
        encs = None if enc is None else \
            [enc[pdf.index.to_numpy()] for _, pdf in eligible]
        order, models = run_bucketed(mats, encs, y_dtype, fit_one)
        return [eligible[i][0] for i in order], models

    def _fit_transformer_fleet(self, work, keys, slices):
        """Compiled transformer-type fleets: one vmapped weighted-stats fit
        per bucket over the preprocessing steps (StandardScaler and
        friends), stored as a stacked state pytree — transform is a gather
        on the key axis + the step's pure apply."""
        from spark_sklearn_tpu.models.preprocessing import resolve_step

        pairs = list(zip(keys, slices))
        if not pairs:
            return None, pairs
        step = resolve_step(self.sklearnEstimator)
        if step is None:
            return None, pairs

        static = dict(self.sklearnEstimator.get_params(deep=False))
        X_all = _stack_x(work[self.xCol]).astype(np.float32)
        if hasattr(step, "check_static"):
            try:
                step.check_static(static, X_all.shape[1])
            except ValueError:
                # configs the compiled path cannot serve (PCA 'mle'/None
                # n_components, out-of-range widths) go straight to the
                # host loop — sklearn raises its own error there if the
                # config is genuinely invalid; the warning below is
                # reserved for unexpected fleet failures
                return None, pairs
        min_needed = (step.min_group_size(static)
                      if hasattr(step, "min_group_size") else 1)

        eligible, host_pairs = [], []
        for key, pdf in pairs:
            (eligible if len(pdf) >= min_needed else host_pairs).append(
                (key, pdf))
        if not eligible:
            return None, host_pairs

        try:
            fleet_keys, states = self._fit_bucketed(
                eligible, X_all, None, None,
                lambda Xg, wg: step.fit(static, Xg, wg))
        except Exception as exc:
            # unsupported static config (e.g. PCA 'mle') -> host loop
            import warnings
            warnings.warn(
                f"compiled keyed transformer fleet failed ({exc!r}); "
                "falling back to per-key host fits", UserWarning)
            return None, host_pairs + eligible
        return dict(
            kind="step", step=step, models=states, meta={}, static=static,
            key_index={k: i for i, k in enumerate(fleet_keys)}), host_pairs


class TpuTransformer:
    """A fitted transformer state as its device representation — the
    transformer-type counterpart of converter.TpuModel, exposed per key by
    `KeyedModel.keyedModels`."""

    def __init__(self, step, state, static):
        self.step = step
        self.state = state
        self.static = static

    def transform(self, X):
        import jax.numpy as jnp
        X = jnp.asarray(np.asarray(X), jnp.float32)
        return np.asarray(self.step.apply(self.static, self.state, X))

    def __repr__(self):
        return f"TpuTransformer(step={self.step.name})"


class KeyedModel:
    """The fitted per-key fleet.  `keyedModels` exposes the per-key
    estimators as a DataFrame like the reference's model DataFrame (minus
    the pickling)."""

    def __init__(self, keyCols, xCol, yCol, outputCol, estimatorType,
                 models: Optional[Dict[tuple, Any]], fleet=None):
        self.keyCols = list(keyCols)
        self.xCol = xCol
        self.yCol = yCol
        self.outputCol = outputCol
        self.estimatorType = estimatorType
        self.models = models            # host fleet: {key: fitted sklearn}
        self.fleet = fleet              # compiled fleet: stacked pytrees

    @property
    def backend(self) -> str:
        """"tpu" (all keys in the compiled fleet), "host" (all keys fitted
        by the per-key sklearn loop), or "hybrid" (keys the compiled path
        cannot serve — too small, missing classes — were host-fitted while
        the rest stayed on the fleet)."""
        if self.fleet is not None and self.models:
            return "hybrid"
        return "tpu" if self.fleet is not None else "host"

    @property
    def keyedModels(self) -> pd.DataFrame:
        """One row per key with an `estimator` cell that supports
        `.predict`/`.transform` on BOTH backends (fitted sklearn estimator
        on the host path, a TpuModel/TpuTransformer view of the stacked
        pytree on the fleet path)."""
        rows = []
        if self.fleet is not None:
            import jax
            from spark_sklearn_tpu.convert.converter import TpuModel
            for key, i in self.fleet["key_index"].items():
                leaf = jax.tree_util.tree_map(
                    lambda a: a[i], self.fleet["models"])
                if self.fleet["kind"] == "step":
                    view: Any = TpuTransformer(
                        self.fleet["step"], leaf, self.fleet["static"])
                else:
                    view = TpuModel(self.fleet["family"], leaf,
                                    self.fleet["static"], self.fleet["meta"])
                rows.append(dict(zip(self.keyCols, key), estimator=view))
        if self.models:
            for key, est in self.models.items():
                rows.append(dict(zip(self.keyCols, key), estimator=est))
        return pd.DataFrame(rows)

    def transform(self, df: pd.DataFrame) -> pd.DataFrame:
        """Per-key apply: predictor -> predict (float), clusterer -> predict
        (int), transformer -> transform (vector).  Keys never seen in fit
        yield NaN/None rows (the reference's join drops them; keeping the
        row with a null is the friendlier DataFrame-native contract)."""
        # positional reassembly: robust to duplicate index labels and to
        # NaN keys (groupby(dropna=False) keeps those rows; their key has no
        # fitted model so they get null output)
        orig_index = df.index
        work = df.reset_index(drop=True)
        out_values: List[Any] = [None] * len(work)
        fleet_groups = []
        for key, pdf in work.groupby(self.keyCols, sort=False, dropna=False):
            if not isinstance(key, tuple):
                key = (key,)
            pos = pdf.index.to_numpy()
            if self.fleet is not None and \
                    key in self.fleet["key_index"]:
                # deferred: all fleet keys predict together, bucketed —
                # one device launch per bucket instead of one per key
                fleet_groups.append((key, pdf, pos))
                continue
            est = self.models.get(key) if self.models else None
            if est is None:
                fill = None if self.estimatorType == "transformer" else np.nan
                for p in pos:
                    out_values[p] = fill
            else:
                X = _stack_x(pdf[self.xCol])
                if self.estimatorType == "transformer":
                    vals = list(np.asarray(est.transform(X)))
                elif self.estimatorType == "clusterer":
                    vals = list(np.asarray(est.predict(X), dtype=np.int64))
                else:
                    pred = np.asarray(est.predict(X))
                    if np.issubdtype(pred.dtype, np.number):
                        pred = pred.astype(np.float64)
                    vals = list(pred)  # string labels pass through as-is
                for p, v in zip(pos, vals):
                    out_values[p] = v
        if fleet_groups:
            for (key, pdf, pos), vals in zip(
                    fleet_groups, self._fleet_predict_all(fleet_groups)):
                for p, v in zip(pos, vals):
                    out_values[p] = v
        res = df.copy()
        res[self.outputCol] = pd.Series(out_values, index=orig_index)
        return res

    def _fleet_predict_all(self, fleet_groups):
        """Bucketed batch predict/transform from the stacked-pytree
        fleet: groups are padded to bucket lengths, each bucket runs ONE
        vmapped program over (gathered model, padded rows) — a per-key
        device dispatch (~ms of launch latency each) would dominate
        transform wall at fleet scale.  Yields one value list per group,
        in `fleet_groups` order."""
        import jax
        import jax.numpy as jnp

        fleet = self.fleet
        static = fleet["static"]
        if fleet["kind"] == "step":
            step = fleet["step"]

            def predict_one(model, X):
                return step.apply(static, model, X)
        else:
            fam = fleet["family"]
            meta = fleet["meta"]

            def predict_one(model, X):
                return fam.predict(model, static, X, meta)

        launch = jax.jit(jax.vmap(predict_one))
        mats = [_stack_x(pdf[self.xCol]).astype(np.float32)
                for _, pdf, _ in fleet_groups]
        midx = np.asarray([fleet["key_index"][key]
                           for key, _, _ in fleet_groups])
        buckets: Dict[int, list] = {}
        for i, m in enumerate(mats):
            buckets.setdefault(bucket_len(len(m)), []).append(i)
        outs: List[Any] = [None] * len(mats)
        d = mats[0].shape[1]
        for L in sorted(buckets):
            idxs = buckets[L]
            Xs = np.zeros((len(idxs), L, d), np.float32)
            for j, gi in enumerate(idxs):
                Xs[j, :len(mats[gi])] = mats[gi]
            models = jax.tree_util.tree_map(
                lambda a: a[midx[np.asarray(idxs)]], fleet["models"])
            Y = np.asarray(launch(models, jnp.asarray(Xs)))
            for j, gi in enumerate(idxs):
                outs[gi] = Y[j, :len(mats[gi])]
        for out in outs:
            if fleet["kind"] == "step":
                yield list(out.astype(np.float64))
            elif fleet["family"].is_classifier:
                yield list(fleet["meta"]["classes"][out.astype(np.int64)])
            elif self.estimatorType == "clusterer":
                yield list(out.astype(np.int64))
            else:
                yield list(out.astype(np.float64))
