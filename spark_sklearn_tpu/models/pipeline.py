"""Compiled sklearn Pipeline support.

Reference behavior: a Pipeline is just another estimator cloned and fitted
whole inside each Spark task, with grid keys like "mlp__alpha" routed by
sklearn's set_params (BASELINE config #5).  Here a Pipeline whose
transformers are all registered preprocessing steps and whose final step is
a compiled family becomes a **fused family**: transformer statistics are
weighted by the fold mask, the transform feeds the final fit inside the same
XLA program (no materialised intermediates), and "step__param" grid keys are
routed to dynamic/static leaves (SURVEY §7.3 hard part #5).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from spark_sklearn_tpu.models import preprocessing as prep
from spark_sklearn_tpu.models.base import Family, resolve_family
from spark_sklearn_tpu.utils.checkpoint import fingerprint


class PipelineFamily:
    """Instance-level family (duck-typed to the Family protocol) built for a
    concrete sklearn Pipeline."""

    #: sklearn raises on a bare sample_weight to Pipeline.fit (step
    #: routing requires "step__sample_weight"); weighted searches take the
    #: host path so that contract is reproduced, not silently reinvented
    accepts_sample_weight = False

    def __init__(self, steps: List[Tuple[str, Any]], final_name: str,
                 final_family):
        self.steps = steps              # [(name, StepImpl), ...] transformers
        self.final_name = final_name
        self.final = final_family
        self.name = f"pipeline({'+'.join(n for n, _ in steps)}" \
                    f"+{final_family.name})"
        self.is_classifier = final_family.is_classifier
        # the sklearn twin's proba dtype is the FINAL step's fact (the
        # transformers only feed it X) — forward it so log_loss clips
        # where the oracle pipeline clips
        self.proba_dtype_rule = getattr(
            final_family, "proba_dtype_rule", "float64")
        self.dynamic_params = {
            f"{final_name}__{k}": v
            for k, v in final_family.dynamic_params.items()
        }
        self._suffix_family: Optional["PipelineFamily"] = None
        if not final_family.has_per_task_fit() and \
                getattr(final_family, "task_batched_accepts_fold_inputs",
                        False):
            # task-batched-only finals (SVC): compose by feeding per-fold
            # transformed inputs into the final's task-batched fit
            self.fit_task_batched = self._fit_task_batched_folds
            hint = getattr(final_family, "max_tasks_hint", None)
            if hint is not None:
                self.max_tasks_hint = hint
        # forward the final step's default scorer (e.g. KMeans -> -inertia)
        # through the transformer chain
        final_default = getattr(final_family, "default_scorer", None)
        if final_default is not None:
            def default_scorer(family, model, static, data, meta, w,
                               _fd=final_default):
                Xt = family._transform(model, static, data["X"])
                return _fd(family.final, model["final"],
                           family._final_static(static),
                           {**data, "X": Xt}, meta, w)
            self.default_scorer = default_scorer

    # -- identity --------------------------------------------------------
    # A family is built anew for every search's Pipeline instance, and the
    # cross-search program cache keys on the family: two families of the
    # same steps and final step are the same family (the steps' PARAMS are
    # statics and key the programs themselves), so a second search of the
    # same Pipeline finds the first one's programs and builds none.
    def _identity(self):
        return (type(self), self.name, self.final_name, self.final,
                tuple(self.steps))

    def __eq__(self, other):
        return isinstance(other, PipelineFamily) \
            and self._identity() == other._identity()

    def __hash__(self):
        return hash(self._identity())

    def has_per_task_fit(self) -> bool:
        # task-batched-only finals (SVC) have no per-task fit to compose:
        # dispatchers that vmap one fit per lane (the keyed fleet) must
        # take their host path instead of tracing into NotImplementedError
        return self.final.has_per_task_fit()

    # -- what a launch reports, and holds --------------------------------
    # The final step's own hooks on the final step's model and statics: a
    # scaler in front changes nothing of what its solver counts.  Not for
    # a task-batched-only final (SVC), whose facts describe its bare
    # launch (one kernel matrix a candidate; here one a fold as well).
    def launch_stats(self, models, static, meta):
        if not self.final.has_per_task_fit():
            return {}
        return self.final.launch_stats(
            models["final"], self._final_static(static), meta)

    def launch_facts(self, static, meta, n_candidates, n_folds):
        if not self.final.has_per_task_fit():
            return {}
        return self.final.launch_facts(
            self._final_static(static), meta, n_candidates, n_folds)

    def launch_workspace(self, n_samples, meta, n_folds, itemsize=4, *,
                         static, row_sets=1):
        """The final step's workspace, told that it reads one matrix of
        rows a fold, and, fixed, those rows themselves: the shared-prefix
        stage's (folds, n, d) buffer (the fused fit holds the same as a
        temporary)."""
        if not self.final.has_per_task_fit():
            return {}
        sets = n_folds if self.steps else row_sets
        ws = dict(self.final.launch_workspace(
            n_samples, meta, n_folds, itemsize,
            static=self._final_static(static), row_sets=sets))
        if ws and self.steps:
            ws["fixed_bytes"] = ws.get("fixed_bytes", 0) + (
                n_folds * int(n_samples) * int(meta["n_features"])
                * itemsize)
        return ws

    def launch_layout(self, dynamic_params, static, meta, n_folds):
        """None: behind transformers a final step's fits read one matrix
        of rows a fold, and nothing of them is shared across candidates."""
        return None

    # -- host side -------------------------------------------------------
    def extract_params(self, estimator) -> Dict[str, Any]:
        out = {}
        for sname, step_est in estimator.named_steps.items():
            for k, v in step_est.get_params(deep=False).items():
                out[f"{sname}__{k}"] = v
        return out

    def prepare_data(self, X, y, dtype=np.float32):
        return self.final.prepare_data(X, y, dtype=dtype)

    def _split_static(self, static):
        per_step: Dict[str, Dict[str, Any]] = {n: {} for n, _ in self.steps}
        per_step[self.final_name] = {}
        for key, v in static.items():
            if "__" not in key:
                continue
            sname, pname = key.split("__", 1)
            if sname in per_step:
                per_step[sname][pname] = v
        return per_step

    # -- shared-prefix search support ------------------------------------
    def prefix_digest(self, static) -> Optional[str]:
        """Content digest of the transformer-chain configuration.

        Candidates whose digests match see the identical transformed
        design matrix: every step's params are static (steps expose no
        dynamic leaves) and the only other fit input is the fold mask,
        which the shared-prefix scheduler keys separately.  The final
        step's params are deliberately EXCLUDED — compile groups that
        differ only in final-step statics share the digest, so the
        cached prefix is reused across groups too.  None when the
        chain is empty (depth 0) or a step opted out of prefix safety.
        """
        if not self.steps:
            return None
        per_step = self._split_static(static)
        parts = []
        for sname, step in self.steps:
            if not getattr(step, "prefix_safe", False):
                return None
            parts.append((sname, getattr(step, "name", step.__name__),
                          tuple(sorted((k, repr(v)) for k, v in
                                       per_step[sname].items()))))
        return fingerprint("prefix-v1", tuple(parts))

    def prefix_transform(self, static, data, fold_w):
        """Prefix-only compiled transform: fold masks (F, n) -> the
        stacked per-fold transformed design matrix (F, n, d') with the
        exact mask-weighted statistics the fused fit computes inline
        (same ops, same order — the split is bit-exact by
        construction)."""
        import jax

        per_step = self._split_static(static)

        def tf(w_f):
            X = data["X"]
            for sname, step in self.steps:
                st = step.fit(per_step[sname], X, w_f)
                X = step.apply(per_step[sname], st, X)
            return X

        with jax.named_scope("sst.prefix.transform"):
            return jax.vmap(tf)(fold_w)                # (F, n, d')

    def suffix_family(self) -> "PipelineFamily":
        """The final-step-only family the shared-prefix scheduler fans
        over cached prefix matrices.  Cached per parent instance so
        program-cache keys (which hash family identity) stay stable
        across chunks/rungs; the name is distinct from the atomic
        pipeline's so persistent-store artifacts never alias programs
        traced on untransformed shapes."""
        if self._suffix_family is None:
            fam = PipelineFamily([], self.final_name, self.final)
            fam.name = f"suffix[{self.name}]"
            self._suffix_family = fam
        return self._suffix_family

    # -- device side -----------------------------------------------------
    def fit(self, dynamic, static, data, train_w, meta):
        per_step = self._split_static(static)
        final_dynamic = {
            k.split("__", 1)[1]: v for k, v in dynamic.items()
            if k.startswith(f"{self.final_name}__")
        }
        X = data["X"]
        states = []
        for sname, step in self.steps:
            st = step.fit(per_step[sname], X, train_w)
            X = step.apply(per_step[sname], st, X)
            states.append(st)
        final_model = self.final.fit(
            final_dynamic, per_step[self.final_name],
            {**data, "X": X}, train_w, meta)
        return {"steps": states, "final": final_model}

    def _fit_task_batched_folds(self, dynamic, static, data, w_task, meta):
        """Task-batched composition: the transformer chain is fitted per
        FOLD (first candidate's fold masks — tasks are candidate-major
        with identical fold masks across candidates) and the stacked
        (F, n, d) result feeds the final family's task-batched fit via
        data["X_folds"].  The final (SVC) caches full-dataset decisions,
        so scoring never needs the transformed X back."""
        import jax

        per_step = self._split_static(static)
        n_folds = int(static.get("__n_folds__", 0))
        if n_folds <= 0:
            raise ValueError("engine must pass __n_folds__")
        fold_w = w_task[:n_folds]                      # (F, n)

        def tf(w_f):
            X = data["X"]
            for sname, step in self.steps:
                st = step.fit(per_step[sname], X, w_f)
                X = step.apply(per_step[sname], st, X)
            return X

        X_folds = jax.vmap(tf)(fold_w)                 # (F, n, d')
        final_dynamic = {
            k.split("__", 1)[1]: v for k, v in dynamic.items()
            if k.startswith(f"{self.final_name}__")
        }
        final_static = {**per_step[self.final_name],
                        "__n_folds__": n_folds,
                        "__bf16__": static.get("__bf16__", False)}
        model = self.final.fit_task_batched(
            final_dynamic, final_static, {**data, "X_folds": X_folds},
            w_task, meta)
        # steps=None marks decision-cached mode: _transform is skipped
        # (the final never consumes X at scoring time)
        return {"steps": None, "final": model}

    def _transform(self, model, static, X):
        if model["steps"] is None:       # decision-cached task-batched mode
            return X
        per_step = self._split_static(static)
        for (sname, step), st in zip(self.steps, model["steps"]):
            X = step.apply(per_step[sname], st, X)
        return X

    def _final_static(self, static):
        return self._split_static(static)[self.final_name]

    def predict(self, model, static, X, meta):
        X = self._transform(model, static, X)
        return self.final.predict(model["final"], self._final_static(static),
                                  X, meta)

    def decision(self, model, static, X, meta):
        X = self._transform(model, static, X)
        return self.final.decision(model["final"],
                                   self._final_static(static), X, meta)

    def predict_proba(self, model, static, X, meta):
        X = self._transform(model, static, X)
        return self.final.predict_proba(
            model["final"], self._final_static(static), X, meta)

    def sklearn_attrs(self, model, static, meta):
        return self.final.sklearn_attrs(
            model["final"], self._final_static(static), meta)


class BinnedInvariantPipelineFamily:
    """Pipeline of monotone per-feature scalers feeding a histogram-tree
    final.  Quantile binning is invariant under strictly monotone
    per-feature maps, so the scaler steps provably cannot change the
    binned codes the tree consumes: the compiled fit/score delegate
    straight to the final family (the transform is the identity on
    codes), keeping scaler+GBDT/RF grids fully compiled — the TPU-first
    answer to BASELINE-config-#4/#5-shaped pipelines."""

    accepts_sample_weight = False    # same Pipeline.fit contract as above

    #: the protocol's defaults: the model IS the final step's, so its
    #: iteration leaves are reported as a bare tree family's are
    launch_stats = Family.launch_stats
    launch_facts = Family.launch_facts
    launch_workspace = Family.launch_workspace
    launch_layout = Family.launch_layout

    def __init__(self, final_name: str, final_family):
        self.final_name = final_name
        self.final = final_family
        self.name = f"pipeline(binned-invariant+{final_family.name})"
        self.is_classifier = final_family.is_classifier
        self.keyed_compatible = False
        self.dynamic_params = {
            f"{final_name}__{k}": v
            for k, v in final_family.dynamic_params.items()
        }
        if hasattr(final_family, "fit_task_batched"):
            # a forest's launch shares its trees across the candidates
            # (models/trees.py): the codes are the bare family's, so is
            # the launch
            self.fit_task_batched = self._fit_task_batched

    def has_per_task_fit(self) -> bool:
        return True

    def _strip(self, d):
        pref = f"{self.final_name}__"
        return {k[len(pref):]: v for k, v in d.items()
                if k.startswith(pref)}

    def extract_params(self, estimator) -> Dict[str, Any]:
        out = {}
        for sname, step_est in estimator.named_steps.items():
            for k, v in step_est.get_params(deep=False).items():
                out[f"{sname}__{k}"] = v
        return out

    def prepare_data(self, X, y, dtype=np.float32):
        return self.final.prepare_data(X, y, dtype=dtype)

    def observe_candidates(self, candidates, base_params, meta):
        if hasattr(self.final, "observe_candidates"):
            self.final.observe_candidates(
                [self._strip(c) for c in candidates],
                self._strip(base_params), meta)

    def fit(self, dynamic, static, data, train_w, meta):
        return self.final.fit(self._strip(dynamic), self._strip(static),
                              data, train_w, meta)

    def _fit_task_batched(self, dynamic, static, data, train_w, meta):
        return self.final.fit_task_batched(
            self._strip(dynamic),
            {**self._strip(static), "__n_folds__": static["__n_folds__"]},
            data, train_w, meta)

    def predict(self, model, static, X, meta):
        return self.final.predict(model, self._strip(static), X, meta)

    def decision(self, model, static, X, meta):
        return self.final.decision(model, self._strip(static), X, meta)

    def predict_proba(self, model, static, X, meta):
        return self.final.predict_proba(model, self._strip(static), X,
                                        meta)

    def sklearn_attrs(self, model, static, meta):
        return self.final.sklearn_attrs(model, self._strip(static), meta)


def make_pipeline_family(pipeline):
    """Pipeline instance -> a pipeline family, or None when any step is
    outside the compiled registries (-> Tier B host path runs the pipeline
    whole)."""
    try:
        steps = list(pipeline.steps)
    except AttributeError:
        return None
    if not steps:
        return None
    *transformers, (final_name, final_est) = steps
    resolved = []
    for sname, t in transformers:
        if t is None or t == "passthrough":
            continue
        step = prep.resolve_step(t)
        if step is None:
            return None
        resolved.append((sname, step))
    final_family = resolve_family(final_est)
    if final_family is None or isinstance(
            final_family, (PipelineFamily, BinnedInvariantPipelineFamily)):
        return None
    if not getattr(final_family, "keyed_compatible", True):
        # tree finals consume pre-binned "codes"; they compose only with
        # monotone per-feature steps, under which the codes are provably
        # unchanged (anything else -> Tier B)
        if all(getattr(s, "monotone_per_feature", False)
               for _, s in resolved):
            return BinnedInvariantPipelineFamily(final_name, final_family)
        return None
    if not final_family.has_per_task_fit() and not getattr(
            final_family, "task_batched_accepts_fold_inputs", False):
        # task-batched-only finals must understand per-fold inputs
        return None
    return PipelineFamily(resolved, final_name, final_family)
