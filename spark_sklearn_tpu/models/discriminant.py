"""LinearDiscriminantAnalysis (lsqr solver) — closed-form discriminants.

Reference counterpart: sklearn's LDA running whole inside Spark tasks
(reference: grid_search.py -> sklearn _fit_and_score); the canonical
search grids the `shrinkage` float with solver='lsqr'/'eigen'.  The
compiled redesign covers solver='lsqr' (sklearn _solve_lstsq):

    means_c   = per-class fold means
    cov       = sum_c priors_c * shrunk(empirical_cov(X_c), s)
              = one weighted Gram matmul over class-mean residuals,
                then (1-s)*cov + s*(trace/d)*I
    coef      = lstsq(cov, means.T).T        (min-norm, like sklearn)
    intercept = -0.5 diag(means @ coef.T) + log priors

with sklearn's exact binary collapse (coef row1-row0, scalar
intercept, sigmoid probabilities).  `shrinkage` is a dynamic scalar
(None == 0.0 arithmetically), so a whole shrinkage grid is one
compiled program.  solver='svd' (rank-truncated, different singular
behavior), 'eigen' (different decision parameterisation) and
shrinkage='auto' (Ledoit-Wolf) raise -> the designed host fallback
runs sklearn exactly.  LDA.fit takes no sample_weight (sklearn), so
accepts_sample_weight is False.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import warnings

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, register_family)
from spark_sklearn_tpu.models.naive_bayes import (_class_sums,
                                                  _prep_classifier_data)

_EPS = 1e-12


class LinearDiscriminantFamily(Family):
    name = "lda"
    is_classifier = True
    dynamic_params = {"shrinkage": np.float32}
    accepts_sample_weight = False
    #: sklearn 1.9's LDA(solver="lsqr") returns float64 probas even for
    #: a float32 X (grid.py's log_loss clip resolves the eps per family)
    proba_dtype_rule = "float64"

    @classmethod
    def check_static(cls, static):
        solver = static.get("solver", "svd")
        if solver != "lsqr":
            raise NotCompiledError(
                f"solver={solver!r} is not compiled (lsqr only); use "
                "backend='host'")
        if static.get("shrinkage") == "auto":
            raise NotCompiledError(
                "shrinkage='auto' (Ledoit-Wolf) is not compiled; use "
                "backend='host'")
        if static.get("covariance_estimator") is not None:
            raise NotCompiledError(
                "covariance_estimator is not compiled; use "
                "backend='host'")

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        """Host-side static/priors validation, per candidate (sklearn
        LDA.fit raises for negative priors, warns and renormalizes
        non-normalized ones — the compiled fit normalizes too, so the
        warning fires here, once per search)."""
        cls.check_static(base_params)
        seen = set()
        for params in [base_params] + [
                {**base_params, **c} for c in candidates]:
            cls.check_static(params)
            priors = params.get("priors")
            if priors is None or id(priors) in seen:
                continue
            seen.add(id(priors))
            p = np.asarray(priors, np.float64)
            k = meta.get("n_classes")
            if k is not None and len(p) != k:
                raise ValueError(
                    f"priors must have length n_classes ({k}); got "
                    f"{len(p)}")
            if (p < 0).any():
                raise ValueError("priors must be non-negative")
            if abs(p.sum() - 1.0) > 1e-5:
                warnings.warn("The priors do not sum to 1. "
                              "Renormalizing", UserWarning, stacklevel=2)

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        return _prep_classifier_data(X, y, dtype)

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        cls.check_static(static)
        X, y1h = data["X"], data["y1h"]
        d = X.shape[1]
        s_raw = dynamic.get("shrinkage", static.get("shrinkage"))
        s = jnp.asarray(0.0 if s_raw is None else s_raw, X.dtype)
        counts, wy, sums = _class_sums(y1h, train_w, X)      # (k,), (k, d)
        cnt = jnp.maximum(counts, _EPS)
        means = sums / cnt[:, None]                          # (k, d)
        priors = static.get("priors")
        if priors is not None:
            pri = jnp.asarray(priors, X.dtype)
            # sklearn warns and renormalizes (the warning fires
            # host-side in observe_candidates)
            pri = pri / jnp.maximum(jnp.sum(pri), _EPS)
        else:
            pri = counts / jnp.maximum(jnp.sum(counts), _EPS)
        # within-class covariance, priors-weighted (sklearn _class_cov):
        # residuals about each sample's OWN class mean (two-pass — the
        # same f32-cancellation discipline as the NB variance), scaled
        # so the weighted Gram sums priors_c/n_c per row
        r = X - means[data["y"]]                             # (n, d)
        row_w = train_w * (pri / cnt)[data["y"]]             # (n,)
        cov = (r * row_w[:, None]).T @ r                     # (d, d)
        mu = jnp.trace(cov) / d
        cov = (1.0 - s) * cov + s * mu * jnp.eye(d, dtype=X.dtype)
        coef, *_ = jnp.linalg.lstsq(cov, means.T)            # (d, k)
        coef = coef.T                                        # (k, d)
        intercept = -0.5 * jnp.sum(means * coef, axis=1) \
            + jnp.log(jnp.maximum(pri, _EPS))
        return {"coef": coef, "intercept": intercept}

    @classmethod
    def decision(cls, model, static, X, meta):
        Z = X @ model["coef"].T + model["intercept"][None, :]
        if meta["n_classes"] == 2:
            # sklearn's binary collapse: one row, log-likelihood ratio
            return Z[:, 1] - Z[:, 0]
        return Z

    @classmethod
    def predict(cls, model, static, X, meta):
        dec = cls.decision(model, static, X, meta)
        if meta["n_classes"] == 2:
            return (dec > 0).astype(jnp.int32)
        return jnp.argmax(dec, axis=1).astype(jnp.int32)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        dec = cls.decision(model, static, X, meta)
        if meta["n_classes"] == 2:
            p = jax.nn.sigmoid(dec)
            return jnp.stack([1.0 - p, p], axis=1)
        return jax.nn.softmax(dec, axis=1)

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        coef = np.asarray(model["coef"])
        icpt = np.asarray(model["intercept"])
        if meta["n_classes"] == 2:
            coef = (coef[1] - coef[0]).reshape(1, -1)
            icpt = np.asarray([icpt[1] - icpt[0]])
        return {"coef_": coef, "intercept_": icpt,
                "classes_": meta["classes"],
                "n_features_in_": meta["n_features"]}


register_family(
    LinearDiscriminantFamily,
    "sklearn.discriminant_analysis.LinearDiscriminantAnalysis",
)
