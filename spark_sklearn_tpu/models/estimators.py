"""Native TPU estimator classes with the sklearn estimator contract.

These are standalone replacements for the sklearn estimators the compiled
families cover: same constructor params and fitted attributes, but `.fit`
runs the family's jitted JAX program on the TPU.  They subclass sklearn's
BaseEstimator so `clone()`/`get_params`/`set_params` (the contract the
reference relies on everywhere — reference: grid_search.py uses
sklearn.base.clone) work unchanged, and they dispatch to the Tier-A compiled
search path automatically.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np
import jax.numpy as jnp

from sklearn.base import BaseEstimator, ClassifierMixin, RegressorMixin

from spark_sklearn_tpu.models.linear import (
    ElasticNetFamily,
    LinearRegressionFamily,
    LogisticRegressionFamily,
    RidgeFamily,
)


class _TpuEstimatorBase(BaseEstimator):
    """Single-fit plumbing shared by every native estimator (linear here,
    MLP in standalone.py): prepare -> params from the sklearn contract ->
    one jitted family fit with all-ones weights -> fitted attrs."""

    _family = None
    #: sklearn 1.9's BaseEstimator._validate_params (which the search's
    #: host-side prevalidation calls on every candidate) reads this
    #: declaration; the native estimators declare no constraints — the
    #: compiled solvers accept any finite value
    _parameter_constraints: dict = {}

    def _fit_family(self, X, y, sample_weight=None):
        import jax

        family = self._family
        X = np.asarray(X)
        data, meta = family.prepare_data(X, y)
        n = X.shape[0]
        w = (np.ones(n, dtype=np.float32) if sample_weight is None
             else np.asarray(sample_weight, dtype=np.float32))
        params = family.extract_params(self)
        if hasattr(family, "observe_candidates"):
            family.observe_candidates([], params, meta)
        model = jax.jit(
            lambda d, wv: family.fit({}, params, d, wv, meta))(
            {k: jnp.asarray(v) for k, v in data.items()}, jnp.asarray(w))
        self._model = model
        self._meta = meta
        self._static = params
        for k, v in family.sklearn_attrs(model, params, meta).items():
            setattr(self, k, v)
        return self

    def _predict_family(self, X):
        X = jnp.asarray(np.asarray(X), jnp.float32)
        return self._family.predict(self._model, self._static, X, self._meta)


class LogisticRegression(ClassifierMixin, _TpuEstimatorBase):
    """TPU-native logistic regression (lbfgs, L2).  Mirrors
    sklearn.linear_model.LogisticRegression's core surface."""

    _family = LogisticRegressionFamily

    def __init__(self, penalty="l2", C=1.0, tol=1e-4, fit_intercept=True,
                 max_iter=100, random_state=None):
        self.penalty = penalty
        self.C = C
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.max_iter = max_iter
        self.random_state = random_state

    def fit(self, X, y, sample_weight=None):
        return self._fit_family(X, y, sample_weight)

    def predict(self, X):
        idx = np.asarray(self._predict_family(X))
        return self.classes_[idx]

    def decision_function(self, X):
        X = jnp.asarray(np.asarray(X), self._model["coef"].dtype)
        return np.asarray(self._family.decision(
            self._model, self._static, X, self._meta))

    def predict_proba(self, X):
        X = jnp.asarray(np.asarray(X), self._model["coef"].dtype)
        return np.asarray(self._family.predict_proba(
            self._model, self._static, X, self._meta))

    def predict_log_proba(self, X):
        return np.log(self.predict_proba(X))


class _TpuRegressorBase(RegressorMixin, _TpuEstimatorBase):
    def fit(self, X, y, sample_weight=None):
        return self._fit_family(X, y, sample_weight)

    def predict(self, X):
        return np.asarray(self._predict_family(X))


class Ridge(_TpuRegressorBase):
    _family = RidgeFamily

    def __init__(self, alpha=1.0, fit_intercept=True, tol=1e-4,
                 random_state=None):
        self.alpha = alpha
        self.fit_intercept = fit_intercept
        self.tol = tol
        self.random_state = random_state


class LinearRegression(_TpuRegressorBase):
    _family = LinearRegressionFamily

    def __init__(self, fit_intercept=True):
        self.fit_intercept = fit_intercept


class ElasticNet(_TpuRegressorBase):
    _family = ElasticNetFamily

    def __init__(self, alpha=1.0, l1_ratio=0.5, fit_intercept=True,
                 max_iter=1000, tol=1e-4, random_state=None):
        self.alpha = alpha
        self.l1_ratio = l1_ratio
        self.fit_intercept = fit_intercept
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state


class Lasso(ElasticNet):
    _family = ElasticNetFamily

    def __init__(self, alpha=1.0, fit_intercept=True, max_iter=1000,
                 tol=1e-4, random_state=None):
        super().__init__(alpha=alpha, l1_ratio=1.0,
                         fit_intercept=fit_intercept, max_iter=max_iter,
                         tol=tol, random_state=random_state)
