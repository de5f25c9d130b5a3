"""Standalone native estimators for the non-linear families.

`models/estimators.py` covers the linear natives; these wrap the SVC / MLP
/ tree families with the sklearn estimator contract so the framework is
usable with no sklearn estimator objects at all.  Each `.fit` runs the
family's compiled program with a single all-ones weight vector (one
"task"), mirroring how the search fits the refitted best estimator.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from sklearn.base import BaseEstimator, ClassifierMixin, RegressorMixin


class SVC(ClassifierMixin, BaseEstimator):
    """TPU-native kernel SVM (FISTA dual ascent — models/svm.py)."""

    def __init__(self, C=1.0, kernel="rbf", gamma="scale", degree=3,
                 coef0=0.0, max_iter=-1, tol=1e-3, class_weight=None,
                 random_state=None):
        self.C = C
        self.kernel = kernel
        self.gamma = gamma
        self.degree = degree
        self.coef0 = coef0
        self.max_iter = max_iter
        self.tol = tol
        self.class_weight = class_weight
        self.random_state = random_state

    def fit(self, X, y):
        from spark_sklearn_tpu.models.svm import SVCFamily, _resolve_gamma

        X = np.asarray(X, np.float32)
        data, meta = SVCFamily.prepare_data(X, y)
        self._meta = meta
        self._static = dict(self.get_params(deep=False))
        self._X_train = data["X"]
        self._y = data["y"]
        self.classes_ = meta["classes"]
        self.n_features_in_ = meta["n_features"]
        self._gamma_val = _resolve_gamma(
            self._static.get("gamma", "scale"), meta)
        # the fit IS the dual solve; signed alphas + KKT intercepts are
        # the model (the representer form d(x) = sum_i alpha_i y_i
        # K(x_i, x) + b serves training AND new data with one kernel
        # matmul)
        self._alphas, self._intercepts = self._solve_alphas()
        return self

    def _pair_decisions(self, X):
        import jax.numpy as jnp
        from spark_sklearn_tpu.models.svm import _kernel
        K = _kernel(jnp.asarray(np.asarray(X, np.float32)),
                    jnp.asarray(self._X_train), self._static.get(
                        "kernel", "rbf"), self._gamma_val,
                    float(self._static.get("degree", 3)),
                    float(self._static.get("coef0", 0.0)))
        return np.asarray(K @ self._alphas.T) + \
            self._intercepts[None, :]                # (n_new, P)

    def _solve_alphas(self):
        """One dual solve via the family's shared FISTA kernel
        (models/svm.py::fista_dual_ascent — numerics live in one place)."""
        from spark_sklearn_tpu.models.svm import (
            _kernel, _power_step, fista_dual_ascent)
        X = jnp.asarray(self._X_train)
        y = jnp.asarray(self._y)
        n = X.shape[0]
        k = self._meta["n_classes"]
        pairs = jnp.asarray(self._meta["pairs"])
        K = _kernel(X, X, self._static.get("kernel", "rbf"),
                    self._gamma_val, float(self._static.get("degree", 3)),
                    float(self._static.get("coef0", 0.0)))
        ypos = (y[None, :] == pairs[:, 0][:, None])
        yneg = (y[None, :] == pairs[:, 1][:, None])
        yb = ypos.astype(jnp.float32) - yneg.astype(jnp.float32)
        if k == 2:
            yb = -yb
        box = (ypos | yneg).astype(jnp.float32)
        C = float(self._static.get("C", 1.0))
        max_iter = int(self._static.get("max_iter", -1))
        if max_iter in (-1, 0):
            max_iter = 300
        from spark_sklearn_tpu.models.base import class_weight_multiplier
        cw = class_weight_multiplier(
            jnp.ones((n,), jnp.float32), jnp.asarray(self._y),
            self._meta, self._static.get("class_weight"))
        bound = C * box if cw is None else C * box * cw[None, :]
        from spark_sklearn_tpu.models.svm import _tol_or_default
        A, b, _ = fista_dual_ascent(
            K, yb, bound, _power_step(K, n, jnp.float32, centred=True),
            max_iter,
            tol=_tol_or_default(self._static))
        return np.asarray(A * yb), np.asarray(b)      # signed alphas + b

    def decision_function(self, X):
        from spark_sklearn_tpu.models.svm import SVCFamily
        dec = jnp.asarray(self._pair_decisions(X))
        if self._meta["n_classes"] == 2:
            return np.asarray(dec[:, 0])
        return np.asarray(SVCFamily._votes(dec, self._meta))

    def predict(self, X):
        from spark_sklearn_tpu.models.svm import SVCFamily
        dec = jnp.asarray(self._pair_decisions(X))
        idx = np.asarray(SVCFamily.predict(
            {"pair_dec": dec}, self._static, None, self._meta))
        return self.classes_[idx]


from spark_sklearn_tpu.models.estimators import _TpuEstimatorBase


class MLPClassifier(ClassifierMixin, _TpuEstimatorBase):
    from spark_sklearn_tpu.models.mlp import MLPClassifierFamily as _family

    def __init__(self, hidden_layer_sizes=(100,), activation="relu",
                 solver="adam", alpha=1e-4, batch_size="auto",
                 learning_rate_init=1e-3, max_iter=200, random_state=None,
                 momentum=0.9, beta_1=0.9, beta_2=0.999, epsilon=1e-8):
        self.hidden_layer_sizes = hidden_layer_sizes
        self.activation = activation
        self.solver = solver
        self.alpha = alpha
        self.batch_size = batch_size
        self.learning_rate_init = learning_rate_init
        self.max_iter = max_iter
        self.random_state = random_state
        self.momentum = momentum
        self.beta_1 = beta_1
        self.beta_2 = beta_2
        self.epsilon = epsilon

    def fit(self, X, y):
        return self._fit_family(X, y)

    def predict(self, X):
        return self.classes_[np.asarray(self._predict_family(X))]

    def predict_proba(self, X):
        return np.asarray(self._family.predict_proba(
            self._model, self._static,
            jnp.asarray(np.asarray(X, np.float32)), self._meta))


class MLPRegressor(RegressorMixin, _TpuEstimatorBase):
    from spark_sklearn_tpu.models.mlp import MLPRegressorFamily as _family

    def __init__(self, hidden_layer_sizes=(100,), activation="relu",
                 solver="adam", alpha=1e-4, batch_size="auto",
                 learning_rate_init=1e-3, max_iter=200, random_state=None):
        self.hidden_layer_sizes = hidden_layer_sizes
        self.activation = activation
        self.solver = solver
        self.alpha = alpha
        self.batch_size = batch_size
        self.learning_rate_init = learning_rate_init
        self.max_iter = max_iter
        self.random_state = random_state

    def fit(self, X, y):
        return self._fit_family(X, y)

    def predict(self, X):
        return np.asarray(self._predict_family(X))
