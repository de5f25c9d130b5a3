"""The plain reference: what one (candidate, fold) fit of the searched
estimator has to answer, written from the estimator's published
definition and from nothing of the program under test.

It imports nothing of ``spark_sklearn_tpu`` and takes nothing the program
made.  Straightforward ``jax.numpy``: float32 with every matrix product at
``highest`` precision (on a TPU a float32 product otherwise runs in
bfloat16 passes), no kernels, no carried logits, no fused line search.
The control of the comparison is this same code with ``dtype=bfloat16``.

``LogisticRegression`` (scikit-learn, lbfgs, l2, multinomial): minimise
over W (k, d) and b (k,)

    sum_i [ logsumexp(W x_i + b) - (W x_i + b)[y_i] ]  +  ||W||^2 / (2 C)

from W = 0, b = 0 by L-BFGS (history 10, backtracking Armijo line search,
c1 = 1e-4) until the largest gradient entry of the *mean* loss is at most
``tol`` or ``max_iter`` iterations are done; predict the class of the
largest logit; score by accuracy.  Departures from scikit-learn's own
solver (scipy's L-BFGS-B): a backtracking line search in place of
More-Thuente, and no stop on the relative decrease of the objective.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HISTORY = 10
ARMIJO_C1 = 1e-4
MAX_HALVINGS = 15


def _lbfgs(fun, x0, max_iter, gtol):
    """Minimise ``fun`` from ``x0``: textbook L-BFGS, two-loop recursion
    over the last HISTORY curvature pairs, step 1 halved until Armijo's
    condition holds."""
    value_and_grad = jax.value_and_grad(fun)
    dtype = x0.dtype
    size = x0.shape[0]

    def direction(g, s_hist, y_hist, n_pairs):
        q = g
        coeffs = []
        for age in range(HISTORY):          # newest pair first
            slot = (n_pairs - 1 - age) % HISTORY
            use = age < jnp.minimum(n_pairs, HISTORY)
            s, y = s_hist[slot], y_hist[slot]
            rho = jnp.where(use, 1.0 / jnp.where(use, s @ y, 1.0), 0.0)
            a = rho * (s @ q)
            q = q - a * y
            coeffs.append((s, y, rho, a))
        s, y = coeffs[0][0], coeffs[0][1]
        scale = jnp.where(n_pairs > 0,
                          (s @ y) / jnp.where(n_pairs > 0, y @ y, 1.0), 1.0)
        r = scale.astype(dtype) * q
        for s, y, rho, a in reversed(coeffs):
            r = r + (a - rho * (y @ r)) * s
        return -r

    def step(state):
        x, f, g, s_hist, y_hist, n_pairs, it = state
        p = direction(g, s_hist, y_hist, n_pairs)
        slope = g @ p
        downhill = slope < 0
        p = jnp.where(downhill, p, -g)
        slope = jnp.where(downhill, slope, -(g @ g))
        t0 = jnp.where(
            it == 0,
            jnp.minimum(1.0, 1.0 / (jnp.max(jnp.abs(g))
                                    + jnp.finfo(dtype).eps)),
            1.0).astype(dtype)

        def too_long(trial):
            t, f_t, halvings = trial
            return jnp.logical_and(
                jnp.logical_not(f_t <= f + ARMIJO_C1 * t * slope),
                halvings < MAX_HALVINGS)

        def halve(trial):
            t, _, halvings = trial
            t = (0.5 * t).astype(dtype)
            return t, fun(x + t * p), halvings + 1

        t, _, _ = jax.lax.while_loop(
            too_long, halve, (t0, fun(x + t0 * p), jnp.asarray(0)))
        x_new = x + t * p
        f_new, g_new = value_and_grad(x_new)
        ok = jnp.isfinite(f_new)
        x_new = jnp.where(ok, x_new, x)
        f_new = jnp.where(ok, f_new, f)
        g_new = jnp.where(ok, g_new, g)
        s, y = x_new - x, g_new - g
        keep = (s @ y) > 1e-10
        slot = n_pairs % HISTORY
        s_hist = jnp.where(keep, s_hist.at[slot].set(s), s_hist)
        y_hist = jnp.where(keep, y_hist.at[slot].set(y), y_hist)
        return (x_new, f_new, g_new, s_hist, y_hist,
                n_pairs + keep.astype(jnp.int32), it + 1)

    def unfinished(state):
        g, it = state[2], state[6]
        return jnp.logical_and(it < max_iter, jnp.max(jnp.abs(g)) > gtol)

    f0, g0 = value_and_grad(x0)
    hist = jnp.zeros((HISTORY, size), dtype)
    state = jax.lax.while_loop(
        unfinished, step,
        (x0, f0, g0, hist, hist, jnp.asarray(0, jnp.int32),
         jnp.asarray(0, jnp.int32)))
    return state[0], state[6]


def _fit_and_score(C, X_train, Y_train, X_test, y_test, *, max_iter, tol):
    """One fit at regularisation ``C`` and its test accuracy."""
    dtype = X_train.dtype
    n, d = X_train.shape
    k = Y_train.shape[1]

    def unpack(w):
        return w[:k * d].reshape(k, d), w[k * d:]

    def objective(w):
        W, b = unpack(w)
        Z = X_train @ W.T + b
        fit = jax.scipy.special.logsumexp(Z, axis=1) - jnp.sum(
            Z * Y_train, axis=1)
        return jnp.sum(fit) + jnp.sum(W * W) / (2.0 * C)

    w, n_iter = _lbfgs(objective, jnp.zeros((k * d + k,), dtype),
                       max_iter, jnp.asarray(tol * n, dtype))
    W, b = unpack(w)
    predicted = jnp.argmax(X_test @ W.T + b, axis=1)
    return jnp.mean((predicted == y_test).astype(jnp.float32)), n_iter


@functools.partial(jax.jit, static_argnames=("max_iter", "tol"))
def _fold_scores(Cs, X_train, Y_train, X_test, y_test, *, max_iter, tol):
    fit = functools.partial(_fit_and_score, max_iter=max_iter, tol=tol)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(fit, in_axes=(0, None, None, None, None))(
            Cs, X_train, Y_train, X_test, y_test)


def logistic_cv_scores(X, y, splits, candidates, config,
                       dtype=jnp.float32):
    """Test accuracy of every (candidate, fold), ``(len(candidates),
    len(splits))``, and the iterations each fit ran.  ``candidates`` are
    parameter dicts that set ``C``; the estimator's other parameters are
    the configuration's.  One fold at a time, so that the device holds
    one fold's rows beside the lanes' state."""
    params = config["estimator"]["params"]
    onehot = np.eye(config["data"]["n_classes"], dtype=np.float32)
    Cs = jnp.asarray(np.asarray([c["C"] for c in candidates], np.float32),
                     dtype)
    scores, iters = [], []
    for train, test in splits:
        s, it = _fold_scores(
            Cs, jnp.asarray(X[train], dtype),
            jnp.asarray(onehot[y[train]], dtype),
            jnp.asarray(X[test], dtype), jnp.asarray(y[test]),
            max_iter=int(params.get("max_iter", 100)),
            tol=float(params.get("tol", 1e-4)))
        scores.append(np.asarray(s, np.float64))
        iters.append(np.asarray(it))
    return np.stack(scores, axis=1), np.stack(iters, axis=1)
