"""Linear model families: logistic regression, ridge/OLS, elastic-net.

Reference counterpart: spark-sklearn's Converter supports exactly
LogisticRegression and LinearRegression (reference: converter.py), and its
GridSearchCV runs any sklearn estimator on CPU executors.  Here the linear
families are first-class compiled citizens: one jitted program per compile
group, `vmap` over the candidate axis, masked sample weights over the fold
axis, MXU-friendly dense matmuls.

Numeric conventions follow sklearn so the vendored oracle tests pass:
  - LogisticRegression: minimise sum-logloss + 0.5/C * ||coef||^2 (intercept
    unpenalised), lbfgs.  sklearn 1.9 hands scipy the MEAN loss, so
    its `tol` bounds max|grad| of the objective divided by the sum of the
    sample weights; here that is max|grad| <= tol * sum(weights) (`_sum_tol`).
  - Ridge: weighted normal equations with unpenalised intercept.
  - LinearRegression: lstsq on weighted-centred data.
  - ElasticNet/Lasso: FISTA on 1/(2n) LSQ + alpha*(l1_ratio*L1 + (1-l1_ratio)
    /2*L2), centred intercept.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, encode_labels, register_family)
from spark_sklearn_tpu.ops.solvers import lbfgs


def _multinomial_loss(Z, wT, y1h):
    """Weighted multinomial log-loss per lane: logits Z (n, B, k), row
    weights wT (n, B), one-hot labels y1h (n, k) -> (B,)."""
    lse = jax.scipy.special.logsumexp(Z, axis=2)              # (n, B)
    fit_term = lse - jnp.einsum("nbk,nk->nb", Z, y1h)
    return jnp.sum(wT * fit_term, axis=0)


def _multinomial_trial_losses(Z, Zp, alphas, wT, y1h):
    """`_multinomial_loss(Z + a*Zp, ...)` for every row a of alphas
    (T, B) -> (T, B): the line search of `glm_lbfgs_batched`, written so
    that XLA:TPU makes ONE pass over (Z, Zp) of it and writes nothing
    but the (T, B) sums.

    `jax.vmap` of the loss over the trial axis compiles there to five
    fusions that hand each other f32[T, n, B] tensors through HBM, and
    to two relayout copies of Z: the reduction over classes is a
    `reduce` of its own and is not fused into the one over rows.  So:

    - the class axis is unrolled over the k (n, B) class planes, sliced
      BEFORE the step is applied (slicing Z + a*Zp materialises
      f32[T, n, B, k]): both reductions over classes, the logsumexp and
      the label's logit, become elementwise;
    - the trial axis is unrolled too, not vmapped: under vmap every
      plane is broadcast along the trial axis, and XLA:TPU does not fuse
      a slice into a broadcast, it writes the twenty planes to HBM first
      (a copy of Z and one of Zp an iteration);
    - the T sums over rows are ONE variadic `lax.reduce`, which is one
      fusion; T separate `jnp.sum`s compile to T passes over (Z, Zp).

    Row by row it is the arithmetic of `_multinomial_loss`: the shift is
    the trial's own maximum, the label's logit is subtracted before the
    sum over rows (summed apart, sum(w*lse) - sum(w*z_y) cancels to a
    loss of 1e-3 from terms of 1e5 on a fit that separates its
    classes), a non-finite trial reads non-finite.  Only the order of
    the sums is the compiler's.  Measured on a v5e: PERF.md, PR 27."""
    k = Z.shape[2]
    planes = [(Z[:, :, j], Zp[:, :, j], y1h[:, j, None]) for j in range(k)]

    def weighted_rows(a):                                 # (B,) -> (n, B)
        zt = [z + a[None, :] * zp for z, zp, _ in planes]
        m = functools.reduce(jnp.maximum, zt)
        lse = m + jnp.log(sum(jnp.exp(z - m) for z in zt))
        z_label = sum(z * y for z, (_, _, y) in zip(zt, planes))
        return wT * (lse - z_label)

    rows = [weighted_rows(a) for a in alphas]             # T x (n, B)
    sums = lax.reduce(
        rows, [jnp.zeros((), Z.dtype)] * len(rows),
        lambda xs, ys: [x + y for x, y in zip(xs, ys)], dimensions=(0,))
    return jnp.stack(sums)


def _batched_penalty(static):
    """(penalty, l1_ratio) as `fit_task_batched` solves it: "elasticnet"
    goes to FISTA, everything else to L-BFGS."""
    penalty = static.get("penalty", "l2")
    l1_ratio = static.get("l1_ratio", 0.0) or 0.0
    if penalty == "deprecated":
        penalty = "l2" if not l1_ratio else "elasticnet"
    if penalty == "l1":
        penalty, l1_ratio = "elasticnet", 1.0
    if penalty == "elasticnet" and not l1_ratio:
        penalty = "l2"   # pure-l2 config: quasi-Newton is ~10x cheaper
    return penalty, l1_ratio


def _sum_tol(tol, train_w):
    """sklearn's lbfgs `tol` on this module's sum-loss objective: the
    gradient of sklearn's mean-loss objective is ours divided by the
    summed sample weights (class weights folded in), so its
    `gtol=tol` stop is max|grad| <= tol * sum(weights) here.  Without
    the factor the solver runs ~n_samples times tighter than sklearn
    and lands on a different (more overfit) model wherever the
    regularisation is weak."""
    return tol * jnp.sum(train_w, axis=-1)


def _is_bcoo(X) -> bool:
    """True when X is a device BCOO operand (the sparse Tier-A path).
    jnp.matmul/einsum reject BCOO, so the matmul sites below switch to
    the equivalent `@`-operator forms when this holds."""
    from jax.experimental import sparse as jsparse
    return isinstance(X, jsparse.BCOO)


# ----------------------------------------------------------------------------
# Logistic regression
# ----------------------------------------------------------------------------

class LogisticRegressionFamily(Family):
    name = "logistic_regression"
    is_classifier = True
    dynamic_params = {"C": np.float32, "tol": np.float32}
    #: the GLM solvers only touch X through Ax/AT, both expressible as
    #: BCOO-legal operator-form matmuls
    supports_sparse = True

    #: sorted chunking needs enough candidates to amortise the extra
    #: dispatches on the GLM solvers (policy applied by the engine)
    min_sort_candidates = 32

    @classmethod
    def convergence_proxy(cls, dynamic_params, static):
        """Ascending-difficulty proxy for sorted chunking: larger C =
        weaker regularisation = slower L-BFGS/FISTA convergence.  None
        when C is not in the grid (nothing to grade by); the engine
        applies the size threshold and constant-proxy guard."""
        return dynamic_params.get("C")

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        classes, y_enc = encode_labels(y)
        data = {
            "X": np.ascontiguousarray(X, dtype=dtype),
            "y": y_enc,
            "y1h": np.eye(len(classes), dtype=dtype)[y_enc],
        }
        meta = {"n_classes": int(len(classes)), "classes": classes,
                "n_features": int(X.shape[1])}
        return data, meta

    @classmethod
    def prepare_data_sparse(cls, X, y, dtype=np.float32):
        from spark_sklearn_tpu.sparse.csr import SparseOperand
        classes, y_enc = encode_labels(y)
        op = SparseOperand.from_csr(X, dtype=dtype)
        data = {"X": op,
                "y": y_enc,
                "y1h": np.eye(len(classes), dtype=dtype)[y_enc]}
        # signature tuple (truthy, hashable) -> program-store/fusion
        # keys via freeze(meta); see naive_bayes._prep_classifier_sparse
        meta = {"n_classes": int(len(classes)), "classes": classes,
                "n_features": int(X.shape[1]), "sparse": op.signature()}
        return data, meta

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        X = data["X"]
        n, d = X.shape
        k = meta["n_classes"]
        C = jnp.asarray(dynamic.get("C", static.get("C", 1.0)), X.dtype)
        tol = dynamic.get("tol", static.get("tol", 1e-4))
        max_iter = int(static.get("max_iter", 100))
        fit_intercept = bool(static.get("fit_intercept", True))
        penalty = static.get("penalty", "l2")
        l1_ratio = static.get("l1_ratio", 0.0)
        if penalty == "deprecated":
            # sklearn >=1.8 sentinel: regularisation is l2 unless l1_ratio
            # mixes in an l1 term
            penalty = "l2" if not l1_ratio else "elasticnet"
        if penalty in ("l1", "elasticnet"):
            if penalty == "l1" or l1_ratio:
                # one-task view of the batched FISTA path (refit and keyed
                # fleets share the exact numerics of the search sweep)
                model = cls.fit_task_batched(
                    {k_: jnp.asarray(v)[None]
                     for k_, v in dynamic.items()},
                    static, data, train_w[None, :], meta)
                return jax.tree_util.tree_map(lambda a: a[0], model)
            penalty = "l2"   # elasticnet with l1_ratio == 0
        if penalty not in ("l2", None, "none"):
            raise NotCompiledError(
                f"penalty={penalty!r} is not compiled; use backend='host'")
        from spark_sklearn_tpu.models.base import apply_class_weight
        train_w = apply_class_weight(
            train_w, data["y"], meta, static.get("class_weight"))
        l2 = (0.5 / C) if penalty == "l2" else 0.0
        tol = _sum_tol(jnp.asarray(tol, X.dtype), train_w)

        if k == 2:
            yb = data["y"].astype(X.dtype)

            def loss(w_flat):
                w, b = w_flat[:d], w_flat[d]
                z = X @ w + (b if fit_intercept else 0.0)
                per = jnp.logaddexp(0.0, z) - yb * z
                pen = l2 * jnp.dot(w, w)
                return jnp.sum(train_w * per) + pen

            res = lbfgs(loss, jnp.zeros(d + 1, X.dtype),
                        max_iter=max_iter, tol=tol)
            w = res.x
            return {"coef": w[:d][None, :], "intercept": w[d:d + 1],
                    "converged": res.converged, "n_iter": res.n_iter}
        else:
            y1h = data["y1h"]

            def loss(w_flat):
                W = w_flat[: k * d].reshape(k, d)
                b = w_flat[k * d:]
                Z = X @ W.T + (b if fit_intercept else 0.0)
                lse = jax.scipy.special.logsumexp(Z, axis=1)
                per = lse - jnp.sum(Z * y1h, axis=1)
                pen = l2 * jnp.sum(W * W)
                return jnp.sum(train_w * per) + pen

            res = lbfgs(loss, jnp.zeros(k * d + k, X.dtype),
                        max_iter=max_iter, tol=tol)
            W = res.x[: k * d].reshape(k, d)
            b = res.x[k * d:]
            if not fit_intercept:
                b = jnp.zeros_like(b)
            return {"coef": W, "intercept": b,
                    "converged": res.converged, "n_iter": res.n_iter}

    @classmethod
    def linesearch_one_pass(cls, static, meta):
        """True where `fit_task_batched` hands `glm_lbfgs_batched` the
        one-pass evaluator of the line search's trial losses: the
        multinomial L-BFGS fit (its loss has a class axis to unroll)."""
        return (meta["n_classes"] != 2
                and _batched_penalty(static)[0] != "elasticnet")

    @classmethod
    def launch_facts(cls, static, meta, n_candidates, n_folds):
        return {"linesearch_one_pass":
                int(cls.linesearch_one_pass(static, meta))}

    @classmethod
    def launch_stats(cls, models, static, meta):
        """The default's iterations, and a staged line search's count of
        iterations that ran its second stage (on every lane, as n_iter
        is)."""
        stats = super().launch_stats(models, static, meta)
        if "ls_second_pass" in models:
            stats["linesearch_second_pass"] = jnp.max(
                models["ls_second_pass"]).astype(jnp.int32)
        return stats

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        """All (candidate x fold) tasks as ONE wide-matmul program.

        `dynamic` leaves and `train_w` carry a leading task axis B; the
        logits for every task come from a single `X @ W_all` contraction of
        width B*k, which keeps the MXU tiles full (a vmap of per-task fits
        leaves them mostly empty for small k).  Returns model pytrees with
        leading axis B.
        """
        from spark_sklearn_tpu.ops.solvers import glm_lbfgs_batched

        X = data["X"]
        n, d = X.shape
        k = meta["n_classes"]
        B = train_w.shape[0]
        C = jnp.asarray(dynamic.get("C", static.get("C", 1.0)), X.dtype)
        C = jnp.broadcast_to(C, (B,))
        tol = jnp.broadcast_to(jnp.asarray(
            dynamic.get("tol", static.get("tol", 1e-4)), X.dtype), (B,))
        max_iter = int(static.get("max_iter", 100))
        fit_intercept = bool(static.get("fit_intercept", True))
        penalty, l1_ratio = _batched_penalty(static)
        if penalty not in ("l2", "elasticnet", None, "none"):
            raise NotCompiledError(
                f"penalty={penalty!r} is not compiled; use backend='host'")
        from spark_sklearn_tpu.models.base import apply_class_weight
        train_w = apply_class_weight(
            train_w, data["y"], meta, static.get("class_weight"))
        use_fista = penalty == "elasticnet"
        inv_C_raw = 1.0 / C
        inv_C = inv_C_raw if penalty == "l2" else jnp.zeros_like(C)
        wT = train_w.T                                        # (n, B)
        # MXU-native precision: cast matmul OPERANDS to bf16, accumulate
        # fp32; everything else (losses, solver state) stays fp32.  A
        # BCOO X stays in its own dtype (f32) — the sparse matmuls run
        # as gather/scatter, where a bf16 downcast buys nothing
        sparse_X = _is_bcoo(X)
        bf16 = bool(static.get("__bf16__", False)) and not sparse_X
        mm_dtype = jnp.bfloat16 if bf16 else X.dtype
        Xm = X if sparse_X else X.astype(mm_dtype)

        if k == 2:
            yb = data["y"].astype(X.dtype)                    # (n,)

            def Ax(x):                                        # -> Z (n, B)
                if sparse_X:
                    Z = Xm @ x[:, :d].T
                else:
                    Z = jnp.einsum("nd,bd->nb", Xm,
                                   x[:, :d].astype(mm_dtype),
                                   preferred_element_type=X.dtype)
                return Z + x[None, :, d] if fit_intercept else Z

            def data_loss(Z):
                per = jnp.logaddexp(0.0, Z) - yb[:, None] * Z
                return jnp.sum(wT * per, axis=0)

            def data_grad(Z):                                 # dL/dZ (n, B)
                return wT * (jax.nn.sigmoid(Z) - yb[:, None])

            def AT(G):                                        # -> (B, d+1)
                if sparse_X:
                    gW = G.T @ Xm
                else:
                    gW = jnp.einsum("nb,nd->bd", G.astype(mm_dtype), Xm,
                                    preferred_element_type=X.dtype)
                gb = jnp.sum(G, axis=0) if fit_intercept else \
                    jnp.zeros((B,), X.dtype)
                return jnp.concatenate([gW, gb[:, None]], axis=1)

            def reg_loss(x):
                return 0.5 * inv_C * jnp.sum(x[:, :d] ** 2, axis=1)

            def reg_grad(x):
                g = inv_C[:, None] * x[:, :d]
                return jnp.concatenate(
                    [g, jnp.zeros((B, 1), X.dtype)], axis=1)

            if use_fista:
                res, n_exec = _fista_elasticnet(
                    Ax, data_loss, data_grad, AT, inv_C_raw, l1_ratio,
                    B, d + 1, d, X.dtype, max_iter, tol)
            else:
                res = glm_lbfgs_batched(
                    Ax, data_loss, data_grad, AT, reg_loss, reg_grad,
                    jnp.zeros((B, d + 1), X.dtype), max_iter=max_iter,
                    tol=_sum_tol(tol, train_w))
                n_exec = res.n_iter
            W = res.x[:, :d]
            b = res.x[:, d]
            if not fit_intercept:
                b = jnp.zeros_like(b)
            return {"coef": W[:, None, :], "intercept": b[:, None],
                    "converged": res.converged, "n_iter": res.n_iter,
                    "n_iter_exec": n_exec}

        y1h = data["y1h"]                                     # (n, k)
        kd = k * d

        def Ax(x):                                            # -> Z (n,B,k)
            W = x[:, :kd].reshape(B, k, d)
            if sparse_X:
                # einsum rejects BCOO; the reshape-matmul form is the
                # identical contraction
                Z = (Xm @ W.reshape(B * k, d).T).reshape(n, B, k)
            else:
                Z = jnp.einsum("nd,bkd->nbk", Xm,             # ONE matmul
                               W.astype(mm_dtype),
                               preferred_element_type=X.dtype)
            return Z + x[None, :, kd:] if fit_intercept else Z

        def data_loss(Z):
            return _multinomial_loss(Z, wT, y1h)

        def trial_data_loss(Z, Zp, alphas):
            return _multinomial_trial_losses(Z, Zp, alphas, wT, y1h)

        def data_grad(Z):                                     # (n, B, k)
            P = jax.nn.softmax(Z, axis=2)
            return wT[:, :, None] * (P - y1h[:, None, :])

        def AT(G):                                            # -> (B, D)
            if sparse_X:
                gW = (G.reshape(n, B * k).T @ Xm).reshape(B, k, d)
            else:
                gW = jnp.einsum("nbk,nd->bkd", G.astype(mm_dtype), Xm,
                                preferred_element_type=X.dtype)
            gW = gW.reshape(B, kd)
            gb = jnp.sum(G, axis=0) if fit_intercept else \
                jnp.zeros((B, k), X.dtype)
            return jnp.concatenate([gW, gb], axis=1)

        def reg_loss(x):
            return 0.5 * inv_C * jnp.sum(x[:, :kd] ** 2, axis=1)

        def reg_grad(x):
            g = inv_C[:, None] * x[:, :kd]
            return jnp.concatenate(
                [g, jnp.zeros((B, k), X.dtype)], axis=1)

        if use_fista:
            res, n_exec = _fista_elasticnet(
                Ax, data_loss, data_grad, AT, inv_C_raw, l1_ratio,
                B, kd + k, kd, X.dtype, max_iter, tol, curvature=0.5)
        else:
            res = glm_lbfgs_batched(
                Ax, data_loss, data_grad, AT, reg_loss, reg_grad,
                jnp.zeros((B, kd + k), X.dtype), max_iter=max_iter,
                tol=_sum_tol(tol, train_w),
                trial_data_loss=trial_data_loss)
            n_exec = res.n_iter
        W = res.x[:, :kd].reshape(B, k, d)
        b = res.x[:, kd:]
        if not fit_intercept:
            b = jnp.zeros_like(b)
        model = {"coef": W, "intercept": b,
                 "converged": res.converged, "n_iter": res.n_iter,
                 "n_iter_exec": n_exec}
        if res.ls_second_pass is not None:
            # a staged line search's count for the launch, on every lane
            # as n_iter is (launch_stats reports it)
            model["ls_second_pass"] = jnp.broadcast_to(
                res.ls_second_pass, (B,))
        return model

    @classmethod
    def decision(cls, model, static, X, meta):
        Z = X @ model["coef"].T + model["intercept"]
        if meta["n_classes"] == 2:
            return Z[:, 0]
        return Z

    @classmethod
    def views_task_batched(cls, models, static, data, meta, needed):
        """Scorer views for ALL tasks from ONE wide matmul.

        `models` carries a flat leading task axis T (coef (T, k, d),
        intercept (T, k)); the logits for every task come from a single
        `X @ W_all^T` contraction of width T*k — the scoring twin of
        `fit_task_batched`'s wide-matmul layout (a vmap of per-task
        matvecs leaves the MXU tiles mostly empty for small k)."""
        X = data["X"]
        n = X.shape[0]
        W = models["coef"]                                 # (T, k, d)
        b = models["intercept"]                            # (T, k)
        T, k, d = W.shape
        if _is_bcoo(X):
            Z = X @ W.reshape(T * k, d).T                  # ONE matmul
        else:
            Z = jnp.matmul(X, W.reshape(T * k, d).T,       # ONE matmul
                           preferred_element_type=X.dtype)
        Z = Z.reshape(n, T, k) + b[None]
        Z = jnp.moveaxis(Z, 0, 1)                          # (T, n, k)
        views = {}
        if meta["n_classes"] == 2:
            z = Z[:, :, 0]                                 # (T, n)
            if "decision" in needed:
                views["decision"] = z
            if "pred" in needed:
                views["pred"] = (z > 0).astype(jnp.int32)
            if "proba" in needed:
                p1 = jax.nn.sigmoid(z)
                views["proba"] = jnp.stack([1.0 - p1, p1], axis=-1)
        else:
            if "decision" in needed:
                views["decision"] = Z
            if "pred" in needed:
                views["pred"] = jnp.argmax(Z, axis=-1).astype(jnp.int32)
            if "proba" in needed:
                views["proba"] = jax.nn.softmax(Z, axis=-1)
        return views

    @classmethod
    def predict(cls, model, static, X, meta):
        Z = cls.decision(model, static, X, meta)
        if meta["n_classes"] == 2:
            return (Z > 0).astype(jnp.int32)
        return jnp.argmax(Z, axis=1).astype(jnp.int32)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        Z = cls.decision(model, static, X, meta)
        if meta["n_classes"] == 2:
            p1 = jax.nn.sigmoid(Z)
            return jnp.stack([1.0 - p1, p1], axis=1)
        return jax.nn.softmax(Z, axis=1)

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        attrs = {
            "coef_": np.asarray(model["coef"]),
            "intercept_": np.asarray(model["intercept"]),
            "classes_": meta["classes"],
            "n_features_in_": meta["n_features"],
        }
        if "n_iter" in model:  # absent on Converter.toTPU-built models
            attrs["n_iter_"] = np.asarray([int(model["n_iter"])])
        return attrs


def _fista_elasticnet(Ax, data_loss, data_grad, AT, inv_C, l1_ratio,
                      B, D, n_pen, dtype, max_iter, tol,
                      curvature=0.25):
    """Elastic-net logistic via proximal FISTA: per-coefficient l1/l2
    weights cover the first n_pen entries (coefficients); the remaining
    intercept entries stay unpenalised, matching sklearn's convention."""
    from spark_sklearn_tpu.ops.solvers import glm_fista_batched

    l1r = jnp.asarray(l1_ratio, dtype)
    lam1 = (inv_C * l1r)[:, None]
    lam2 = (inv_C * (1.0 - l1r))[:, None]
    pen_mask = jnp.concatenate(
        [jnp.ones((B, n_pen), dtype), jnp.zeros((B, D - n_pen), dtype)],
        axis=1)
    # sklearn caps saga's EPOCHS at max_iter; FISTA steps are cheaper so
    # the internal budget is larger, but the reported n_iter is rescaled
    # onto the caller's max_iter axis so sklearn's "n_iter_ >= max_iter
    # means unconverged" idiom holds
    res = glm_fista_batched(
        Ax, data_loss, data_grad, AT,
        l1=lam1 * pen_mask, l2=lam2 * pen_mask,
        x0=jnp.zeros((B, D), dtype),
        max_iter=max(10 * max_iter, 1000), tol=tol, curvature=curvature)
    n_rep = jnp.where(res.converged,
                      jnp.minimum(res.n_iter, max_iter - 1), max_iter)
    # (rescaled-for-sklearn, actually-executed): FLOP/MFU accounting must
    # see the internal budget's true count, not the max_iter-axis rescale
    return res._replace(n_iter=n_rep), res.n_iter


# ----------------------------------------------------------------------------
# Ridge / LinearRegression
# ----------------------------------------------------------------------------

def _weighted_center(X, y, w):
    wsum = jnp.sum(w) + jnp.finfo(X.dtype).eps
    xm = (w @ X) / wsum
    ym = jnp.sum(w * y) / wsum
    return X - xm, y - ym, xm, ym


def _centered_problem(static, X, y, train_w):
    """Shared OLS/Ridge preamble: positive= guard + optional weighted
    centering.  Returns (Xc, yc, xm, ym)."""
    if static.get("positive", False):
        raise NotCompiledError(
            "positive=True is not compiled; use backend='host'")
    if bool(static.get("fit_intercept", True)):
        return _weighted_center(X, y, train_w)
    d = X.shape[1]
    return X, y, jnp.zeros((d,), X.dtype), jnp.asarray(0.0, X.dtype)


class RidgeFamily(Family):
    name = "ridge"
    is_classifier = False
    dynamic_params = {"alpha": np.float32}
    # closed-form normal equations: the Gram's conditioning amplifies f32
    # rounding ~1e-4 past sklearn's f64 answers, so the search engine runs
    # this family under x64 (tiny d x d solves — negligible cost)
    wants_float64 = True
    #: the fit is a function of raw second moments {sum w, w@X, sum wy,
    #: X'WX, X'Wy} — additive over row shards; finalize re-centres them
    #: (x64, so the moment expansion stays at solver tolerance)
    supports_stream = True

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        data = {"X": np.ascontiguousarray(X, dtype=dtype),
                "y": np.ascontiguousarray(y, dtype=dtype)}
        meta = {"n_features": int(X.shape[1])}
        return data, meta

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        X, y = data["X"], data["y"]
        d = X.shape[1]
        alpha = jnp.asarray(dynamic.get("alpha", static.get("alpha", 1.0)),
                            X.dtype)
        Xc, yc, xm, ym = _centered_problem(static, X, y, train_w)
        Xw = Xc * train_w[:, None]
        A = Xw.T @ Xc + alpha * jnp.eye(d, dtype=X.dtype)
        b = Xw.T @ yc
        w = jax.scipy.linalg.solve(A, b, assume_a="pos")
        intercept = ym - jnp.dot(xm, w)
        return {"coef": w, "intercept": intercept}

    # --- streaming-fold protocol -----------------------------------------
    @classmethod
    def stream_fit_partial(cls, static, data, fit_w, meta):
        if static.get("positive", False):
            raise NotCompiledError(
                "positive=True is not compiled; use backend='host'")
        X, y = data["X"], data["y"]

        def one_fold(w):
            Xw = X * w[:, None]
            return {"wsum": jnp.sum(w), "s": w @ X,
                    "ys": jnp.sum(w * y),
                    "G": Xw.T @ X, "c": Xw.T @ y}

        return jax.vmap(one_fold)(fit_w)

    @classmethod
    def stream_fit_finalize(cls, dynamic, static, stats, meta):
        if static.get("positive", False):
            raise NotCompiledError(
                "positive=True is not compiled; use backend='host'")
        G, s, c = stats["G"], stats["s"], stats["c"]
        dt = G.dtype
        d = s.shape[0]
        alpha = jnp.asarray(dynamic.get("alpha", static.get("alpha", 1.0)),
                            dt)
        if bool(static.get("fit_intercept", True)):
            # centred normal equations from raw moments:
            #   A = X'WX - s xm' - xm s' + (sum w) xm xm'
            #   b = X'Wy - ym s - ys xm + (sum w) xm ym
            # (xm, ym use the same eps-guarded weight sum as
            # _weighted_center)
            wsum = stats["wsum"] + jnp.finfo(dt).eps
            xm = s / wsum
            ym = stats["ys"] / wsum
            A = G - jnp.outer(s, xm) - jnp.outer(xm, s) \
                + stats["wsum"] * jnp.outer(xm, xm)
            b = c - ym * s - stats["ys"] * xm + stats["wsum"] * xm * ym
        else:
            A, b = G, c
            xm = jnp.zeros((d,), dt)
            ym = jnp.asarray(0.0, dt)
        A = A + alpha * jnp.eye(d, dtype=dt)
        w = jax.scipy.linalg.solve(A, b, assume_a="pos")
        return {"coef": w, "intercept": ym - jnp.dot(xm, w)}

    @classmethod
    def predict(cls, model, static, X, meta):
        return X @ model["coef"] + model["intercept"]

    @classmethod
    def views_task_batched(cls, models, static, data, meta, needed):
        """All T tasks' predictions as ONE (n, d) @ (d, T) matmul."""
        if "pred" not in needed:
            return {}
        X = data["X"]
        pred = jnp.matmul(X, models["coef"].T,
                          preferred_element_type=X.dtype)   # (n, T)
        return {"pred": (pred + models["intercept"][None]).T}

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"coef_": np.asarray(model["coef"]),
                "intercept_": float(model["intercept"]),
                "n_features_in_": meta["n_features"]}


class LinearRegressionFamily(RidgeFamily):
    name = "linear_regression"
    # lstsq's minimum-norm answer on rank-deficient X is NOT a function
    # of the normal-equation moments — undo the inherited capability
    supports_stream = False

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        """Weighted OLS as minimum-norm lstsq (SVD), matching sklearn's
        scipy.linalg.lstsq path: on rank-deficient X the solution is the
        minimum-norm one, where a ridge-with-tiny-alpha stand-in (the
        round-1 implementation) diverges from sklearn."""
        X, y = data["X"], data["y"]
        Xc, yc, xm, ym = _centered_problem(static, X, y, train_w)
        sw = jnp.sqrt(train_w)
        w, *_ = jnp.linalg.lstsq(Xc * sw[:, None], yc * sw)
        intercept = ym - jnp.dot(xm, w)
        return {"coef": w, "intercept": intercept}


# ----------------------------------------------------------------------------
# ElasticNet / Lasso (FISTA)
# ----------------------------------------------------------------------------

class ElasticNetFamily(Family):
    name = "elastic_net"
    is_classifier = False
    dynamic_params = {"alpha": np.float32, "l1_ratio": np.float32}

    prepare_data = RidgeFamily.prepare_data

    min_sort_candidates = 32

    @classmethod
    def convergence_proxy(cls, dynamic_params, static):
        """Smaller alpha = weaker penalty = slower FISTA convergence,
        so ascending difficulty = DESCENDING alpha (negated proxy)."""
        alpha = dynamic_params.get("alpha")
        return None if alpha is None else -np.asarray(alpha)

    @classmethod
    def extract_params(cls, estimator):
        params = dict(estimator.get_params(deep=False))
        if type(estimator).__name__ == "Lasso":
            params["l1_ratio"] = 1.0
        return params

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        X, y = data["X"], data["y"]
        d = X.shape[1]
        alpha = jnp.asarray(dynamic.get("alpha", static.get("alpha", 1.0)),
                            X.dtype)
        l1r = jnp.asarray(
            dynamic.get("l1_ratio", static.get("l1_ratio", 0.5)), X.dtype)
        max_iter = int(static.get("max_iter", 1000))
        n_eff = jnp.sum(train_w) + jnp.finfo(X.dtype).eps
        Xc, yc, xm, ym = _centered_problem(static, X, y, train_w)
        Xw = Xc * train_w[:, None]
        # Lipschitz constant of (1/n) X^T W X via power iteration
        G = Xw.T @ Xc / n_eff
        v = jnp.ones((d,), X.dtype) / jnp.sqrt(d)

        def power(i, v):
            v = G @ v
            return v / (jnp.linalg.norm(v) + jnp.finfo(X.dtype).eps)

        v = jax.lax.fori_loop(0, 30, power, v)
        L = jnp.dot(v, G @ v) + alpha * (1.0 - l1r) + 1e-6
        lam1 = alpha * l1r
        lam2 = alpha * (1.0 - l1r)

        def grad(w):
            r = Xc @ w - yc
            return (Xw.T @ r) / n_eff + lam2 * w

        def soft(u, t):
            return jnp.sign(u) * jnp.maximum(jnp.abs(u) - t, 0.0)

        def body(carry, _):
            w, z, t = carry
            w_new = soft(z - grad(z) / L, lam1 / L)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            z_new = w_new + (t - 1.0) / t_new * (w_new - w)
            return (w_new, z_new, t_new), None

        w0 = jnp.zeros((d,), X.dtype)
        (w, _, _), _ = jax.lax.scan(
            body, (w0, w0, jnp.asarray(1.0, X.dtype)), None, length=max_iter)
        intercept = ym - jnp.dot(xm, w)
        return {"coef": w, "intercept": intercept}

    predict = RidgeFamily.predict
    views_task_batched = RidgeFamily.views_task_batched
    sklearn_attrs = RidgeFamily.sklearn_attrs


register_family(
    LogisticRegressionFamily,
    "sklearn.linear_model._logistic.LogisticRegression",
    "sklearn.linear_model.LogisticRegression",
    "spark_sklearn_tpu.models.estimators.LogisticRegression",
)
register_family(
    RidgeFamily,
    "sklearn.linear_model._ridge.Ridge",
    "sklearn.linear_model.Ridge",
    "spark_sklearn_tpu.models.estimators.Ridge",
)
register_family(
    LinearRegressionFamily,
    "sklearn.linear_model._base.LinearRegression",
    "sklearn.linear_model.LinearRegression",
    "spark_sklearn_tpu.models.estimators.LinearRegression",
)
register_family(
    ElasticNetFamily,
    "sklearn.linear_model._coordinate_descent.ElasticNet",
    "sklearn.linear_model.ElasticNet",
    "sklearn.linear_model._coordinate_descent.Lasso",
    "sklearn.linear_model.Lasso",
    "spark_sklearn_tpu.models.estimators.ElasticNet",
    "spark_sklearn_tpu.models.estimators.Lasso",
)
