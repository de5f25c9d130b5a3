"""SVR / LinearSVC / LinearSVR families — the remaining libsvm/liblinear
estimators, re-designed for the MXU.

Reference counterpart: sklearn's SVR/LinearSVC/LinearSVR run unchanged as
host Python inside Spark tasks (reference: grid_search.py -> sklearn
_fit_and_score).  The TPU redesign:

- SVR solves the epsilon-SVR dual with the SAME box-and-hyperplane
  projected ascent as SVC (models/svm.py): the paired variables
  u = (a, a*) live in one (M, 2n) row per subproblem, the signs
  s = (+1...,-1...) take the role SVC's labels play in the equality
  constraint sum(a - a*) = 0, and the tiled kernel [[K,K],[K,K]] acts
  through ONE (M, n) @ (n, n) matmul per iteration (its top eigenvalue is
  2*lambda_max(K), so SVC's power-iteration step halves).
- LinearSVC/LinearSVR solve liblinear's smooth PRIMAL losses
  (squared_hinge / squared_epsilon_insensitive) with the same batched
  L-BFGS engine as logistic regression (ops/solvers.glm_lbfgs_batched),
  and the nonsmooth losses (hinge / epsilon_insensitive) through their
  box-constrained DUAL QPs with accelerated projected gradient
  (`_box_fista`) — the TPU answer to liblinear's sequential dual
  coordinate descent; all (candidate x fold) tasks advance as one wide
  matmul either way.  liblinear's augmented-column intercept convention
  (intercept_scaling, intercept REGULARISED) is reproduced exactly.
  crammer_singer and penalty='l1' raise -> the search falls back to the
  host tier, matching sklearn bit-for-bit there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, register_family)
from spark_sklearn_tpu.models.svm import (
    _box_fista,
    _kernel,
    _masked_mean_or_mid,
    _power_step,
    _project_box_hyperplane,
    _project_box_sum,
    _resolve_gamma,
    _run_dual,
    _tol_or_default,
)


def svr_dual_ascent(K, y, eps, bound_half, step, max_iter, tol=None):
    """Nesterov-accelerated projected ascent on the epsilon-SVR dual

        max_{a,a*}  -0.5 (a-a*)' K (a-a*) - eps 1'(a+a*) + y'(a-a*)
        0 <= a_i, a*_i <= C_i,   sum_i (a_i - a*_i) = 0

    in the stacked form u = (a, a*) with signs s = (+1^n, -1^n): the
    equality is sum(s*u) = 0 (SVC's hyperplane with s for labels) and the
    quadratic acts through beta = a - a* so each iteration is one
    (M, n) @ (n, n) matmul.  bound_half: (M, n) per-sample C (fold-masked,
    sample-weight-scaled); applies to both halves.  `tol` enables the
    per-lane prox-residual exit on the stacked (a, a*) iterate — the
    batched analog of libsvm's eps rule for epsilon-SVR (sklearn SVR
    tol, default 1e-3), same machinery SVC's duals got in round 4.
    Returns (beta, b, n_iter)."""
    M, n = bound_half.shape
    dtype = K.dtype
    s = jnp.concatenate([jnp.ones((n,), dtype), -jnp.ones((n,), dtype)])
    lin = s * jnp.concatenate([y, y]) - eps            # (2n,) per-element
    bound = jnp.concatenate([bound_half, bound_half], axis=1)   # (M, 2n)

    def grad(Z):                       # descent form of the ascent grad
        beta = (Z * s).reshape(M, 2, n).sum(axis=1)    # a - a*  (M, n)
        V_half = beta @ K                              # (M, n)
        V = jnp.concatenate([V_half, V_half], axis=1)  # (M, 2n)
        return -(lin - s * V)

    U, n_it = _run_dual(
        grad, lambda Zt: _project_box_hyperplane(Zt, s[None, :], bound),
        jnp.zeros_like(bound), step, max_iter, tol, dtype)
    beta = (U * s).reshape(M, 2, n).sum(axis=1)
    return beta, _svr_intercept(K, U, beta, y, eps, bound_half), n_it


def _svr_intercept(K, U, beta, y, eps, bound_half):
    """KKT intercept (libsvm's -rho for epsilon-SVR): over free SVs,
    y - f0 - b = +eps for 0 < a < C and -eps for 0 < a* < C; when nothing
    is free, the midpoint of the feasible [max lower, min upper] interval
    from the at-bound conditions."""
    M, n = bound_half.shape
    f0 = beta @ K                                       # (M, n)
    E = y[None, :] - f0
    a = U[:, :n]
    a_star = U[:, n:]
    inb = bound_half > 0
    tol_lo = bound_half * 1e-6
    tol_hi = bound_half * (1.0 - 1e-6)
    free_a = inb & (a > tol_lo) & (a < tol_hi)
    free_as = inb & (a_star > tol_lo) & (a_star < tol_hi)
    nfree = jnp.sum(free_a, axis=1) + jnp.sum(free_as, axis=1)
    b_free = (jnp.sum(jnp.where(free_a, E - eps, 0.0), axis=1)
              + jnp.sum(jnp.where(free_as, E + eps, 0.0), axis=1)) \
        / jnp.maximum(nfree, 1)
    # at-bound conditions: a=0 -> b >= E-eps; a*=C -> b >= E+eps;
    #                      a=C -> b <= E-eps; a*=0 -> b <= E+eps
    big = jnp.asarray(jnp.inf, E.dtype)
    lb = jnp.maximum(
        jnp.max(jnp.where(inb & (a <= tol_lo), E - eps, -big), axis=1),
        jnp.max(jnp.where(inb & (a_star >= tol_hi), E + eps, -big), axis=1))
    ub = jnp.minimum(
        jnp.min(jnp.where(inb & (a >= tol_hi), E - eps, big), axis=1),
        jnp.min(jnp.where(inb & (a_star <= tol_lo), E + eps, big), axis=1))
    b_mid = 0.5 * (lb + ub)
    b_mid = jnp.where(jnp.isfinite(b_mid), b_mid,
                      jnp.where(jnp.isfinite(lb), lb,
                                jnp.where(jnp.isfinite(ub), ub, 0.0)))
    return jnp.where(nfree > 0, b_free, b_mid)


def nu_svr_dual_ascent(K, y, nu, bound_half, step, max_iter, tol=None):
    """libsvm's nu-SVR dual (solve_nu_svr): stacked u = (a, a*) with
    per-element box C (already folded into `bound_half` by the caller,
    fold/sample-weight-scaled), sum over EACH half = C*nu*l/2 — i.e.
    nu/2 of the half's total box capacity, which keeps the libsvm value
    under fold masks and sample weights — and no epsilon in the
    objective: the tube width is implicit, recovered from the KKT
    conditions together with b.  Always feasible for nu in (0, 1].
    `tol` enables the per-lane residual exit (libsvm eps rule); returns
    (f, n_iter)."""
    M, n = bound_half.shape
    dtype = K.dtype
    s = jnp.concatenate([jnp.ones((n,), dtype), -jnp.ones((n,), dtype)])
    lin = s * jnp.concatenate([y, y])
    zero = jnp.zeros_like(bound_half)
    pos_b = jnp.concatenate([bound_half, zero], axis=1)       # (M, 2n)
    neg_b = jnp.concatenate([zero, bound_half], axis=1)
    cap = jnp.sum(bound_half, axis=1)
    target = jnp.broadcast_to(0.5 * nu * cap, (M,))
    feasible = target <= cap * (1.0 + 1e-6)

    def project(Zt):
        return _project_box_sum(Zt, pos_b, target) + \
            _project_box_sum(Zt, neg_b, target)

    def grad(Z):                       # descent form of the ascent grad
        beta = (Z * s).reshape(M, 2, n).sum(axis=1)
        V_half = beta @ K
        V = jnp.concatenate([V_half, V_half], axis=1)
        return -(lin - s * V)

    U, n_it = _run_dual(grad, project,
                        project(jnp.zeros((M, 2 * n), dtype)),
                        step, max_iter, tol, dtype)
    beta = (U * s).reshape(M, 2, n).sum(axis=1)
    # KKT: free a  -> y - f0 - b = +eps  (E estimates b + eps)
    #      free a* -> y - f0 - b = -eps  (E estimates b - eps)
    E = y[None, :] - beta @ K
    a, a_star = U[:, :n], U[:, n:]
    inb = bound_half > 0
    t_lo = bound_half * 1e-6
    t_hi = bound_half * (1.0 - 1e-6)
    free_a = inb & (a > t_lo) & (a < t_hi)
    free_as = inb & (a_star > t_lo) & (a_star < t_hi)
    # bound directions (cf. _svr_intercept's at-bound table): a=0 rows
    # LOWER-bound b+eps, a=C rows upper-bound it; a*=C rows LOWER-bound
    # b-eps, a*=0 rows upper-bound it
    m_a = _masked_mean_or_mid(E, free_a, inb & (a <= t_lo),
                              inb & (a >= t_hi))
    m_as = _masked_mean_or_mid(E, free_as, inb & (a_star >= t_hi),
                               inb & (a_star <= t_lo))
    b = 0.5 * (m_a + m_as)
    f = beta @ K + b[:, None]
    return jnp.where(feasible[:, None], f, jnp.nan), n_it


class SVRFamily(Family):
    name = "svr"
    is_classifier = False
    dynamic_params = {"C": np.float32, "gamma": np.float32,
                      "epsilon": np.float32}
    #: the third per-candidate scalar next to C/gamma (NuSVR swaps in nu)
    aux_param = "epsilon"
    aux_default = 0.1
    # task-batched only (like SVC): the keyed fleet and per-task callers
    # skip it via has_per_task_fit(); keyed_compatible stays True so
    # make_pipeline_family composes it as a fold-input final, NOT as a
    # binned-invariant tree final
    task_batched_accepts_fold_inputs = True

    @classmethod
    def _fold_dual(cls, K, y, C_c, aux_c, w_rows, step, max_iter,
                   tol=None):
        """Solve the fold subproblems for one candidate; returns ((F, n)
        full-set regression values, executed iterations).  `aux_c` is
        epsilon here; `tol` enables the per-candidate residual exit."""
        bound = C_c * w_rows
        beta, b, n_it = svr_dual_ascent(
            K, y, aux_c, bound, step, max_iter, tol)
        return beta @ K + b[:, None], n_it

    @staticmethod
    def max_tasks_hint(n_samples: int, meta) -> int:
        budget = 1 << 30
        return max(1, budget // max(1, n_samples * 8))

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        data = {"X": np.ascontiguousarray(X, dtype=dtype),
                "y": np.ascontiguousarray(y, dtype=dtype)}
        meta = {"n_features": int(X.shape[1]),
                "x_var": float(np.var(np.asarray(X)))}
        return data, meta

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        """Candidate-major tasks (task t = (cand t//F, fold t%F)); one
        kernel per candidate shared by its F fold subproblems.  Caches the
        full-dataset regression values f(x) per task (the search scores on
        masked rows, so predict never rebuilds kernels)."""
        X, y = data["X"], data["y"]
        n, d = X.shape
        B = train_w.shape[0]
        kind = static.get("kernel", "rbf")
        if kind == "precomputed":
            raise NotCompiledError(
                "precomputed kernels are not compiled; use backend='host'")
        degree = float(static.get("degree", 3))
        coef0 = float(static.get("coef0", 0.0))
        max_iter = int(static.get("max_iter", -1))
        if max_iter in (-1, 0):
            max_iter = 300
        # libsvm's eps stopping rule (sklearn SVR tol, default 1e-3):
        # each candidate's paired (a, a*) dual exits at ITS convergence
        # inside the per-candidate scan — the same per-candidate tol
        # exit SVC's pair duals got in round 4 (VERDICT r4 next #2)
        tol_exit = _tol_or_default(static)
        n_folds = int(static.get("__n_folds__", 0))
        if n_folds <= 0:
            raise ValueError("engine must pass __n_folds__ for SVR")
        nc = B // n_folds

        gamma_default = _resolve_gamma(static.get("gamma", "scale"), meta)
        ap = cls.aux_param
        C_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get("C", static.get("C", 1.0)), X.dtype), (B,))
        g_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get("gamma", gamma_default), X.dtype), (B,))
        e_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get(ap, static.get(ap, cls.aux_default)),
            X.dtype), (B,))
        C_cand = C_task.reshape(nc, n_folds)[:, 0]
        g_cand = g_task.reshape(nc, n_folds)[:, 0]
        e_cand = e_task.reshape(nc, n_folds)[:, 0]
        w_cand = train_w.reshape(nc, n_folds, n)

        X_folds = data.get("X_folds")      # (F, n, d) pipeline mode
        gamma_is_scale = "gamma" not in dynamic and \
            static.get("gamma", "scale") == "scale"

        def one_candidate(carry, inp):
            C_c, g_c, e_c, w_f = inp
            if X_folds is None:
                K = _kernel(X, X, kind, g_c, degree, coef0)
                step = 0.5 * _power_step(K, n, X.dtype)   # lam_max doubles
                f, it = cls._fold_dual(
                    K, y, C_c, e_c, w_f, step, max_iter, tol_exit)
            else:
                def per_fold(Xf, w_row):
                    if gamma_is_scale:
                        mrow = (w_row > 0).astype(Xf.dtype)
                        cnt = jnp.sum(mrow) * Xf.shape[1] + 1e-12
                        mu = jnp.sum(Xf * mrow[:, None]) / cnt
                        var = jnp.sum(((Xf - mu) ** 2)
                                      * mrow[:, None]) / cnt
                        g_f = 1.0 / (Xf.shape[1]
                                     * jnp.maximum(var, 1e-12))
                    else:
                        g_f = g_c
                    Kf = _kernel(Xf, Xf, kind, g_f, degree, coef0)
                    step = 0.5 * _power_step(Kf, n, Xf.dtype)
                    ff, itf = cls._fold_dual(
                        Kf, y, C_c, e_c, w_row[None, :], step,
                        max_iter, tol_exit)
                    return ff[0], itf

                f, its = jax.vmap(per_fold)(X_folds, w_f)  # (F, n), (F,)
                it = jnp.max(its)
            return carry, (f, it)

        _, (fs, its) = jax.lax.scan(
            one_candidate, 0.0, (C_cand, g_cand, e_cand, w_cand))
        # per-candidate executed dual iterations repeat across the fold
        # axis for the engine's per-launch accounting (same layout as SVC)
        return {"f": fs.reshape(B, n),
                "n_iter": jnp.repeat(its, n_folds)}

    @classmethod
    def predict(cls, model, static, X, meta):
        return model["f"]

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"n_features_in_": meta["n_features"]}


# ----------------------------------------------------------------------------
# liblinear primal + dual families
# ----------------------------------------------------------------------------

def _check_linear_svc_static(static):
    if static.get("penalty", "l2") != "l2":
        raise NotCompiledError(
            "penalty='l1' is not compiled; use backend='host'")
    if static.get("loss", "squared_hinge") not in (
            "squared_hinge", "hinge"):
        raise NotCompiledError(
            f"loss={static.get('loss')!r} is not compiled; use "
            "backend='host'")
    if static.get("multi_class", "ovr") != "ovr":
        raise NotCompiledError(
            "multi_class='crammer_singer' is not compiled; use "
            "backend='host'")


def _gram_step(Xa, dtype):
    """1 / lambda_max(Xa Xa^T) via power iteration through the factored
    Gram (never materialised: two (n, da) matmuls per step)."""
    n = Xa.shape[0]
    v = jnp.ones((n,), dtype) / jnp.sqrt(n)

    def power(i, v):
        u = Xa @ (v @ Xa)
        return u / (jnp.linalg.norm(u) + 1e-30)

    v = jax.lax.fori_loop(0, 20, power, v)
    lam = jnp.dot(v, Xa @ (v @ Xa)) + 1e-6
    return 1.0 / lam


class LinearSVCFamily(Family):
    """liblinear's L2-regularised squared-hinge primal, one-vs-rest.

    liblinear regularises the intercept via the appended
    intercept_scaling column — reproduced exactly (coef dimension d+1,
    all penalised), so scores track sklearn's LinearSVC, not a
    hand-rolled unpenalised-intercept variant.
    """

    name = "linear_svc"
    is_classifier = True
    dynamic_params = {"C": np.float32, "tol": np.float32}

    min_sort_candidates = 32

    @classmethod
    def convergence_proxy(cls, dynamic_params, static):
        """Larger C = weaker regularisation = slower convergence (both
        the hinge dual's residual exit and the squared-hinge primal's
        L-BFGS stall exit fire sooner at small C) — sorted chunking
        lets the easy launches retire early."""
        return dynamic_params.get("C")

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        from spark_sklearn_tpu.models.base import encode_labels
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
    def fit(cls, dynamic, static, data, train_w, meta):
        model = cls.fit_task_batched(
            {k: jnp.asarray(v)[None] for k, v in dynamic.items()},
            static, data, train_w[None, :], meta)
        return jax.tree_util.tree_map(lambda a: a[0], model)

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        from spark_sklearn_tpu.ops.solvers import glm_lbfgs_batched

        _check_linear_svc_static(static)
        X = data["X"]
        n, d = X.shape
        k = meta["n_classes"]
        ko = 1 if k == 2 else k          # liblinear: one machine for binary
        B = train_w.shape[0]
        C = jnp.broadcast_to(jnp.asarray(
            dynamic.get("C", static.get("C", 1.0)), X.dtype), (B,))
        tol = jnp.broadcast_to(jnp.asarray(
            dynamic.get("tol", static.get("tol", 1e-4)), X.dtype), (B,))
        max_iter = int(static.get("max_iter", 1000))
        fit_intercept = bool(static.get("fit_intercept", True))
        isc = float(static.get("intercept_scaling", 1.0))

        from spark_sklearn_tpu.models.base import apply_class_weight
        train_w = apply_class_weight(
            train_w, data["y"], meta, static.get("class_weight"))

        # liblinear intercept: an appended constant column, REGULARISED
        Xa = jnp.concatenate(
            [X, jnp.full((n, 1), isc, X.dtype)], axis=1) if fit_intercept \
            else X
        da = Xa.shape[1]
        # targets in {-1, +1}: OvR per class; binary = one machine for
        # classes_[1]
        if k == 2:
            T = (2.0 * data["y"].astype(X.dtype) - 1.0)[:, None]  # (n, 1)
        else:
            T = 2.0 * data["y1h"] - 1.0                           # (n, k)
        wT = train_w.T                                            # (n, B)

        if static.get("loss", "squared_hinge") == "hinge":
            # liblinear's l1-loss dual per OvR machine m:
            #   min_a 0.5 a'Q a - 1'a,  0 <= a_i <= C * w_i,
            #   Q = diag(t) Xa Xa' diag(t)  (same spectrum as the Gram)
            # No equality constraint — the intercept is the regularised
            # appended column, exactly liblinear.  Solved by accelerated
            # projected gradient; the coordinate-descent answer is the
            # same optimum (the dual is a strictly convex QP on a box).
            step = _gram_step(Xa, X.dtype)
            Tt = T.T[None, :, :]                       # (1, ko, n)
            bound = (C[:, None, None]
                     * train_w[:, None, :])            # (B, 1->ko, n)

            def grad(a):                               # a (B, ko, n)
                v = jnp.einsum("bkn,nd->bkd", a * Tt, Xa)
                q = jnp.einsum("bkd,nd->bkn", v, Xa) * Tt
                return q - 1.0

            def project(a):
                return jnp.clip(a, 0.0, bound)

            a0 = jnp.zeros((B, ko, n), X.dtype)
            a, n_iter, converged = _box_fista(
                grad, project, a0, step, max_iter, tol=tol)
            W = jnp.einsum("bkn,nd->bkd", a * Tt, Xa)  # (B, ko, da)
            if fit_intercept:
                coef, intercept = W[:, :, :d], W[:, :, d] * isc
            else:
                coef = W
                intercept = jnp.zeros((B, ko), X.dtype)
            return {"coef": coef, "intercept": intercept,
                    "converged": converged, "n_iter": n_iter}

        def Ax(x):                                    # (B, da*ko) -> Z
            W = x.reshape(B, ko, da)
            return jnp.einsum("nd,bkd->nbk", Xa, W)

        def data_loss(Z):
            r = jnp.maximum(0.0, 1.0 - T[:, None, :] * Z)
            return C * jnp.sum(wT[:, :, None] * r * r, axis=(0, 2))

        def data_grad(Z):
            r = jnp.maximum(0.0, 1.0 - T[:, None, :] * Z)
            return C[None, :, None] * wT[:, :, None] \
                * (-2.0 * T[:, None, :] * r)

        def AT(G):
            return jnp.einsum("nbk,nd->bkd", G, Xa).reshape(B, ko * da)

        def reg_loss(x):
            return 0.5 * jnp.sum(x * x, axis=1)

        def reg_grad(x):
            return x

        res = glm_lbfgs_batched(
            Ax, data_loss, data_grad, AT, reg_loss, reg_grad,
            jnp.zeros((B, ko * da), X.dtype), max_iter=max_iter, tol=tol)
        W = res.x.reshape(B, ko, da)
        if fit_intercept:
            coef = W[:, :, :d]
            intercept = W[:, :, d] * isc
        else:
            coef = W
            intercept = jnp.zeros((B, ko), X.dtype)
        return {"coef": coef, "intercept": intercept,
                "converged": res.converged, "n_iter": res.n_iter}

    @classmethod
    def decision(cls, model, static, X, meta):
        Z = X @ jnp.swapaxes(model["coef"], -1, -2) + model["intercept"]
        if meta["n_classes"] == 2:
            return Z[..., 0]
        return Z

    @classmethod
    def predict(cls, model, static, X, meta):
        Z = cls.decision(model, static, X, meta)
        if meta["n_classes"] == 2:
            return (Z > 0).astype(jnp.int32)
        return jnp.argmax(Z, axis=-1).astype(jnp.int32)

    @classmethod
    def views_task_batched(cls, models, static, data, meta, needed):
        """Scorer views for all T tasks from one wide `X @ W_all^T`
        matmul (coef (T, ko, d) — the ovr/binary twin of the GLM
        family's wide scoring layout)."""
        X = data["X"]
        n = X.shape[0]
        W = models["coef"]                                 # (T, ko, d)
        b = models["intercept"]                            # (T, ko)
        T, ko, d = W.shape
        Z = jnp.matmul(X, W.reshape(T * ko, d).T,
                       preferred_element_type=X.dtype)
        Z = jnp.moveaxis(Z.reshape(n, T, ko) + b[None], 0, 1)  # (T, n, ko)
        z = Z[:, :, 0] if meta["n_classes"] == 2 else Z
        views = {}
        if "decision" in needed:
            views["decision"] = z
        if "pred" in needed:
            views["pred"] = (z > 0).astype(jnp.int32) \
                if meta["n_classes"] == 2 \
                else jnp.argmax(Z, axis=-1).astype(jnp.int32)
        return views

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {
            "coef_": np.asarray(model["coef"]),
            "intercept_": np.asarray(model["intercept"]),
            "classes_": meta["classes"],
            "n_features_in_": meta["n_features"],
            "n_iter_": int(np.asarray(model["n_iter"]))
            if "n_iter" in model else None,
        }


class LinearSVRFamily(Family):
    """liblinear's squared-epsilon-insensitive primal (LinearSVR with
    loss='squared_epsilon_insensitive'; the nonsmooth default
    'epsilon_insensitive' raises -> host tier).  Same regularised
    appended-column intercept convention as LinearSVC."""

    name = "linear_svr"
    is_classifier = False
    dynamic_params = {"C": np.float32, "tol": np.float32,
                      "epsilon": np.float32}

    min_sort_candidates = 32
    convergence_proxy = LinearSVCFamily.convergence_proxy

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        data = {"X": np.ascontiguousarray(X, dtype=dtype),
                "y": np.ascontiguousarray(y, dtype=dtype)}
        meta = {"n_features": int(X.shape[1])}
        return data, meta

    @classmethod
    def fit(cls, dynamic, static, data, train_w, meta):
        model = cls.fit_task_batched(
            {k: jnp.asarray(v)[None] for k, v in dynamic.items()},
            static, data, train_w[None, :], meta)
        return jax.tree_util.tree_map(lambda a: a[0], model)

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        from spark_sklearn_tpu.ops.solvers import glm_lbfgs_batched

        loss = static.get("loss", "epsilon_insensitive")
        if loss not in ("epsilon_insensitive",
                        "squared_epsilon_insensitive"):
            raise NotCompiledError(
                f"loss={loss!r} is not compiled; use backend='host'")
        X, y = data["X"], data["y"]
        n, d = X.shape
        B = train_w.shape[0]
        C = jnp.broadcast_to(jnp.asarray(
            dynamic.get("C", static.get("C", 1.0)), X.dtype), (B,))
        eps_t = jnp.broadcast_to(jnp.asarray(
            dynamic.get("epsilon", static.get("epsilon", 0.0)),
            X.dtype), (B,))
        tol = jnp.broadcast_to(jnp.asarray(
            dynamic.get("tol", static.get("tol", 1e-4)), X.dtype), (B,))
        max_iter = int(static.get("max_iter", 1000))
        fit_intercept = bool(static.get("fit_intercept", True))
        isc = float(static.get("intercept_scaling", 1.0))

        Xa = jnp.concatenate(
            [X, jnp.full((n, 1), isc, X.dtype)], axis=1) if fit_intercept \
            else X
        da = Xa.shape[1]
        wT = train_w.T                                  # (n, B)

        if loss == "epsilon_insensitive":
            # liblinear's l1-loss dual in beta = a - a*: since a_i a*_i = 0
            # at the optimum, the paired dual collapses to
            #   min_b 0.5 b'(Xa Xa')b - y'b + eps*|b|_1,  |b_i| <= C*w_i
            # — a box-constrained lasso QP whose prox is soft-threshold
            # then clip (the box is symmetric/separable).  The intercept
            # is the regularised appended column, exactly liblinear.
            step = _gram_step(Xa, X.dtype)
            bound = C[:, None] * train_w                # (B, n)

            def grad(b):                                # (B, n)
                return (b @ Xa) @ Xa.T - y[None, :]

            def project(b):
                s = jnp.sign(b) * jnp.maximum(
                    jnp.abs(b) - step * eps_t[:, None], 0.0)
                return jnp.clip(s, -bound, bound)

            beta, n_iter, converged = _box_fista(
                grad, project, jnp.zeros((B, n), X.dtype), step, max_iter,
                tol=tol)
            Wd = beta @ Xa                              # (B, da)
            if fit_intercept:
                coef, intercept = Wd[:, :d], Wd[:, d] * isc
            else:
                coef = Wd
                intercept = jnp.zeros((B,), X.dtype)
            return {"coef": coef, "intercept": intercept,
                    "converged": converged, "n_iter": n_iter}

        def Ax(x):                                      # (B, da) -> (n, B)
            return Xa @ x.T

        def data_loss(Z):
            r = jnp.maximum(0.0, jnp.abs(Z - y[:, None]) - eps_t[None, :])
            return C * jnp.sum(wT * r * r, axis=0)

        def data_grad(Z):
            e = Z - y[:, None]
            r = jnp.maximum(0.0, jnp.abs(e) - eps_t[None, :])
            return C[None, :] * wT * 2.0 * jnp.sign(e) * r

        def AT(G):
            return G.T @ Xa

        res = glm_lbfgs_batched(
            Ax, data_loss, data_grad, AT,
            lambda x: 0.5 * jnp.sum(x * x, axis=1), lambda x: x,
            jnp.zeros((B, da), X.dtype), max_iter=max_iter, tol=tol)
        if fit_intercept:
            coef = res.x[:, :d]
            intercept = res.x[:, d] * isc
        else:
            coef = res.x
            intercept = jnp.zeros((B,), X.dtype)
        return {"coef": coef, "intercept": intercept,
                "converged": res.converged, "n_iter": res.n_iter}

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
                "intercept_": np.asarray(model["intercept"]),
                "n_features_in_": meta["n_features"]}


class NuSVRFamily(SVRFamily):
    """nu-SVR: SVR's kernel scaffold with libsvm's nu dual — per-sample
    box C (solve_nu_svr's convention), per-half sum C*nu*l/2, epsilon
    implicit (recovered with b from the free-SV KKT conditions in
    `nu_svr_dual_ascent`)."""

    name = "nu_svr"
    dynamic_params = {"C": np.float32, "gamma": np.float32,
                      "nu": np.float32}
    aux_param = "nu"
    aux_default = 0.5

    @classmethod
    def _fold_dual(cls, K, y, C_c, aux_c, w_rows, step, max_iter,
                   tol=None):
        return nu_svr_dual_ascent(
            K, y, aux_c, C_c * w_rows, step, max_iter, tol)


register_family(
    SVRFamily,
    "sklearn.svm._classes.SVR",
    "sklearn.svm.SVR",
)
register_family(
    NuSVRFamily,
    "sklearn.svm._classes.NuSVR",
    "sklearn.svm.NuSVR",
)
register_family(
    LinearSVCFamily,
    "sklearn.svm._classes.LinearSVC",
    "sklearn.svm.LinearSVC",
)
register_family(
    LinearSVRFamily,
    "sklearn.svm._classes.LinearSVR",
    "sklearn.svm.LinearSVR",
)
