"""Batched, jit/vmap-safe solvers.

The reference never solves anything itself — each Spark task calls
`estimator.fit`, which reaches scipy's L-BFGS / liblinear / libsvm on a CPU
executor (reference: grid_search.py -> sklearn _fit_and_score -> est.fit).
On TPU the solver must BE the program: fixed-shape, static control flow, no
Python in the loop, batchable with `vmap` over hyperparameter candidates so
the MXU sees one big batched problem instead of thousands of small ones.

`lbfgs` is a limited-memory BFGS with rolling history buffers and an Armijo
backtracking line search, written entirely with `lax.while_loop`/`fori_loop`
so that XLA compiles one program per (shape, max_iter) and `vmap` lifts it
over candidates (a batched while_loop runs until every lane converges).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


class LBFGSResult(NamedTuple):
    x: jnp.ndarray
    fun: jnp.ndarray
    grad_norm: jnp.ndarray
    n_iter: jnp.ndarray
    converged: jnp.ndarray
    #: iterations in which `glm_lbfgs_batched` ran the second stage of its
    #: line search, a scalar; None (no leaf, so no output of any program)
    #: from every other solver and from its generic, unstaged search
    ls_second_pass: Optional[jnp.ndarray] = None


#: trial steps that the staged line search of `glm_lbfgs_batched` evaluates
#: before it asks whether any live lane needs the other `ls_trials - 4`.
#: On the benchmark's grids 99.4 % of lane-iterations pick among the first
#: four and the knee of "every live lane did" is at 3-4 (PERF.md, PR 29).
_LS_FIRST_STAGE = 4


def _two_loop(g, s_mem, y_mem, rho, gamma, total, n_valid, m):
    """Two-loop recursion over a rolling history buffer.

    `total` is the number of pairs ever inserted (ring head = total % m);
    `n_valid = min(total, m)`.  Slot `(total - 1 - i) % m` holds the i-th most
    recent pair; slots with i >= n_valid are masked out so the same program
    serves warmup and steady state.
    """

    def bwd(i, carry):
        q, alpha = carry
        idx = jnp.mod(total - 1 - i, m)
        valid = i < n_valid
        a = rho[idx] * jnp.dot(s_mem[idx], q)
        a = jnp.where(valid, a, 0.0)
        q = q - a * y_mem[idx]
        alpha = alpha.at[idx].set(a)
        return q, alpha

    q, alpha = lax.fori_loop(0, m, bwd, (g, jnp.zeros((m,), g.dtype)))
    r = gamma * q

    def fwd(i, r):
        idx = jnp.mod(total - n_valid + i, m)
        valid = i < n_valid
        b = rho[idx] * jnp.dot(y_mem[idx], r)
        corr = (alpha[idx] - b) * s_mem[idx]
        return r + jnp.where(valid, corr, 0.0)

    r = lax.fori_loop(0, m, fwd, r)
    return -r


@partial(jax.jit, static_argnums=(0, 2, 4, 6))
def lbfgs(
    fun: Callable,
    x0: jnp.ndarray,
    max_iter: int = 100,
    tol: float = 1e-4,
    history: int = 10,
    c1: float = 1e-4,
    ls_max: int = 30,
) -> LBFGSResult:
    """Minimise `fun(x) -> scalar` from flat `x0`.

    Matches the role scipy's lbfgs plays for sklearn's LogisticRegression
    (sum-loss objective, gradient-infinity-norm stopping at `tol`; the
    caller converts sklearn's mean-loss `tol` — models/linear._sum_tol).
    """
    m = history
    d = x0.shape[0]
    dtype = x0.dtype
    vg = jax.value_and_grad(fun)
    f0, g0 = vg(x0)

    state = dict(
        x=x0, f=f0, g=g0,
        s_mem=jnp.zeros((m, d), dtype),
        y_mem=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        gamma=jnp.asarray(1.0, dtype),
        n_valid=jnp.asarray(0, jnp.int32),
        it=jnp.asarray(0, jnp.int32),
    )

    def gnorm(g):
        return jnp.max(jnp.abs(g))

    def cond(st):
        return jnp.logical_and(st["it"] < max_iter, gnorm(st["g"]) > tol)

    def body(st):
        x, f, g = st["x"], st["f"], st["g"]
        p = _two_loop(g, st["s_mem"], st["y_mem"], st["rho"], st["gamma"],
                      st["n_valid"], jnp.minimum(st["n_valid"], m), m)
        dginit = jnp.dot(g, p)
        # fall back to steepest descent if the direction lost descent-ness
        bad = dginit >= 0
        p = jnp.where(bad, -g, p)
        dginit = jnp.where(bad, -jnp.dot(g, g), dginit)

        # first step: scale so the initial trial is modest
        a0 = jnp.where(
            st["it"] == 0,
            jnp.minimum(jnp.asarray(1.0, dtype),
                        1.0 / (gnorm(g) + jnp.finfo(dtype).eps)),
            jnp.asarray(1.0, dtype),
        )

        def ls_cond(carry):
            alpha, k, fnew = carry
            armijo = fnew <= f + c1 * alpha * dginit
            return jnp.logical_and(k < ls_max, jnp.logical_not(armijo))

        def ls_body(carry):
            alpha, k, _ = carry
            alpha = alpha * 0.5
            return alpha, k + 1, fun(x + alpha * p)

        alpha, _, _ = lax.while_loop(
            ls_cond, ls_body, (a0, jnp.asarray(0, jnp.int32), fun(x + a0 * p)))

        x_new = x + alpha * p
        f_new, g_new = vg(x_new)
        # reject non-finite steps outright (error_score semantics handle the
        # rest at the search layer)
        ok = jnp.isfinite(f_new)
        x_new = jnp.where(ok, x_new, x)
        f_new = jnp.where(ok, f_new, f)
        g_new = jnp.where(ok, g_new, g)

        s = x_new - x
        yv = g_new - g
        sy = jnp.dot(s, yv)
        update = sy > 1e-10
        head = jnp.mod(st["n_valid"], m)
        s_mem = jnp.where(update, st["s_mem"].at[head].set(s), st["s_mem"])
        y_mem = jnp.where(update, st["y_mem"].at[head].set(yv), st["y_mem"])
        rho = jnp.where(update, st["rho"].at[head].set(1.0 / sy), st["rho"])
        gamma = jnp.where(update, sy / (jnp.dot(yv, yv) + jnp.finfo(dtype).eps),
                          st["gamma"])
        n_valid = jnp.where(update, st["n_valid"] + 1, st["n_valid"])

        return dict(x=x_new, f=f_new, g=g_new, s_mem=s_mem, y_mem=y_mem,
                    rho=rho, gamma=gamma, n_valid=n_valid, it=st["it"] + 1)

    st = lax.while_loop(cond, body, state)
    return LBFGSResult(
        x=st["x"], fun=st["f"], grad_norm=gnorm(st["g"]), n_iter=st["it"],
        converged=gnorm(st["g"]) <= tol)


def _armijo_pick(armijo):
    """Each lane's pick among its trial steps, (T, B) bool -> (B,): the
    first (largest-step) passing trial; where no trial passed, the last
    (smallest) step rather than stall."""
    first_ok = jnp.argmax(armijo, axis=0)
    found = jnp.any(armijo, axis=0)
    return jnp.where(found, first_ok, armijo.shape[0] - 1)


def glm_lbfgs_batched(
    Ax: Callable,          # x (B,D) -> Z (n, B) or (n, B, k)  ONE matmul
                           # (lane axis MUST be position 1 — see _bcast)
    data_loss: Callable,   # Z                  -> (B,)   elementwise+reduce
    data_grad: Callable,   # Z                  -> dL/dZ  elementwise
    AT: Callable,          # dL/dZ              -> (B,D)  ONE matmul
    reg_loss: Callable,    # x (B,D)            -> (B,)
    reg_grad: Callable,    # x (B,D)            -> (B,D)
    x0: jnp.ndarray,
    max_iter: int = 100,
    tol=1e-4,
    history: int = 10,
    c1: float = 1e-4,
    ls_trials: int = 16,
    trial_data_loss: Optional[Callable] = None,
                           # (Z, Zp, alphas (T,B)) -> (T,B): data_loss of
                           # Z + a*Zp for every row a of alphas, in one pass
) -> LBFGSResult:
    """L-BFGS for batched GLMs: objective f(x) = data_loss(A(x)) + reg(x)
    with A *linear* in x.

    The TPU-shaped trick: logits are linear in the parameters, so along a
    search direction p the logits move as Z(x + a*p) = Zx + a*Zp.  Carrying
    Zx in the solver state means one iteration costs exactly TWO wide
    matmuls — Ax(p) forward and AT(dL/dZ) backward — and the backtracking
    line search needs no matmul: all `ls_trials` candidate steps are
    evaluated every iteration, elementwise on (Z, Zp), and each lane keeps
    its largest Armijo-passing step.

    What that line search compiles to depends on `data_loss`.  By default
    it is `jax.vmap` of `data_loss(Z + a*Zp)` over the trial axis.  XLA:TPU
    makes one fusion of that where the loss reduces over rows alone or
    sums over classes and rows at once (binary logistic, squared hinge,
    squared epsilon-insensitive: compiled for a described v5e, PR 27).  A
    loss that reduces over a class axis INSIDE the sum over rows
    (logsumexp over k) compiled to five fusions that hand each other
    f32[ls_trials, n, B] tensors through HBM plus two relayout copies of
    Z: 65 % of the solver's device time on a v5e (PERF.md section 6,
    PR 27).  A caller with such a loss hands `trial_data_loss`, which
    produces the (ls_trials, B) data terms itself
    (models/linear.py::_multinomial_trial_losses); the regulariser's
    trial term and the Armijo pick stay here.  Same mathematics either
    way; the order of summation, so the last bits, differ.

    Such a line search is bound by arithmetic (ls_trials * k `exp` a row
    and lane), most of it never used: a lane takes the FIRST step that
    passes, 99 % of the time one of the first four.  So under
    `trial_data_loss` the evaluation is staged: the first
    `_LS_FIRST_STAGE` steps always, the others (`lax.cond`) only in an
    iteration where some lane that is not done passed none of them.  The
    steps offered, the Armijo rule and the pick are those of the single
    pass; `LBFGSResult.ls_second_pass` counts the iterations that ran
    the second stage.  The generic search is bound by its read of
    (Z, Zp) and stays a single pass.
    """
    m = history
    B, D = x0.shape
    dtype = x0.dtype
    eps = jnp.finfo(dtype).eps
    tol = jnp.broadcast_to(jnp.asarray(tol, dtype), (B,))

    # the phases carry jax.named_scope names (obs/spans.py, kind
    # "scope"): debug metadata only — the compiled instructions are the
    # same with and without them (tests/test_scopes_tpu_compile.py) —
    # by which a profiler trace says which phase a device op belongs to
    def full_grad(x, Z):
        with jax.named_scope("glm_lbfgs.gradient"):
            dZ = data_grad(Z)
        with jax.named_scope("glm_lbfgs.backward"):
            g_data = AT(dZ)
        with jax.named_scope("glm_lbfgs.gradient"):
            return g_data + reg_grad(x)

    def full_f(x, Z):
        return data_loss(Z) + reg_loss(x)

    with jax.named_scope("glm_lbfgs.init"):
        Z0 = Ax(x0)
        f0 = full_f(x0, Z0)
        g0 = full_grad(x0, Z0)

    state = dict(
        x=x0, Z=Z0, f=f0, g=g0,
        s_mem=jnp.zeros((m, B, D), dtype),
        y_mem=jnp.zeros((m, B, D), dtype),
        rho=jnp.zeros((m, B), dtype),
        gamma=jnp.ones((B,), dtype),
        it=jnp.asarray(0, jnp.int32),
        done=jnp.zeros((B,), bool),
        stall=jnp.zeros((B,), jnp.int32),
    )
    # the staged line search (below) counts its second passes; the generic
    # search carries the state it always carried
    staged = trial_data_loss is not None and ls_trials > _LS_FIRST_STAGE
    if staged:
        state["ls_second_pass"] = jnp.asarray(0, jnp.int32)

    def gnorm(g):
        return jnp.max(jnp.abs(g), axis=1)

    def cond(st):
        return jnp.logical_and(st["it"] < max_iter,
                               jnp.logical_not(jnp.all(st["done"])))

    def body(st):
        x, Z, f, g, it = st["x"], st["Z"], st["f"], st["g"], st["it"]
        with jax.named_scope("glm_lbfgs.direction"):
            n_hist = jnp.minimum(it, m)

            def bwd(i, carry):
                q, alpha = carry
                idx = jnp.mod(it - 1 - i, m)
                s_i = lax.dynamic_index_in_dim(st["s_mem"], idx, 0, False)
                y_i = lax.dynamic_index_in_dim(st["y_mem"], idx, 0, False)
                rho_i = lax.dynamic_index_in_dim(st["rho"], idx, 0, False)
                a = jnp.where(i < n_hist,
                              rho_i * jnp.sum(s_i * q, axis=1), 0.0)
                q = q - a[:, None] * y_i
                return q, alpha.at[i].set(a)

            q, alpha_rec = lax.fori_loop(
                0, m, bwd, (g, jnp.zeros((m, B), dtype)))
            r = st["gamma"][:, None] * q

            def fwd(i, r):
                j = m - 1 - i
                idx = jnp.mod(it - 1 - j, m)
                s_i = lax.dynamic_index_in_dim(st["s_mem"], idx, 0, False)
                y_i = lax.dynamic_index_in_dim(st["y_mem"], idx, 0, False)
                rho_i = lax.dynamic_index_in_dim(st["rho"], idx, 0, False)
                b = rho_i * jnp.sum(y_i * r, axis=1)
                corr = (alpha_rec[j] - b)[:, None] * s_i
                return r + jnp.where(j < n_hist, 1.0, 0.0) * corr

            r = lax.fori_loop(0, m, fwd, r)
            p = -r

            dginit = jnp.sum(g * p, axis=1)
            bad = dginit >= 0
            p = jnp.where(bad[:, None], -g, p)
            dginit = jnp.where(bad, -jnp.sum(g * g, axis=1), dginit)
            # a lane whose direction went non-finite (overflowed gradient or
            # history) is frozen this iteration: p=0 keeps x/Z exact under
            # x + alpha*p, where alpha*non-finite would be NaN and poison the
            # state (the pre-step-masking code preserved the last finite
            # iterate with where()-guards; this keeps that guarantee)
            lane_bad = jnp.logical_not(jnp.logical_and(
                jnp.all(jnp.isfinite(p), axis=1), jnp.isfinite(dginit)))
            p = jnp.where(lane_bad[:, None], 0.0, p)
            dginit = jnp.where(lane_bad, 0.0, dginit)

            a0 = jnp.where(
                it == 0,
                jnp.minimum(jnp.ones((B,), dtype), 1.0 / (gnorm(g) + eps)),
                jnp.ones((B,), dtype))

        # --- matmul-free backtracking line search -------------------------
        # Z moves linearly along p, so a trial is elementwise on
        # Zx + a*Zp.  A sequential halving loop with an all-lanes early
        # exit is a trap at large B: ONE stubborn lane forces EVERY lane
        # through all trials, each a full Z-sized memory pass.  Instead
        # ALL ls_trials candidate steps are evaluated every iteration and
        # each lane picks its largest passing step.  The generic form is
        # a vmap of data_loss over the trial axis; whether that is one
        # pass over (Z, Zp) is up to the compiler (see the docstring), so
        # a caller that can say how its loss evaluates along a direction
        # in one pass hands trial_data_loss.
        with jax.named_scope("glm_lbfgs.forward"):
            Zp = Ax(p)                           # the ONE forward matmul

        with jax.named_scope("glm_lbfgs.linesearch"):
            def eval_trial(a):
                Zt = Z + _bcast(a, Z) * Zp
                return data_loss(Zt) + reg_loss(x + a[:, None] * p)

            def trial_losses(a):                 # (t, B) steps -> losses
                return trial_data_loss(Z, Zp, a) + jax.vmap(
                    lambda a: reg_loss(x + a[:, None] * p))(a)

            def passes(losses, a):
                return losses <= f[None, :] + c1 * a * dginit[None, :]

            halvings = 0.5 ** jnp.arange(ls_trials, dtype=dtype)
            alphas = a0[None, :] * halvings[:, None]            # (T, B)
            if trial_data_loss is None:
                losses = jax.vmap(eval_trial)(alphas)           # (T, B)
            elif not staged:
                # no more trials than the first stage holds: the single
                # pass, which is also what the tests hold the staged
                # search against (tests/test_linesearch_staged.py)
                losses = trial_losses(alphas)
            else:
                first, rest = (alphas[:_LS_FIRST_STAGE],
                               alphas[_LS_FIRST_STAGE:])
                losses = trial_losses(first)
                # a lane with a non-finite loss passes nothing, so it
                # asks for the rest; a done lane's pick is never used
                need_rest = jnp.any(jnp.logical_not(jnp.logical_or(
                    st["done"], jnp.any(passes(losses, first), axis=0))))
                losses = jnp.concatenate([losses, lax.cond(
                    need_rest, lambda: trial_losses(rest),
                    lambda: jnp.full(rest.shape, jnp.inf, dtype))])
                ls_second_pass = st["ls_second_pass"] + need_rest.astype(
                    jnp.int32)
            pick = _armijo_pick(passes(losses, alphas))         # (B,)
            alpha = jnp.take_along_axis(alphas, pick[None, :], axis=0)[0]
            f_pick = jnp.take_along_axis(losses, pick[None, :], axis=0)[0]

        # mask the STEP, not the state: dead lanes (done, or a non-finite
        # trial loss) take alpha=0, so x_new == x and Z_new == Z exactly
        # and g_new recomputes to the same value — no Z-sized select
        # passes (profiled at ~4ms/iteration of pure bandwidth)
        with jax.named_scope("glm_lbfgs.step"):
            live = jnp.logical_and(jnp.isfinite(f_pick),
                                   jnp.logical_not(st["done"]))
            alpha = jnp.where(live, alpha, 0.0)
            x_new = x + alpha[:, None] * p
            Z_new = Z + _bcast(alpha, Z) * Zp
            # the picked trial's loss IS full_f(x_new, Z_new): reuse, no pass
            f_new = jnp.where(live, f_pick, f)
        g_new = full_grad(x_new, Z_new)              # the ONE backward matmul

        with jax.named_scope("glm_lbfgs.history"):
            s = x_new - x
            yv = g_new - g
            sy = jnp.sum(s * yv, axis=1)
            update = jnp.logical_and(sy > 1e-10, live)
            slot = jnp.mod(it, m)
            s_mem = lax.dynamic_update_index_in_dim(
                st["s_mem"], jnp.where(update[:, None], s, 0.0), slot, 0)
            y_mem = lax.dynamic_update_index_in_dim(
                st["y_mem"], jnp.where(update[:, None], yv, 0.0), slot, 0)
            rho = lax.dynamic_update_index_in_dim(
                st["rho"],
                jnp.where(update, 1.0 / jnp.where(sy > 1e-10, sy, 1.0), 0.0),
                slot, 0)
            gamma = jnp.where(update,
                              sy / (jnp.sum(yv * yv, axis=1) + eps),
                              st["gamma"])
            # float32 stall detector: the sum-loss gradient has a rounding
            # floor that often sits ABOVE tol (n terms x eps32), so the tol
            # exit alone can be unreachable and every lane burns max_iter.
            # A lane whose relative objective improvement stays below ~eps32
            # for 3 consecutive iterations has hit that floor — its iterate
            # is pinned by rounding, and the remaining lockstep iterations
            # are pure waste.  (Safe for the strongly-convex GLM objectives
            # this solver serves: genuine progress never hides behind
            # consecutive sub-eps steps.)
            rel_impr = (f - f_new) / jnp.maximum(jnp.abs(f), eps)
            stall = jnp.where(jnp.logical_and(live, rel_impr <= eps),
                              st["stall"] + 1, 0)
            done = jnp.logical_or(
                st["done"],
                jnp.logical_or(gnorm(g_new) <= tol, stall >= 3))
        new = dict(x=x_new, Z=Z_new, f=f_new, g=g_new, s_mem=s_mem,
                   y_mem=y_mem, rho=rho, gamma=gamma, it=it + 1,
                   done=done, stall=stall)
        if staged:
            new["ls_second_pass"] = ls_second_pass
        return new

    st = lax.while_loop(cond, body, state)
    gn = jnp.max(jnp.abs(st["g"]), axis=1)
    return LBFGSResult(
        x=st["x"], fun=st["f"], grad_norm=gn,
        n_iter=jnp.broadcast_to(st["it"], (B,)), converged=gn <= tol,
        ls_second_pass=st.get("ls_second_pass"))


def glm_fista_batched(
    Ax: Callable,          # x (B,D) -> Z (n, B) or (n, B, k)  ONE matmul
    data_loss: Callable,   # Z -> (B,)
    data_grad: Callable,   # Z -> dL/dZ
    AT: Callable,          # dL/dZ -> (B,D)  ONE matmul
    l1: jnp.ndarray,       # (B, D) per-coefficient l1 weights (0 = none)
    l2: jnp.ndarray,       # (B, D) per-coefficient l2 weights
    x0: jnp.ndarray,
    max_iter: int = 1000,
    tol=1e-4,
    curvature: float = 0.25,
) -> LBFGSResult:
    """Proximal FISTA for batched GLMs with elastic-net penalties.

    Covers the l1/elasticnet logistic regressions L-BFGS cannot (soft
    thresholding handles the non-smooth term).  Same TPU shape as
    `glm_lbfgs_batched`: logits move linearly along the momentum
    extrapolation (Z_v = Z_x + beta*(Z_x - Z_prev) — no matmul), so one
    iteration costs exactly TWO wide matmuls: the gradient pullback
    AT(dL/dZ(Z_v)) and the fresh Ax(x_new) after the prox step.

    Step size 1/L with L = curvature*lambda_max(A^T A) + max(l2):
    `curvature` bounds the data-loss hessian's per-sample scale (0.25 for
    binary logistic; 0.5 for softmax, whose diag(p)-pp^T has eigenvalues
    <= 1/2).  Fold weights w <= 1 only shrink the true constant, so the
    unweighted Gram bound stays safe.  Estimated per lane by power
    iteration through Ax/AT.
    """
    B, D = x0.shape
    dtype = x0.dtype
    tol = jnp.broadcast_to(jnp.asarray(tol, dtype), (B,))

    # per-lane Lipschitz bound via power iteration on x -> AT(0.25*Ax(x)):
    # 0.25*A^T A dominates the logistic hessian A^T W'' A (w'' <= 0.25)
    def power(i, v):
        u = AT(0.25 * Ax(v))
        nrm = jnp.sqrt(jnp.sum(u * u, axis=1, keepdims=True)) + 1e-30
        return u / nrm

    v0 = jnp.ones((B, D), dtype) / jnp.sqrt(D)
    v = lax.fori_loop(0, 20, power, v0)
    u = AT(0.25 * Ax(v))
    L = jnp.sqrt(jnp.sum(u * u, axis=1)) + jnp.max(l2, axis=1) + 1e-6
    step = (1.0 / L)[:, None]                               # (B, 1)

    def soft(u_, t_):
        return jnp.sign(u_) * jnp.maximum(jnp.abs(u_) - t_, 0.0)

    def body(carry):
        x, x_prev, Zx, Zx_prev, t, it, done = carry
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t_next
        v_pt = x + beta * (x - x_prev)
        Zv = Zx + beta * (Zx - Zx_prev)   # logits are linear in params
        g = AT(data_grad(Zv)) + l2 * v_pt
        x_new = soft(v_pt - step * g, step * l1)
        Zx_new = Ax(x_new)                                  # ONE matmul
        shift = jnp.max(jnp.abs(x_new - x), axis=1)
        done_new = jnp.logical_or(done, shift <= tol)
        x_new = jnp.where(done[:, None], x, x_new)
        Zx_new = jnp.where(_bcast(done, Zx), Zx, Zx_new)
        return (x_new, x, Zx_new, Zx, t_next, it + 1, done_new)

    def cond(carry):
        *_, it, done = carry
        return jnp.logical_and(it < max_iter,
                               jnp.logical_not(jnp.all(done)))

    Z0 = Ax(x0)
    x, _, Zx, _, _, n_iter, done = lax.while_loop(
        cond, body,
        (x0, x0, Z0, Z0, jnp.asarray(1.0, dtype),
         jnp.asarray(0, jnp.int32), jnp.zeros((B,), bool)))
    f = data_loss(Zx) + jnp.sum(l1 * jnp.abs(x) + 0.5 * l2 * x * x, axis=1)
    return LBFGSResult(
        x=x, fun=f, grad_norm=jnp.zeros((B,), dtype),
        n_iter=jnp.broadcast_to(n_iter, (B,)), converged=done)


def _bcast(v, like):
    """(B,) -> broadcastable against Z.

    CONTRACT: Ax must put the lane axis at position 1 — Z is (n, B) or
    (n, B, k).  Shape-based guessing is forbidden (n can equal B)."""
    if like.ndim == 3:        # (n, B, k)
        return v[None, :, None]
    return v[None, :]         # (n, B)
