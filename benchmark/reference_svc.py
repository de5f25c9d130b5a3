"""The plain reference of the kernel SVM cells: what one (candidate, fold)
fit of ``sklearn.svm.SVC(kernel="rbf")`` has to answer, written from the
estimator's published definition (Chang & Lin, LIBSVM, 2011) and from
nothing of the program under test.

It imports nothing of ``spark_sklearn_tpu`` and takes nothing the program
made.  Straightforward ``jax.numpy``: float32 with every matrix product at
``highest`` precision, no kernels, no masks over the whole data set.  The
control of the comparison is this same code with ``dtype=bfloat16`` (the
rows, the Gram and the alphas in bfloat16).

``SVC`` on k classes is k(k-1)/2 two-class machines, one for every pair of
classes (i < j), each trained on THAT pair's own training rows only, as
libsvm sees the subproblem: with K the pair's own Gram matrix,
K_ab = exp(-gamma ||x_a - x_b||^2), and t_a = +1 for class i, -1 for class
j, minimise over a

    1/2 a' (t t' * K) a  -  1' a,      0 <= a <= C,    t' a = 0.

The solver is accelerated projected gradient (Beck & Teboulle 2009) with
O'Donoghue & Candes' gradient restart.  The projection onto the box cut by
the hyperplane is exact (the root of a monotone function, by bisection).
The step is 1/L with L the largest eigenvalue of the quadratic ON the
hyperplane t'a = 0, where every iterate and every difference of iterates
lies.  It stops at libsvm's own measure of optimality, the KKT gap
m(a) - M(a) (the maximal violating pair's), under ``KKT_GAP``: a tenth of
sklearn's default ``tol``.  The intercept is the mean of t_a - f(x_a) over
the free support vectors (0 < a < C; the bisection leaves an alpha within
rounding of its bound, so one within a hundred-thousandth of C of a bound is at
it), or the middle of the feasible interval where none is free.  A test row gets the vote of every pair, and
the class of the most votes; votes tie by the summed decision values
squashed into (-1/3, 1/3), which is scikit-learn's
``_ovr_decision_function``.  The score is accuracy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KKT_GAP = 1e-4
MAX_ITER = 4000
CHECK_EVERY = 10      # iterations between two readings of the KKT gap
POWER_ITERS = 60
BISECTIONS = 60
AT_BOUND = 1e-5       # an alpha this close to 0 or C (as a share of C) is at it


def _rbf(A, B, gamma):
    """exp(-gamma ||a - b||^2) for rows of A against rows of B."""
    d2 = (jnp.sum(A * A, axis=-1)[..., :, None]
          - 2.0 * jnp.einsum("...ad,...bd->...ab", A, B)
          + jnp.sum(B * B, axis=-1)[..., None, :])
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


def _project(z, t, upper):
    """The nearest point of {0 <= a <= upper, t'a = 0} to each row of z:
    clip(z - nu t, 0, upper) for the nu at which t'a = 0; t'a falls as nu
    grows."""
    reach = jnp.max(jnp.abs(z), axis=-1) + jnp.max(upper, axis=-1)
    lo, hi = -reach, reach

    def halve(_, bracket):
        lo, hi = bracket
        mid = 0.5 * (lo + hi)
        a = jnp.clip(z - mid[..., None] * t, 0.0, upper)
        above = jnp.sum(t * a, axis=-1) > 0
        return jnp.where(above, mid, lo), jnp.where(above, hi, mid)

    lo, hi = jax.lax.fori_loop(0, BISECTIONS, halve, (lo, hi))
    return jnp.clip(z - (0.5 * (lo + hi))[..., None] * t, 0.0, upper)


def _kkt_gap(a, grad, t, upper):
    """libsvm's m(a) - M(a) and the two numbers themselves, per row."""
    inside = upper > 0
    can_rise = inside & (a < upper * (1.0 - AT_BOUND))
    can_fall = inside & (a > upper * AT_BOUND)
    up = jnp.where(t > 0, can_rise, can_fall)
    low = jnp.where(t > 0, can_fall, can_rise)
    v = -t * grad
    big = jnp.asarray(jnp.inf, v.dtype)
    m = jnp.max(jnp.where(up, v, -big), axis=-1)
    M = jnp.min(jnp.where(low, v, big), axis=-1)
    return m - M, m, M


def _solve_pairs(K, t, valid, Cs):
    """The duals of P pairs x c values of C on the pairs' own Gram
    matrices K (P, m, m); t (P, m) the signs, valid (P, m) which of the m
    slots hold a row (pairs of unequal size are padded).  Returns alphas
    (P, c, m), intercepts (P, c), the iterations run and the widest KKT
    gap left."""
    dtype = K.dtype
    P, m = t.shape
    c = Cs.shape[0]
    T = t[:, None, :]
    upper = (valid[:, None, :] * Cs[None, :, None]).astype(dtype)

    def Q(a):                      # (t t' * K) a, per pair
        return T * jnp.einsum("pcm,pmn->pcn", T * a, K)

    # L: the top eigenvalue of Q on the hyperplane t'a = 0
    n_rows = jnp.sum(valid, axis=-1)[:, None, None].astype(jnp.float32)

    def onto_plane(v):
        return (v - T * (jnp.sum(T * v, axis=-1, keepdims=True)
                         / n_rows).astype(dtype)) * valid[:, None, :]

    def power(_, v):
        v = onto_plane(Q(onto_plane(v))).astype(jnp.float32)
        return (v / (jnp.linalg.norm(v, axis=-1, keepdims=True)
                     + 1e-30)).astype(dtype)

    v0 = jax.random.normal(jax.random.PRNGKey(0), (P, 1, m), jnp.float32)
    v = jax.lax.fori_loop(0, POWER_ITERS, power,
                          onto_plane(v0.astype(dtype)))
    L = jnp.sum(v * onto_plane(Q(v)), axis=-1).astype(jnp.float32)
    step = (1.0 / (1.05 * L + 1e-12)).astype(dtype)[..., None]   # (P,1,1)

    def unfinished(state):
        _, _, _, it, gap = state
        return jnp.logical_and(it < MAX_ITER, jnp.max(gap) > KKT_GAP)

    def one_step(_, state):
        a, z, theta = state
        a_new = _project(z - step * (Q(z) - 1.0), T, upper)
        # restart the momentum where it points uphill
        uphill = jnp.sum(((z - a_new) * (a_new - a)).astype(jnp.float32),
                         axis=-1, keepdims=True) > 0
        theta_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * theta * theta))
        beta = jnp.where(uphill, 0.0, (theta - 1.0) / theta_new)
        theta_new = jnp.where(uphill, 1.0, theta_new)
        return a_new, a_new + beta.astype(dtype) * (a_new - a), theta_new

    def advance(state):
        a, z, theta, it, _ = state
        a, z, theta = jax.lax.fori_loop(0, CHECK_EVERY, one_step,
                                        (a, z, theta))
        gap, _, _ = _kkt_gap(a, Q(a) - 1.0, T, upper)
        return a, z, theta, it + CHECK_EVERY, gap.astype(jnp.float32)

    a0 = jnp.zeros((P, c, m), dtype)
    a, _, _, n_iter, gap = jax.lax.while_loop(
        unfinished, advance,
        (a0, a0, jnp.ones((P, c, 1), jnp.float32),
         jnp.asarray(0, jnp.int32), jnp.full((P, c), jnp.inf, jnp.float32)))

    # intercept: t_a - f0(x_a) = -t_a grad_a on the free support vectors
    grad = Q(a) - 1.0
    e = -T * grad
    free = (a > upper * AT_BOUND) & (a < upper * (1.0 - AT_BOUND))
    n_free = jnp.sum(free, axis=-1)
    mean_free = jnp.sum(jnp.where(free, e, 0.0).astype(jnp.float32),
                        axis=-1) / jnp.maximum(n_free, 1)
    _, m_up, m_low = _kkt_gap(a, grad, T, upper)
    middle = 0.5 * (m_up + m_low).astype(jnp.float32)
    b = jnp.where(n_free > 0, mean_free, middle)
    return a, b.astype(dtype), n_iter, jnp.max(gap)


@functools.partial(jax.jit, static_argnames=("n_classes",))
def _fold_gamma_scores(gamma, Cs, X_pairs, t, valid, X_test, y_test,
                       pairs, *, n_classes):
    """Test accuracy of the c candidates that share ``gamma``, on one
    fold.  X_pairs (P, m, d): each pair's own training rows."""
    with jax.default_matmul_precision("highest"):
        dtype = X_pairs.dtype
        gamma = gamma.astype(dtype)
        K = _rbf(X_pairs, X_pairs, gamma)
        a, b, n_iter, gap = _solve_pairs(K, t, valid, Cs)
        coef = a * t[:, None, :]                                 # (P, c, m)

        def decide(pair):
            Xp, coef_p, b_p = pair
            return _rbf(X_test, Xp, gamma) @ coef_p.T + b_p      # (n_test, c)

        dec = jax.lax.map(decide, (X_pairs, coef, b))            # (P, n, c)
        dec = jnp.transpose(dec, (2, 1, 0)).astype(jnp.float32)  # (c, n, P)
        first = jax.nn.one_hot(pairs[:, 0], n_classes, dtype=jnp.float32)
        second = jax.nn.one_hot(pairs[:, 1], n_classes, dtype=jnp.float32)
        won = (dec > 0).astype(jnp.float32)
        votes = won @ first + (1.0 - won) @ second               # (c, n, k)
        if n_classes == 2:
            # scikit-learn's two-class sign: positive means classes_[1]
            predicted = (dec[..., 0] < 0).astype(jnp.int32)
        else:
            conf = dec @ first - dec @ second
            predicted = jnp.argmax(
                votes + conf / (3.0 * (jnp.abs(conf) + 1.0)), axis=-1)
        score = jnp.mean((predicted == y_test[None, :]).astype(jnp.float32),
                         axis=-1)
        return score, n_iter, gap, dec


def _pair_rows(y_train, pairs):
    """Per pair the indices of its own training rows, padded to the
    longest pair with -1."""
    rows = [np.flatnonzero((y_train == i) | (y_train == j))
            for i, j in pairs]
    m = max(len(r) for r in rows)
    return np.stack([np.pad(r, (0, m - len(r)), constant_values=-1)
                     for r in rows])


def svc_ovo_cv_scores(X, y, splits, candidates, config, dtype=jnp.float32,
                      decisions=False):
    """Test accuracy of every (candidate, fold), ``(len(candidates),
    len(splits))``, and the iterations each solve ran.  ``candidates`` are
    parameter dicts that set ``C`` and ``gamma``.  Candidates of one gamma
    share their Gram matrices: one fold and one gamma at a time, all pairs
    and all their C together.  With ``decisions`` a third value: the pair
    decisions of every candidate on the LAST fold's test rows."""
    params = config["estimator"]["params"]
    if params.get("kernel", "rbf") != "rbf":
        raise ValueError("this reference knows the rbf kernel only")
    k = config["data"]["n_classes"]
    pairs = np.array([(i, j) for i in range(k) for j in range(i + 1, k)],
                     np.int32)
    by_gamma = {}
    for at, cand in enumerate(candidates):
        by_gamma.setdefault(float(cand["gamma"]), []).append(at)
    scores = np.empty((len(candidates), len(splits)))
    iters = np.zeros((len(candidates), len(splits)), np.int64)
    last = {}
    for f, (train, test) in enumerate(splits):
        X_train, y_train = X[train], y[train]
        rows = _pair_rows(y_train, pairs)
        valid = rows >= 0
        X_pairs = jnp.asarray(X_train[np.maximum(rows, 0)], dtype)
        t = np.where(y_train[np.maximum(rows, 0)] == pairs[:, :1], 1.0, -1.0)
        t = jnp.asarray(t * valid, dtype)
        for gamma, members in by_gamma.items():
            Cs = np.asarray([candidates[i]["C"] for i in members],
                            np.float32)
            s, n_iter, gap, dec = _fold_gamma_scores(
                jnp.asarray(gamma, jnp.float32), jnp.asarray(Cs, dtype),
                X_pairs, t, jnp.asarray(valid, dtype),
                jnp.asarray(X[test], dtype), jnp.asarray(y[test]),
                jnp.asarray(pairs), n_classes=k)
            scores[members, f] = np.asarray(s, np.float64)
            iters[members, f] = int(n_iter)
            if decisions and f == len(splits) - 1:
                for j, i in enumerate(members):
                    last[i] = np.asarray(dec[j])
    if decisions:
        return scores, iters, [last[i] for i in range(len(candidates))]
    return scores, iters
