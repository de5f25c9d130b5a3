"""SVC family — kernel SVM re-designed for the MXU.

Reference counterpart: sklearn's SVC (libsvm SMO, one C++ working-set solve
per Spark task; BASELINE config #2 is an SVC(rbf) CxGamma grid on MNIST-10k).
SMO is a scalar, data-dependent algorithm that cannot map to a systolic
array, so the TPU redesign solves the same dual QP with **projected
gradient ascent** where every iteration is ONE kernel matmul for all
(fold x class-pair) subproblems of a candidate at once:

  max_a  1'a - 0.5 a' Q a,   0 <= a_i <= C,  sum_i y_i a_i = 0,
  Q = (y y') * K

This is the true libsvm dual, equality constraint included: each ascent
step projects onto the box-and-hyperplane set via a vectorized bisection
(`_project_box_hyperplane`) and the intercept comes from the KKT
conditions (`_kkt_intercept`, libsvm's -rho).  The step size is
1/lambda_max of the CENTRED kernel (`_power_step(centred=True)`): safe
for every masked subproblem because a principal submatrix of a PSD matrix
cannot have a larger top eigenvalue, the y-sign flip DKD is a similarity
transform, and the equality constraint keeps every iterate's y*a summing
to zero, where K and the centred K are the same quadratic form.

Multi-class follows sklearn: one-vs-one over all k(k-1)/2 pairs with
majority voting (confidence-scaled tie-break like _ovr_decision_function).

**The layout of a dual.**  The dual of pair (i, j) has alphas on the rows of
classes i and j only.  Where the search's task-batched fit sees three or
more classes, one shared X (no per-fold inputs) and class counts balanced
enough that k blocks of the largest class, rounded up to a multiple of 8,
hold at most 1.25 n rows (`_block_rows`), it takes the rows in class order
once a launch (`_class_sorted`), builds each candidate's kernel matrix on
them, and keeps every dual's iterate, signs and bounds as its two classes'
blocks side by side, `(folds * pairs, 2 * n_b)`: the projection, step,
momentum and residual walk those, and the product contracts each dual over
its own rows (`_BlockKernel`).  Everywhere else (binary problems, skewed
class counts, a compiled Pipeline's per-fold kernels, the standalone SVC)
a dual is a full `(n,)` row with bound 0 outside its pair, and the product
is `Z @ K` (`_DenseKernel`).  One algorithm on two layouts:
`fista_dual_ascent` and `nu_dual_ascent` take the product as an operator
and share every other line; `search_report["dual_rows_per_launch"]` says
which layout a launch ran.

**One kernel matrix for the C of a gamma.**  The launch scans kernel
matrices, not candidates.  A C x gamma grid has as many kernels as
gammas: where the candidates the engine hands over fall into runs of one
length S >= 2 that share gamma and differ in C (nu) only,
`SVCFamily.launch_layout` orders them kernel-major on the host and hands
the launch S as a static fact.  A scan step then builds the matrix, its
bfloat16 copy and the step size once and advances the S candidates'
subproblems stacked on the duals' leading axis, each row's box scaled by
its own candidate's C: in either layout the product reads the matrix once
for all of them.  A candidate stays what it is alone: the `tol` exit is
judged a candidate, one that is done stops moving while the loop runs on
for the others, and it reports its own count (`_stacked_tol`, `_box_fista`).
Ragged runs, distinct gammas, a chunk that is not made of whole runs and
per-fold kernels build a kernel a candidate, the program they always ran;
`search_report["gram_builds_per_launch"]` says which.

Deviation from libsvm (documented, tested at the accuracy level): a
fixed iteration budget (300 where `max_iter` is -1) beside the `tol` exit
on the prox-gradient residual.  In exact float32 (XLA:CPU) `tol` ends the
solve; on a TPU the product's one bfloat16 pass leaves the residual a
floor of 0.015-0.06, over the default `tol`, so there the budget ends it
and `search_report["dual_iters_per_candidate"]` says so (PERF.md, PR 28).
"""

from __future__ import annotations

import warnings
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from spark_sklearn_tpu.models.base import (
    Family, NotCompiledError, encode_labels, register_family)


def _pairs(k: int) -> np.ndarray:
    return np.array([(i, j) for i in range(k) for j in range(i + 1, k)],
                    dtype=np.int32)


def _kernel(X1, X2, kind, gamma, degree, coef0):
    if kind == "linear":
        return X1 @ X2.T
    if kind == "poly":
        return (gamma * (X1 @ X2.T) + coef0) ** degree
    if kind == "sigmoid":
        return jnp.tanh(gamma * (X1 @ X2.T) + coef0)
    # rbf
    sq1 = jnp.sum(X1 * X1, axis=1)
    sq2 = jnp.sum(X2 * X2, axis=1)
    d2 = sq1[:, None] - 2.0 * (X1 @ X2.T) + sq2[None, :]
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


#: a block is the largest class rounded up to the chip's sublane tile, so
#: that the (k, n_b, k n_b) view of the kernel matrix is the matrix as it
#: lies.  Not the lane tile of 128: the product is bound by the bytes of
#: the matrix, and 2 048-row blocks for classes of 2 000 read 4.9 % more
#: (traced windows of the benchmark's cell: 6.76 s a search against 7.02 s;
#: PERF.md, PR 32)
_BLOCK_TILE = 8
#: k * n_b over n at most: padding costs under 1.6 x the kernel matrix
_BLOCK_PAD_MAX = 1.25


def _block_rows(meta, n):
    """The block size `n_b` of the class-sorted, block-compact layout for
    `n` rows with `meta["class_counts"]`, or None where a dual keeps a
    dense `(n,)` row: fewer than three classes (one pair holds every
    row), counts that are not this data's, or classes so unequal that k
    blocks of the largest hold over 1.25 n rows."""
    counts = meta.get("class_counts")
    if counts is None or len(counts) < 3 or sum(counts) != n:
        return None
    n_b = -(-max(counts) // _BLOCK_TILE) * _BLOCK_TILE
    return n_b if len(counts) * n_b <= _BLOCK_PAD_MAX * n else None


def _class_sorted(y, counts, n_b):
    """Rows in class order, each class padded to a block of `n_b`
    (traced; `counts` static, `y` the encoded labels they count).
    Returns `rows` (k * n_b,): the caller's row that each slot holds (a
    pad slot holds some real row: whatever reads it masks it), `valid`
    (k, n_b): 1 on the slots that hold a row of their class, and `slot`
    (n,): where each of the caller's rows went."""
    n = y.shape[0]
    counts = np.asarray(counts, np.int32)
    first = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    order = jnp.argsort(y, stable=True).astype(jnp.int32)
    r = jnp.arange(n_b, dtype=jnp.int32)[None, :]
    valid = r < counts[:, None]
    rows = order[jnp.where(valid, first[:, None] + r, 0).reshape(-1)]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    slot = y * n_b + rank - jnp.asarray(first)[y]
    return rows, valid, slot


class _DenseKernel:
    """The duals' product with a kernel matrix whose rows are the duals'
    columns: a dual is a full row, zero outside its pair."""

    def __init__(self, K):
        self.K = K
        self.dtype = K.dtype

    def own(self, V):
        """V K on the columns a dual's iterate holds."""
        return V @ self.K

    def all(self, V):
        """V K on every row of the data."""
        return V @ self.K

    def restrict(self, R):
        """`own` out of the result of `all`."""
        return R


class _BlockKernel:
    """The product for duals in the block-compact layout.  `K` is the
    kernel matrix of the class-sorted, padded rows, so viewed as
    `(k, n_b, k, n_b)` its block `[a, :, b, :]` is class a against class
    b.  `V` is `(folds * pairs, 2 * n_b)`, fold-major: the blocks of the
    pair's two classes side by side.  The product goes by source class:
    the class-a blocks of the k - 1 pairs x folds that hold class a are
    one `(k * folds, n_b)` left-hand side against the `(n_b, k * n_b)`
    rows of class a (the k - 1 and a block of zeros where b = a), so K is
    read once and each dual is contracted over its own 2 n_b rows."""

    def __init__(self, K, pairs, n_folds, n_b):
        self.dtype = K.dtype
        self.k = k = K.shape[0] // n_b
        self.n_b, self.F, self.P = n_b, n_folds, len(pairs)
        self.K3 = K.reshape(k, n_b, k * n_b)
        self.pairs = pairs = np.asarray(pairs)
        # the (2 P) class blocks of a fold, (pair, side)-major, into the
        # (k, k) grid [class, partner] and back; 2 P is the block of zeros
        grid = np.full((k, k), 2 * self.P, np.int32)
        grid[pairs[:, 0], pairs[:, 1]] = 2 * np.arange(self.P)
        grid[pairs[:, 1], pairs[:, 0]] = 2 * np.arange(self.P) + 1
        self.to_grid = grid.reshape(-1)
        self.from_grid = np.stack(
            [pairs[:, 0] * k + pairs[:, 1],
             pairs[:, 1] * k + pairs[:, 0]], axis=1).astype(np.int32)

    def _by_class(self, V):
        """(k, k * F, k * n_b): [a, (b, f)] the class-a block of the dual
        of pair {a, b} and fold f against all of K's columns."""
        k, n_b, F, P = self.k, self.n_b, self.F, self.P
        blocks = jnp.transpose(V.reshape(F, P, 2, n_b), (1, 2, 0, 3))
        blocks = jnp.concatenate(
            [blocks.reshape(2 * P, F, n_b),
             jnp.zeros((1, F, n_b), V.dtype)])[self.to_grid]
        return jnp.einsum("amr,arn->amn",
                          blocks.reshape(k, k * F, n_b), self.K3)

    def _to_rows(self, G, width):
        """[class, partner] grids of (F, width) back to fold-major rows."""
        G = G.reshape(self.k * self.k, self.F, width)[
            self.from_grid.reshape(-1)]
        return jnp.transpose(
            G.reshape(self.P, 2, self.F, width), (2, 0, 1, 3))

    def own(self, V):
        """On the block of class a of a dual: what its class-a block and
        its partner's block contribute there.  Sliced out of the product
        as it lies (a 5-d view of it is a copy of all of it)."""
        k, n_b, F = self.k, self.n_b, self.F
        R = self._by_class(V)
        cols = [slice(a * n_b, (a + 1) * n_b) for a in range(k)]
        mine = jnp.stack([R[a, :, cols[a]] for a in range(k)])
        partners = jnp.stack(
            [R[:, a * F:(a + 1) * F, cols[a]] for a in range(k)])
        G = mine.reshape(k, k, F, n_b) + partners
        return self._to_rows(G, n_b).reshape(F * self.P, 2 * n_b)

    def all(self, V):
        """(folds * pairs, k * n_b), the rows in class-sorted order."""
        R = self._to_rows(self._by_class(V), self.k * self.n_b)
        return jnp.sum(R, axis=2).reshape(self.F * self.P, -1)

    def restrict(self, R):
        R = R.reshape(self.F, self.P, self.k, self.n_b)
        return R[:, np.arange(self.P)[:, None], self.pairs, :].reshape(
            self.F * self.P, 2 * self.n_b)


def _as_product(K):
    """A kernel matrix as the dense product; an operator as it is."""
    return K if hasattr(K, "own") else _DenseKernel(K)


#: the key of the static fact `SVCFamily.launch_layout` hands the launch:
#: the run length S of candidates that share a kernel matrix
_KERNEL_RUN = "__kernel_run__"


def _kernel_run(static, n_candidates, fold_inputs=False):
    """The run length a launch of `n_candidates` groups its candidates by:
    the layout's fact, else 1 — no fact (a direct caller, a grid with
    nothing to share, a launch the engine knows not to be made of whole
    runs: `Family.launch_layout`), a width that is not a multiple of it
    (a group cut into narrower chunks), per-fold rows (a compiled
    Pipeline: a kernel a candidate and fold)."""
    S = int((static or {}).get(_KERNEL_RUN, 1))
    return S if S > 1 and not fold_inputs and n_candidates % S == 0 else 1


def _power_start(n, dtype):
    """The centred power iteration's first vector."""
    return jax.random.normal(jax.random.PRNGKey(0), (n,), dtype)


def _power_step(K, n, dtype, centred=False, start=None, valid=None):
    """1/lambda_max(K) via power iteration — a safe ascent step for every
    masked/sign-flipped subproblem (principal submatrices of a PSD matrix
    cannot have a larger top eigenvalue).

    `centred`: the top eigenvalue of K on the vectors that sum to zero
    (of C K C, C = I - 11'/n) instead.  That is the curvature a dual with
    the equality constraint sum_i y_i a_i = 0 can meet: v = y * a sums to
    zero for every feasible a, masked to any subproblem's rows and under
    any signs, so every iterate, every momentum point and every
    difference of two of them lies there, and a'Qa = v'Kv = v'(CKC)v.
    An RBF kernel's top eigenvector is nearly the constant vector, which
    the constraint removes: on the benchmark's 20 000 MNIST-width rows
    lambda_max falls from 11 787 to 223 at gamma 0.004 and from 432 to 57
    at gamma 0.03, and with it the iterations a dual needs (PERF.md,
    PR 28).  A 10 % margin there, since the spectrum under the constant
    is flatter and the estimate comes from below.

    `valid` (with `centred`): K is padded, `n` of its rows are real and
    `valid` is 1 on them; the vector stays 0 on the pads and the mean is
    over the real rows, so the pads move nothing (a block of identical pad
    rows would otherwise be an eigenvalue of its own).  `start`: the first
    vector, where the caller wants the one of another row order."""
    def centre(v):
        if valid is None:
            return v - jnp.mean(v)
        return (v - jnp.sum(v) / n) * valid

    def apply(v):
        if not centred:
            return K @ v
        return centre(K @ centre(v))

    def power(i, v):
        v = apply(v)
        return v / (jnp.linalg.norm(v) + 1e-12)

    v0 = start if start is not None else (
        _power_start(n, dtype) if centred
        else jnp.ones((n,), dtype) / jnp.sqrt(n))
    v = jax.lax.fori_loop(0, 20, power, v0)
    margin = 1.1 if centred else 1.0
    return 1.0 / (margin * jnp.dot(v, apply(v)) + 1e-6)


def _box_fista(grad_fn, project, x0, step, max_iter, tol=None):
    """Nesterov-accelerated projected gradient on a constrained QP — the
    ONE loop behind every dual here (SVC pairs, nu-duals, SVR pairs, the
    liblinear hinge/epsilon duals): the TPU answer to libsvm/liblinear's
    sequential working-set and coordinate-descent solvers, where every
    (subproblem, sample) coordinate advances together through wide
    matmuls.  Minimises; ascent callers negate their gradient.

    With `tol=None` (SVC/NuSVC duals, which check KKT themselves) runs a
    fixed iteration count and returns `x`.  With a per-lane `tol` array
    (leading axis of x0 = lanes) it ALSO measures convergence honestly:
    the per-lane prox-gradient residual max|z - prox(z - step*grad(z))|
    divided by `step` — the generalized-gradient magnitude, so the
    criterion is scale-free in the step size (an absolute iterate-shift
    test would spuriously fire on the first iteration whenever
    1/lambda_max(Gram) < tol).  Not liblinear's dual-violation bound,
    but a real measurement rather than an assumed one.  Exits once every
    lane has converged and returns (x, n_iter, converged).

    A `tol` of two axes, (problems, lanes of each): the lanes are those
    of several problems, stacked on x0's leading axis to share the
    gradient's one product, and each problem ends as it would alone.
    Alone its loop ends with the iteration after which all of ITS lanes
    have been under `tol`: here its lanes stop moving there (a select on
    the update) while the loop goes on for the others, so its `x` and
    its lanes' counts are those of its own solve; `n_iter` and
    `converged` come back in `tol`'s shape."""
    dtype = x0.dtype

    def advance(x, z, t):
        with jax.named_scope("sst.box_fista.gradient"):
            g = grad_fn(z)
        with jax.named_scope("sst.box_fista.project"):
            x_new = project(z - step * g)
        with jax.named_scope("sst.box_fista.momentum"):
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            z_new = x_new + ((t - 1.0) / t_new) * (x_new - x)
        return x_new, z_new, t_new

    if tol is None:
        x, _, _ = jax.lax.fori_loop(
            0, max_iter, lambda i, carry: advance(*carry),
            (x0, x0, jnp.asarray(1.0, dtype)))
        return x

    lanes = jnp.shape(tol)
    lane_axes = tuple(range(1, x0.ndim))

    def cond(carry):
        *_, it, _n, done = carry
        return jnp.logical_and(it < max_iter,
                               jnp.logical_not(jnp.all(done)))

    def body(carry):
        x, z, t, it, n_iter, done = carry
        x_new, z_new, t_new = advance(x, z, t)
        with jax.named_scope("sst.box_fista.momentum"):
            resid = jnp.max(jnp.abs(x_new - z), axis=lane_axes) / step
            done_new = jnp.logical_or(done, resid.reshape(lanes) <= tol)
            n_iter = jnp.where(jnp.logical_and(jnp.logical_not(done),
                                               done_new), it + 1, n_iter)
            if len(lanes) == 2:
                live = jnp.repeat(
                    jnp.logical_not(jnp.all(done, axis=1)),
                    lanes[1]).reshape((-1,) + (1,) * len(lane_axes))
                x_new = jnp.where(live, x_new, x)
                z_new = jnp.where(live, z_new, z)
        return x_new, z_new, t_new, it + 1, n_iter, done_new

    x, _, _, it, n_iter, done = jax.lax.while_loop(
        cond, body,
        (x0, x0, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32),
         jnp.full(lanes, max_iter, jnp.int32), jnp.zeros(lanes, bool)))
    n_iter = jnp.where(done, n_iter, it)
    return x, n_iter, done


def _project_box_hyperplane(Z, yb, bound, n_bisect=40):
    """Euclidean projection of each row of Z onto its subproblem's feasible
    set {0 <= a_i <= bound_i} intersected with {sum_i y_i a_i = 0}.

    `bound` is per-element (C, class_weight-scaled C, or 0 outside the
    subproblem's rows).  The projection is clip(z - nu*y, 0, bound) for
    the nu making the hyperplane constraint hold; g(nu) = sum(y * clip(z
    - nu*y, 0, bound)) is monotone decreasing, so nu comes from a
    fixed-count vectorized bisection (cheap elementwise work next to the
    (M, n) @ (n, n) ascent matmul)."""
    lo = -(jnp.max(jnp.abs(Z), axis=1) + jnp.max(bound, axis=1))
    hi = -lo

    def bis(i, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        a = jnp.clip(Z - mid[:, None] * yb, 0.0, bound)
        g = jnp.sum(yb * a, axis=1)
        take_hi = g > 0
        return jnp.where(take_hi, mid, lo), jnp.where(take_hi, hi, mid)

    lo, hi = jax.lax.fori_loop(0, n_bisect, bis, (lo, hi))
    nu = 0.5 * (lo + hi)
    return jnp.clip(Z - nu[:, None] * yb, 0.0, bound)


def _project_box_sum(Z, bound, target, n_bisect=40):
    """Euclidean projection of each row of Z onto
    {0 <= a_i <= bound_i, sum_i a_i = target} — clip(z - lam, 0, bound)
    for the lam making the sum hit `target` (monotone decreasing in lam,
    fixed-count vectorized bisection).  `target` is per-row (M,)."""
    zmax = jnp.max(jnp.abs(Z), axis=1) + jnp.max(bound, axis=1) + 1.0
    lo, hi = -zmax, zmax

    def bis(i, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        g = jnp.sum(jnp.clip(Z - mid[:, None], 0.0, bound), axis=1)
        take_hi = g > target
        return jnp.where(take_hi, mid, lo), jnp.where(take_hi, hi, mid)

    lo, hi = jax.lax.fori_loop(0, n_bisect, bis, (lo, hi))
    mid = 0.5 * (lo + hi)
    return jnp.clip(Z - mid[:, None], 0.0, bound)


def _masked_mean_or_mid(vals, free, at_hi, at_lo):
    """libsvm's r1/r2 rule: mean of `vals` over free SVs; when none are
    free, the midpoint of [max over at-upper-bound, min over at-0]."""
    big = jnp.asarray(jnp.inf, vals.dtype)
    nfree = jnp.sum(free, axis=1)
    mean_free = jnp.sum(jnp.where(free, vals, 0.0), axis=1) / \
        jnp.maximum(nfree, 1)
    lb = jnp.max(jnp.where(at_hi, vals, -big), axis=1)
    ub = jnp.min(jnp.where(at_lo, vals, big), axis=1)
    mid = 0.5 * (lb + ub)
    mid = jnp.where(jnp.isfinite(mid), mid,
                    jnp.where(jnp.isfinite(lb), lb,
                              jnp.where(jnp.isfinite(ub), ub, 0.0)))
    return jnp.where(nfree > 0, mean_free, mid)


def _run_dual(grad, project, x0, step, max_iter, tol, dtype):
    """Shared tol dispatch for the kernel duals: `tol=None` runs the
    fixed count; otherwise `_box_fista`'s per-lane residual exit (the
    batched analog of libsvm's eps rule) with the executed-iteration
    max reported for accounting.  `tol` a scalar: x0's rows are the
    subproblems of one candidate.  `tol` of shape (candidates,
    subproblems of each) (`_stacked_tol`) IS how a caller says that the
    rows are those of several candidates of one kernel, stacked: each is
    judged and counted by its own subproblems, and the count is one a
    candidate.  Every solver between `fit_task_batched` and here hands
    `tol` on as it got it."""
    if tol is None:
        x = _box_fista(grad, project, x0, step, max_iter)
        return x, jnp.asarray(max_iter, jnp.int32)
    x, n_it, _ = _box_fista(
        grad, project, x0, step, max_iter,
        tol=jnp.full(jnp.shape(tol) or (x0.shape[0],), tol, dtype))
    return x, jnp.max(n_it, axis=-1).astype(jnp.int32)


def _stacked_tol(tol, candidates, subproblems, dtype):
    """`tol` for the stacked subproblems of `candidates` candidates of
    one kernel: the shape (candidates, subproblems of each) is what
    tells `_run_dual` and `_box_fista` so.  A solve without `tol` has
    nothing to judge a candidate by and keeps None."""
    if tol is None:
        return None
    return jnp.full((candidates, subproblems), tol, dtype)


def _tol_or_default(static):
    """sklearn's SVC tol (libsvm eps), defaulting to libsvm's 1e-3."""
    tol = static.get("tol", 1e-3)
    return 1e-3 if tol is None else float(tol)


def _probability_value_on(value):
    """sklearn 1.9 deprecated SVC's `probability` and made its DEFAULT
    the string "deprecated" — which is truthy, so a naive bool() turns
    every plain SVC search into one that computes Platt calibration.
    Only an explicit boolean True (python or numpy) counts."""
    return isinstance(value, (bool, np.bool_)) and bool(value)


def _probability_on(params):
    return _probability_value_on(params.get("probability", False))


def nu_dual_ascent(K, yb, bound, nu, step, max_iter, tol=None):
    """libsvm's nu-SVC dual (Solver_NU), batched over M subproblems:

        min_a 0.5 a'Q a,   0 <= a_i <= bound_i,
        y'a = 0,  e'a = nu * l          (l = subproblem row count)

    The two equalities DECOMPOSE over the class signs: sum over the
    positive half = sum over the negative half = nu*l/2, so each
    projection is two independent box+sum bisections — no coupled 2-D
    multiplier search.  After the solve, the KKT multipliers follow
    libsvm's calculate_rho: free +1 SVs average the gradient to r1, free
    -1 SVs to r2; the decision is rescaled by r = (r1+r2)/2 (alpha /= r,
    rho = (r1-r2)/2 / r).  Returns per-subproblem full-set decision rows;
    infeasible subproblems (nu*l/2 exceeding a half's box capacity — the
    case where sklearn raises 'specified nu is infeasible') come back as
    NaN rows for the engine's failed-fit detector.  `K`: the kernel
    matrix, or the product with it as an operator (`_BlockKernel`).
    `nu`: a scalar, or one value a subproblem; `tol`: a scalar, or
    `_stacked_tol`'s where M stacks several candidates' subproblems.
    """
    K = _as_product(K)
    pos_b = jnp.where(yb > 0, bound, 0.0)
    neg_b = jnp.where(yb < 0, bound, 0.0)
    l_sub = jnp.sum(bound > 0, axis=1).astype(K.dtype)
    target = 0.5 * nu * l_sub                                   # (M,)
    cap = jnp.minimum(jnp.sum(pos_b, axis=1), jnp.sum(neg_b, axis=1))
    feasible = target <= cap * (1.0 + 1e-6)

    def project(Zt):
        return _project_box_sum(Zt, pos_b, target) + \
            _project_box_sum(Zt, neg_b, target)

    def grad(Z):
        return yb * K.own(Z * yb)

    A, n_it = _run_dual(grad, project, project(jnp.zeros_like(bound)),
                        step, max_iter, tol, K.dtype)

    with jax.named_scope("sst.svc.decision"):
        V = K.all(A * yb)
    G = yb * K.restrict(V)             # gradient of 0.5 a'Qa
    inb = bound > 0
    at_lo = A <= bound * 1e-6
    at_hi = A >= bound * (1.0 - 1e-6)
    free = inb & ~at_lo & ~at_hi
    pos, neg = yb > 0, yb < 0
    r1 = _masked_mean_or_mid(G, free & pos, inb & pos & at_hi,
                             inb & pos & at_lo)
    r2 = _masked_mean_or_mid(G, free & neg, inb & neg & at_hi,
                             inb & neg & at_lo)
    r = 0.5 * (r1 + r2)                # lambda_e: the alpha rescale
    rho = 0.5 * (r1 - r2)              # lambda_y
    ok = jnp.logical_and(feasible, r > 1e-12)
    dec = (V - rho[:, None]) / r[:, None]
    return jnp.where(ok[:, None], dec, jnp.nan), n_it


def _kkt_intercept(K, A, yb, bound):
    """Per-subproblem intercept b from the KKT conditions (libsvm's -rho):
    mean of E_i = y_i - f0(x_i) over free SVs; when every alpha sits at a
    bound, the midpoint of the feasible [max lower, min upper] interval.
    `K`: the product as an operator, A / yb / bound in its layout."""
    V = K.own(A * yb)                        # (M, the duals' columns)
    E = yb - V
    inb = bound > 0
    at_lo = A <= bound * 1e-6
    at_hi = A >= bound * (1.0 - 1e-6)
    free = inb & ~at_lo & ~at_hi
    nfree = jnp.sum(free, axis=1)
    b_free = jnp.sum(jnp.where(free, E, 0.0), axis=1) / \
        jnp.maximum(nfree, 1)
    lo_mask = inb & ((at_lo & (yb > 0)) | (at_hi & (yb < 0)))
    up_mask = inb & ((at_lo & (yb < 0)) | (at_hi & (yb > 0)))
    big = jnp.asarray(jnp.inf, E.dtype)
    max_lo = jnp.max(jnp.where(lo_mask, E, -big), axis=1)
    min_up = jnp.min(jnp.where(up_mask, E, big), axis=1)
    b_mid = 0.5 * (max_lo + min_up)
    b_mid = jnp.where(
        jnp.isfinite(b_mid), b_mid,
        jnp.where(jnp.isfinite(max_lo), max_lo,
                  jnp.where(jnp.isfinite(min_up), min_up, 0.0)))
    return jnp.where(nfree > 0, b_free, b_mid)


def fista_dual_ascent(K, yb, bound, step, max_iter, tol=None):
    """Nesterov-accelerated projected gradient ascent on the SVM dual

        max_a  1'a - 0.5 a' Q a,   0 <= a_i <= bound_i,
        sum_i y_i a_i = 0

    (the true libsvm dual, equality constraint included; per-sample upper
    bounds carry both the subproblem box mask and class_weight-scaled C).
    K: (n, n) kernel; yb/bound: (M, n) signed labels and box bounds for M
    subproblems advanced together — every iteration is ONE (M, n) @ (n, n)
    matmul plus a vectorized hyperplane projection.  Or K the product as
    an operator and yb/bound in its layout (`_BlockKernel`: a dual's own
    two class blocks).  Returns
    (A, b, n_iter): alphas, the KKT intercept per subproblem, and the
    executed iteration count (== max_iter when tol is None; with `tol`,
    the per-lane prox-gradient-residual exit stops when every subproblem
    is below it — the batched analog of libsvm's eps stopping rule,
    which defaults to the same 1e-3 the sklearn `tol` parameter
    carries; `_stacked_tol`'s where the M subproblems are several
    candidates' stacked: `n_iter` is then one count a candidate).  Shared
    by the search's task-batched fit and the standalone SVC so the
    numerics live once."""

    K = _as_product(K)

    def grad(Z):                       # descent form of the ascent grad
        return -(1.0 - yb * K.own(Z * yb))

    A, n_it = _run_dual(
        grad, lambda Zt: _project_box_hyperplane(Zt, yb, bound),
        jnp.zeros_like(bound), step, max_iter, tol, K.dtype)
    with jax.named_scope("sst.svc.intercept"):
        b = _kkt_intercept(K, A, yb, bound)
    return A, b, n_it


def _platt_fit(f, t, w, n_iter=50):
    """Vectorized Platt sigmoid calibration: per task (leading axis),
    minimise the weighted logloss of P(y=1|f) = sigmoid(-(A*f + B))
    against Platt's smoothed targets `t` with sample weights `w`, by
    damped Newton on the 2-parameter convex problem (closed-form 2x2
    solve per task — libsvm's sigmoid_train, batched).  Damping = per-
    task step halving: full Newton steps can overshoot on near-separable
    folds (libsvm guards with the same line search); a step that fails
    to decrease the loss at every halving is rejected outright, and
    tasks whose gradient is below libsvm's eps stop moving.

    Returns (A, B) arrays of shape f.shape[:1]."""
    B_ = f.shape[0]
    dtype = f.dtype
    wsum = jnp.sum(w, axis=1) + 1e-12
    # libsvm init: A=0, B=log((prior0+1)/(prior1+1)) from the targets
    np_w = jnp.sum(w * t, axis=1)
    nn_w = wsum - np_w
    A0 = jnp.zeros((B_,), dtype)
    B0 = jnp.log((nn_w + 1.0) / (np_w + 1.0))

    def loss(A, Bb):
        # sum_i w_i * [log(1+e^{u_i}) - (1-t_i) u_i], the stable form of
        # the weighted cross-entropy of targets t under p = sigmoid(-u)
        u = A[:, None] * f + Bb[:, None]
        return jnp.sum(w * (jnp.logaddexp(0.0, u) - (1.0 - t) * u),
                       axis=1)

    halvings = (2.0 ** -jnp.arange(8)).astype(dtype)   # 1, 1/2, .. 1/128

    def body(i, carry):
        A, Bb = carry
        u = A[:, None] * f + Bb[:, None]
        s = jax.nn.sigmoid(u)                    # = 1 - p
        r = w * (s - (1.0 - t))                  # dL/du per sample
        gA = jnp.sum(r * f, axis=1)
        gB = jnp.sum(r, axis=1)
        h = w * s * (1.0 - s)
        hAA = jnp.sum(h * f * f, axis=1) + 1e-9
        hAB = jnp.sum(h * f, axis=1)
        hBB = jnp.sum(h, axis=1) + 1e-9
        det = hAA * hBB - hAB * hAB
        dA = (hBB * gA - hAB * gB) / det
        dB = (hAA * gB - hAB * gA) / det
        # step halving: first step size that does not increase the loss
        # wins; none -> no update this iteration (monotone by design)
        L0 = loss(A, Bb)
        Ls = jax.vmap(lambda st: loss(A - st * dA, Bb - st * dB))(halvings)
        ok = Ls <= L0[None, :]
        first = jnp.argmax(ok, axis=0)
        step = jnp.where(jnp.any(ok, axis=0), halvings[first], 0.0)
        # converged tasks (libsvm eps) stop moving
        step = jnp.where(
            jnp.maximum(jnp.abs(gA), jnp.abs(gB)) >= 1e-5, step, 0.0)
        # a rejected step must not touch A/B at all: with a non-finite
        # Newton direction (degenerate 2x2 system), 0 * inf = NaN would
        # poison the task permanently
        upd = step > 0
        return (jnp.where(upd, A - step * dA, A),
                jnp.where(upd, Bb - step * dB, Bb))

    A, Bb = jax.lax.fori_loop(0, n_iter, body, (A0, B0))
    return A, Bb


def _pair_probs_to_R(r, pairs, k):
    """(n, P) per-pair sigmoid probabilities -> the (n, k, k) pairwise
    matrix Wu-Lin consumes: R[i_p, j_p] = r_p, R[j_p, i_p] = 1 - r_p,
    with libsvm's clip away from {0, 1}.  Shared by the search-internal
    (train-fold Platt) and converted-model (libsvm probA/probB) paths so
    the coupling input can never desynchronize between them."""
    r = jnp.clip(r, 1e-7, 1.0 - 1e-7)
    pos = jax.nn.one_hot(pairs[:, 0], k, dtype=r.dtype)
    neg = jax.nn.one_hot(pairs[:, 1], k, dtype=r.dtype)
    return jnp.einsum("np,pi,pj->nij", r, pos, neg) \
        + jnp.einsum("np,pi,pj->nij", 1.0 - r, neg, pos)


def _pairwise_coupling(R, n_iter=100):
    """Wu & Lin (2004) "second approach" pairwise coupling — libsvm's
    multiclass_probability, batched over arbitrary leading axes.

    R[..., i, j] ~ P(class i | class i or j) from per-pair Platt
    sigmoids (diagonal ignored).  Solves min_p sum_{i!=j}
    (r_ji p_i - r_ij p_j)^2 on the simplex by libsvm's normalised
    Gauss-Seidel sweeps (fixed iteration count; libsvm's max is
    max(100, k) with early exit — the extra sweeps past convergence
    are no-ops since diff -> 0).  Returns (..., k) probabilities."""
    k = R.shape[-1]
    eye = jnp.eye(k, dtype=R.dtype)
    R0 = R * (1.0 - eye)
    RT = jnp.swapaxes(R0, -1, -2)
    # Q[t,t] = sum_{j!=t} r_jt^2 ; Q[t,j] = -r_jt * r_tj  (symmetric PSD)
    Q = -(RT * R0)
    Q = Q + eye * jnp.sum(RT ** 2, axis=-1)[..., :, None]

    def outer(_, p):
        Qp = jnp.einsum("...tj,...j->...t", Q, p)
        pQp = jnp.sum(p * Qp, axis=-1)

        def inner(t, carry):
            p, Qp, pQp = carry
            Qtt = Q[..., t, t]
            diff = (-Qp[..., t] + pQp) / Qtt
            pQp = (pQp + diff * (diff * Qtt + 2.0 * Qp[..., t])) \
                / (1.0 + diff) ** 2
            Qp = (Qp + diff[..., None] * Q[..., t, :]) \
                / (1.0 + diff[..., None])
            p = (p + diff[..., None] * eye[t]) / (1.0 + diff[..., None])
            return p, Qp, pQp

        p, _, _ = jax.lax.fori_loop(0, k, inner, (p, Qp, pQp))
        return p

    p0 = jnp.full(R.shape[:-1], 1.0 / k, dtype=R.dtype)
    return jax.lax.fori_loop(0, n_iter, outer, p0)


def _resolve_gamma(gamma, meta):
    if isinstance(gamma, str):
        if gamma == "scale":
            # X variance precomputed host-side in prepare_data
            return 1.0 / (meta["n_features"] * meta["x_var"])
        if gamma == "auto":
            return 1.0 / meta["n_features"]
        raise ValueError(f"gamma={gamma!r} not understood")
    return float(gamma)


class SVCFamily(Family):
    name = "svc"
    is_classifier = True
    dynamic_params = {"C": np.float32, "gamma": np.float32}
    #: libsvm computes probabilities in f64 whatever the input dtype, so
    #: sklearn's log_loss clips them at f64 eps (engine: logloss_clip_eps)
    proba_dtype_rule = "float64"
    #: the per-candidate scalar the dual consumes (NuSVC swaps in "nu")
    primary_param = "C"
    primary_default = 1.0
    #: the task-batched fit understands per-fold-transformed inputs
    #: (data["X_folds"], shape (F, n, d)) — what compiled Pipelines feed it
    task_batched_accepts_fold_inputs = True

    @classmethod
    def _pair_dec(cls, K, p_c, base_bound, yb, step, max_iter, tol=None):
        """Solve the M stacked pair subproblems and return their (M, n)
        full-set decision rows plus the executed iteration count.  `K`
        is the product with the kernel matrix (`_DenseKernel`, or
        `_BlockKernel` with `base_bound` and `yb` in its layout and the
        decision rows in class-sorted order), `p_c` the candidate's
        primary scalar (C here: scales the box), `base_bound` the
        fold/weight/pair box mask; `tol` enables the per-lane residual
        exit (libsvm's eps stopping rule).  Where the subproblems are
        those of several candidates of one kernel, candidate-major, `p_c`
        is (M,), each row its candidate's, and `tol` `_stacked_tol`'s:
        the count is then one a candidate."""
        K = _as_product(K)
        bound = (p_c[:, None] if jnp.ndim(p_c) else p_c) * base_bound
        A, b, n_it = fista_dual_ascent(K, yb, bound, step, max_iter, tol)
        with jax.named_scope("sst.svc.decision"):
            return K.all(A * yb) + b[:, None], n_it

    # kernel matrices + per-task decision caches are the memory hot spot;
    # tell the search to keep task batches small
    @staticmethod
    def max_tasks_hint(n_samples: int, meta) -> int:
        k = meta["n_classes"]
        p = max(1, k * (k - 1) // 2)
        budget = 1 << 30   # ~1 GiB of decision cache per launch
        return max(1, budget // max(1, n_samples * p * 4))

    @staticmethod
    def launch_workspace(n_samples: int, meta, n_folds: int,
                         itemsize: int = 4, *, static=None, row_sets=1):
        """What a launch holds besides its arguments, for the memory
        ledger (read off the launch compiled for a v5e at 20 000 rows x
        16 candidates: 5.03 GB with dense duals, 3.45 GB with
        block-compact ones, 3.53 GB with those of 4 candidates a kernel
        stacked).  Whatever the width, since kernels are scanned: ONE
        kernel matrix and the bfloat16 copy the MXU reads, and eight
        (folds x pairs, columns of a dual) arrays (iterates, signs,
        bounds, gradient, the projection's temporaries) for each of the
        S candidates of a kernel (`launch_layout`'s fact in `static`;
        1 without it).  Dense duals: n columns, and the matrix a third
        time in the layout the product wants.  Block-compact duals: the
        matrix of the k n_b sorted and padded rows, 2 n_b columns, and
        the (k, S k folds, k n_b) result of the product by class.  A
        candidate: its cached (folds, n, pairs) pair decisions, about
        three times over (the scan's stacked output, its transpose, the
        task-major copy)."""
        k = meta["n_classes"]
        S = 1 if row_sets > 1 else int((static or {}).get(_KERNEL_RUN, 1))
        m = n_folds * max(1, k * (k - 1) // 2)
        n = int(n_samples)
        n_b = _block_rows(meta, n)
        if n_b is None:
            fixed = n * n * (2 * itemsize + 2) + 8 * S * m * n * itemsize
        else:
            n_p = k * n_b
            fixed = n_p * n_p * (itemsize + 2) + S * (
                8 * m * 2 * n_b + k * k * n_folds * n_p) * itemsize
        return {"fixed_bytes": fixed,
                "per_candidate_bytes": 3 * m * n * itemsize}

    @classmethod
    def launch_facts(cls, static, meta, n_candidates, n_folds):
        """Kernel matrices built and dual subproblems advanced by a
        launch of `n_candidates` (padding included: a padded candidate
        is computed; one matrix for the S candidates of a run where the
        launch groups them, `_kernel_run`), and the columns one dual's
        iterate holds: its two class blocks in the block-compact layout,
        every row otherwise."""
        k = meta["n_classes"]
        facts = {"gram_builds":
                     n_candidates // _kernel_run(static, n_candidates),
                 "dual_subproblems":
                     n_candidates * n_folds * max(1, k * (k - 1) // 2)}
        if "class_counts" in meta:
            n = sum(meta["class_counts"])
            n_b = _block_rows(meta, n)
            facts["dual_rows"] = n if n_b is None else 2 * n_b
        return facts

    @classmethod
    def launch_layout(cls, dynamic_params, static, meta, n_folds):
        """The group's candidates kernel-major, and the run length S
        the launch may stack: where they fall into runs of one length
        >= 2 that share `gamma` (by float32 value; the other kernel
        parameters are static, equal within a compile group) the order
        goes by gamma, then by the primary scalar, and S is the run
        length.  None where the runs are ragged or every gamma is its
        own: the launch then builds a kernel a candidate.  What S
        candidates' stacked duals hold is `launch_workspace`'s to price
        and the ledger's to bound: a group cut narrower than a run
        builds a kernel a candidate too (`_kernel_run`)."""
        nc = max((np.size(v) for v in dynamic_params.values()), default=0)
        gamma, primary = (
            np.broadcast_to(np.asarray(
                dynamic_params.get(name, 0.0), np.float32), (nc,))
            for name in ("gamma", cls.primary_param))
        counts = np.unique(gamma, return_counts=True)[1]
        if nc < 2 or counts[0] < 2 or np.any(counts != counts[0]):
            return None
        S = int(counts[0])
        return np.lexsort((primary, gamma)), {_KERNEL_RUN: S}, S

    @classmethod
    def launch_stats(cls, models, static, meta):
        """The default's maximum and sum, and the counts themselves: the
        fit's `n_iter` is per task (a candidate's folds share its
        count)."""
        stats = super().launch_stats(models, static, meta)
        stats["dual_iters"] = models["n_iter"].astype(jnp.int32).reshape(-1)
        return stats

    @classmethod
    def extract_params(cls, estimator):
        params = dict(estimator.get_params(deep=False))
        return params

    @classmethod
    def observe_candidates(cls, candidates, base_params, meta):
        """Host-side, once per fit: warn about the compiled Platt
        approximation when any candidate requests probability=True
        (the traced fit code cannot warn reliably — a program-cache
        hit skips tracing entirely)."""
        if _probability_on(base_params) or any(
                _probability_on(c) for c in candidates):
            warnings.warn(
                "compiled SVC(probability=True): Platt calibration uses "
                "train-fold decision values, not libsvm's internal "
                "5-fold CV — probabilities are slightly overconfident "
                "vs sklearn's (documented in docs/ROADMAP.md)",
                UserWarning, stacklevel=2)

    @classmethod
    def prepare_data(cls, X, y, dtype=np.float32):
        classes, y_enc = encode_labels(y)
        k = len(classes)
        data = {
            "X": np.ascontiguousarray(X, dtype=dtype),
            "y": y_enc,
        }
        meta = {"n_classes": int(k), "classes": classes,
                "n_features": int(X.shape[1]),
                "x_var": float(np.var(np.asarray(X))),
                "pairs": _pairs(k),
                # static: what decides a dual's layout (_block_rows)
                "class_counts": tuple(
                    int(c) for c in np.bincount(y_enc, minlength=k))}
        return data, meta

    @classmethod
    def fit_task_batched(cls, dynamic, static, data, train_w, meta):
        """Tasks arrive candidate-major (task t = (cand t//F, fold t%F)).
        One `lax.scan` step per kernel matrix: it is built once and
        shared by every (fold x pair) subproblem, which are advanced
        together — each ascent iteration is a single (F*P, n) @ (n, n)
        matmul, or in the block-compact layout (module docstring) a
        product by class that contracts each dual over its own rows.
        A kernel is a candidate's, or — where `static` carries
        `launch_layout`'s fact and the launch is made of whole runs
        (`_kernel_run`) — that of S consecutive candidates that differ
        in the primary scalar only: their S x F x P subproblems are
        stacked on the duals' leading axis, each row's box scaled by
        its own candidate's scalar, and every candidate ends at its own
        count (`_stacked_tol`, `_box_fista`).
        Returns per-task full-dataset pair decisions in the caller's row
        order (the search scores on masked rows of the training X, so
        caching decisions avoids rebuilding kernels in the scoring
        phase)."""
        X = data["X"]
        y = data["y"]
        n, d = X.shape
        k = meta["n_classes"]
        pairs = jnp.asarray(meta["pairs"])                    # (P, 2)
        P = pairs.shape[0]
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
        # libsvm's eps stopping rule (sklearn tol, default 1e-3): each
        # candidate's dual solve ends at ITS convergence — an easy
        # (small-C) candidate stops in tens of iterations; with a scan
        # step of its own it exits there, stacked on a kernel's other
        # candidates it stops moving there and the step runs as long as
        # the slowest of them
        tol_exit = _tol_or_default(static)
        # tasks are candidate-major with a fixed fold count injected by the
        # engine; the candidate count is B // n_folds
        n_folds = int(static.get("__n_folds__", 0))
        if n_folds <= 0:
            raise ValueError("engine must pass __n_folds__ for SVC")
        nc = B // n_folds

        gamma_default = _resolve_gamma(static.get("gamma", "scale"), meta)
        pp = cls.primary_param
        C_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get(pp, static.get(pp, cls.primary_default)),
            X.dtype), (B,))
        g_task = jnp.broadcast_to(jnp.asarray(
            dynamic.get("gamma", gamma_default), X.dtype), (B,))
        C_cand = C_task.reshape(nc, n_folds)[:, 0]
        g_cand = g_task.reshape(nc, n_folds)[:, 0]
        w_cand = train_w.reshape(nc, n_folds, n)

        # per-pair signed labels: +1 for pairs[p,0], -1 for pairs[p,1]
        ypos = (y[None, :] == pairs[:, 0][:, None])
        yneg = (y[None, :] == pairs[:, 1][:, None])
        ybin = ypos.astype(X.dtype) - yneg.astype(X.dtype)    # (P, n)
        if k == 2:
            # sklearn convention: binary decision_function > 0 -> classes_[1]
            ybin = -ybin
        in_pair = (ypos | yneg).astype(X.dtype)               # (P, n)

        X_folds = data.get("X_folds")     # (F, n, d) fold-transformed, or
        # None (plain SVC: one shared X, one kernel per candidate)
        gamma_is_scale = "gamma" not in dynamic and \
            static.get("gamma", "scale") == "scale"

        # class_weight scales each sample's box bound: 0 <= a_i <= C * cw_i
        # (libsvm's per-class C); "balanced" follows each fold's counts
        from spark_sklearn_tpu.models.base import class_weight_multiplier
        w_fold_masks = train_w.reshape(nc, n_folds, n)[0]     # (F, n)
        cw_fold = class_weight_multiplier(
            w_fold_masks, y, meta, static.get("class_weight"))
        if cw_fold is None:
            cw_fold = jnp.ones((n_folds, n), X.dtype)

        # candidates a kernel: the scan below goes over nc // S kernels,
        # and S x F takes the place of the folds in all that a step stacks
        S = _kernel_run(static, nc, X_folds is not None)
        SF = S * n_folds
        if S > 1:
            tol_exit = _stacked_tol(tol_exit, S, n_folds * P, X.dtype)
            C_cand = jnp.repeat(C_cand, n_folds * P).reshape(nc // S, -1)
            g_cand = g_cand[::S]
            w_cand = w_cand.reshape(nc // S, SF, n)
            cw_fold = jnp.tile(cw_fold, (S, 1))

        n_b = None if X_folds is not None else _block_rows(meta, n)
        if n_b is not None:
            # class-sorted, block-compact duals: the rows in class order
            # and everything the boxes are made of, once a launch
            with jax.named_scope("sst.svc.compact"):
                rows, valid, slot = _class_sorted(
                    y, meta["class_counts"], n_b)
                valid = valid.astype(X.dtype)                 # (k, n_b)
                valid_row = valid.reshape(-1)
                X_s = X[rows]                                 # (k n_b, d)
                w_cand = (jnp.take(train_w, rows, axis=1).reshape(
                    nc // S, SF, k, n_b) * valid)
                cw_s = jnp.take(cw_fold, rows, axis=1).reshape(
                    SF, k, n_b)
                v0_s = _power_start(n, X.dtype)[rows] * valid_row
                # +1 on the rows of pairs[p, 0], -1 on those of
                # pairs[p, 1], 0 on the pads
                yb_s = jnp.broadcast_to(
                    (valid[meta["pairs"]] * jnp.asarray(
                        [1.0, -1.0], X.dtype)[None, :, None])[None],
                    (SF, P, 2, n_b)).reshape(-1, 2 * n_b)

        def one_kernel(carry, inp):
            # C_c: the candidate's scalar, or a row's; w_f (S F, n)
            C_c, g_c, w_f = inp
            if n_b is not None:
                with jax.named_scope("sst.svc.gram"):
                    K = _kernel(X_s, X_s, kind, g_c, degree, coef0)
                with jax.named_scope("sst.svc.power_step"):
                    step = _power_step(K, n, X.dtype, centred=True,
                                       start=v0_s, valid=valid_row)
                with jax.named_scope("sst.svc.compact"):
                    base = (w_f * cw_s)[:, meta["pairs"], :].reshape(
                        -1, 2 * n_b)
                dec, it = cls._pair_dec(
                    _BlockKernel(K, meta["pairs"], SF, n_b),
                    C_c, base, yb_s, step, max_iter, tol_exit)
                with jax.named_scope("sst.svc.compact"):
                    if S > 1:
                        # a candidate at a time, the gather the ungrouped
                        # launch takes: XLA:TPU (libtpu 0.0.34) compiles
                        # ONE gather of the stacked (900, 20000) rows to
                        # a program that reads NaN in 1 071 columns and
                        # other rows' values beside them (PERF.md, PR 34)
                        dec = jnp.concatenate([
                            jnp.take(d, slot, axis=1)
                            for d in jnp.split(dec, S)])
                    else:
                        dec = jnp.take(dec, slot, axis=1)
                dec = dec.reshape(SF, P, n)
            elif X_folds is None:
                with jax.named_scope("sst.svc.gram"):
                    K = _kernel(X, X, kind, g_c, degree, coef0)   # (n, n)
                with jax.named_scope("sst.svc.power_step"):
                    step = _power_step(K, n, X.dtype, centred=True)
                # subproblem box masks: (F, P, n) -> flatten (F*P, n)
                base = ((w_f * cw_fold)[:, None, :]
                        * in_pair[None, :, :]).reshape(-1, n)
                yb = jnp.broadcast_to(
                    ybin[None], (SF, P, n)).reshape(-1, n)
                dec, it = cls._pair_dec(
                    K, C_c, base, yb, step, max_iter, tol_exit)
                dec = dec.reshape(SF, P, n)
            else:
                # pipeline mode: each fold has its own transformed X, so
                # kernels are per (candidate, fold); the P pair
                # subproblems of a fold advance together and folds batch
                # via vmap (an (F, P, n) x (F, n, n) bmm on the MXU).
                # gamma='scale' must follow the TRANSFORMED fold X
                # (sklearn resolves it on the X the final step receives).
                def per_fold(Xf, w_row, cw_row):
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
                    with jax.named_scope("sst.svc.gram"):
                        Kf = _kernel(Xf, Xf, kind, g_f, degree, coef0)
                    with jax.named_scope("sst.svc.power_step"):
                        step = _power_step(Kf, n, Xf.dtype, centred=True)
                    base = (w_row * cw_row)[None, :] * in_pair
                    return cls._pair_dec(
                        Kf, C_c, base, ybin, step, max_iter,
                        tol_exit)                         # (P, n), it

                dec, its = jax.vmap(per_fold)(
                    X_folds, w_f, cw_fold)                # (F,P,n), (F,)
                it = jnp.max(its)
            # one count a candidate (a solve without `tol` has one for all)
            it = jnp.broadcast_to(it, (S,)) if S > 1 else it
            return carry, (jnp.transpose(dec, (0, 2, 1)), it)  # (SF,n,P)

        _, (decs, its) = jax.lax.scan(
            one_kernel, 0.0, (C_cand, g_cand, w_cand))
        # (nc / S, S F, n, P) -> task-major (B, n, P); per-candidate
        # executed dual iterations repeat across the fold axis for the
        # engine's per-launch accounting
        model = {"pair_dec": decs.reshape(B, n, P),
                 "n_iter": jnp.repeat(its.reshape(-1), n_folds)}
        if _probability_on(static):
            # compiled Platt scaling: calibrate a sigmoid on the
            # TRAIN-fold decision values per task, stored with the model
            # so predict_proba / neg_log_loss scoring stay compiled.
            # Approximation vs libsvm: libsvm calibrates on internal
            # 5-fold CV decisions; these are in-sample train decisions
            # (slightly overconfident — documented in docs/ROADMAP.md;
            # the user-facing warning fires host-side per fit, in
            # observe_candidates — this code is jit-traced, so a warn
            # here would fire only on the first compile)
            if k == 2:
                fdec = model["pair_dec"][:, :, 0]             # (B, n)
                yp = (y == 1).astype(X.dtype)[None, :]        # classes_[1]
                np_w = jnp.sum(train_w * yp, axis=1)
                nn_w = jnp.sum(train_w * (1.0 - yp), axis=1)
                t_pos = (np_w + 1.0) / (np_w + 2.0)
                t_neg = 1.0 / (nn_w + 2.0)
                t = jnp.where(yp > 0, t_pos[:, None], t_neg[:, None])
                A, Bb = _platt_fit(fdec, t, train_w)
                model["platt"] = jnp.stack([A, Bb], axis=1)   # (B, 2)
            else:
                # multiclass: one Platt sigmoid per PAIR, fitted on that
                # pair's train-fold members only; predict_proba couples
                # them with Wu-Lin (libsvm's multiclass_probability)
                f_bp = jnp.transpose(
                    model["pair_dec"], (0, 2, 1))             # (B, P, n)
                yp = ypos.astype(X.dtype)                     # (P, n)
                w_bp = train_w[:, None, :] * in_pair[None]    # (B, P, n)
                np_w = jnp.sum(w_bp * yp[None], axis=2)       # (B, P)
                nn_w = jnp.sum(w_bp, axis=2) - np_w
                t_pos = (np_w + 1.0) / (np_w + 2.0)
                t_neg = 1.0 / (nn_w + 2.0)
                t = jnp.where(yp[None] > 0,
                              t_pos[..., None], t_neg[..., None])
                A, Bb = _platt_fit(f_bp.reshape(B * P, n),
                                   t.reshape(B * P, n),
                                   w_bp.reshape(B * P, n))
                model["platt_pair"] = jnp.stack(
                    [A, Bb], axis=1).reshape(B, P, 2)
        return model

    # -- prediction from cached decisions (search-internal) or from the
    # -- support-vector/representer form (Converter.toTPU) ----------------
    @classmethod
    def _pair_dec_of(cls, model, static, X, meta):
        """Pair decisions (n, P): the search caches them per task
        ("pair_dec", full training set, X ignored); converted models
        carry the representer form instead ("sv_X" support vectors +
        per-pair signed "alphas" + "intercepts") and evaluate new X
        with one kernel matmul."""
        if "pair_dec" in model:
            return model["pair_dec"]
        g = meta.get("resolved_gamma")
        if g is None:
            g = _resolve_gamma(static.get("gamma", "scale"), meta)
        K = _kernel(X, model["sv_X"], static.get("kernel", "rbf"), g,
                    float(static.get("degree", 3)),
                    float(static.get("coef0", 0.0)))
        return K @ model["alphas"].T + model["intercepts"][None, :]

    @classmethod
    def _votes(cls, dec, meta):
        pairs = jnp.asarray(meta["pairs"])                    # (P, 2)
        k = meta["n_classes"]
        P = pairs.shape[0]
        pos_mat = jax.nn.one_hot(pairs[:, 0], k, dtype=dec.dtype)  # (P, k)
        neg_mat = jax.nn.one_hot(pairs[:, 1], k, dtype=dec.dtype)
        win_pos = (dec > 0).astype(dec.dtype)                 # (n, P)
        votes = win_pos @ pos_mat + (1.0 - win_pos) @ neg_mat
        # confidence tie-break, bounded to (-.5, .5) like sklearn's
        # _ovr_decision_function
        conf = dec @ pos_mat - dec @ neg_mat                  # (n, k)
        conf = conf / (3.0 * (jnp.abs(conf) + 1.0))
        return votes + conf

    @classmethod
    def predict(cls, model, static, X, meta):
        dec = cls._pair_dec_of(model, static, X, meta)
        if meta["n_classes"] == 2:
            return (dec[:, 0] > 0).astype(jnp.int32)
        return jnp.argmax(cls._votes(dec, meta),
                          axis=1).astype(jnp.int32)

    @classmethod
    def decision(cls, model, static, X, meta):
        dec = cls._pair_dec_of(model, static, X, meta)
        if meta["n_classes"] == 2:
            return dec[:, 0]
        return cls._votes(dec, meta)

    @classmethod
    def predict_proba(cls, model, static, X, meta):
        """Compiled Platt probabilities (probability=True — calibration
        fitted alongside the duals in fit_task_batched).  Binary: one
        sigmoid.  Multiclass: per-pair sigmoids coupled with Wu-Lin
        (`_pairwise_coupling`, libsvm's multiclass_probability), fully
        compiled — proba-scoring multiclass searches stay on the
        compiled tier."""
        if "probA" in model:
            # converted sklearn SVC: libsvm's own (probA_, probB_) pair
            # sigmoids — exact parity with sklearn's predict_proba
            dec = cls._pair_dec_of(model, static, X, meta)
            A, Bp = model["probA"], model["probB"]
            k = meta["n_classes"]
            if k == 2:
                # libsvm's binary pair is classes_[0]-positive while the
                # public decision_function is classes_[1]-positive, so
                # the calibrated sigmoid sees the NEGATED public margin
                r0 = jax.nn.sigmoid(-(A[0] * (-dec[:, 0]) + Bp[0]))
                return jnp.stack([r0, 1.0 - r0], axis=1)
            pairs = jnp.asarray(meta["pairs"])
            r = jax.nn.sigmoid(-(dec * A[None, :] + Bp[None, :]))
            return _pairwise_coupling(_pair_probs_to_R(r, pairs, k))
        if "platt" in model:
            f = model["pair_dec"][:, 0]
            A, B = model["platt"][0], model["platt"][1]
            p1 = jax.nn.sigmoid(-(A * f + B))
            return jnp.stack([1.0 - p1, p1], axis=1)
        if "platt_pair" in model:
            k = meta["n_classes"]
            pairs = jnp.asarray(meta["pairs"])
            f = model["pair_dec"]                             # (n, P)
            A = model["platt_pair"][:, 0]                     # (P,)
            B = model["platt_pair"][:, 1]
            r = jax.nn.sigmoid(-(f * A[None, :] + B[None, :]))
            return _pairwise_coupling(_pair_probs_to_R(r, pairs, k))
        raise NotImplementedError(
            "predict_proba requires SVC(probability=True)")

    @classmethod
    def sklearn_attrs(cls, model, static, meta):
        return {"classes_": meta["classes"],
                "n_features_in_": meta["n_features"]}


class NuSVCFamily(SVCFamily):
    """nu-SVC: same one-vs-one kernel machinery as SVC, but each pair
    subproblem solves libsvm's nu-parameterised dual (`nu_dual_ascent`)
    — box bound 1 per sample (class_weight-scaled), the two equality
    constraints split into per-class-half sum projections, and the
    decision rescaled by the KKT multiplier r.  Infeasible nu (sklearn
    raises ValueError in fit) surfaces as NaN decisions -> the search's
    failed-fit detector assigns error_score, the compiled analog of the
    host tier's raise."""

    name = "nu_svc"
    dynamic_params = {"nu": np.float32, "gamma": np.float32}
    primary_param = "nu"
    primary_default = 0.5

    @classmethod
    def _pair_dec(cls, K, p_c, base_bound, yb, step, max_iter, tol=None):
        return nu_dual_ascent(K, yb, base_bound, p_c, step, max_iter, tol)


register_family(
    SVCFamily,
    "sklearn.svm._classes.SVC",
    "sklearn.svm.SVC",
)
register_family(
    NuSVCFamily,
    "sklearn.svm._classes.NuSVC",
    "sklearn.svm.NuSVC",
)
