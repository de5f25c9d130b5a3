"""The class-sorted, block-compact layout of the one-vs-one duals
(``models/svm.py``: ``_block_rows``, ``_class_sorted``, ``_BlockKernel``).

One algorithm on two layouts: where the search's task-batched fit sees
three or more balanced classes a dual's iterate is its two classes' blocks
side by side and the product contracts it over its own rows; everywhere
else a dual is a dense row.  The dense layout is the reference here: on the
same inputs both give the same alphas, intercepts and decisions up to the
order of the float32 sums (XLA:CPU, exact float32 products).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_sklearn_tpu as sst
from spark_sklearn_tpu.models.svm import (
    NuSVCFamily, SVCFamily, _BlockKernel, _block_rows, _kernel, _pairs,
    _power_start, _power_step, fista_dual_ascent, nu_dual_ascent)

FOLDS = 3


def _data(counts, d=5, seed=0, shuffle=True):
    """Overlapping blobs, `counts[c]` rows of class c, rows shuffled."""
    rng = np.random.default_rng(seed)
    y = np.repeat(np.arange(len(counts)), counts)
    if shuffle:
        y = rng.permutation(y)
    centres = rng.standard_normal((len(counts), d)) * 1.2
    X = (centres[y] + rng.standard_normal((len(y), d))).astype(np.float32)
    return X, y


# --- which layout ----------------------------------------------------------

@pytest.mark.parametrize("counts, want", [
    ((2000,) * 10, 2000),          # the benchmark's cell
    ((1999,) * 10, 2000),
    ((30, 30, 30), 32),            # a multiple of the sublane tile
    ((40, 36, 32), 40),
    ((31, 31, 31, 31), 32),
    ((25, 25, 25, 25), None),      # 4 x 32 = 128 > 1.25 x 100
    ((48, 8, 8), None),            # skewed: 3 x 48 > 1.25 x 64
    ((32, 32), None)])             # binary: one pair holds every row
def test_block_rows(counts, want):
    assert _block_rows({"class_counts": counts}, sum(counts)) == want


def test_block_rows_needs_this_datas_counts():
    assert _block_rows({}, 90) is None
    assert _block_rows({"class_counts": (30, 30, 30)}, 96) is None


# --- the operator against the dense product --------------------------------

def _dual_inputs(counts, seed=0):
    """One candidate's duals on class-sorted, padded rows in both
    layouts: the kernel matrix, (yb, bound) dense over all k * n_b
    columns and compact over each dual's two blocks, the step."""
    k = len(counts)
    n = sum(counts)
    n_b = _block_rows({"class_counts": counts}, n)
    assert n_b is not None
    X, y = _data(counts, seed=seed, shuffle=False)
    rng = np.random.default_rng(seed + 1)
    pairs = _pairs(k)
    P = len(pairs)
    valid = np.arange(n_b)[None, :] < np.asarray(counts)[:, None]
    X_s = np.zeros((k * n_b, X.shape[1]), np.float32)
    X_s[valid.reshape(-1)] = X           # pads: rows of zeros
    K = _kernel(jnp.asarray(X_s), jnp.asarray(X_s), "rbf", 0.3, 3.0, 0.0)
    w = (rng.random((FOLDS, k, n_b)) > 0.3) * valid      # fold masks
    sign = np.asarray([1.0, -1.0], np.float32)
    yb_c = np.broadcast_to(
        (valid[pairs] * sign[None, :, None])[None],
        (FOLDS, P, 2, n_b)).reshape(FOLDS * P, 2 * n_b)
    bound_c = w[:, pairs, :].reshape(FOLDS * P, 2 * n_b)
    yb_d = np.zeros((FOLDS, P, k, n_b), np.float32)
    bound_d = np.zeros((FOLDS, P, k, n_b), np.float32)
    for p, (i, j) in enumerate(pairs):
        yb_d[:, p, i], yb_d[:, p, j] = valid[i], -1.0 * valid[j]
        bound_d[:, p, i], bound_d[:, p, j] = w[:, i], w[:, j]
    dense = (jnp.asarray(yb_d.reshape(FOLDS * P, -1)),
             jnp.asarray(bound_d.reshape(FOLDS * P, -1)))
    compact = (jnp.asarray(yb_c.astype(np.float32)),
               jnp.asarray(bound_c.astype(np.float32)))
    step = _power_step(K, n, jnp.float32, centred=True,
                       start=_power_start(k * n_b, jnp.float32)
                       * valid.reshape(-1),
                       valid=jnp.asarray(valid.reshape(-1), jnp.float32))
    op = _BlockKernel(K, pairs, FOLDS, n_b)
    return K, op, dense, compact, step


COUNTS = {"k3": (30, 30, 30), "k4": (31, 31, 31, 31), "k10": (16,) * 10,
          "unequal": (40, 36, 32)}


@pytest.mark.parametrize("case", sorted(COUNTS))
def test_block_product_is_the_dense_product(case):
    """`own` is the dense product on each dual's two blocks, `all` on
    every column, `restrict` takes the one out of the other."""
    K, op, (yb_d, _), (yb_c, _), _ = _dual_inputs(COUNTS[case])
    rng = np.random.default_rng(3)
    V_c = jnp.asarray(rng.standard_normal(yb_c.shape), jnp.float32) * \
        jnp.abs(yb_c)
    # the same values as dense rows: zero outside the dual's blocks
    k, n_b, P = op.k, op.n_b, op.P
    V_d = np.zeros((FOLDS, P, k, n_b), np.float32)
    blocks = np.asarray(V_c).reshape(FOLDS, P, 2, n_b)
    for p, (i, j) in enumerate(op.pairs):
        V_d[:, p, i], V_d[:, p, j] = blocks[:, p, 0], blocks[:, p, 1]
    want = V_d.reshape(FOLDS * P, -1) @ np.asarray(K)
    np.testing.assert_allclose(op.all(V_c), want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(op.own(V_c), op.restrict(jnp.asarray(want)),
                               rtol=0, atol=2e-5)
    assert op.own(V_c).shape == V_c.shape


@pytest.mark.parametrize("case", sorted(COUNTS))
def test_svc_dual_compact_equals_dense(case):
    K, op, (yb_d, bound_d), (yb_c, bound_c), step = _dual_inputs(
        COUNTS[case])
    A_d, b_d, it_d = fista_dual_ascent(K, yb_d, 2.0 * bound_d, step, 300,
                                       1e-3)
    A_c, b_c, it_c = fista_dual_ascent(op, yb_c, 2.0 * bound_c, step, 300,
                                       1e-3)
    assert int(it_c) == int(it_d) < 300          # tol ended both
    np.testing.assert_allclose(A_c, op.restrict(A_d), rtol=0, atol=2e-5)
    np.testing.assert_allclose(b_c, b_d, rtol=0, atol=2e-5)
    # nothing outside a dual's own blocks
    assert float(jnp.abs(A_d).sum()) == pytest.approx(
        float(jnp.abs(A_c).sum()), rel=1e-5)


@pytest.mark.parametrize("case", ["k3", "unequal"])
def test_nu_dual_compact_equals_dense(case):
    K, op, (yb_d, bound_d), (yb_c, bound_c), step = _dual_inputs(
        COUNTS[case])
    dec_d, it_d = nu_dual_ascent(K, yb_d, bound_d, 0.4, step, 300, 1e-3)
    dec_c, it_c = nu_dual_ascent(op, yb_c, bound_c, 0.4, step, 300, 1e-3)
    assert int(it_c) == int(it_d)
    assert np.isfinite(np.asarray(dec_d)).all()
    np.testing.assert_allclose(dec_c, dec_d, rtol=0, atol=5e-5)


def test_pads_do_not_move_the_power_step():
    counts = (40, 36, 32)
    n, n_b = sum(counts), 40
    X, _ = _data(counts, shuffle=False)
    K = _kernel(jnp.asarray(X), jnp.asarray(X), "rbf", 0.3, 3.0, 0.0)
    want = float(_power_step(K, n, jnp.float32, centred=True))
    valid = (np.arange(n_b)[None, :]
             < np.asarray(counts)[:, None]).reshape(-1)
    # pads as the launch has them: copies of a real row, so a block of
    # identical rows that the mask has to keep out
    X_s = np.repeat(X[:1], len(valid), axis=0)
    X_s[valid] = X
    start = np.zeros(len(valid), np.float32)
    start[valid] = np.asarray(_power_start(n, jnp.float32))
    K_s = _kernel(jnp.asarray(X_s), jnp.asarray(X_s), "rbf", 0.3, 3.0, 0.0)
    got = float(_power_step(
        K_s, n, jnp.float32, centred=True, start=jnp.asarray(start),
        valid=jnp.asarray(valid, jnp.float32)))
    assert got == pytest.approx(want, rel=1e-6)
    # and the test can see a pad: unmasked, the same matrix reads another
    unmasked = float(_power_step(K_s, len(valid), jnp.float32,
                                 centred=True, start=jnp.asarray(start)))
    assert abs(unmasked - want) > 1e-3 * want


# --- the task-batched fit, both layouts ------------------------------------

def _fold_of(n):
    return np.random.default_rng(1).integers(0, FOLDS, n)


def _fit(family, X, y, compact, static=None, cands=(0.5, 5.0),
         fold_of=None):
    data, meta = family.prepare_data(X, y)
    if not compact:
        meta = {k: v for k, v in meta.items() if k != "class_counts"}
    n = len(y)
    assert (_block_rows(meta, n) is not None) == compact
    if fold_of is None:
        fold_of = _fold_of(n)
    w = np.tile(np.stack([(fold_of != f).astype(np.float32)
                          for f in range(FOLDS)]), (len(cands), 1))
    dyn = {family.primary_param:
           jnp.asarray(np.repeat(cands, FOLDS), jnp.float32),
           "gamma": jnp.full((len(cands) * FOLDS,), 0.3, jnp.float32)}
    st = {"kernel": "rbf", "__n_folds__": FOLDS, **(static or {})}
    model = jax.jit(lambda dyn, data, w: family.fit_task_batched(
        dyn, st, data, w, meta))(
        dyn, {k: jnp.asarray(v) for k, v in data.items()}, jnp.asarray(w))
    pred = jax.vmap(lambda m: family.predict(m, st, None, meta))(
        {"pair_dec": model["pair_dec"]})
    return np.asarray(model["pair_dec"]), np.asarray(model["n_iter"]), \
        np.asarray(pred)


FITS = {
    "k3": (SVCFamily, (30, 30, 30), None, (0.5, 5.0)),
    "k4": (SVCFamily, (31, 31, 31, 31), None, (0.5, 5.0)),
    "k10": (SVCFamily, (16,) * 10, None, (0.5, 5.0)),
    "unequal": (SVCFamily, (40, 36, 32), None, (0.5, 5.0)),
    "balanced_weights": (SVCFamily, (40, 36, 32),
                         {"class_weight": "balanced"}, (0.5, 5.0)),
    "nusvc": (NuSVCFamily, (30, 30, 30), None, (0.3, 0.5)),
}


@pytest.mark.parametrize("case", sorted(FITS))
def test_fit_compact_equals_dense(case):
    family, counts, static, cands = FITS[case]
    X, y = _data(counts)
    dec_c, it_c, pred_c = _fit(family, X, y, True, static, cands)
    dec_d, it_d, pred_d = _fit(family, X, y, False, static, cands)
    assert dec_c.shape == dec_d.shape == (
        len(cands) * FOLDS, len(y), len(_pairs(len(counts))))
    # the exit is a threshold on a residual that differs in its last bits
    assert np.abs(it_c - it_d).max() <= 3 and it_d.max() < 300
    np.testing.assert_allclose(dec_c, dec_d, rtol=0, atol=2e-3)
    # the same count of iterations: equal to rounding
    fixed = {**(static or {}), "max_iter": 60, "tol": 0.0}
    dec_c, it_c, pred_c = _fit(family, X, y, True, fixed, cands)
    dec_d, it_d, pred_d = _fit(family, X, y, False, fixed, cands)
    assert it_c.tolist() == it_d.tolist() == [60] * len(it_d)
    np.testing.assert_allclose(dec_c, dec_d, rtol=0, atol=1e-4)
    # a decision within rounding of zero may fall on either side
    near = (np.abs(dec_d) < 1e-4).any(axis=2)
    assert ((pred_c == pred_d) | near).all()
    assert (pred_c == pred_d).mean() > 0.999


def test_pair_dec_comes_back_in_the_callers_row_order():
    """Shuffling the rows shuffles the decisions with them: the class
    sort and its pads are the launch's own business."""
    X, y = _data((40, 36, 32))
    perm = np.random.default_rng(7).permutation(len(y))
    dec, _, _ = _fit(SVCFamily, X, y, True)
    shuffled, _, _ = _fit(SVCFamily, X[perm], y[perm], True,
                          fold_of=_fold_of(len(y))[perm])
    np.testing.assert_allclose(shuffled, dec[:, perm], rtol=0, atol=1e-4)


# --- what the report says --------------------------------------------------

@pytest.mark.parametrize("counts, want", [
    ((30, 30, 30), 64),            # 2 x n_b, n_b = 32
    ((45, 45), 90),                # binary: dense rows
    ((66, 12, 12), 90)],           # skewed: dense rows
    ids=["balanced", "binary", "skewed"])
def test_dual_rows_per_launch(counts, want):
    from sklearn.svm import SVC
    X, y = _data(counts)
    rep = sst.GridSearchCV(
        SVC(kernel="rbf"), {"C": [0.5, 5.0]}, cv=3, refit=False,
        backend="tpu").fit(X, y).search_report
    assert rep["dual_rows_per_launch"] == [want] * len(
        rep["lanes_per_launch"])
