"""The plain reference of the boosting cell: what one (candidate, fold) fit of
``GradientBoostingClassifier`` on two classes has to answer under the
configuration's written rule, from nothing of the program under test.

It imports nothing of ``spark_sklearn_tpu`` and takes nothing the program
made: the forest reference's binning (``reference_forest.bin_features``: the
two configurations share the codes table), its own histograms, its own
routing.  One fold and
one learning rate at a time, a stage at a time, a level at a time; no
kernel, no lanes.  ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, and not numpy on the host: a
stage's three levels are histograms of 116 202 rows x 54 features x 2
statistics, a tenth of a second of ``numpy.bincount`` each, and the 1 500
trees of a run's comparison (three 100-stage runs x 5 folds) would be
minutes of the host; as ONE product a level of the rows' 0/1 masks ``code <=
bin`` with their statistics, in blocks of rows, they are about a minute of
the chip the run already holds.  At ``highest`` a float32 product on a TPU
is exact in each term (the mask is 0 or 1), so a sum differs from the
program's in the order of its float32 additions only.

The model, as the configuration states it (``guarantees``, ``assumed``):

- **bins**: a feature's 255 edges are its ``k / 256`` quantiles over ALL
  rows of the data set (``numpy.quantile(..., method="lower")``), a row's
  code is ``searchsorted(edges, x, side="right")``, 0..255;
- **loss**: scikit-learn's half-binomial loss on the raw score F, the
  log-odds of class 1: ``p = sigmoid(F)``, a row's gradient ``g = p - y``
  and hessian ``h = p (1 - p)``; ``F0 = log(q / (1 - q))`` with q the
  fold's training share of class 1, clipped to [1e-6, 1 - 1e-6];
- **stage** t = 0 .. n_estimators - 1: ONE tree on (g, h) at the current
  F, over the fold's training rows (weight 1; with ``subsample`` < 1 times
  ``jax.random.uniform(k_t, (n,)) < subsample`` over all n rows, ``k_t =
  jax.random.split(PRNGKey(random_state), T)[t]``, T the grid's largest
  count; unused at the default 1.0), then ``F += learning_rate * leaf`` on
  every row, the fold's test rows too (they are routed, and count for
  nothing);
- **tree**: level by level to ``max_depth`` (3).  For a node, a feature f
  and a bin b < 255, left = the node's counted rows with code <= b, right
  the others; with (G, H) the sums of g and h, ``gain = GL^2 / (HL + 1e-6)
  + GR^2 / (HR + 1e-6) - G^2 / (H + 1e-6)``, only where HL >= 1 and HR >= 1
  (``min_samples_leaf`` 1, held on the hessians' weight).  The split is the
  first largest gain in (feature, bin) order over EVERY feature; a node
  splits where that gain exceeds 1e-7 and is a leaf otherwise; a row goes
  right where its code exceeds b.  **leaf** = ``-G / (H + 1e-6)``: one
  Newton step;
- **prediction**: class 1 where F > 0.

Departures from scikit-learn's ``GradientBoostingClassifier``, each also in
the configuration's ``guarantees``:

1. 256 quantile bins a feature where scikit-learn scans every distinct
   value for the exact split;
2. the second-order gain above over (g, h), where scikit-learn fits a
   ``friedman_mse`` regression tree to the residuals ``y - p`` and then
   replaces each leaf by one Newton step ``sum(y - p) / sum(p (1 - p))``:
   the leaf values agree, the chosen splits may not;
3. ``min_samples_leaf`` bounds a side's hessian weight, not its row count;
4. ``subsample`` < 1 follows the ``jax.random`` rule above, not numpy's
   ``RandomState``.

Candidates of one learning rate are ONE run read at their counts: stage t
does not depend on the count.

The control of the comparison is not this file in a lower precision (the
trees' precision lives in how the program multiplies its statistics): it is
the program itself with a row's statistics in ONE bfloat16 part
(``benchmark/tests/faults_at_size_boost.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference_forest import N_BINS, bin_features   # the same codes table

LAMBDA = 1e-6
MIN_GAIN = 1e-7
#: rows a product takes at a time: the 8 192 x 54 x 256 float32 masks are
#: 453 MB
ROW_BLOCK = 8192
DEFAULTS = {"n_estimators": 100, "learning_rate": 0.1, "max_depth": 3,
            "subsample": 1.0, "min_samples_leaf": 1, "random_state": None,
            "loss": "log_loss", "criterion": "friedman_mse",
            "max_features": None, "init": None}


def settings_of(config, candidate):
    out = dict(DEFAULTS)
    out.update(config["estimator"]["params"])
    out.update(candidate)
    if (out["loss"], out["criterion"], out["max_features"], out["init"],
            out["min_samples_leaf"]) != ("log_loss", "friedman_mse", None,
                                         None, 1) \
            or out["max_depth"] is None:
        raise ValueError(f"the reference states no rule for {out}")
    return out


def stage_keys(random_state, n_stages):
    key = jax.random.PRNGKey(0 if random_state is None else int(random_state))
    return jax.random.split(key, n_stages)


def left_sums(codes, sums_of):
    """``(d, N_BINS, k)``: for every feature f and bin b the sum of the
    ``sums_of (n, k)`` columns over the rows with ``code[f] <= b``, the left
    side of a split there: one product a block of rows of the 0/1 masks
    ``code <= bin`` with the columns.  Two (feature, bin) that cut a node's
    rows alike have equal masks, so equal sums to the bit, and the first of
    them stays the first largest."""
    (n, d), k = codes.shape, sums_of.shape[1]
    block = min(ROW_BLOCK, n)
    pad = -n % block
    codes = jnp.pad(codes, ((0, pad), (0, 0))).reshape(-1, block, d)
    sums_of = jnp.pad(sums_of, ((0, pad), (0, 0))).reshape(-1, block, k)
    bins = jnp.arange(N_BINS, dtype=jnp.int32)

    def add_block(left, rows):
        block_codes, block_sums = rows
        mask = (block_codes.astype(jnp.int32)[:, :, None] <= bins).astype(
            jnp.float32)                            # (rows, d, bins)
        return left + jnp.einsum("rfb,rk->fbk", mask, block_sums), None

    left, _ = jax.lax.scan(
        add_block, jnp.zeros((d, N_BINS, k), jnp.float32), (codes, sums_of))
    return left


def grow_tree(codes, g, h, w, depth):
    """One tree on the rows of weight > 0.  Returns every row's leaf value
    ``(n,)`` (rows of weight 0 are routed too) and the tree's splits,
    ``(2^depth - 1,)`` feature (-1: a leaf, or never reached) and bin in
    heap order (the children of node i are 2 i + 1 and 2 i + 2)."""
    n, d = codes.shape
    node = jnp.zeros((n,), jnp.int32)               # heap ids
    frozen = jnp.zeros((n,), bool)                  # the row sits in a leaf
    counted = w > 0
    stats = jnp.stack([g * w, h * w], axis=1)       # (n, 2)
    features, bins = [], []
    for level in range(depth):
        n_nodes, offset = 2 ** level, 2 ** level - 1
        local = jnp.clip(node - offset, 0, n_nodes - 1)
        mine = jax.nn.one_hot(local, n_nodes, dtype=jnp.float32) * (
            counted & ~frozen)[:, None]             # (n, nodes)
        left = left_sums(
            codes, (mine[:, :, None] * stats[:, None, :]).reshape(n, -1)
        ).reshape(d, N_BINS, n_nodes, 2).transpose(2, 0, 1, 3)
        total = left[:, :1, -1:, :]                 # any feature's last bin
        right = total - left
        gl, hl, gr, hr = (left[..., 0], left[..., 1],
                          right[..., 0], right[..., 1])
        gain = (gl * gl / (hl + LAMBDA) + gr * gr / (hr + LAMBDA)
                - total[..., 0] ** 2 / (total[..., 1] + LAMBDA))
        gain = jnp.where((hl >= 1.0) & (hr >= 1.0), gain, -jnp.inf)
        gain = gain.at[:, :, -1].set(-jnp.inf)
        flat = gain.reshape(n_nodes, d * N_BINS)
        best = jnp.argmax(flat, axis=1)             # the first largest
        splits = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0] \
            > MIN_GAIN
        feature = (best // N_BINS).astype(jnp.int32)
        cut = (best % N_BINS).astype(jnp.int32)
        features.append(jnp.where(splits, feature, -1))
        bins.append(cut)
        code_at = jnp.take_along_axis(
            codes, feature[local][:, None], axis=1)[:, 0].astype(jnp.int32)
        moves = splits[local] & ~frozen
        node = jnp.where(
            moves, 2 * node + 1 + (code_at > cut[local]).astype(jnp.int32),
            node)
        frozen = frozen | ~splits[local]
    n_all = 2 ** (depth + 1) - 1
    sums = (jax.nn.one_hot(node, n_all, dtype=jnp.float32)
            * counted[:, None]).T @ stats           # (nodes, 2)
    value = -sums[:, 0] / (sums[:, 1] + LAMBDA)
    return value[node], jnp.concatenate(features), jnp.concatenate(bins)


@functools.partial(jax.jit, static_argnames=("depth", "n_stages"))
def boost(codes, y, w, learning_rate, subsample, keys, counts, *, depth,
          n_stages):
    """One fold's run at one learning rate: ``(len(counts), n)`` raw scores
    F after each of ``counts`` stages, F after the last stage, and every
    stage's splits ``(n_stages, 2^depth - 1)`` feature and bin."""
    n = codes.shape[0]
    y = y.astype(jnp.float32)
    share = jnp.clip(jnp.sum(w * y) / jnp.sum(w), 1e-6, 1 - 1e-6)
    F = jnp.full((n,), jnp.log(share / (1.0 - share)), jnp.float32)

    def stage(carry, t):
        F, read = carry
        p = jax.nn.sigmoid(F)
        w_t = w * (jax.random.uniform(keys[t], (n,)) < subsample)
        leaf, feature, cut = grow_tree(codes, p - y, p * (1.0 - p), w_t,
                                       depth)
        F = F + learning_rate * leaf
        read = jnp.where((counts == t + 1)[:, None], F[None, :], read)
        return (F, read), (feature, cut)

    with jax.default_matmul_precision("highest"):
        (F, read), (features, cuts) = jax.lax.scan(
            stage, (F, jnp.zeros((counts.shape[0], n), jnp.float32)),
            jnp.arange(n_stages))
    return read, F, features, cuts


def boost_cv_scores(X, y, splits, candidates, config):
    """``(len(candidates), n_folds)`` test accuracies and the stages run.
    Candidates of one learning rate are one run a fold, to the largest of
    their counts, read at each."""
    y = np.asarray(y)
    classes, y_enc = np.unique(y, return_inverse=True)
    if len(classes) != 2:
        raise ValueError("the reference states the two-class model only")
    codes = jnp.asarray(bin_features(X))
    labels = jnp.asarray(y_enc, jnp.int32)
    n = codes.shape[0]
    settings = [settings_of(config, c) for c in candidates]
    runs = {}
    for j, s in enumerate(settings):
        rest = tuple(sorted((k, v) for k, v in s.items()
                            if k != "n_estimators"))
        runs.setdefault(rest, []).append(j)
    # the stage keys follow the GRID's largest count, which a block of the
    # sample may not hold: under jax's partitionable threefry
    # split(key, T)[t] does not depend on T > t
    scores = np.empty((len(candidates), len(splits)))
    stages = 0
    for rest, members in runs.items():
        s = dict(rest)
        counts = np.asarray([settings[j]["n_estimators"] for j in members],
                            np.int32)
        n_stages = int(counts.max())
        keys = stage_keys(s["random_state"], n_stages)
        for f, (train, test) in enumerate(splits):
            in_fold = np.zeros(n, np.float32)
            in_fold[train] = 1.0
            read, _, _, _ = boost(
                codes, labels, jnp.asarray(in_fold),
                np.float32(s["learning_rate"]), np.float32(s["subsample"]),
                keys, jnp.asarray(counts), depth=int(s["max_depth"]),
                n_stages=n_stages)
            predicted = np.asarray(read)[:, test] > 0
            scores[members, f] = np.mean(predicted == y_enc[test][None, :],
                                         axis=1)
            stages += n_stages
    return scores, stages
