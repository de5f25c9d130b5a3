"""Binned, level-wise decision-tree growth as fixed-shape XLA programs.

The reference runs sklearn's exact-split CART (Cython, per-node sorted
scans) inside Spark tasks.  Exact splitting is a data-dependent, pointer-
chasing algorithm with no MXU mapping, so the TPU redesign uses the
histogram method every modern GBDT uses (LightGBM/XGBoost-style), which is
all segment-sums and cumulative sums over fixed shapes:

  - features are pre-binned host-side to uint8 codes (native quantile_bin,
    see native/tpusk_native.cpp);
  - a tree grows level-by-level (static python loop over max_depth): the
    (node, feature, bin) gradient/hessian histograms of the whole level,
    cumulative over the bins, are the left/right split statistics
    (ops/tree_hist.py: a grouped one-hot product kernel on a TPU, one
    `segment_sum` a statistic and a cumsum elsewhere), and the best
    (feature, bin) per node is an argmax — no per-node control flow;
  - where a node may split on a random subset of the features only (a
    forest's `max_features`), the subsets of a level are drawn BEFORE its
    histograms, which are built at each node's own features and no
    others: the feature axis of everything a level computes is then the
    node's slot, `max_features` wide instead of `d`;
  - nodes live in a heap-indexed array (children of i at 2i+1/2i+2) so the
    tree is a pytree of fixed arrays: feat, thresh_bin, leaf flag, value.

Leaf values are Newton steps -G/(H+lambda) (squared loss: mean residual),
which reproduces sklearn's mean-of-leaf behavior for regression and the
one-hot-target trick approximates gini for classification forests.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from spark_sklearn_tpu.ops import tree_hist


class Tree(NamedTuple):
    feat: jnp.ndarray        # (max_nodes,) int32, -1 = leaf/unused
    thresh: jnp.ndarray      # (max_nodes,) int32 bin threshold (go left if
                             # code <= thresh)
    value: jnp.ndarray       # (max_nodes, n_out) leaf values
    is_leaf: jnp.ndarray     # (max_nodes,) bool
    leaf: jnp.ndarray        # (n,) int32: the node each grown-on row ends
                             # in, so value[leaf] is the tree's prediction
                             # for its own rows without a second walk


def feature_subsets(key, level, n_nodes, d, max_features):
    """``(n_nodes, max_features)`` int32: each node's own features of a
    level, ascending.  A node takes the features whose score
    ``uniform(fold_in(key, level), (n_nodes, d))[node]`` is no larger than
    its ``max_features``-th smallest, and of those (more than
    ``max_features`` only where two float32 scores tie there) the first
    ``max_features`` in feature order."""
    scores = jax.random.uniform(jax.random.fold_in(key, level),
                                (n_nodes, d))
    kth = jnp.sort(scores, axis=1)[:, max_features - 1][:, None]
    chosen = scores <= kth
    # a chosen feature's place among the node's chosen: its slot
    place = jnp.cumsum(chosen, axis=1, dtype=jnp.int32) - 1
    slot = jnp.arange(max_features, dtype=jnp.int32)
    feature = jnp.arange(d, dtype=jnp.int32)
    return jnp.sum(
        jnp.where(chosen[:, None, :]
                  & (place[:, None, :] == slot[None, :, None]),
                  feature[None, None, :], 0), axis=2)


def grow_tree(codes, g, h, w, max_depth, n_bins, min_child_weight=1e-3,
              reg_lambda=1.0, feat_mask_key=None, max_features=None,
              n_out=1, integer_stats=False, order=None):
    """Grow one tree on binned features.

    codes: (n, d) uint8 (or int32) bin codes.  g/h: (n, n_out)/(n,)
    gradient & hessian per sample (hessian shared across outputs).  w: (n,)
    sample weights (0 excludes — CV fold masks and bootstrap weights both
    enter here).  `integer_stats`: the caller's word that every g * w and
    h * w is an integer of at most 256 in size (a forest's bootstrap count
    x one-hot class), which one bfloat16 part holds exactly.  Returns a
    Tree whose value column holds the Newton leaf step per output.

    `order`: ``tree_hist.row_order(codes, w)`` made once for every tree
    of a loop whose `w` is the same (a booster's stages at `subsample`
    1.0).  g, h and w then come in that order, and the tree's `leaf`
    goes out in it; `codes` stay the caller's.

    With `feat_mask_key` and `max_features < d`, a node splits on one of
    its own `max_features` features, a fresh subset a node and level
    (`feature_subsets`: the forest analog of sklearn's per-split
    max_features), and a level's histograms are built at those and no
    others: their feature axis is the node's slot.  Slots ascend by
    feature, so the first largest gain in (slot, bin) order is the first
    in (feature, bin) order over the subset.  Otherwise a slot is a
    feature.

    A level's cumulative (node, slot, bin) histograms and the rows'
    way down come from ``ops/tree_hist.py`` (the grouped kernels on a TPU,
    the plain ``segment_sum`` form elsewhere); every node's sums, and with
    them the leaf values, are read off the histograms (the root's totals,
    then each split's left and right side), so that no row is scattered.
    """
    d = codes.shape[1]
    n_last = 2 ** max_depth                   # nodes below the last level
    feat, thresh, is_leaf = [], [], []        # a level's (nodes,) each
    subset = (feat_mask_key is not None and max_features is not None
              and max_features < d)
    n_slots = max_features if subset else d

    # the statistics of a row: its hessian, then a gradient an output
    stats = jnp.concatenate([(h * w)[:, None], g * w[:, None]],
                            axis=1).astype(jnp.float32)   # (n, 1 + n_out)
    rows = tree_hist.levels_of(codes, stats, n_bins, integer_stats,
                               order=order)
    sums = []                                 # a level's (nodes, 1 + n_out)

    for level in range(max_depth):
        n_nodes = 2 ** level
        sel = None
        if subset:
            with jax.named_scope("sst.tree.split"):
                sel = feature_subsets(feat_mask_key, level, n_nodes, d,
                                      max_features)
        # (nodes, slots, 1 + n_out, bins), or wider in slots and
        # statistics where the kernel pads them: the padding reads zero
        cum = rows.histograms(level, sel)
        wide = cum.shape[1]

        with jax.named_scope("sst.tree.split"):
            # a node's sums are known before its histograms: the root's
            # from its own last bin, a child's from its parent's split
            if level == 0:
                sums.append(cum[:, 0, :1 + n_out, -1])
            whole = jnp.pad(
                sums[-1], ((0, 0), (0, cum.shape[2] - 1 - n_out))
            )[:, None, :, None]                             # (nodes,1,S,1)
            # every statistic's left, right and whole in one pass over
            # the histograms (a statistic is a row of every (8, bins)
            # tile: a slice a statistic would walk the array each time)
            rest = whole - cum
            left_h, tot_h = cum[:, :, :1, :], whole[:, :, :1, :]
            right_h = tot_h - left_h
            terms = (cum ** 2 / (left_h + reg_lambda)
                     + rest ** 2 / (right_h + reg_lambda)
                     - whole ** 2 / (tot_h + reg_lambda))
            # gain summed over outputs (multi-output = one-hot targets:
            # the sum is the full variance-reduction criterion, not just
            # class 0's), in their order; row 0 is the hessian's own
            stat = jnp.arange(cum.shape[2])
            is_output = (stat >= 1) & (stat <= n_out)
            gain = jnp.sum(
                jnp.where(is_output[None, None, :, None], terms, 0.0),
                axis=2)
            left_h, right_h = left_h[:, :, 0, :], right_h[:, :, 0, :]
            # no split with too little on a side
            ok = (left_h >= min_child_weight) & (right_h >= min_child_weight)
            if wide > n_slots:      # nor at a slot of the padding
                ok = ok & (jnp.arange(wide) < n_slots)[None, :, None]
            gain = jnp.where(ok, gain, -jnp.inf)
            # never split on the last bin (empty right side by
            # construction)
            gain = gain.at[..., -1].set(-jnp.inf)

            flat_gain = gain.reshape(n_nodes, wide * n_bins)
            best = jnp.argmax(flat_gain, axis=1)            # (n_nodes,)
            best_gain = jnp.take_along_axis(
                flat_gain, best[:, None], axis=1)[:, 0]
            slot = (best // n_bins).astype(jnp.int32)
            bb = (best % n_bins).astype(jnp.int32)
            bf = slot if sel is None else jnp.take_along_axis(
                sel, slot[:, None], axis=1)[:, 0]
            do_split = best_gain > 1e-7

            feat.append(jnp.where(do_split, bf, -1))
            thresh.append(bb)
            is_leaf.append(jnp.logical_not(do_split))

            # the sums of the children a split makes: left at the chosen
            # (slot, bin), right the rest of the node's
            total = sums[-1]
            left = jnp.take_along_axis(
                jnp.take_along_axis(
                    cum, slot[:, None, None, None], axis=1)[:, 0],
                bb[:, None, None], axis=2)[:, :1 + n_out, 0]  # (nodes, S)
            sums.append(jnp.stack([left, total - left], axis=1).reshape(
                2 * n_nodes, -1))

        rows.route(level, bf, bb, do_split)

    with jax.named_scope("sst.tree.split"):
        # whatever sits below the last level is a leaf
        feat = jnp.concatenate(feat + [jnp.full((n_last,), -1, jnp.int32)])
        thresh = jnp.concatenate(thresh + [jnp.zeros((n_last,), jnp.int32)])
        is_leaf = jnp.concatenate(is_leaf + [jnp.ones((n_last,), bool)])
        # leaf values: Newton step per output from the node's sums
        node_sums = jnp.concatenate(sums, axis=0)           # (max_nodes, S)
        value = -node_sums[:, 1:] / (node_sums[:, :1] + reg_lambda)
    return Tree(feat=feat, thresh=thresh, value=value, is_leaf=is_leaf,
                leaf=rows.leaves())
