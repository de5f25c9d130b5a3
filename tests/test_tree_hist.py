"""The level-histogram and routing kernels (``ops/tree_hist.py``) against
the plain ``segment_sum`` form they replace on a TPU, on XLA:CPU in
interpret mode: bit for bit on integer statistics (a forest's bootstrap
count x one-hot class), to float32 rounding on the boosters' gradients,
over levels on and between the sorts, outputs, zero-weight rows, a node
with no rows and lanes under ``vmap``; with each node's own feature subset
``sel`` both forms build the every-feature histograms read at ``sel``; the
grower on either form grows the same tree, by the subset path the tree of
every feature with the gains masked; the family's price of a lane's
histograms is what the kernel allocates."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_sklearn_tpu.models import trees as tree_models
from spark_sklearn_tpu.ops import tree_hist
from spark_sklearn_tpu.ops.trees import grow_tree

N_BINS = 256


def subsets(n_nodes, d, slots, seed):
    """``(n_nodes, slots)`` int32: a subset of the features a node,
    ascending."""
    rng = np.random.default_rng(seed)
    return np.sort(np.stack([rng.permutation(d)[:slots]
                             for _ in range(n_nodes)]), axis=1).astype(
        np.int32)


def rows(n, d, n_out, n_nodes, seed, integer, zero_share=0.4,
         empty_node=True):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, N_BINS, (n, d), dtype=np.uint8)
    if integer:
        w = rng.poisson(1.0, n).astype(np.float32)
        w *= rng.random(n) >= zero_share          # the fold's test rows
        y = rng.integers(0, n_out, n)
        stats = np.concatenate(
            [w[:, None], -w[:, None] * np.eye(n_out, dtype=np.float32)[y]],
            axis=1)
    else:
        stats = rng.standard_normal((n, 1 + n_out)).astype(np.float32)
        stats *= (rng.random((n, 1)) >= zero_share)
    local = rng.integers(0, n_nodes, n).astype(np.int32)
    if empty_node and n_nodes > 2:
        local[local == n_nodes // 2] = 0
    live = rng.random(n) < 0.8
    return codes, stats, local, live


def both_forms(codes, stats, local, live, level, tile, integer,
               splits=None, sel=None):
    """A level's cumulative histograms from the plain form and from the
    kernels (padding cut off), with every row set down in node `local` of
    the level, of every feature or at each node's own `sel`; with `splits`
    (feature, bin, whether) a node, also where each row goes from there:
    (node, still on its way) a form."""
    l0 = level - level % tree_hist.LEVELS_PER_SORT
    offset, n_stats = 2 ** level - 1, stats.shape[1]
    d = codes.shape[1] if sel is None else sel.shape[1]
    if sel is not None:
        sel = jnp.asarray(sel)

    @jax.jit
    def run(codes, stats, local, live):
        plain = tree_hist.PlainLevels(codes, stats, N_BINS)
        plain.node, plain.frozen = offset + local, jnp.logical_not(live)
        rows = tree_hist.GroupedLevels(codes, stats, N_BINS, integer,
                                       tile=tile, interpret=True)
        # the rows sorted at the last sorting level above, then each in
        # its node of this level
        rows.heap = (2 ** l0 - 1) + (local >> (level - l0))
        rows.live = jnp.where(live, rows.live, 0)
        rows._sort(l0)
        rows.heap = offset + local[rows.perm]
        out = [plain.histograms(level, sel),
               rows.histograms(level, sel)[:, :d, :n_stats]]
        if splits is not None:
            plain.route(level, *splits)
            rows.route(level, *splits)
            on_way = jax.lax.sort((rows.perm, rows.live), num_keys=1)[1]
            out += [plain.node, jnp.logical_not(plain.frozen),
                    rows.leaves(), on_way > 0]
        return out

    return [np.asarray(a) for a in run(codes, stats, local, live)]


def plain_sums(codes, stats, local, live, n_nodes):
    return np.cumsum(np.asarray(tree_hist.plain_level_histograms(
        jnp.asarray(codes), jnp.asarray(stats), jnp.asarray(local),
        jnp.asarray(live), n_nodes, N_BINS)), axis=3)


def at_subsets(hists, sel):
    """``(nodes, d, S, bins)`` every-feature histograms read at each
    node's own features: ``(nodes, slots, S, bins)``."""
    return np.take_along_axis(hists, sel[:, :, None, None], axis=1)


@pytest.mark.parametrize("n,d,n_out,level,tile,slots", [
    (700, 5, 3, 0, 128, None),    # the root: one node, every row
    (700, 5, 3, 2, 128, None),
    (1000, 54, 7, 3, 256, None),  # the covtype cell's widths
    (900, 70, 2, 4, 128, None),   # three feature blocks, one group of 16
    (1200, 9, 1, 5, 128, None),   # a sorted level: two groups
    (1500, 6, 4, 7, 512, None),   # two levels below a sort: 8 groups
    (2000, 3, 7, 9, 128, None),   # four levels below: 32 groups of 16
    # each node's own subset: the slot is the kernel's feature axis
    (700, 5, 3, 0, 128, 2),       # the root's
    (1000, 54, 7, 3, 256, 7),     # covtype's 7 of 54: no multiple of 32
    (1200, 9, 1, 5, 128, 3),      # a sorted level
    (1500, 6, 4, 7, 512, 2),      # 8 groups of 16 subsets
    (900, 54, 7, 2, 512, 7),      # two tiles, each with every node's rows
    (900, 70, 2, 4, 128, 40),     # more slots than a block: two blocks
    (2000, 12, 7, 9, 128, 11),    # all but one feature, 32 groups
])
def test_integer_statistics_bit_for_bit(n, d, n_out, level, tile, slots):
    n_nodes = 2 ** level
    codes, stats, local, live = rows(n, d, n_out, n_nodes, n + d, True)
    sel = None if slots is None else subsets(n_nodes, d, slots, level)
    plain, kernel = both_forms(codes, stats, local, live, level, tile, True,
                               sel=sel)
    held = d if slots is None else slots
    assert plain.shape == kernel.shape == (n_nodes, held, 1 + n_out, N_BINS)
    assert np.array_equal(plain, kernel)
    every = plain_sums(codes, stats, local, live, n_nodes)
    assert np.array_equal(
        plain, every if sel is None else at_subsets(every, sel))
    assert kernel[:, :, 0, -1].sum() == stats[live, 0].sum() * held
    if n_nodes > 2:
        assert not kernel[n_nodes // 2].any()     # the node with no rows


@pytest.mark.parametrize("n,d,n_out,level,tile,slots", [
    (900, 10, 1, 1, 128, None),   # a boosting stage: hessian and gradient
    (900, 40, 1, 3, 256, None),
    (1200, 3, 5, 6, 128, None),
    (900, 40, 1, 3, 256, 6),      # a regressor forest's subsets
    (1200, 10, 1, 6, 128, 3),
])
def test_float_statistics_to_float32_rounding(n, d, n_out, level, tile,
                                              slots):
    codes, stats, local, live = rows(n, d, n_out, 2 ** level, n, False)
    sel = None if slots is None else subsets(2 ** level, d, slots, n)
    plain, kernel = both_forms(codes, stats, local, live, level, tile,
                               False, sel=sel)
    # a product is exact (three bfloat16 parts); the sums differ in order
    scale = np.abs(stats).sum(axis=0).max()
    assert np.abs(plain - kernel).max() <= 1e-6 * scale
    if sel is not None:
        every = plain_sums(codes, stats, local, live, 2 ** level)
        assert np.abs(plain - at_subsets(every, sel)).max() <= 1e-6 * scale
    # ... and one part would not do
    _, rough = both_forms(codes, stats, local, live, level, tile, True,
                          sel=sel)
    assert np.abs(plain - rough).max() > 1e-5 * scale


@pytest.mark.parametrize("case", ["all_zero_weight", "none_live",
                                  "one_row", "every_row_one_node"])
def test_edges_of_the_partition(case):
    n, d, n_out, level = 400, 4, 2, 3
    codes, stats, local, live = rows(n, d, n_out, 8, 3, True, 0.0,
                                     empty_node=False)
    if case == "all_zero_weight":
        stats[:] = 0
    elif case == "none_live":
        live[:] = False
    elif case == "one_row":
        live[:] = False
        live[17] = True
        stats[17] = [2, -2, 0]
    else:
        local[:] = 5
    plain, kernel = both_forms(codes, stats, local, live, level, 128, True)
    assert np.array_equal(plain, kernel)


@pytest.mark.parametrize("n,d,level,tile", [
    (700, 5, 0, 128), (900, 40, 3, 256), (1200, 9, 5, 128),
    (2000, 3, 8, 128)])
def test_every_row_goes_to_its_child(n, d, level, tile):
    """The routing kernel against the plain form's gathers: rows that
    count and rows that count for nothing alike, rows in a leaf stay."""
    n_nodes = 2 ** level
    codes, stats, local, live = rows(n, d, 2, n_nodes, n, True)
    rng = np.random.default_rng(level)
    splits = (jnp.asarray(rng.integers(0, d, n_nodes), jnp.int32),
              jnp.asarray(rng.integers(0, N_BINS, n_nodes), jnp.int32),
              jnp.asarray(rng.random(n_nodes) < 0.7))
    _, _, node, on_way, k_node, k_on_way = both_forms(
        codes, stats, local, live, level, tile, True, splits)
    assert np.array_equal(node, k_node)
    assert np.array_equal(on_way, k_on_way)
    moved = node != (n_nodes - 1) + local
    assert moved.any() and np.array_equal(moved, on_way)


@pytest.mark.parametrize("slots", [None, 3])
def test_lanes_of_a_launch_are_one_call(slots):
    """Two nested ``vmap``s (the engine's candidates and folds) reach each
    kernel as one grid axis of their product; the nodes' subsets, the
    same for every lane, are not batched and go to each."""
    n, d, n_out = 500, 6, 3
    codes, stats, _, _ = rows(n, d, n_out, 1, 11, True)
    weights = np.stack([np.roll(stats, 91 * i, axis=0) for i in range(6)])
    sel = None if slots is None else subsets(1, d, slots, 4)
    held = d if slots is None else slots

    def one(s):
        lane = tree_hist.GroupedLevels(codes, s, N_BINS, True, tile=128,
                                       interpret=True)
        return lane.histograms(
            0, None if sel is None else jnp.asarray(sel)
        )[:, :held, :1 + n_out]

    lanes = jax.vmap(jax.vmap(one))
    stacked = weights.reshape(2, 3, n, -1)
    got = np.asarray(jax.jit(lanes)(stacked))
    assert str(jax.make_jaxpr(lanes)(stacked)).count("pallas_call") == 1
    for i in range(6):
        want = plain_sums(codes, weights[i], np.zeros(n, np.int32),
                          np.ones(n, bool), 1)
        if sel is not None:
            want = at_subsets(want, sel)
        assert np.array_equal(got[i // 3, i % 3], want)


@pytest.mark.parametrize("tile,n_groups", [(128, 4), (256, 16), (512, 64)])
def test_items_cover_every_group_once(tile, n_groups):
    rng = np.random.default_rng(tile)
    n = 3000
    counts = rng.multinomial(rng.integers(0, n),
                             np.ones(n_groups) / n_groups)
    counts[rng.integers(0, n_groups)] = 0
    starts = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    n_tiles = -(-n // tile)
    items = np.asarray(tree_hist.group_items(
        jnp.asarray(starts), tile, n_tiles, n_tiles + n_groups))
    _, group, _, _ = items
    assert (np.diff(group) >= 0).all() and set(group) == set(range(n_groups))
    covered = np.zeros(n, int)
    for t, k, a, b in items.T:
        assert 0 <= t < n_tiles and 0 <= a <= tile and 0 <= b <= tile
        if b > a:
            span = slice(t * tile + a, t * tile + b)
            assert starts[k] <= span.start and span.stop <= starts[k + 1]
            covered[span] += 1
    assert (covered[:starts[-1]] == 1).all()
    assert not covered[starts[-1]:].any()


def grow_tree_masked(codes, g, h, w, max_depth, n_bins, min_child_weight,
                     reg_lambda, feat_mask_key, max_features, n_out,
                     integer_stats):
    """The grower as it was before a node's subset was drawn first: EVERY
    feature's histograms of a level, the gain of a feature outside the
    node's subset set to -inf after the fact, the argmax over (feature,
    bin).  The subset is `feature_subsets`' (the first `max_features` of
    the chosen); the root's sums are read at its first own feature, where
    the subset path has them."""
    from spark_sklearn_tpu.ops.trees import Tree, feature_subsets
    d, n_stats = codes.shape[1], 1 + n_out
    stats = jnp.concatenate([(h * w)[:, None], g * w[:, None]],
                            axis=1).astype(jnp.float32)
    rows = tree_hist.levels_of(codes, stats, n_bins, integer_stats)
    feat, thresh, is_leaf, sums = [], [], [], []
    for level in range(max_depth):
        n_nodes = 2 ** level
        sel = feature_subsets(feat_mask_key, level, n_nodes, d,
                              max_features)
        # (nodes, d, S, bins), the kernel's padding cut off
        cum = rows.histograms(level)[:, :d, :n_stats]
        if level == 0:
            sums.append(cum[:, sel[0, 0], :, -1])
        whole = sums[-1][:, None, :, None]
        rest = whole - cum
        left_h, tot_h = cum[:, :, :1, :], whole[:, :, :1, :]
        right_h = tot_h - left_h
        terms = (cum ** 2 / (left_h + reg_lambda)
                 + rest ** 2 / (right_h + reg_lambda)
                 - whole ** 2 / (tot_h + reg_lambda))
        gain = jnp.sum(terms[:, :, 1:], axis=2)
        left_h, right_h = left_h[:, :, 0, :], right_h[:, :, 0, :]
        ok = (left_h >= min_child_weight) & (right_h >= min_child_weight)
        gain = jnp.where(ok, gain, -jnp.inf).at[..., -1].set(-jnp.inf)
        fmask = jnp.any(sel[:, :, None] == jnp.arange(d)[None, None, :],
                        axis=1)
        gain = jnp.where(fmask[:, :, None], gain, -jnp.inf)
        flat_gain = gain.reshape(n_nodes, d * n_bins)
        best = jnp.argmax(flat_gain, axis=1)
        best_gain = jnp.take_along_axis(flat_gain, best[:, None],
                                        axis=1)[:, 0]
        bf = (best // n_bins).astype(jnp.int32)
        bb = (best % n_bins).astype(jnp.int32)
        do_split = best_gain > 1e-7
        feat.append(jnp.where(do_split, bf, -1))
        thresh.append(bb)
        is_leaf.append(jnp.logical_not(do_split))
        left = cum[jnp.arange(n_nodes), bf, :, bb]
        sums.append(jnp.stack([left, sums[-1] - left], axis=1).reshape(
            2 * n_nodes, -1))
        rows.route(level, bf, bb, do_split)
    n_last = 2 ** max_depth
    node_sums = jnp.concatenate(sums, axis=0)
    return Tree(
        feat=jnp.concatenate(feat + [jnp.full((n_last,), -1, jnp.int32)]),
        thresh=jnp.concatenate(thresh + [jnp.zeros((n_last,), jnp.int32)]),
        value=-node_sums[:, 1:] / (node_sums[:, :1] + reg_lambda),
        is_leaf=jnp.concatenate(is_leaf + [jnp.ones((n_last,), bool)]),
        leaf=rows.leaves())


@pytest.mark.parametrize("depth,n_out,max_features,integer", [
    (1, 3, None, True), (4, 5, 3, True), (7, 2, 4, True),
    (6, 1, None, False),
    (6, 1, 5, False),           # a regressor forest's subsets: three parts
    (5, 7, 11, True),           # all but one feature
    (3, 2, 12, True),           # max_features = d: every feature
])
def test_the_grower_grows_the_same_tree_on_either_form(
        depth, n_out, max_features, integer, monkeypatch):
    """... and, where a node has a subset of its own, the tree of every
    feature's histograms with the gains masked after the fact."""
    rng = np.random.default_rng(depth)
    n, d = 900, 12
    codes = jnp.asarray(rng.integers(0, N_BINS, (n, d), dtype=np.uint8))
    # sums of counts, exact in any order: the forms' trees are the same
    y1h = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    w = jnp.asarray(rng.poisson(1.0, n).astype(np.float32)
                    * (rng.random(n) < 0.8))
    kwargs = dict(min_child_weight=1.0, reg_lambda=1e-9,
                  feat_mask_key=jax.random.PRNGKey(3),
                  max_features=max_features, n_out=n_out,
                  integer_stats=integer)

    def grow(grower=grow_tree, targets=y1h):
        return jax.jit(lambda: grower(
            codes, -jnp.asarray(targets), jnp.ones((n,), jnp.float32), w,
            depth, N_BINS, **kwargs))()

    def same(one, other):
        for name in one._fields:
            assert np.array_equal(np.asarray(getattr(one, name)),
                                  np.asarray(getattr(other, name))), name

    subset = max_features is not None and max_features < d
    # a regressor's statistics where the caller says no integers: on ONE
    # form the sums of a slot are the sums of its feature, in their order
    real = y1h if integer else rng.standard_normal((n, n_out)).astype(
        np.float32)
    plain = grow()
    if subset:
        same(grow(targets=real), grow(grow_tree_masked, real))
    shapes = []
    monkeypatch.setattr(tree_hist, "levels_of", recording_levels(shapes))
    kernel = grow()
    same(plain, kernel)
    # a level's histograms hold the node's own slots, or every feature
    # padded to the kernel's blocks as before
    held = -(-max_features // 8) * 8 if subset else 32
    assert shapes == [(2 ** level, held, 8, N_BINS)
                      for level in range(depth)]
    if subset:
        same(grow(targets=real), grow(grow_tree_masked, real))
    # a tree's own rows: the leaf each ended in is where a walk ends
    feat, thresh, is_leaf = (np.asarray(a) for a in (
        plain.feat, plain.thresh, plain.is_leaf))
    node = np.zeros(n, int)
    for _ in range(depth):
        f = feat[node]
        right = np.asarray(codes)[np.arange(n), np.maximum(f, 0)] \
            > thresh[node]
        node = np.where(is_leaf[node] | (f < 0), node, 2 * node + 1 + right)
    assert np.array_equal(node, np.asarray(plain.leaf))


def recording_levels(shapes):
    """``levels_of`` for the kernels' form in interpret mode, which notes
    the shape of every level's histograms."""
    class Recording(tree_hist.GroupedLevels):
        def histograms(self, level, sel=None):
            out = super().histograms(level, sel)
            shapes.append(out.shape)
            return out

    return functools.partial(Recording, tile=128, interpret=True)


@pytest.mark.parametrize("max_features,with_key", [
    (None, True), (12, True), (40, True), (3, False), (None, False)])
def test_without_a_subset_the_histograms_are_every_features(
        max_features, with_key, monkeypatch):
    """`max_features >= d` and `feat_mask_key=None` trace to the shapes
    the grower had before subsets were drawn first, and draw none."""
    n, d, depth = 300, 12, 3
    shapes = []
    monkeypatch.setattr(tree_hist, "levels_of", recording_levels(shapes))
    text = str(jax.make_jaxpr(lambda codes, g, w: grow_tree(
        codes, g, jnp.ones((n,), jnp.float32), w, depth, N_BINS,
        feat_mask_key=jax.random.PRNGKey(1) if with_key else None,
        max_features=max_features))(
        jnp.zeros((n, d), jnp.uint8), jnp.zeros((n, 1)), jnp.ones((n,))))
    assert shapes == [(2 ** level, 32, 8, N_BINS) for level in range(depth)]
    assert "random_bits" not in text and "threefry" not in text


@pytest.mark.parametrize("tie", [False, True])
@pytest.mark.parametrize("level,d,max_features", [(0, 54, 7), (4, 54, 7),
                                                  (3, 10, 3)])
def test_subsets_are_the_references(level, d, max_features, tie,
                                    monkeypatch):
    """`feature_subsets` against `benchmark/reference_forest.py`'s on the
    same key: the same features in the same (ascending) order, also where
    two scores tie at the `max_features`-th and the chosen are one too
    many: the first `max_features` in feature order stay."""
    from spark_sklearn_tpu.ops.trees import feature_subsets
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import reference_forest
    n_nodes, key = 2 ** level, jax.random.PRNGKey(11)
    if tie:
        uniform = jax.random.uniform

        def tied(k, shape, *a, **kw):
            scores = uniform(k, shape, *a, **kw)
            # node 0: the LAST feature takes the kth smallest score, so
            # the chosen are max_features + 1 (or it was the kth itself)
            kth = jnp.sort(scores[0])[max_features - 1]
            return scores.at[0, shape[1] - 1].set(kth)

        monkeypatch.setattr(jax.random, "uniform", tied)
    got = np.asarray(feature_subsets(
        jax.random.fold_in(key, 7), level, n_nodes, d, max_features))
    want = reference_forest.feature_subsets(key, level, n_nodes, d,
                                            max_features)
    assert got.shape == (n_nodes, max_features) and got.dtype == np.int32
    assert np.array_equal(got, want)
    assert (np.diff(got, axis=1) > 0).all()
    if tie:
        scores = np.asarray(jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(key, 7), level),
            (n_nodes, d)))
        chosen = scores[0] <= np.sort(scores[0])[max_features - 1]
        if chosen.sum() > max_features:     # the tie: the last one goes
            assert d - 1 not in got[0]
            assert np.array_equal(got[0],
                                  np.flatnonzero(chosen)[:max_features])


@pytest.mark.parametrize("depth,d,n_classes,max_features,held", [
    (10, 54, 7, None, 64), (6, 54, 7, None, 64), (3, 100, 2, None, 128),
    # a node's own features: a block of whole sublane tiles, or blocks of 32
    (10, 54, 7, "sqrt", 8), (8, 54, 7, "sqrt", 8), (3, 100, 2, "sqrt", 16),
    (4, 100, 2, 0.4, 64), (5, 54, 7, 54, 64)])
def test_the_ledger_prices_what_the_kernel_allocates(
        depth, d, n_classes, max_features, held, monkeypatch):
    """``hist_bytes_per_lane`` and ``launch_workspace`` against the shape
    the kernel's output really has (on a TPU: features, or the slots of a
    node's own subset, and statistics padded to its blocks)."""
    monkeypatch.setattr(tree_hist, "on_tpu", lambda: True)
    family = tree_models.RandomForestClassifierFamily
    meta = {"n_features": d, "n_classes": n_classes,
            "unit_fit_weights": True}
    static = {"max_depth": depth, "max_features": max_features}
    slots = family._max_features(static, d)
    subset = slots < d
    n, level, tile = 2048, depth - 1, tree_hist.ROW_TILE
    n_items = n // tile + 2 * tree_hist._group_shape(level)[1]
    s8 = -(-(1 + n_classes) // 8) * 8
    d_pad = tree_hist._padded_features(d)
    operands = [jax.ShapeDtypeStruct((1, 4, n_items), jnp.int32),
                jax.ShapeDtypeStruct((1, d_pad, n), jnp.uint8),
                jax.ShapeDtypeStruct((1, s8, n), jnp.bfloat16),
                jax.ShapeDtypeStruct((1, 8, n), jnp.int32)]
    if subset:
        operands.append(jax.eval_shape(lambda: tree_hist._pick(
            jnp.zeros((2 ** level, slots), jnp.int32), d_pad)[None]))
    out = jax.eval_shape(
        functools.partial(tree_hist._hist_lanes_impl, level=level,
                          n_slots=slots if subset else d, n_bins=N_BINS,
                          parts=1, tile=tile, interpret=False),
        *operands)
    assert out.shape == (1, 2 ** level, held, s8, N_BINS)
    lane_bytes = int(np.prod(out.shape)) * out.dtype.itemsize
    facts = family.launch_facts(static, meta, 3, 5)
    assert facts == {"hist_bytes": lane_bytes,
                     "hist_features": slots if subset else d}
    # a forest a fold whatever the launch's width; a candidate adds votes
    ws = family.launch_workspace(n, meta, 5, static=static)
    assert ws["fixed_bytes"] >= 5 * 1.39 * lane_bytes
    assert ws["fixed_bytes"] < 5 * (2 * lane_bytes + n * 1024)
    assert ws["per_candidate_bytes"] == 5 * n * 4 * n_classes * 4
    if (depth, d) == (10, 54):
        # 268 MB of every feature (54 padded to the kernel's 64), 33.5 MB
        # of a node's own seven (padded to 8)
        assert lane_bytes == 512 * (8 if subset else 64) * 8 * 256 * 4
    monkeypatch.setattr(tree_hist, "on_tpu", lambda: False)
    assert family.launch_facts(static, meta, 3, 5)["hist_bytes"] == \
        2 ** level * (slots if subset else d) * (1 + n_classes) * 256 * 4


TREE_SCOPES = ("sst.tree.bootstrap", "sst.tree.partition",
               "sst.tree.histogram", "sst.tree.split", "sst.tree.route",
               "sst.tree.predict")


@pytest.fixture(scope="module")
def span_lint():
    """``sstlint span-unknown-name`` over the three files that open the
    grower's scopes."""
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    if str(repo) not in sys.path:
        sys.path.insert(0, str(repo))
    from tools.sstlint import run_lint
    result = run_lint(root=repo, rules=["span-unknown-name"])
    return [f for f in result["findings"]
            if f["rule"] == "span-unknown-name"]


@pytest.mark.parametrize("scope", TREE_SCOPES)
def test_scope_is_declared_and_passes_the_span_lint(scope, span_lint):
    from spark_sklearn_tpu.obs.spans import SPAN_VOCABULARY, \
        known_scope_names
    assert scope in known_scope_names()
    declared = {d.name: d for d in SPAN_VOCABULARY}[scope]
    assert declared.kind == "scope" and declared.layer == "solvers"
    assert not [f for f in span_lint if scope in f["message"]], span_lint
    assert not span_lint


@pytest.mark.parametrize("batched", ["neither", "index", "both", "nested"])
def test_take_rows_is_the_gather_under_any_vmap(batched):
    """``take_rows`` reads the lanes' tables as one table laid end to end:
    the same rows as ``table[index]`` a lane."""
    rng = np.random.default_rng(0)
    tables = rng.integers(0, 1000, (2, 3, 50, 4)).astype(np.int32)
    index = rng.integers(0, 50, (2, 3, 70)).astype(np.int32)
    if batched == "neither":
        got = tree_hist.take_rows(tables[0, 0], index[0, 0])
        want = tables[0, 0][index[0, 0]]
    elif batched == "index":
        got = jax.vmap(lambda i: tree_hist.take_rows(tables[0, 0], i))(
            index[0])
        want = tables[0, 0][index[0]]
    elif batched == "both":
        got = jax.vmap(tree_hist.take_rows)(tables[0], index[0])
        want = np.stack([tables[0, k][index[0, k]] for k in range(3)])
    else:
        got = jax.jit(jax.vmap(jax.vmap(tree_hist.take_rows)))(tables, index)
        want = np.stack([[tables[a, k][index[a, k]] for k in range(3)]
                         for a in range(2)])
        text = str(jax.make_jaxpr(jax.vmap(jax.vmap(tree_hist.take_rows)))(
            tables, index))
        assert "i32[300,4]" in text       # one table of 2 x 3 x 50 rows
    assert np.array_equal(np.asarray(got), want)
