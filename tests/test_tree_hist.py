"""The level-histogram and routing kernels (``ops/tree_hist.py``) against
the plain ``segment_sum`` form they replace on a TPU, on XLA:CPU in
interpret mode: bit for bit on integer statistics (a forest's bootstrap
count x one-hot class), to float32 rounding on the boosters' gradients,
over levels on and between the sorts, outputs, zero-weight rows, a node
with no rows and lanes under ``vmap``; the grower on either form grows the
same tree; the family's price of a lane's histograms is what the kernel
allocates."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_sklearn_tpu.models import trees as tree_models
from spark_sklearn_tpu.ops import tree_hist
from spark_sklearn_tpu.ops.trees import grow_tree

N_BINS = 256


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
               splits=None):
    """A level's cumulative histograms from the plain form and from the
    kernels (padding cut off), with every row set down in node `local` of
    the level; with `splits` (feature, bin, whether) a node, also where
    each row goes from there: (node, still on its way) a form."""
    l0 = level - level % tree_hist.LEVELS_PER_SORT
    offset, n_stats = 2 ** level - 1, stats.shape[1]
    d = codes.shape[1]

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
        out = [plain.histograms(level),
               rows.histograms(level)[:, :d, :n_stats]]
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


@pytest.mark.parametrize("n,d,n_out,level,tile", [
    (700, 5, 3, 0, 128),          # the root: one node, every row
    (700, 5, 3, 2, 128),
    (1000, 54, 7, 3, 256),        # the covtype cell's widths
    (900, 70, 2, 4, 128),         # three feature blocks, one group of 16
    (1200, 9, 1, 5, 128),         # a sorted level: two groups
    (1500, 6, 4, 7, 512),         # two levels below a sort: 8 groups
    (2000, 3, 7, 9, 128),         # four levels below: 32 groups of 16
])
def test_integer_statistics_bit_for_bit(n, d, n_out, level, tile):
    n_nodes = 2 ** level
    codes, stats, local, live = rows(n, d, n_out, n_nodes, n + d, True)
    plain, kernel = both_forms(codes, stats, local, live, level, tile, True)
    assert plain.shape == kernel.shape == (n_nodes, d, 1 + n_out, N_BINS)
    assert np.array_equal(plain, kernel)
    assert np.array_equal(plain, plain_sums(codes, stats, local, live,
                                            n_nodes))
    assert kernel[:, :, 0, -1].sum() == stats[live, 0].sum() * d
    if n_nodes > 2:
        assert not kernel[n_nodes // 2].any()     # the node with no rows


@pytest.mark.parametrize("n,d,n_out,level,tile", [
    (900, 10, 1, 1, 128),         # a boosting stage: hessian and gradient
    (900, 40, 1, 3, 256),
    (1200, 3, 5, 6, 128),
])
def test_float_statistics_to_float32_rounding(n, d, n_out, level, tile):
    codes, stats, local, live = rows(n, d, n_out, 2 ** level, n, False)
    plain, kernel = both_forms(codes, stats, local, live, level, tile,
                               False)
    # a product is exact (three bfloat16 parts); the sums differ in order
    scale = np.abs(stats).sum(axis=0).max()
    assert np.abs(plain - kernel).max() <= 1e-6 * scale
    # ... and one part would not do
    _, rough = both_forms(codes, stats, local, live, level, tile, True)
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


def test_lanes_of_a_launch_are_one_call():
    """Two nested ``vmap``s (the engine's candidates and folds) reach each
    kernel as one grid axis of their product."""
    n, d, n_out = 500, 6, 3
    codes, stats, _, _ = rows(n, d, n_out, 1, 11, True)
    weights = np.stack([np.roll(stats, 91 * i, axis=0) for i in range(6)])

    def one(s):
        lane = tree_hist.GroupedLevels(codes, s, N_BINS, True, tile=128,
                                       interpret=True)
        return lane.histograms(0)[:, :d, :1 + n_out]

    lanes = jax.vmap(jax.vmap(one))
    stacked = weights.reshape(2, 3, n, -1)
    got = np.asarray(jax.jit(lanes)(stacked))
    assert str(jax.make_jaxpr(lanes)(stacked)).count("pallas_call") == 1
    for i in range(6):
        want = plain_sums(codes, weights[i], np.zeros(n, np.int32),
                          np.ones(n, bool), 1)
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


@pytest.mark.parametrize("depth,n_out,max_features,integer", [
    (1, 3, None, True), (4, 5, 3, True), (7, 2, 4, True),
    (6, 1, None, False)])
def test_the_grower_grows_the_same_tree_on_either_form(
        depth, n_out, max_features, integer, monkeypatch):
    rng = np.random.default_rng(depth)
    n, d = 900, 12
    codes = jnp.asarray(rng.integers(0, N_BINS, (n, d), dtype=np.uint8))
    y1h = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    w = jnp.asarray(rng.poisson(1.0, n).astype(np.float32)
                    * (rng.random(n) < 0.8))

    def grow():
        return jax.jit(lambda: grow_tree(
            codes, -jnp.asarray(y1h), jnp.ones((n,), jnp.float32), w, depth,
            N_BINS, min_child_weight=1.0, reg_lambda=1e-9,
            feat_mask_key=jax.random.PRNGKey(3), max_features=max_features,
            n_out=n_out, integer_stats=integer))()

    plain = grow()
    monkeypatch.setattr(tree_hist, "levels_of", functools.partial(
        tree_hist.GroupedLevels, tile=128, interpret=True))
    kernel = grow()
    for name in plain._fields:
        assert np.array_equal(np.asarray(getattr(plain, name)),
                              np.asarray(getattr(kernel, name))), name
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


@pytest.mark.parametrize("depth,d,n_classes", [(10, 54, 7), (6, 54, 7),
                                               (3, 100, 2)])
def test_the_ledger_prices_what_the_kernel_allocates(depth, d, n_classes,
                                                     monkeypatch):
    """``hist_bytes_per_lane`` and ``launch_workspace`` against the shape
    the kernel's output really has (on a TPU: features and statistics
    padded to its blocks)."""
    monkeypatch.setattr(tree_hist, "on_tpu", lambda: True)
    family = tree_models.RandomForestClassifierFamily
    meta = {"n_features": d, "n_classes": n_classes,
            "unit_fit_weights": True}
    static = {"max_depth": depth}
    n, level, tile = 2048, depth - 1, tree_hist.ROW_TILE
    n_items = n // tile + 2 * tree_hist._group_shape(level)[1]
    s8 = -(-(1 + n_classes) // 8) * 8
    out = jax.eval_shape(
        functools.partial(tree_hist._hist_lanes_impl, level=level, n_feat=d,
                          n_bins=N_BINS, parts=1, tile=tile,
                          interpret=False),
        jax.ShapeDtypeStruct((1, 4, n_items), jnp.int32),
        jax.ShapeDtypeStruct((1, tree_hist._padded_features(d), n),
                             jnp.uint8),
        jax.ShapeDtypeStruct((1, s8, n), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 8, n), jnp.int32))
    lane_bytes = int(np.prod(out.shape)) * out.dtype.itemsize
    facts = family.launch_facts(static, meta, 3, 5)
    assert facts == {"hist_bytes": lane_bytes}
    # a forest a fold whatever the launch's width; a candidate adds votes
    ws = family.launch_workspace(n, meta, 5, static=static)
    assert ws["fixed_bytes"] >= 5 * 1.39 * lane_bytes
    assert ws["fixed_bytes"] < 5 * (2 * lane_bytes + n * 1024)
    assert ws["per_candidate_bytes"] == 5 * n * 4 * n_classes * 4
    if (depth, d) == (10, 54):
        # the issue's 226 MB, 54 features padded to the kernel's 64
        assert lane_bytes == 512 * 64 * 8 * 256 * 4
    monkeypatch.setattr(tree_hist, "on_tpu", lambda: False)
    assert family.launch_facts(static, meta, 3, 5)["hist_bytes"] == \
        2 ** level * d * (1 + n_classes) * 256 * 4


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
