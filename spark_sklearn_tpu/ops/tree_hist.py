"""Level histograms of the binned tree grower: per (node, feature, bin) the
sums of every statistic over the rows that sit in the node, as cumulative
sums over the bins (``bin <= b``: the left side of a split at ``b``).

Which features: every one of the ``d`` (a booster; a forest whose
``max_features`` is ``d``), or, where each node of the level draws its own
subset (``sel (nodes, slots)``, a node's features ascending), the node's
own ``slots`` features and no others.  The feature axis of what is built is
then the node's *slot* ``j``: a row adds to slot ``j`` under the code of
ITS node's ``j``-th feature, so a level costs ``slots`` products a tile
where every feature costs ``d``, and its histograms hold ``slots / d`` of
the bytes.  A gain is only ever read at a node's own features, so nothing
that could be chosen is left out.

Two forms of one step of ``ops/trees.py::grow_tree``:

- :class:`PlainLevels`: a ``segment_sum`` over ``n x slots`` flat ids a
  statistic and a ``cumsum`` over the bins, every row sent to its child by
  gathers.  The plain form: what the kernels are tested against, and what
  XLA:CPU runs.  XLA:TPU serialises scatters, so at 10^5 rows it is seconds
  a level there.
- :class:`GroupedLevels`: rows **grouped by node**, so that a level costs
  ``2 * rows * n_bins * slots * S`` product FLOPs whatever its node count.  A
  tree's rows are put into node order at every fifth level only (one sort,
  one gather of a row's packed codes and statistics): between two sorts the
  nodes of a *group* (16 nodes that descend from neighbours in the sorted
  order) stay side by side, and the kernel tells a group's nodes apart in
  the product's other operand.  The sorted axis is cut at every multiple of
  the row tile and at every group's first row; each piece is one *item*
  ``(tile, group, first row, last row)`` in scalar prefetch.  A grid step
  takes one item, builds in VMEM the ``(16 nodes x statistics, T)`` operand
  (a row's statistics in its own node's rows, zeros elsewhere), with a
  ``sel`` the ``(slots, T)`` slot codes (one small product of the group's
  ``(16 nodes x slots, d)`` one-hot of ``sel`` with the tile's codes, then
  each row's own node's rows of it) and, a slot at a time, the
  ``(n_bins, T)`` mask ``code <= bin``, and adds
  their product to the group's block, which stays resident while
  consecutive items name the same group.  Rows that count for nothing (a
  fold's test rows, a tree's out-of-bag rows) are sorted behind the others
  and left out of the product; a second, small kernel sends every row to
  its child from the same items.  Lanes of a launch are a grid axis
  (``jax.vmap`` lands there through ``custom_vmap``).

The statistics enter the product in bfloat16 against an exact mask and are
accumulated in float32.  Where the caller says they are small integers (a
forest's bootstrap count x one-hot class) one bfloat16 part is the value
and a histogram is exact to 2^24; otherwise three parts (``hi + mid + lo``
is the float32 value to the bit) keep the boosters' gradients in float32.

Which form runs is the backend's platform (:func:`levels_of`), not a
switch: the kernels on a TPU, the plain form elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows a grid step takes
ROW_TILE = 512
#: features a grid step's blocks hold at most (a uint8 tile is 32
#: sublanes): the histogram kernel's output block, and with every feature
#: built its block of codes; the codes are padded to a multiple of it
FEATURE_BLOCK = 32
#: nodes of one group: with 8 statistic rows a node, the 128 rows of one
#: pass of the MXU
GROUP_NODES = 16
#: levels between two sorts: a group's 16 nodes at the last of them
#: descend from ONE node of the sorted level
LEVELS_PER_SORT = 5


def _round_up(x, m):
    return -(-int(x) // m) * m


def _padded_features(n_features):
    return _round_up(n_features, FEATURE_BLOCK)


def _group_shape(level):
    """(nodes a group, groups) of a level."""
    k = min(GROUP_NODES, 2 ** level)
    return k, 2 ** level // k


def _slot_blocks(n_slots, subset):
    """(slots a block, blocks) of the histogram kernel's feature axis:
    every feature in blocks of ``FEATURE_BLOCK``; a node's own features
    (``subset``) in one block of whole sublane tiles where that is no
    more, in blocks of ``FEATURE_BLOCK`` otherwise."""
    block = FEATURE_BLOCK
    if subset:
        block = min(_round_up(n_slots, 8), block)
    return block, -(-int(n_slots) // block)


# ---------------------------------------------------------------------------
# the plain form
# ---------------------------------------------------------------------------

def plain_level_histograms(codes, stats, local, live, n_nodes, n_bins,
                           sel=None):
    """``(n_nodes, slots, S, n_bins)`` plain (not cumulative) histograms:
    one ``segment_sum`` a statistic over the ``n x slots`` flat (node,
    slot, bin) ids.  A slot is a feature (``slots = d``) or, with ``sel
    (n_nodes, slots)``, the row's own node's feature ``sel[node, slot]``."""
    n = codes.shape[0]
    if sel is not None:
        codes = jnp.take_along_axis(codes, sel[local], axis=1)
    d = codes.shape[1]
    ids = (local[:, None] * d + jnp.arange(d, dtype=jnp.int32)[None, :]
           ) * n_bins + codes.astype(jnp.int32)             # (n, d)
    ids = jnp.where(live[:, None], ids, 0).reshape(-1)
    num_seg = n_nodes * d * n_bins

    def hist(v):                                            # v: (n,)
        vals = jnp.where(live, v, 0.0)
        flat = jnp.broadcast_to(vals[:, None], (n, d)).reshape(-1)
        return jax.ops.segment_sum(
            flat, ids, num_segments=num_seg).reshape(n_nodes, d, n_bins)

    return jnp.stack([hist(stats[:, s]) for s in range(stats.shape[1])],
                     axis=2)


class PlainLevels:
    """A tree's rows in the caller's order, a node id a row."""

    def __init__(self, codes, stats, n_bins, integer_stats=False):
        del integer_stats            # float32 sums are exact for integers
        self.codes, self.stats, self.n_bins = codes, stats, n_bins
        n = codes.shape[0]
        self.node = jnp.zeros((n,), jnp.int32)      # heap id per row
        self.frozen = jnp.zeros((n,), bool)         # row sits in a leaf

    def _local(self, n_nodes):
        # a frozen row sits higher up: it counts for nothing, and reads
        # any node's split below
        return jnp.clip(self.node - (n_nodes - 1), 0, n_nodes - 1)

    def histograms(self, level, sel=None):
        """``(2^level, d, S, n_bins)``, or ``(2^level, slots, S, n_bins)``
        at each node's own features ``sel (2^level, slots)``."""
        n_nodes = 2 ** level
        with jax.named_scope("sst.tree.histogram"):
            return jnp.cumsum(plain_level_histograms(
                self.codes, self.stats, self._local(n_nodes),
                jnp.logical_not(self.frozen), n_nodes, self.n_bins, sel),
                axis=3)

    def route(self, level, feature, threshold, splits):
        with jax.named_scope("sst.tree.route"):
            local = self._local(2 ** level)
            code_at = jnp.take_along_axis(
                self.codes, feature[local][:, None], axis=1)[:, 0]
            go_right = code_at.astype(jnp.int32) > threshold[local]
            moves = splits[local] & jnp.logical_not(self.frozen)
            self.node = jnp.where(
                moves, 2 * self.node + 1 + go_right.astype(jnp.int32),
                self.node)
            self.frozen = self.frozen | jnp.logical_not(splits[local])

    def leaves(self):
        return self.node


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _hist_kernel(tile_ref, group_ref, lo_ref, hi_ref, codes_ref, stats_ref,
                 state_ref, *rest, n_items, n_slots, s8, parts, n_bins,
                 k_nodes, n_groups, offset):
    """One item's share of its group's block ``(k_nodes, slots a block,
    s8, n_bins)``.  ``codes_ref`` holds the block's own features of the
    tile, which ARE its slot codes; or, with ``pick_ref`` (the group's
    ``(k_nodes x slots a block, d_pad)`` one-hot of its nodes' own
    features), every feature of the tile, and a row's slot codes are its
    own node's rows of ``pick @ codes``.  Slots past ``n_slots`` are never
    built and read zero."""
    del tile_ref
    pick_ref = rest[0] if len(rest) == 3 else None
    out_ref, slot_codes = rest[-2:]
    lane, fb, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    at = lane * n_items + i
    group = jnp.minimum(group_ref[at], n_groups - 1)
    before = jnp.minimum(group_ref[jnp.maximum(at - 1, lane * n_items)],
                         n_groups - 1)

    @pl.when(jnp.logical_or(i == 0, group != before))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo, hi = lo_ref[at], hi_ref[at]

    # items of the rows that count for nothing name groups past the last
    @pl.when(jnp.logical_and(hi > lo, group_ref[at] < n_groups))
    def _():
        rows = stats_ref.shape[-1]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        heap, live = state_ref[0, 0:1, :], state_ref[0, 1:2, :]
        mine = heap - (offset + group * k_nodes)            # node in group
        counts = jnp.logical_and(jnp.logical_and(pos >= lo, pos < hi),
                                 live > 0)
        st = stats_ref[0].astype(jnp.float32)               # (parts*s8, T)
        blocks = [jnp.where(jnp.logical_and(counts, mine == k), st, 0.0)
                  for k in range(k_nodes)]
        if len(blocks) * st.shape[0] % 16:      # bfloat16 packs 16 rows
            blocks.append(jnp.zeros_like(st))
        operand = jnp.concatenate(blocks, axis=0).astype(jnp.bfloat16)
        codes = codes_ref[0].astype(jnp.int32)
        block = slot_codes.shape[0]
        if pick_ref is None:
            slot_codes[...] = codes
        else:
            # codes and the one-hot are exact in bfloat16, their product
            # in float32: (k_nodes * block, T), node k's slots' codes of
            # every row; a row keeps its own node's
            picked = jax.lax.dot_general(
                pick_ref[0, 0],
                codes.astype(jnp.float32).astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            own = picked[:block]
            for k in range(1, k_nodes):
                own = jnp.where(mine == k,
                                picked[k * block:(k + 1) * block], own)
            slot_codes[...] = own.astype(jnp.int32)
        bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, rows), 0)
        # slots of the last block past the level's own are never read
        n_here = jnp.minimum(block, n_slots - fb * block)

        def one_slot(f, carry):
            row = slot_codes[pl.ds(f, 1), :]                # (1, T)
            mask = jnp.where(row <= bins, 1.0, 0.0).astype(jnp.bfloat16)
            # the mask is the product's stationary operand: with the
            # statistics there instead, and the mask's rows streamed, a
            # depth-10 tree's kernels ran 409 ms for 308 (PERF.md, PR 35)
            acc = jax.lax.dot_general(
                operand, mask, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (k*parts*s8, bins)
            for k in range(k_nodes):
                part = [acc[(k * parts + p) * s8:(k * parts + p + 1) * s8]
                        for p in range(parts)]
                out_ref[0, k, f] += functools.reduce(jnp.add, part)
            return carry

        jax.lax.fori_loop(0, n_here, one_slot, 0)


def _route_kernel(tile_ref, group_ref, lo_ref, hi_ref, word_ref, codes_ref,
                  state_ref, out_ref, codes_i32, *, n_items, k_nodes,
                  n_groups, offset):
    lane, i = pl.program_id(0), pl.program_id(1)
    at = lane * n_items + i
    before = tile_ref[jnp.maximum(at - 1, lane * n_items)]

    @pl.when(jnp.logical_or(i == 0, tile_ref[at] != before))
    def _():
        out_ref[...] = state_ref[...]

    lo, hi = lo_ref[at], hi_ref[at]

    @pl.when(hi > lo)
    def _():
        rows = state_ref.shape[-1]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        inside = jnp.logical_and(pos >= lo, pos < hi)
        heap, live = state_ref[0, 0:1, :], state_ref[0, 1:2, :]
        # the rows that count for nothing sit in groups n_groups..2n-1
        first = (group_ref[at] % n_groups) * k_nodes
        mine = heap - (offset + first)
        codes_i32[...] = codes_ref[0].astype(jnp.int32)
        new_heap, new_live = heap, jnp.zeros_like(live)
        for k in range(k_nodes):
            word = word_ref[lane * (n_groups * k_nodes) + first + k]
            code_at = codes_i32[pl.ds(word >> 9, 1), :]     # (1, T)
            here = jnp.logical_and(mine == k, ((word >> 8) & 1) > 0)
            child = 2 * heap + 1 + (code_at > (word & 0xFF)).astype(
                jnp.int32)
            new_heap = jnp.where(here, child, new_heap)
            new_live = jnp.where(here, live, new_live)
        moves = jnp.logical_and(inside, live > 0)
        out_ref[0, 0:1, :] = jnp.where(moves, new_heap, out_ref[0, 0:1, :])
        out_ref[0, 1:2, :] = jnp.where(moves, new_live, out_ref[0, 1:2, :])


def _flat(tables):
    """``(L, 4, items)`` -> four ``(L * items,)`` scalar-prefetch rows."""
    n_lanes, _, n_items = tables.shape
    flat = tables.transpose(1, 0, 2).reshape(4, n_lanes * n_items)
    return flat[0], flat[1], flat[2], flat[3]


def _hist_lanes_impl(tables, codes_t, stats_t, state, *pick, level,
                     n_slots, n_bins, parts, tile, interpret):
    """``tables (L, 4, items)`` int32, ``codes_t (L, d_pad, n_pad)`` uint8,
    ``stats_t (L, parts * S8, n_pad)`` bfloat16, ``state (L, 8, n_pad)``
    int32 -> ``(L, 2^level, blocks x slots a block, S8, n_bins)`` float32,
    cumulative over the bins.  The slots are the ``n_slots = d`` features,
    or with one more operand, ``pick (L, blocks, 2^level x slots a block,
    d_pad)`` bfloat16 (a node's slot's feature, one-hot), each node's own
    ``n_slots``."""
    (pick,) = pick or (None,)
    n_lanes, _, n_items = tables.shape
    d_pad, r = codes_t.shape[1], stats_t.shape[1]
    s8 = r // parts
    k_nodes, n_groups = _group_shape(level)
    block, n_blocks = _slot_blocks(n_slots, pick is not None)
    # a block's own features, or every feature whatever the block
    code_rows = block if pick is None else d_pad

    def at(lane, i):
        return lane * n_items + i

    def group(g, lane, i):
        return jnp.minimum(g[at(lane, i)], n_groups - 1)

    in_specs = [
        pl.BlockSpec((1, code_rows, tile),
                     lambda l, fb, i, t, g, lo, hi: (
                         l, fb if pick is None else 0, t[at(l, i)])),
        pl.BlockSpec((1, r, tile),
                     lambda l, fb, i, t, g, lo, hi: (l, 0, t[at(l, i)])),
        pl.BlockSpec((1, 8, tile),
                     lambda l, fb, i, t, g, lo, hi: (l, 0, t[at(l, i)])),
    ]
    operands = [codes_t, stats_t, state]
    if pick is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, k_nodes * block, d_pad),
            lambda l, fb, i, t, g, lo, hi: (l, fb, group(g, l, i), 0)))
        operands.append(pick)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_lanes, n_blocks, n_items),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, k_nodes, block, s8, n_bins),
            lambda l, fb, i, t, g, lo, hi: (l, group(g, l, i), fb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block, tile), jnp.int32)],
    )
    kernel = functools.partial(
        _hist_kernel, n_items=n_items, n_slots=n_slots, s8=s8, parts=parts,
        n_bins=n_bins, k_nodes=k_nodes, n_groups=n_groups,
        offset=2 ** level - 1)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_lanes, 2 ** level, n_blocks * block, s8, n_bins),
            jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="sst_tree_histogram",
    )(*_flat(tables), *operands)


def _route_lanes_impl(tables, words, codes_t, state, *, level, tile,
                      interpret):
    """``words (L, 2^level)`` int32 (a node's bin, whether it splits, its
    feature) -> the rows' new ``state (L, 8, n_pad)``."""
    n_lanes, _, n_items = tables.shape
    d_pad = codes_t.shape[1]
    k_nodes, n_groups = _group_shape(level)

    def at(lane, i):
        return lane * n_items + i

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_lanes, n_items),
        in_specs=[
            pl.BlockSpec((1, d_pad, tile),
                         lambda l, i, t, g, lo, hi, w: (l, 0, t[at(l, i)])),
            pl.BlockSpec((1, 8, tile),
                         lambda l, i, t, g, lo, hi, w: (l, 0, t[at(l, i)])),
        ],
        out_specs=pl.BlockSpec(
            (1, 8, tile), lambda l, i, t, g, lo, hi, w: (l, 0, t[at(l, i)])),
        scratch_shapes=[pltpu.VMEM((d_pad, tile), jnp.int32)],
    )
    kernel = functools.partial(
        _route_kernel, n_items=n_items, k_nodes=k_nodes, n_groups=n_groups,
        offset=2 ** level - 1)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(state.shape, jnp.int32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        input_output_aliases={6: 0},    # rows no item names keep theirs
        interpret=interpret,
        name="sst_tree_route",
    )(*_flat(tables), words.reshape(-1), codes_t, state)


@functools.lru_cache(maxsize=None)
def _lanes(impl, **static):
    """A kernel call with the lanes in front of every operand, and
    ``jax.vmap`` of it as one call over the merged lanes.  The call is a
    ``jit`` of its own so that its trace is kept by shape: batching a
    ``while_loop``'s body walks it to a fixed point, which traced every
    kernel of every level four times where two will do (the lane-less
    shapes once, the merged lanes' once)."""
    call = jax.custom_batching.custom_vmap(
        jax.jit(functools.partial(impl, **static)))

    @call.def_vmap
    def _(axis_size, in_batched, *operands):
        merged = []
        for x, batched in zip(operands, in_batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            merged.append(x.reshape((axis_size * x.shape[1],) + x.shape[2:]))
        out = call(*merged)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call


@jax.custom_batching.custom_vmap
def take_rows(table, index):
    """``table[index]``, rows of a 2-d table.  Under ``jax.vmap`` the
    lanes' tables are laid end to end and read by one gather with the
    lane's offset in the index: XLA:TPU gathers 2.2 M rows of one table
    in 3 ms and of fifteen batched ones in 20 (PERF.md, PR 35)."""
    return table[index]


@take_rows.def_vmap
def _take_rows_lanes(axis_size, in_batched, table, index):
    if not in_batched[0]:
        return take_rows(table, index.reshape(-1)).reshape(
            index.shape + table.shape[1:]), True
    if not in_batched[1]:
        index = jnp.broadcast_to(index, (axis_size,) + index.shape)
    n_rows = table.shape[1]
    offset = jnp.arange(axis_size, dtype=index.dtype)[:, None] * n_rows
    out = take_rows(table.reshape((axis_size * n_rows,) + table.shape[2:]),
                    (index + offset).reshape(-1))
    return out.reshape(index.shape + table.shape[2:]), True


# ---------------------------------------------------------------------------
# rows into node order, and the items
# ---------------------------------------------------------------------------

def pack_codes(codes):
    """``(n, d_pad / 4)`` int32: a row's codes as packed bytes (XLA:TPU
    gathers 32-bit words; these are what travels when the rows are put in
    order)."""
    n, d = codes.shape
    d_pad = _padded_features(d)
    padded = jnp.pad(codes.astype(jnp.uint8), ((0, 0), (0, d_pad - d)))
    return jax.lax.bitcast_convert_type(
        padded.reshape(n, d_pad // 4, 4), jnp.int32)


def pack_stats(stats, parts):
    """``(n, parts * S8 / 2)`` int32: the bfloat16 parts of a row's
    statistics, two a word."""
    s8 = _round_up(stats.shape[1], 8)
    return jax.lax.bitcast_convert_type(
        split_parts(stats, s8, parts).reshape(
            stats.shape[0], parts * s8 // 2, 2), jnp.int32)


def split_parts(stats, s8, parts):
    """``(n, S)`` float32 -> ``(n, parts * s8)`` bfloat16: the value's
    first bfloat16 part, or ``[hi | mid | lo]`` with ``hi + mid + lo ==
    stats`` to the bit."""
    rest = jnp.pad(stats.astype(jnp.float32),
                   ((0, 0), (0, s8 - stats.shape[1])))
    out = []
    for _ in range(parts):
        # rounded by an operation of its own: a convert to bfloat16 and
        # back is one XLA:TPU may leave out (excess precision is allowed
        # it), and then `rest - part` is zero and so is every part after
        # the first
        part = jax.lax.reduce_precision(rest, exponent_bits=8,
                                        mantissa_bits=7)
        out.append(part.astype(jnp.bfloat16))
        rest = rest - part
    return jnp.concatenate(out, axis=1)


def group_items(starts, tile, n_tiles, n_items):
    """The items of one lane's level, ``(4, n_items)`` int32 rows ``tile,
    group, first, last`` (positions inside the tile).  ``starts (G + 1,)``:
    each group's first position on the sorted axis, and the end of the
    last.  A group with no rows is one empty item (its block is written as
    zeros); items past the level's own name the last group and no row."""
    n_groups = starts.shape[0] - 1
    first, last = starts[:-1], starts[1:]
    t0 = jnp.minimum(first // tile, n_tiles - 1)
    t1 = jnp.maximum(jnp.minimum((last - 1) // tile, n_tiles - 1), t0)
    count = t1 - t0 + 1
    offset = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(count, dtype=jnp.int32)])
    item = jnp.arange(n_items, dtype=jnp.int32)
    real = item < offset[-1]
    group = jnp.minimum(jnp.sum(item[:, None] >= offset[None, 1:], axis=1,
                                dtype=jnp.int32), n_groups - 1)
    tile_of = jnp.where(real, t0[group] + item - offset[group], t1[-1])
    base = tile_of * tile
    lo = jnp.clip(first[group] - base, 0, tile)
    hi = jnp.where(real, jnp.clip(last[group] - base, 0, tile), 0)
    return jnp.stack([tile_of, group, lo, hi]).astype(jnp.int32)


def _pick(sel, d_pad):
    """``sel (nodes, slots)`` -> ``(blocks, nodes x slots a block, d_pad)``
    bfloat16: 1 where the column is the feature of the row's (node, slot);
    a slot past the node's own is a row of zeros."""
    n_nodes, slots = sel.shape
    block, n_blocks = _slot_blocks(slots, True)
    feature = jnp.arange(d_pad, dtype=jnp.int32)
    pick = jnp.pad(sel[:, :, None] == feature[None, None, :],
                   ((0, 0), (0, n_blocks * block - slots), (0, 0)))
    return pick.reshape(n_nodes, n_blocks, block, d_pad).transpose(
        1, 0, 2, 3).reshape(n_blocks, n_nodes * block, d_pad).astype(
        jnp.bfloat16)


class GroupedLevels:
    """A tree's rows in the kernels' order: sorted every
    ``LEVELS_PER_SORT`` levels by (counts for nothing, node), their codes
    and statistics gathered into that order, the node each sits in beside
    them."""

    def __init__(self, codes, stats, n_bins, integer_stats=False,
                 tile=ROW_TILE, interpret=False):
        self.n, self.d = codes.shape
        self.n_bins = n_bins
        self.parts = 1 if integer_stats else 3
        self.tile, self.interpret = tile, interpret
        self.n_tiles = -(-self.n // tile)
        self.n_pad = self.n_tiles * tile
        with jax.named_scope("sst.tree.partition"):
            # the two tables the sorts gather from, each written once a
            # tree and not again inside a gather
            self.code_words, self.stat_words = jax.lax.optimization_barrier(
                (pack_codes(codes), pack_stats(stats, self.parts)))
            counted = jnp.any(stats != 0, axis=1)
        self.perm = jnp.arange(self.n, dtype=jnp.int32)
        self.heap = jnp.zeros((self.n,), jnp.int32)
        # 0: the row sits in a leaf; on its way down: 2 where it counts,
        # 1 where it counts for nothing
        self.live = 1 + counted.astype(jnp.int32)

    # -- the order of the rows ---------------------------------------------
    def _sort(self, level):
        """Rows into (counts for nothing, node) order; rows in a leaf
        last."""
        n_keys = 2 ** level
        with jax.named_scope("sst.tree.partition"):
            local = self.heap - (n_keys - 1)
            key = jnp.where(
                self.live > 0,
                jnp.where(self.live > 1, 0, n_keys) + local,
                2 * n_keys).astype(jnp.int32)
            key, self.perm, self.heap, self.live = jax.lax.sort(
                (key, self.perm, self.heap, self.live), num_keys=1)
            # each key's first position: the rows with a smaller key
            self.bounds = jnp.sum(
                key[:, None] < jnp.arange(2 * n_keys + 1,
                                          dtype=jnp.int32)[None, :],
                axis=0, dtype=jnp.int32)
            self.sorted_level = level
            pad = ((0, self.n_pad - self.n), (0, 0))
            self.codes_t = jax.lax.bitcast_convert_type(
                jnp.pad(take_rows(self.code_words, self.perm), pad),
                jnp.uint8).reshape(self.n_pad, -1).T
            self.stats_t = jax.lax.bitcast_convert_type(
                jnp.pad(take_rows(self.stat_words, self.perm), pad),
                jnp.bfloat16).reshape(self.n_pad, -1).T

    def _state(self):
        return jnp.pad(jnp.stack([self.heap, self.live]),
                       ((0, 6), (0, self.n_pad - self.n)))

    def _items(self, level):
        """The level's items: its groups of the rows that count, then as
        many of the rows that count for nothing."""
        _, n_groups = _group_shape(level)
        keys_a_group = 2 ** self.sorted_level // n_groups
        return group_items(self.bounds[::keys_a_group], self.tile,
                           self.n_tiles, self.n_tiles + 2 * n_groups)

    # -- a level -------------------------------------------------------------
    def histograms(self, level, sel=None):
        """``(2^level, d_pad, S8, n_bins)``, or at each node's own
        features ``sel (2^level, slots)`` ``(2^level, slots padded to the
        kernel's blocks, S8, n_bins)``: features, slots and statistics
        past the data's own read zero."""
        if level % LEVELS_PER_SORT == 0:
            self._sort(level)
        with jax.named_scope("sst.tree.partition"):
            self.tables = self._items(level)
        with jax.named_scope("sst.tree.histogram"):
            call = _lanes(_hist_lanes_impl, level=level,
                          n_slots=self.d if sel is None else sel.shape[1],
                          n_bins=self.n_bins, parts=self.parts,
                          tile=self.tile, interpret=self.interpret)
            operands = [self.tables, self.codes_t, self.stats_t,
                        self._state()]
            if sel is not None:
                operands.append(_pick(sel, self.codes_t.shape[0]))
            return call(*(x[None] for x in operands))[0]

    def route(self, level, feature, threshold, splits):
        with jax.named_scope("sst.tree.route"):
            word = (threshold | (splits.astype(jnp.int32) << 8)
                    | (feature << 9)).astype(jnp.int32)
            call = _lanes(_route_lanes_impl, level=level, tile=self.tile,
                          interpret=self.interpret)
            state = call(self.tables[None], word[None], self.codes_t[None],
                         self._state()[None])[0]
            self.heap, self.live = state[0, :self.n], state[1, :self.n]

    def leaves(self):
        """The node each row ended in, in the caller's order."""
        with jax.named_scope("sst.tree.route"):
            return jax.lax.sort((self.perm, self.heap), num_keys=1)[1]


def on_tpu():
    """Whether the programs being traced run on a TPU: the kernels'
    platform."""
    return jax.default_backend() == "tpu"


def levels_of(codes, stats, n_bins, integer_stats=False):
    """A tree's rows in the form the backend's platform runs."""
    form = GroupedLevels if on_tpu() else PlainLevels
    return form(codes, stats, n_bins, integer_stats)


def level_histogram_bytes(depth, n_features, n_stats, n_bins=256,
                          slots=None):
    """Bytes of the deepest level's histograms of one lane, every feature
    of ``n_features`` or, where that is fewer, each node's own ``slots``:
    as the kernel writes them on a TPU (features or slots, and statistics,
    padded to its blocks), as the plain form does elsewhere."""
    subset = slots is not None and slots < n_features
    d, s = int(slots if subset else n_features), int(n_stats)
    if on_tpu():
        block, n_blocks = _slot_blocks(d, subset)
        d, s = block * n_blocks, _round_up(s, 8)
    return (2 ** max(int(depth) - 1, 0) * d * s * int(n_bins)
            * np.dtype(np.float32).itemsize)


def row_bytes(n_features, n_stats, integer_stats):
    """Bytes of one row as the kernels' sorted copy holds it: its codes,
    the bfloat16 parts of its statistics, its node and whether it is in a
    leaf (a tile of 8 words)."""
    parts = 1 if integer_stats else 3
    return (_padded_features(n_features)
            + parts * _round_up(n_stats, 8) * 2 + 8 * 4)
