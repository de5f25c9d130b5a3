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
  the product's other operand.  At level 0, where every row is in the root,
  the order only puts the rows that count first; where those rows are the
  same for every tree of a loop (a booster whose ``subsample`` is 1.0: the
  fold's training rows) the caller makes that order once
  (:func:`row_order`, a :class:`RowOrder` a lane), hands its rows' statistics
  in it to every tree, and the trees sort and gather nothing at level 0 and
  sort nothing back: the first sort of such a tree is at level 5, relative
  to the order.  The sorted axis is cut at every multiple of
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
  (``jax.vmap`` lands there through ``custom_vmap``).  Where a ``vmap``
  batches the statistics and the rows' nodes but not the codes and the
  items (a booster's runs of one fold, whose rows stay in one order; its
  classes), its lanes *share* the kernels' code tiles: a grid step takes
  one tile and the statistics of every lane that shares it, builds each
  feature's mask once and streams all their operands through it in one
  product (a routing step reads the tile once for all of them).

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
from typing import NamedTuple

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
#: VMEM a grid step holds for the lanes that share its code tile (of the
#: kernels' 48 MiB limit): more lanes than fit take one step each group
SHARED_VMEM = 32 * 2 ** 20


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


def _hist_lane_bytes(k_nodes, block, s8, parts, n_bins, tile):
    """VMEM one more lane of a shared code tile costs a histogram grid
    step: its output block and its statistics' and rows' blocks, each
    twice (the pipeline's two buffers), its operand rows (float32, then
    bfloat16) and their product with a mask."""
    rows = k_nodes * parts * s8
    return (2 * k_nodes * block * s8 * n_bins * 4
            + 2 * tile * (parts * s8 * 2 + 8 * 4)
            + rows * tile * (4 + 2) + rows * n_bins * 4)


def _step_runs(runs, lane_bytes):
    """``(a, b)``: the lanes of a shared code tile one grid step takes of
    ``runs = (outer, inner)``, a divisor of each, as many as
    ``SHARED_VMEM`` holds at ``lane_bytes`` a lane (one at least)."""
    fit = max(1, SHARED_VMEM // lane_bytes)
    outer, inner = ([k for k in range(1, n + 1) if n % k == 0]
                    for n in runs)
    return max(((a, b) for a in outer for b in inner if a * b <= fit),
               key=lambda ab: ab[0] * ab[1])


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

def _run_leads(step_runs):
    """The leading index of each lane of a grid step in its per-lane
    blocks: ``(0,)`` where a step is one lane, ``(a, 0, b)`` over the
    step's ``(outer, inner)`` shared lanes."""
    if step_runs is None:
        return [(0,)]
    return [(a, 0, b) for a in range(step_runs[0])
            for b in range(step_runs[1])]


def _hist_kernel(tile_ref, group_ref, lo_ref, hi_ref, codes_ref, stats_ref,
                 state_ref, *rest, n_items, n_slots, s8, parts, n_bins,
                 k_nodes, n_groups, offset, step_runs):
    """One item's share of its group's block ``(k_nodes, slots a block,
    s8, n_bins)``, of each lane the step takes.  ``codes_ref`` holds the
    block's own features of the tile, which ARE its slot codes; or, with
    ``pick_ref`` (the group's ``(k_nodes x slots a block, d_pad)`` one-hot
    of its nodes' own features), every feature of the tile, and a row's
    slot codes are its own node's rows of ``pick @ codes``.  Slots past
    ``n_slots`` are never built and read zero.  With ``step_runs`` the
    step's lanes share the tile: their operands are stacked, and a mask
    is built once for all of them."""
    del tile_ref
    pick_ref = rest[0] if len(rest) == 3 else None
    out_ref, slot_codes = rest[-2:]
    last = 2 if step_runs is None else 3            # the grid's last axis
    lane = pl.program_id(0)
    fb, i = pl.program_id(last - 1), pl.program_id(last)
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
        leads = _run_leads(step_runs)
        blocks = []
        for lead in leads:
            heap = state_ref[lead + (slice(0, 1), slice(None))]
            live = state_ref[lead + (slice(1, 2), slice(None))]
            mine = heap - (offset + group * k_nodes)        # node in group
            counts = jnp.logical_and(jnp.logical_and(pos >= lo, pos < hi),
                                     live > 0)
            st = stats_ref[lead].astype(jnp.float32)        # (parts*s8, T)
            blocks += [jnp.where(jnp.logical_and(counts, mine == k), st, 0.0)
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
            for j, lead in enumerate(leads):
                for k in range(k_nodes):
                    base = (j * k_nodes + k) * parts
                    part = [acc[(base + p) * s8:(base + p + 1) * s8]
                            for p in range(parts)]
                    out_ref[lead + (k, f)] += functools.reduce(jnp.add, part)
            return carry

        jax.lax.fori_loop(0, n_here, one_slot, 0)


def _route_kernel(tile_ref, group_ref, lo_ref, hi_ref, word_ref, codes_ref,
                  state_ref, out_ref, codes_i32, *, n_items, k_nodes,
                  n_groups, offset, runs, n_lanes, step_runs):
    """One item's rows of each lane the step takes, each to its child by
    its own lane's words; lanes that share the tile read it once."""
    lane = pl.program_id(0)
    i = pl.program_id(1 if step_runs is None else 2)
    # (outer, inner) of the step's lanes' group
    a, b = (None, None) if step_runs is None else divmod(
        pl.program_id(1), runs[1] // step_runs[1])
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
        leads = _run_leads(step_runs)
        loaded = [(state_ref[lead + (slice(0, 1), slice(None))],
                   state_ref[lead + (slice(1, 2), slice(None))])
                  for lead in leads]
        # the rows that count for nothing sit in groups n_groups..2n-1
        first = (group_ref[at] % n_groups) * k_nodes
        state = [(heap, live, heap - (offset + first))
                 for heap, live in loaded]
        codes_i32[...] = codes_ref[0].astype(jnp.int32)
        for lead, (heap, live, mine) in zip(leads, state):
            row = lane
            if step_runs is not None:
                # this lane's row of words in the (outer, lanes, inner)
                # layout
                row = ((a * step_runs[0] + lead[0]) * n_lanes + lane) \
                    * runs[1] + b * step_runs[1] + lead[2]
            new_heap, new_live = heap, jnp.zeros_like(live)
            for k in range(k_nodes):
                word = word_ref[row * (n_groups * k_nodes) + first + k]
                code_at = codes_i32[pl.ds(word >> 9, 1), :]     # (1, T)
                here = jnp.logical_and(mine == k, ((word >> 8) & 1) > 0)
                child = 2 * heap + 1 + (code_at > (word & 0xFF)).astype(
                    jnp.int32)
                new_heap = jnp.where(here, child, new_heap)
                new_live = jnp.where(here, live, new_live)
            moves = jnp.logical_and(inside, live > 0)
            heap_at = lead + (slice(0, 1), slice(None))
            live_at = lead + (slice(1, 2), slice(None))
            out_ref[heap_at] = jnp.where(moves, new_heap, out_ref[heap_at])
            out_ref[live_at] = jnp.where(moves, new_live, out_ref[live_at])


def _flat(tables):
    """``(L, 4, items)`` -> four ``(L * items,)`` scalar-prefetch rows."""
    n_lanes, _, n_items = tables.shape
    flat = tables.transpose(1, 0, 2).reshape(4, n_lanes * n_items)
    return flat[0], flat[1], flat[2], flat[3]


def _lane_axes(n_lanes, runs, step):
    """A kernel's lane axes over ``n_lanes`` lanes of code tiles, each
    shared by ``runs = (outer, inner)`` lanes of their own, ``step`` of
    them a grid step (None: one lane, one tile: no sharing): the grid's
    leading axes (lanes, then groups of ``step``), the block of a lane's
    own operand's leading axes ``(outer, L, inner)``, and its block index
    at (lane, group)."""
    if step is None:
        return (n_lanes,), (1,), lambda l, s: (l,)
    inner = runs[1] // step[1]
    return ((n_lanes, runs[0] // step[0] * inner), (step[0], 1, step[1]),
            lambda l, s: (s // inner, l, s % inner))


def _spec(step, shape, index):
    """``BlockSpec(shape)`` whose index is ``index(lane, group, *the
    kernel's own grid indices and prefetched rows)``: group 0 where no
    grid axis of groups is there (``step`` None)."""
    if step is None:
        return pl.BlockSpec(shape, lambda l, *rest: index(l, 0, *rest))
    return pl.BlockSpec(shape, index)


def _hist_lanes_impl(tables, codes_t, stats_t, state, *pick, level,
                     n_slots, n_bins, parts, tile, interpret, runs=(1, 1)):
    """``tables (L, 4, items)`` int32, ``codes_t (L, d_pad, n_pad)`` uint8,
    ``stats_t (L, parts * S8, n_pad)`` bfloat16, ``state (L, 8, n_pad)``
    int32 -> ``(L, 2^level, blocks x slots a block, S8, n_bins)`` float32,
    cumulative over the bins.  The slots are the ``n_slots = d`` features,
    or with one more operand, ``pick (L, blocks, 2^level x slots a block,
    d_pad)`` bfloat16 (a node's slot's feature, one-hot), each node's own
    ``n_slots``.

    With ``runs = (outer, inner)`` (not with ``pick``) every lane's items
    and code tiles are shared by ``outer x inner`` lanes of statistics and
    rows, ``stats_t (outer, L, inner, parts * S8, n_pad)`` and ``state
    (outer, L, inner, 8, n_pad)`` -> ``(outer, L, inner, 2^level, ...)``;
    a grid step takes as many of them as ``SHARED_VMEM`` holds."""
    (pick,) = pick or (None,)
    n_lanes, _, n_items = tables.shape
    d_pad, r = codes_t.shape[1], stats_t.shape[-2]
    s8 = r // parts
    k_nodes, n_groups = _group_shape(level)
    block, n_blocks = _slot_blocks(n_slots, pick is not None)
    # a block's own features, or every feature whatever the block
    code_rows = block if pick is None else d_pad
    step = None
    if runs != (1, 1):
        assert pick is None, "a row's slot codes are its own lane's"
        step = _step_runs(runs, _hist_lane_bytes(k_nodes, block, s8, parts,
                                                 n_bins, tile))
    lanes, own, own_at = _lane_axes(n_lanes, runs, step)

    def at(lane, i):
        return lane * n_items + i

    def group(g, lane, i):
        return jnp.minimum(g[at(lane, i)], n_groups - 1)

    in_specs = [
        _spec(step, (1, code_rows, tile),
              lambda l, s, fb, i, t, g, lo, hi: (
                  l, fb if pick is None else 0, t[at(l, i)])),
        _spec(step, own + (r, tile),
              lambda l, s, fb, i, t, g, lo, hi: own_at(l, s) + (
                  0, t[at(l, i)])),
        _spec(step, own + (8, tile),
              lambda l, s, fb, i, t, g, lo, hi: own_at(l, s) + (
                  0, t[at(l, i)])),
    ]
    operands = [codes_t, stats_t, state]
    if pick is not None:
        in_specs.append(_spec(
            step, (1, 1, k_nodes * block, d_pad),
            lambda l, s, fb, i, t, g, lo, hi: (l, fb, group(g, l, i), 0)))
        operands.append(pick)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=lanes + (n_blocks, n_items),
        in_specs=in_specs,
        out_specs=_spec(
            step, own + (k_nodes, block, s8, n_bins),
            lambda l, s, fb, i, t, g, lo, hi: own_at(l, s) + (
                group(g, l, i), fb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block, tile), jnp.int32)],
    )
    kernel = functools.partial(
        _hist_kernel, n_items=n_items, n_slots=n_slots, s8=s8, parts=parts,
        n_bins=n_bins, k_nodes=k_nodes, n_groups=n_groups,
        offset=2 ** level - 1, step_runs=step)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            stats_t.shape[:-2] + (2 ** level, n_blocks * block, s8, n_bins),
            jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(lanes)
            + ("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="sst_tree_histogram",
    )(*_flat(tables), *operands)


def _route_lanes_impl(tables, words, codes_t, state, *, level, tile,
                      interpret, runs=(1, 1)):
    """``words (L, 2^level)`` int32 (a node's bin, whether it splits, its
    feature) -> the rows' new ``state (L, 8, n_pad)``; with ``runs`` as
    the histograms' (``words (outer, L, inner, 2^level)``)."""
    n_lanes, _, n_items = tables.shape
    d_pad = codes_t.shape[1]
    k_nodes, n_groups = _group_shape(level)
    # a lane of a shared tile holds its rows' state in and out, each twice
    step = None if runs == (1, 1) else _step_runs(runs, 2 * 2 * 8 * tile * 4)
    lanes, own, own_at = _lane_axes(n_lanes, runs, step)

    def at(lane, i):
        return lane * n_items + i

    def rows_at(l, s, i, t, g, lo, hi, w):
        return own_at(l, s) + (0, t[at(l, i)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=lanes + (n_items,),
        in_specs=[
            _spec(step, (1, d_pad, tile),
                  lambda l, s, i, t, g, lo, hi, w: (l, 0, t[at(l, i)])),
            _spec(step, own + (8, tile), rows_at),
        ],
        out_specs=_spec(step, own + (8, tile), rows_at),
        scratch_shapes=[pltpu.VMEM((d_pad, tile), jnp.int32)],
    )
    kernel = functools.partial(
        _route_kernel, n_items=n_items, k_nodes=k_nodes, n_groups=n_groups,
        offset=2 ** level - 1, runs=runs, n_lanes=n_lanes, step_runs=step)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(state.shape, jnp.int32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(lanes) + ("arbitrary",)),
        input_output_aliases={6: 0},    # rows no item names keep theirs
        interpret=interpret,
        name="sst_tree_route",
    )(*_flat(tables), words.reshape(-1), codes_t, state)


#: each kernel's operands that are a lane's own where lanes share code
#: tiles (statistics, words, the rows' state); the others (items, codes)
#: are the tiles'
_OWN = {_hist_lanes_impl: (2, 3), _route_lanes_impl: (1, 3)}


@functools.lru_cache(maxsize=None)
def _lanes(impl, runs=(1, 1), **static):
    """A kernel call with the lanes in front of every operand, and
    ``jax.vmap`` of it as one call over the merged lanes.  The call is a
    ``jit`` of its own so that its trace is kept by shape: batching a
    ``while_loop``'s body walks it to a fixed point, which traced every
    kernel of every level four times where two will do (the lane-less
    shapes once, the merged lanes' once).

    Where the ``vmap`` batches neither the items nor the codes (a
    booster's runs of one fold; a stage's trees a class), its lanes share
    those: the axis goes into ``runs`` (the kernels' ``(outer, inner)``
    axes around the lanes of a lane's own operands, ``_OWN``), not into
    the lanes, and a grid step reads one code tile for all of them.  It
    goes outside the lanes where there are lanes, inside where there is
    one, so that classes, folds and runs nested in that order transpose
    nothing.  Not where a level's histograms are at each node's own
    features: a row's slot codes are then its own lane's."""
    call = jax.custom_batching.custom_vmap(
        jax.jit(functools.partial(impl, runs=runs, **static)))
    own = _OWN[impl]

    @call.def_vmap
    def _(axis_size, in_batched, *operands):
        n_lanes = operands[0].shape[-3]         # tables (.., L, 4, items)
        outer, inner = runs

        def by_run(x, batched):
            """A lane's own operand as ``(axis, outer, L, inner, ...)``."""
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            tail = x.shape[2 if runs == (1, 1) else 4:]
            return x.reshape((axis_size, outer, n_lanes, inner) + tail)

        if len(operands) == 4 and not any(
                in_batched[j] for j in range(4) if j not in own):
            new = ((axis_size * outer, inner) if n_lanes > 1
                   else (1, axis_size * outer * inner))
            merged = []
            for j, (x, batched) in enumerate(zip(operands, in_batched)):
                if j in own:
                    x = by_run(x, batched)
                    x = x.reshape((new[0], n_lanes, new[1]) + x.shape[4:])
                merged.append(x)
            out = _lanes(impl, runs=new, **static)(*merged)
            out = out.reshape((axis_size, outer, n_lanes, inner)
                              + out.shape[3:])
        else:
            merged = []
            for j, (x, batched) in enumerate(zip(operands, in_batched)):
                if j in own and runs != (1, 1):
                    x = by_run(x, batched)
                    if outer > 1:
                        x = jnp.moveaxis(x, 0, 1)
                    merged.append(x.reshape(
                        (outer, axis_size * n_lanes, inner) + x.shape[4:]))
                    continue
                if not batched:
                    x = jnp.broadcast_to(x, (axis_size,) + x.shape)
                merged.append(
                    x.reshape((axis_size * x.shape[1],) + x.shape[2:]))
            out = call(*merged)
            if runs == (1, 1):
                return out.reshape((axis_size, -1) + out.shape[1:]), True
            tail = out.shape[3:]
            if outer > 1:
                out = jnp.moveaxis(out.reshape(
                    (outer, axis_size, n_lanes, inner) + tail), 1, 0)
            out = out.reshape((axis_size, outer, n_lanes, inner) + tail)
        if runs == (1, 1):
            out = out.reshape((axis_size, n_lanes) + out.shape[4:])
        return out, True

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


def split_parts(stats, s8, parts, axis=1):
    """``(n, S)`` float32 -> ``(n, parts * s8)`` bfloat16: the value's
    first bfloat16 part, or ``[hi | mid | lo]`` with ``hi + mid + lo ==
    stats`` to the bit.  With ``axis=0`` the statistics are rows: ``(S,
    n)`` -> ``(parts * s8, n)``, the kernels' layout."""
    pad = [(0, 0), (0, 0)]
    pad[axis] = (0, s8 - stats.shape[axis])
    rest = jnp.pad(stats.astype(jnp.float32), pad)
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
    return jnp.concatenate(out, axis=axis)


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


class RowOrder(NamedTuple):
    """A lane's rows in the kernels' level-0 order, made once for a loop
    of trees whose rows that count are the same in every tree: those rows
    first, then the others, each part in the caller's order (what the
    level-0 sort of every such tree computed)."""
    perm: jnp.ndarray        # (n,) int32: the caller's row at each place
    inv: jnp.ndarray         # (n,) int32: the place of each caller's row
    code_words: jnp.ndarray  # (n, d_pad / 4) int32: packed codes, in order
    codes_t: jnp.ndarray     # (d_pad, n_pad) uint8: the kernels' codes
    bounds: jnp.ndarray      # (3,) int32: 0, the rows that count, n

    def rows(self, x):
        """``x``, rows in the caller's order, in the order."""
        return take_rows(x, self.perm)

    def back(self, x, axis=0):
        """``x``, rows along ``axis`` in the order, in the caller's."""
        return jnp.moveaxis(take_rows(jnp.moveaxis(x, axis, 0), self.inv),
                            0, axis)


def launch_order(codes, w, tile=ROW_TILE):
    """:class:`RowOrder` of the rows whose weight ``w`` is not 0: one
    stable sort, one gather of the packed codes, their transpose."""
    n = codes.shape[0]
    n_pad = _round_up(n, tile)
    with jax.named_scope("sst.tree.partition"):
        counted = w != 0
        _, perm = jax.lax.sort(
            (jnp.logical_not(counted).astype(jnp.int32),
             jnp.arange(n, dtype=jnp.int32)), num_keys=1)
        n_counted = jnp.sum(counted, dtype=jnp.int32)
        inv = jnp.where(
            counted, jnp.cumsum(counted, dtype=jnp.int32) - 1,
            n_counted + jnp.cumsum(jnp.logical_not(counted),
                                   dtype=jnp.int32) - 1)
        # written once, and not again inside the gather
        code_words = take_rows(
            jax.lax.optimization_barrier(pack_codes(codes)), perm)
        codes_t = jax.lax.bitcast_convert_type(
            jnp.pad(code_words, ((0, n_pad - n), (0, 0))),
            jnp.uint8).reshape(n_pad, -1).T
        bounds = jnp.stack([jnp.zeros((), jnp.int32), n_counted,
                            jnp.asarray(n, jnp.int32)])
    return RowOrder(perm, inv, code_words, codes_t, bounds)


class GroupedLevels:
    """A tree's rows in the kernels' order: sorted every
    ``LEVELS_PER_SORT`` levels by (counts for nothing, node), their codes
    and statistics gathered into that order, the node each sits in beside
    them.

    With ``order`` (a :class:`RowOrder` of the rows that count, which are
    then the rows of nonzero weight) the rows and their statistics arrive
    in that order, which is level 0's: no sort and no gather there, the
    statistics laid out for the kernels by elementwise work, and
    :meth:`leaves` in the order's own layout.  Levels 5 and 10 sort
    relative to it."""

    def __init__(self, codes, stats, n_bins, integer_stats=False,
                 tile=ROW_TILE, interpret=False, order=None):
        self.n, self.d = codes.shape
        self.n_bins = n_bins
        self.parts = 1 if integer_stats else 3
        self.tile, self.interpret = tile, interpret
        self.n_tiles = -(-self.n // tile)
        self.n_pad = self.n_tiles * tile
        self.stats = stats
        # the caller's row at each place: None while the rows sit where
        # the caller (or its order) has them
        self.perm = None
        self.heap = jnp.zeros((self.n,), jnp.int32)
        if order is None:
            with jax.named_scope("sst.tree.partition"):
                # the two tables the sorts gather from, each written once
                # a tree and not again inside a gather
                self.code_words, self.stat_words = \
                    jax.lax.optimization_barrier(
                        (pack_codes(codes), pack_stats(stats, self.parts)))
                counted = jnp.any(stats != 0, axis=1)
            self.sorted_level = None
        else:
            assert order.codes_t.shape[1] == self.n_pad, "another tile"
            with jax.named_scope("sst.tree.partition"):
                self.code_words, self.codes_t = order.code_words, \
                    order.codes_t
                self.bounds, self.sorted_level = order.bounds, 0
                self.stat_words = None          # made where a sort needs it
                s8 = _round_up(stats.shape[1], 8)
                self.stats_t = jnp.pad(
                    split_parts(stats.T, s8, self.parts, axis=0),
                    ((0, 0), (0, self.n_pad - self.n)))
                counted = jnp.arange(self.n) < order.bounds[1]
        # 0: the row sits in a leaf; on its way down: 2 where it counts,
        # 1 where it counts for nothing
        self.live = 1 + counted.astype(jnp.int32)

    # -- the order of the rows ---------------------------------------------
    def _sort(self, level):
        """Rows into (counts for nothing, node) order; rows in a leaf
        last."""
        n_keys = 2 ** level
        with jax.named_scope("sst.tree.partition"):
            if self.stat_words is None:
                self.stat_words = jax.lax.optimization_barrier(
                    pack_stats(self.stats, self.parts))
            local = self.heap - (n_keys - 1)
            key = jnp.where(
                self.live > 0,
                jnp.where(self.live > 1, 0, n_keys) + local,
                2 * n_keys).astype(jnp.int32)
            perm = (jnp.arange(self.n, dtype=jnp.int32) if self.perm is None
                    else self.perm)
            key, self.perm, self.heap, self.live = jax.lax.sort(
                (key, perm, self.heap, self.live), num_keys=1)
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
        if level % LEVELS_PER_SORT == 0 and level != self.sorted_level:
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
        """The node each row ended in, in the caller's order (the
        order's, where the rows came in one)."""
        if self.perm is None:       # no sort moved them
            return self.heap
        with jax.named_scope("sst.tree.route"):
            return jax.lax.sort((self.perm, self.heap), num_keys=1)[1]


def on_tpu():
    """Whether the programs being traced run on a TPU: the kernels'
    platform."""
    return jax.default_backend() == "tpu"


def row_order(codes, w):
    """The rows of nonzero weight ``w`` first, made once for a loop of
    trees that all count those rows (:class:`RowOrder`), on the kernels'
    platform; None on the plain form's, whose rows stay where the caller
    has them."""
    return launch_order(codes, w) if on_tpu() else None


def levels_of(codes, stats, n_bins, integer_stats=False, order=None):
    """A tree's rows in the form the backend's platform runs, in the
    :func:`row_order` ``order`` where there is one."""
    if on_tpu():
        return GroupedLevels(codes, stats, n_bins, integer_stats,
                             order=order)
    return PlainLevels(codes, stats, n_bins, integer_stats)


def row_sorts(depth, trees, lanes, ordered):
    """Sorts of rows into node order that ``trees`` trees of ``depth``
    levels on ``lanes`` lanes execute: a tree's at every
    ``LEVELS_PER_SORT``-th level, but level 0's once a lane (its
    :class:`RowOrder`) where the trees grow in one (``ordered``); none on
    the plain form."""
    kernels = on_tpu()
    first = LEVELS_PER_SORT if ordered else 0
    per_tree = len(range(first, int(depth), LEVELS_PER_SORT)) if kernels \
        else 0
    return trees * per_tree + (lanes if ordered and kernels else 0)


def lanes_per_mask(lanes, trees=1, n_stats=2, parts=3):
    """Lanes one ``code <= bin`` mask of the histogram kernel serves at a
    tree's first level, where ``lanes`` lanes of ``trees`` trees each (a
    fold's runs, a stage's classes) share their code tiles: as many of
    them as one grid step holds (deeper levels hold fewer); 1 where
    every lane reads its own tiles; 0 on the plain form, which builds no
    mask."""
    if not on_tpu():
        return 0
    step = _step_runs((int(lanes), int(trees)), _hist_lane_bytes(
        1, FEATURE_BLOCK, _round_up(n_stats, 8), parts, 256, ROW_TILE))
    return step[0] * step[1]


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


def row_bytes(n_features, n_stats, integer_stats, own_codes=True):
    """Bytes of one row as the kernels' sorted copy holds it: its codes
    (not where the lane reads a :class:`RowOrder`'s, whose tiles the
    lanes of its fold share: ``own_codes`` False), the bfloat16 parts of
    its statistics, its node and whether it is in a leaf (a tile of 8
    words)."""
    parts = 1 if integer_stats else 3
    return (_padded_features(n_features) * bool(own_codes)
            + parts * _round_up(n_stats, 8) * 2 + 8 * 4)


def order_row_bytes(n_features):
    """Bytes of one row as a :class:`RowOrder` holds it: its codes packed
    and transposed, its place both ways."""
    return 2 * _padded_features(n_features) + 2 * 4
