"""Lanes one ``code <= bin`` mask of the histogram kernel served in the
traced search: the fewest over its launches of
``search_report["hist_lanes_per_mask"]`` (a launch's fact, at a tree's
first level).  5 in the cell, where a fold's 5 runs hold the same code
tiles and the kernel builds each mask once for all of them; 1 where every
lane reads its own tiles, as the forests' and a subsampled booster's do; 0
on the plain form (no TPU).  ``None`` where the report has no such row (a
program from before it, or a search through another family)."""


def read(ctx):
    lanes = ctx["report"].get("hist_lanes_per_mask")
    if not lanes:
        return None
    return min(lanes)
