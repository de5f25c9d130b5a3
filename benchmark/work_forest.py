"""Operations and bytes a forest search *requires*, from its shapes and the
trees it grew.  Kept with the benchmark so that no later change to the
program can move them.

What is counted is what the algorithm needs and no more: a binned tree
grown level by level on a bootstrap of the fold's training rows.  A level
of one tree reads each in-bag training row once: its ``d`` code bytes and
its statistics (the bootstrap count and the class, 4 bytes each), and adds
the row's ``1 + n_classes`` statistics into one bin of each of the ``d``
features: ``(1 + n_classes) * d`` additions a row a level.  It writes the
level's chosen splits, 8 bytes a node.  The histograms may stay on the chip
and count for nothing, as do the cumulative sums and gains over them; lanes
carried past their own tree count, padded lanes, padded tiles, the fold's
test rows and out-of-bag rows are the program's choice and count for
nothing.  The in-bag rows of a tree are counted at their expectation under
the Poisson(1) bootstrap the configuration states: ``1 - 1/e`` of the
fold's training rows (all of them without bootstrap).

The trees come from the program's own record: ``trees_per_candidate`` (in
``cv_results_`` order) and each compile group's depth
(``search_report["per_group"][g]["static_params"]`` and
``["geometry"]["groups"][g]["n_candidates"]``; a grid over ``max_depth`` and
``n_estimators`` lists a depth's candidates side by side, in the order the
groups were met).

``forest_histograms`` is the configuration's ``work``: it takes the
configuration, the number of candidates and one search's ``search_report``
and returns ``flops``, ``fit_flops`` and ``fit_bytes`` (the last two: the
level histograms alone, what ``sst.tree.histogram`` runs) — or ``None``
where the report does not name every candidate's trees and depth.
"""

from __future__ import annotations

import ast
import math

MAX_COMPILED_DEPTH = 10      # the grower's bound: deeper values are capped
STAT_BYTES = 8               # a row's bootstrap count and class
SPLIT_BYTES = 8              # a node's (feature, bin)


def group_depths(report, n_candidates):
    """``[(depth, candidates), ...]`` of the search's compile groups, or
    ``None`` where the report does not hold them for every candidate."""
    groups = report.get("geometry", {}).get("groups") or []
    per_group = report.get("per_group") or {}
    out = []
    for g in groups:
        rec = per_group.get(g["group"], per_group.get(str(g["group"])))
        try:
            static = ast.literal_eval(rec["static_params"])
            depth = static["max_depth"]
        except (TypeError, KeyError, ValueError, SyntaxError):
            return None
        if not isinstance(depth, int):
            return None
        out.append((min(depth, MAX_COMPILED_DEPTH), int(g["n_candidates"])))
    if sum(n for _, n in out) != n_candidates:
        return None
    return out


def tree_levels(report, n_candidates):
    """(trees, tree-levels, nodes that split at most) of one fold of the
    search: each candidate's own trees, times its depth, and times the
    ``2^depth - 1`` nodes above a tree's last level."""
    trees = report.get("trees_per_candidate")
    depths = group_depths(report, n_candidates)
    if not trees or depths is None or len(trees) != n_candidates \
            or min(trees) < 0:
        return None
    at = n_trees = n_levels = n_nodes = 0
    for depth, n_cand in depths:
        grown = sum(int(t) for t in trees[at:at + n_cand])
        at += n_cand
        n_trees += grown
        n_levels += grown * depth
        n_nodes += grown * (2 ** depth - 1)
    return n_trees, n_levels, n_nodes


def forest_histograms(config, n_candidates, report):
    data = config["data"]
    n, d, k = data["n_samples"], data["n_features"], data["n_classes"]
    n_folds = data["n_folds"]
    n_test = n // n_folds
    n_train = n - n_test
    counted = tree_levels(report, n_candidates)
    if counted is None:
        return None
    n_trees, n_levels, n_nodes = counted
    bootstrap = config["estimator"]["params"].get("bootstrap", True)
    in_bag = n_train * (1.0 - math.exp(-1.0) if bootstrap else 1.0)
    fit_flops = float(n_folds) * n_levels * in_bag * (1 + k) * d
    fit_bytes = float(n_folds) * (n_levels * in_bag * (d + STAT_BYTES)
                                  + n_nodes * SPLIT_BYTES)
    # every tree's leaf distribution added to the votes of all n rows, and
    # one argmax of the test rows' votes a fit
    vote_flops = float(n_folds) * n_trees * n * k
    score_flops = float(n_folds) * n_candidates * n_test * k
    return {"flops": fit_flops + vote_flops + score_flops,
            "fit_flops": fit_flops, "fit_bytes": fit_bytes}
