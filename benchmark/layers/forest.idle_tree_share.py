"""Lockstep tree slots the traced search spent on lanes that were already
done: 1 - (sum of ``trees_per_candidate`` x folds) / (sum of
``tree_slots_per_launch``).  A launch carries every lane through its
largest ``n_estimators``; padded lanes count as spent too.  ``None`` where
the report has no such counters (a program from before them)."""


def read(ctx):
    report = ctx["report"]
    trees = report.get("trees_per_candidate")
    slots = report.get("tree_slots_per_launch")
    if not trees or not slots or min(trees) < 0 or sum(slots) <= 0:
        return None
    folds = ctx["fits_per_search"] // ctx["n_candidates"]
    return 100.0 * (1.0 - sum(trees) * folds / sum(slots))
