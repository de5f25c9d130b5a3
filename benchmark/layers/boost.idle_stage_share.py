"""Lane-stages the traced search spent on lanes that were already done:
1 - (sum of ``trees_per_candidate`` x folds) / (sum of
``tree_steps_per_launch``).  0 where every launch is a run of one count;
41.7 in the cell for one lockstep launch of all 75 lanes; padded lanes
count as spent too.  ``None`` where the report has no such counters."""


def read(ctx):
    report = ctx["report"]
    executed = ctx["load_named"]("layers/boost.tree_steps:executed")(report)
    own = report.get("trees_per_candidate")
    if not executed or not own or min(own) < 0:
        return None
    folds = ctx["fits_per_search"] // ctx["n_candidates"]
    return 100.0 * (1.0 - sum(own) * folds / executed)
