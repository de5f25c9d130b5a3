"""Lane-stages the traced search executed: the sum over its launches of
``search_report["tree_steps_per_launch"]`` (a launch's lanes, padding
included, x the largest ``n_estimators`` among them: every lane is carried
through the launch's longest loop).  4 375 in the cell where no lane idles
(25 lanes x 25 + 50 + 100); 7 500 for one lockstep launch of all 75.
``None`` where the report has no such counter (a program from before it, or
a search through another family)."""


def executed(report):
    steps = report.get("tree_steps_per_launch")
    if not steps or min(steps) < 0:
        return None
    return sum(steps)


def read(ctx):
    return executed(ctx["report"])
