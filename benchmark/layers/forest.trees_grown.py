"""Trees the traced search's launches grew: the sum of
``search_report["trees_grown_per_launch"]`` (a launch's forests, one a
fold, x the trees its loop ran: a tree that several candidates of the
launch read at their own ``n_estimators`` is grown, and counted, once).
A program from before that counter grows every candidate's own trees on
every fold, so there it is the sum of ``trees_per_candidate`` x folds.
``None`` where the report has neither (a search through another family)."""


def read(ctx):
    report = ctx["report"]
    grown = report.get("trees_grown_per_launch")
    if grown:
        return sum(grown)
    trees = report.get("trees_per_candidate")
    if not trees or min(trees) < 0:
        return None
    return sum(trees) * (ctx["fits_per_search"] // ctx["n_candidates"])
