"""Share of the traced search's wall that the search API spent outside the
chunk pipeline (planning, building the grid, assembling ``cv_results_``):
(``attribution.wall_s`` - ``pipeline.wall_s``) over the search's wall."""


def read(ctx):
    rep = ctx["report"]
    try:
        outside = rep["attribution"]["wall_s"] - rep["pipeline"]["wall_s"]
    except KeyError:
        return None
    return 100.0 * max(outside, 0.0) / ctx["search_walls"][0]
