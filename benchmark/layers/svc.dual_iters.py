"""Dual iterations the traced search executed: the sum over candidates of
``search_report["dual_iters_per_candidate"]`` (a candidate's folds and
pairs advance together, so one count a candidate)."""


def read(ctx):
    iters = ctx["load_named"]("work_svc:candidate_iters")(
        ctx["report"], ctx["n_candidates"])
    return None if iters is None else sum(iters)
