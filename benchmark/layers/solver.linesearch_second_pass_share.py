"""Iterations of the traced search in which ``glm_lbfgs_batched`` ran the
second stage of its line search, over all its iterations: the sum of
``search_report["linesearch_second_pass_per_launch"]`` over the sum of
``solver_iters_per_launch``.  ``None`` where the report has no such
counter (a program whose line search is not staged)."""


def read(ctx):
    report = ctx["report"]
    second = report.get("linesearch_second_pass_per_launch")
    iters = report.get("solver_iters_per_launch")
    if second is None or not iters or len(second) != len(iters):
        return None
    print("solver.linesearch_second_pass_share: second passes a launch "
          f"{list(second)} of iterations {list(iters)}", flush=True)
    return 100.0 * sum(second) / sum(iters)
