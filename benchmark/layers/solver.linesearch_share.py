"""``glm_lbfgs.linesearch`` device seconds over the seconds under all
``glm_lbfgs.*`` scopes in the traced search: the share of the solver's
time in the pass over the ``[ls_trials, n, B(, k)]`` trial tensors."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    solver = ctx["load_named"]("scopes:solver_seconds")(scopes)
    if solver <= 0.0:
        print("solver.linesearch_share: no glm_lbfgs.* scope in the trace",
              flush=True)
        return None
    return 100.0 * scopes["scopes"].get("glm_lbfgs.linesearch", 0.0) / solver
