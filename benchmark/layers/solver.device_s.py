"""Device seconds of the traced search under any ``glm_lbfgs.*`` named
scope (``scopes.py``): the solver's own time, without the scoring epilogue
(``sst.score``) and the operations outside every scope."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    seconds = ctx["load_named"]("scopes:solver_seconds")(scopes)
    if seconds <= 0.0:
        print("solver.device_s: no glm_lbfgs.* scope on any device "
              "operation (an executable compiled before the scopes "
              "existed, or a search through another solver)", flush=True)
        return None
    return seconds
