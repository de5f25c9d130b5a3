"""``sst.box_fista.project`` device seconds (the gradient step and the
bisection onto box and hyperplane) over the seconds under all
``sst.svc.*`` and ``sst.box_fista.*`` scopes of the traced search."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    total = ctx["load_named"]("layers/svc.device_s:seconds")(scopes)
    if total <= 0.0:
        return None
    return 100.0 * scopes["scopes"].get("sst.box_fista.project", 0.0) / total
