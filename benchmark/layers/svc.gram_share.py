"""``sst.svc.gram`` + ``sst.svc.power_step`` device seconds over the
seconds under all ``sst.svc.*`` and ``sst.box_fista.*`` scopes of the
traced search: what building a kernel matrix and its step costs, once for
every candidate today, though candidates of one gamma share both."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    seconds = ctx["load_named"]("layers/svc.device_s:seconds")
    total = seconds(scopes)
    if total <= 0.0:
        return None
    return 100.0 * seconds(scopes, ("sst.svc.gram",
                                    "sst.svc.power_step")) / total
