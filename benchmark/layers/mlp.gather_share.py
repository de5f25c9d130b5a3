"""``sst.mlp.gather`` device seconds (a step's minibatch: its rows and
targets gathered from the fold's training rows, once for every candidate of
a fold) over the seconds under all ``sst.mlp.*`` scopes of the traced
search.  The trace can separate it: the gathers are operations of their
own, where the optimiser is fused into the backward products (so a share
of ``sst.mlp.update`` would read the step-size scalars only)."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    seconds = ctx["load_named"]("layers/mlp.device_s:seconds")
    total = seconds(scopes)
    if total <= 0.0:
        return None
    return 100.0 * seconds(scopes, ("sst.mlp.gather",)) / total
