"""``sst.tree.histogram`` device seconds (the level histograms: the grouped
one-hot product kernel) over the seconds under all ``sst.tree.*`` scopes of
the traced search."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    seconds = ctx["load_named"]("layers/forest.device_s:seconds")
    total = seconds(scopes)
    if total <= 0.0:
        return None
    return 100.0 * seconds(scopes, ("sst.tree.histogram",)) / total
