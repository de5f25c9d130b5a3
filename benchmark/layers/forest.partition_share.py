"""``sst.tree.partition`` + ``sst.tree.route`` device seconds (a level's
rows sorted into node order and gathered for the kernel; every row sent to
a child of its node) over the seconds under all ``sst.tree.*`` scopes of
the traced search: what moving rows costs beside the histograms."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    seconds = ctx["load_named"]("layers/forest.device_s:seconds")
    total = seconds(scopes)
    if total <= 0.0:
        return None
    return 100.0 * seconds(
        scopes, ("sst.tree.partition", "sst.tree.route")) / total
