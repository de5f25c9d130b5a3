"""``sst.knn.select`` device seconds (a row block's own-fold columns masked
out and one top-k of the 30 nearest over every row) over the seconds of the
whole neighbour search (``knn.device_s``) in the traced search."""


def read(ctx):
    found = ctx["load_named"]("layers/knn.device_s:search")(ctx)
    if found is None:
        return None
    scopes, total = found
    select = ctx["load_named"]("layers/knn.device_s:seconds")(
        scopes, ("sst.knn.select",))
    return 100.0 * select / total
