"""Device seconds of the traced search under the tree grower's named
scopes, ``sst.tree.*`` (``scopes.py``): bootstrap, partition, histogram,
split, route and predict — the forest family's own time, without the
scoring epilogue (``sst.score``) and the operations that carry only the
launch's name (``sst.fit``)."""

PREFIX = "sst.tree."


def seconds(scopes, names=None):
    """Seconds under every ``sst.tree.*`` scope, or under ``names`` only."""
    return sum(s for name, s in scopes["scopes"].items()
               if (name.startswith(PREFIX) if names is None
                   else name in names))


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    total = seconds(scopes)
    if total <= 0.0:
        print("forest.device_s: no sst.tree.* scope on any device "
              "operation (a program from before the scopes, or a search "
              "through another family)", flush=True)
        return None
    return total
