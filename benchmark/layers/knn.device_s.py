"""Device seconds of the traced search under the neighbour search's named
scopes, ``sst.knn.*``: a row block's distances (``sst.knn.distance``), its
selection (``sst.knn.select``) and its votes, with each task's read of them
(``sst.knn.vote``) — the k-NN family's own time, without the scoring
epilogue (``sst.score``) and the operations that carry only the launch's
name (``sst.fit``).  ``None`` where no device operation carries an
``sst.knn.*`` scope: a program from before them, or a search through another
family."""

PREFIX = "sst.knn."


def seconds(scopes, names=(PREFIX,)):
    """Seconds under the scopes that start with one of ``names``."""
    return sum(s for name, s in scopes["scopes"].items()
               if name.startswith(tuple(names)))


def search(ctx):
    """(the reduced scopes, seconds of the whole neighbour search) of the
    traced search, or ``None`` where it ran none on a device."""
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None or seconds(scopes) <= 0.0:
        return None
    return scopes, seconds(scopes)


def read(ctx):
    found = search(ctx)
    if found is None:
        if ctx.get("trace") is not None:
            print("knn.device_s: no sst.knn.* scope on any device operation "
                  "(a program from before them, or a search through "
                  "another family)", flush=True)
        return None
    return found[1]
