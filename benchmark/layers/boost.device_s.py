"""Device seconds of the traced search under the boosting stage loop's
named scopes: ``sst.boost.*`` (a stage's gradients and row weights before
its trees, the update of F after them) and the tree grower's ``sst.tree.*``
(partition, histogram, split, route, predict) — the boosting family's own
time, without the scoring epilogue (``sst.score``) and the operations that
carry only the launch's name (``sst.fit``).  ``None`` where no device
operation carries an ``sst.boost.*`` scope: a program from before them, or
a search through another family."""

STAGE, TREE = "sst.boost.", "sst.tree."


def seconds(scopes, prefixes=(STAGE, TREE)):
    """Seconds under the scopes that start with one of ``prefixes``."""
    return sum(s for name, s in scopes["scopes"].items()
               if name.startswith(tuple(prefixes)))


def stage_loop(ctx):
    """(the reduced scopes, seconds of the whole stage loop) of the traced
    search, or ``None`` where it ran no boosting stage on a device."""
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None or seconds(scopes, (STAGE,)) <= 0.0:
        return None
    return scopes, seconds(scopes)


def share(ctx, *prefixes):
    """100 x the seconds under ``prefixes`` over the stage loop's."""
    found = stage_loop(ctx)
    if found is None:
        return None
    scopes, total = found
    return 100.0 * seconds(scopes, prefixes) / total


def read(ctx):
    found = stage_loop(ctx)
    if found is None:
        if ctx.get("trace") is not None:
            print("boost.device_s: no sst.boost.* scope on any device "
                  "operation (a program from before the scopes, or a "
                  "search through another family)", flush=True)
        return None
    return found[1]
