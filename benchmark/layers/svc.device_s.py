"""Device seconds of the traced search under the kernel-dual path's named
scopes, ``sst.svc.*`` and ``sst.box_fista.*`` (``scopes.py``): the SVC
family's own time, without the scoring epilogue (``sst.score``) and the
operations that carry only the launch's name (``sst.fit``)."""

PREFIXES = ("sst.svc.", "sst.box_fista.")


def seconds(scopes, prefixes=PREFIXES):
    return sum(s for name, s in scopes["scopes"].items()
               if name.startswith(prefixes))


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    total = seconds(scopes)
    if total <= 0.0:
        print("svc.device_s: no sst.svc.* or sst.box_fista.* scope on any "
              "device operation (a program from before the scopes, or a "
              "search through another solver)", flush=True)
        return None
    return total
