"""Device launches of the traced search (calibration launches included)."""


def read(ctx):
    try:
        return ctx["report"]["pipeline"]["n_launches"]
    except KeyError:
        return None
