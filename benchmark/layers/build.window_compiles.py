"""Programs that went through jax's back end (compiled, or loaded from the
persistent cache) inside the measured window; must read 0."""


def read(ctx):
    return ctx["window_compile"]["programs"]
