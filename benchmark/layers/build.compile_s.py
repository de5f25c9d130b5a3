"""Seconds of set-up spent building programs: trace + lower + XLA compile,
or the persistent-cache load that replaced the compile, summed over
threads (the compile-ahead thread overlaps the device, so this can exceed
the wall it cost).  Moves ``setup_s``."""


def read(ctx):
    return ctx["setup_compile"]["compile_s"]
