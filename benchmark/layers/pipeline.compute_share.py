"""``compute_wall_s`` over ``wall_s`` of the chunk pipeline: the program's
own HOST-CLOCK estimate of how long the device was occupied.  Named for
what it is; the device's idle share comes from the profiler's trace."""


def read(ctx):
    try:
        pipe = ctx["report"]["pipeline"]
        return 100.0 * pipe["compute_wall_s"] / pipe["wall_s"]
    except (KeyError, ZeroDivisionError):
        return None
