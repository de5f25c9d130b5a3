"""1 - union of device-operation intervals over the traced window, from
the profiler's ``.xplane.pb``; nothing where there was no device plane."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
