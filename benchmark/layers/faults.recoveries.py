"""Retries + bisections + host fallbacks + timeouts over all searches of
the window; 0 on a healthy chip."""


def read(ctx):
    total = 0
    for rep in ctx["reports"]:
        faults = rep.get("faults")
        if faults is None:
            return None
        total += sum(int(faults.get(k, 0)) for k in (
            "retries", "bisections", "host_fallbacks", "timeouts"))
    return total
