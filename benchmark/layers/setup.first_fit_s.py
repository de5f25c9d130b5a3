"""Seconds of the process's first ``fit``, the runner's warm-up search
(``fits[0]`` of ``search_report["process"]``): a search's wall plus what
building cost it.  ``None`` on a program without the block."""


def read(ctx):
    process = ctx["report"].get("process")
    if not process or not process.get("fits"):
        return None
    first = process["fits"][0]
    if first["t1_s"] is None:
        return None
    return first["t1_s"] - first["t0_s"]
