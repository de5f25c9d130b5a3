"""Seconds of persistent-cache retrieval up to the end of the traced search,
each load counted once (``search_report["process"]["cache_load_s"]``; the
window loads nothing, so these are set-up's).  ``None`` on a program
without the block."""


def read(ctx):
    process = ctx["report"].get("process")
    if not process:
        return None
    return process["cache_load_s"]
