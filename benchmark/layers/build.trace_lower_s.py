"""Seconds of tracing and lowering up to the end of the traced search, each
thread's wall counted once (``trace_s + lower_s`` of
``search_report["process"]``): all that an AOT artifact store could ever
take out of set-up.  ``None`` on a program without the block."""


def read(ctx):
    process = ctx["report"].get("process")
    if not process:
        return None
    return process["trace_s"] + process["lower_s"]
