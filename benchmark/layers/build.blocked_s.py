"""Seconds that work waited for building, up to the end of the traced
search (``search_report["process"]["build_blocked_s"]``): the program's
``compile.wait`` spans plus every build that ran on a thread inside ``fit``
other than the compile-ahead one.  ``None`` on a program without the
block."""


def read(ctx):
    process = ctx["report"].get("process")
    if not process:
        return None
    return process["build_blocked_s"]
