"""Seconds of ``import spark_sklearn_tpu``, first line to last, as the
program stamped them (``search_report["process"]["import_s"]``; by
third-party root on the ``setup:`` line).  ``None`` on a program without
the block."""


def read(ctx):
    process = ctx["report"].get("process")
    if not process:
        return None
    return process["import_s"]
