"""Seconds between the end of the program's import and the first call into
it (``first_call_s - import_s`` of ``search_report["process"]``): the
caller's own time, which under this runner is ``require_chips``, the TPU
client's start-up.  ``None`` on a program without the block."""


def read(ctx):
    process = ctx["report"].get("process")
    if not process or process.get("first_call_s") is None:
        return None
    return process["first_call_s"] - process["import_s"]
