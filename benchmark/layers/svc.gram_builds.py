"""Kernel matrices the traced search's launches built: the sum of
``search_report["gram_builds_per_launch"]`` (one a candidate where every
candidate builds its own, one a run of candidates of one gamma where the
launch groups them).  ``None`` where the report has no such counter."""


def read(ctx):
    builds = ctx["report"].get("gram_builds_per_launch")
    return sum(builds) if builds else None
