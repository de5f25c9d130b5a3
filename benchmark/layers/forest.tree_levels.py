"""Tree levels the traced search executed: the sum over its launches of
``search_report["tree_levels_per_launch"]`` (lanes x lockstep trees x the
group's depth; a level is one partition, one histogram pass, one split and
one routing).  ``None`` where the report has no such counter."""


def read(ctx):
    levels = ctx["report"].get("tree_levels_per_launch")
    if not levels or min(levels) < 0:
        return None
    return sum(levels)
