"""Padding lanes over computed lanes in the traced search: the planner's
``padding_waste`` series holds each launch's padded fraction."""


def read(ctx):
    rep = ctx["report"]
    lanes = rep.get("lanes_per_launch")
    waste = rep.get("padding_waste")
    if not lanes or not waste or not waste.get("count"):
        return None
    return 100.0 * waste["mean"]
