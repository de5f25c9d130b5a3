"""Lockstep work the solver really did in the traced search: the sum over
launches of executed iterations x lanes."""


def read(ctx):
    counts = ctx["work"].iter_lanes(ctx["report"])
    return None if counts is None else counts[0]
