"""Minibatch steps the traced search executed in lockstep: the sum over
its launches of ``search_report["minibatch_steps_per_launch"]``.  ``None``
where the report has no such counter (a program from before it)."""


def read(ctx):
    steps = ctx["report"].get("minibatch_steps_per_launch")
    if not steps or min(steps) < 0:
        return None
    return sum(steps)
