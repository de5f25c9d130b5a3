"""Seconds the traced search spent inside ``sst.prefix.stage``, the
program's host span around the shared-prefix stage (every distinct
Pipeline prefix computed, or found in the data plane, before the suffix
launches), as the profiler recorded it (``scopes.py``).  0 where the
search staged no prefix; ``None`` where the program mirrors no span."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    spans = scopes["host_spans"]
    if not spans:
        print("prefix.stage_s: no sst.* host event in the trace (the "
              "program mirrors no span into the profiler)", flush=True)
        return None
    return spans.get("sst.prefix.stage", 0.0)
