"""Of the device's idle seconds inside ``fit`` (gaps of 2 ms and more, as
``trace_reduce.idle_gaps`` names them), the share that one of the
program's own host spans covers: ``inside_fit:sst.*`` over all
``inside_fit:*``."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)   # None: no device trace
    if scopes is None:
        return None
    if not scopes["host_spans"]:
        print("search.named_idle_share: no sst.* host event in the trace",
              flush=True)
        return None
    inside = [(name, s) for name, s in ctx["trace"]["idle_gaps"]
              if name.startswith("inside_fit:")]
    total = sum(s for _, s in inside)
    if total <= 0.0:
        return None
    named = sum(s for name, s in inside if name.startswith("inside_fit:sst."))
    return 100.0 * named / total
