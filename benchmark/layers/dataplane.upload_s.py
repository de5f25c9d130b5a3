"""Seconds the traced search spent inside ``sst.dataplane.upload``, the
program's host span around every host-to-device ``device_put``, as the
profiler recorded it (``scopes.py``)."""


def read(ctx):
    scopes = ctx["load_named"]("scopes:read")(ctx)
    if scopes is None:
        return None
    spans = scopes["host_spans"]
    if not spans:
        print("dataplane.upload_s: no sst.* host event in the trace (the "
              "program mirrors no span into the profiler)", flush=True)
        return None
    return spans.get("sst.dataplane.upload", 0.0)
