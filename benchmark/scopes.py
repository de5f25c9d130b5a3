"""What the program's own names say about a traced search: device seconds
per ``jax.named_scope`` (``glm_lbfgs.*``, ``sst.*``), per XLA category, and
the seconds of the program's host spans (``sst.<span>``) that the program
mirrors into the profiler's trace.

The names hang on the trace's ``event_metadata`` (the stats ``tf_op``,
``source``, ``hlo_category``), which ``jax.profiler.ProfileData`` does not
hand out, so this file reads the ``.xplane.pb`` itself: a plain walk of the
protobuf wire format (``XSpace -> XPlane{lines, event_metadata,
stat_metadata} -> XLine -> XEvent``), no dependency.  Times are on the axis
``trace_reduce.load`` uses (a line's ``timestamp_ns`` plus the event's
offset), the window and the fullest device are chosen as
``trace_reduce.reduce`` chooses them, and container operations are left out
as ``trace_reduce.top_ops`` leaves them out, so the sums can be held
against ``busy_s``.

It imports nothing of the program and matches the prefixes only.  A trace
with no device plane (XLA:CPU) reads as ``None``: nothing is reported for
a device that was not there.
"""

from __future__ import annotations

import json
import os
import struct

import trace_reduce

SCOPE_PREFIXES = ("glm_lbfgs.", "sst.")
SOLVER_PREFIX = "glm_lbfgs."
HOST_PREFIX = "sst."
UNSCOPED = "unscoped"
HERE = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, a memoryview for a length-delimited field, 8 or 4 raw bytes
    for the fixed widths."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not in an xplane")
        yield key >> 3, wire, value


def _signed(v):
    return v - (1 << 64) if v >= (1 << 63) else v


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _stat(buf):
    """``(metadata id, value)`` of an XStat; a reference to another stat's
    name comes back as ``("ref", id)``."""
    key = value = None
    for num, _, v in fields(buf):
        if num == 1:
            key = v
        elif num in (3, 4):
            value = _signed(v) if num == 4 else v
        elif num in (5, 6):
            value = _text(v)
        elif num == 7:
            value = ("ref", v)
        elif num == 2:
            value = struct.unpack("<d", bytes(v))[0]
    return key, value


def _map_entry(buf):
    key = value = None
    for num, _, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _event_metadata(buf):
    name, stats = "", []
    for num, _, v in fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 5:
            stats.append(_stat(v))
    return name, stats


def _line(buf, wanted):
    """``(name, [(metadata id, start_s, duration_s), ...])``; the events
    are parsed only where ``wanted(name)``."""
    name, timestamp_ns, raw = "", 0, []
    for num, _, v in fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            timestamp_ns = _signed(v)
        elif num == 4:
            raw.append(v)
    if not wanted(name):
        return name, []
    events = []
    for ev in raw:
        meta = offset_ps = duration_ps = 0
        for num, _, v in fields(ev):
            if num == 1:
                meta = v
            elif num == 2:
                offset_ps = _signed(v)
            elif num == 3:
                duration_ps = _signed(v)
        events.append((meta, (timestamp_ns + offset_ps * 1e-3) * 1e-9,
                       duration_ps * 1e-12))
    return name, events


def read_planes(path):
    """``[{"name", "lines": {line name: [events]}, "events": {id: (name,
    {stat name: value})}}]`` of the device planes (their ``XLA Ops`` line)
    and the host planes (every line) of one ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _, plane in fields(space):
        if num != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for pnum, _, v in fields(plane):
            if pnum == 2:
                name = _text(v)
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                metas.append(v)
            elif pnum == 5:
                key, value = _map_entry(v)
                for snum, _, sv in fields(value):
                    if snum == 2:
                        stat_names[key] = _text(sv)
        device = trace_reduce.DEVICE_PLANE.match(name)
        if not device and not name.startswith("/host:"):
            continue
        wanted = ((lambda n: n == trace_reduce.OPS_LINE) if device
                  else (lambda n: True))
        parsed = {}
        for raw in lines:
            line_name, events = _line(raw, wanted)
            if events:
                parsed.setdefault(line_name, []).extend(events)
        events = {}
        for raw in metas:
            key, value = _map_entry(raw)
            meta_name, stats = _event_metadata(value)
            named = {}
            for sid, sval in stats:
                if isinstance(sval, tuple):
                    sval = stat_names.get(sval[1], "")
                named[stat_names.get(sid, str(sid))] = sval
            events[key] = (meta_name, named)
        planes.append({"name": name, "lines": parsed, "events": events,
                       "device": int(device.group(1)) if device else None})
    return planes


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def scope_of(tf_op):
    """The innermost ``glm_lbfgs.*`` / ``sst.*`` component of an op-name
    path (``jit(f)/sst.fit/while/body/glm_lbfgs.linesearch/vmap()/exp``
    -> ``glm_lbfgs.linesearch``), or ``unscoped``."""
    for part in reversed(str(tf_op or "").split("/")):
        if part.startswith(SCOPE_PREFIXES):
            return part
    return UNSCOPED


def _clip(start, duration, lo, hi):
    return max(0.0, min(start + duration, hi) - max(start, lo))


def reduce(planes, n_devices=1):
    """Seconds per scope and per category on the fullest device, and per
    mirrored host span, inside the window of the searches' annotations,
    with the five longest operations under no ``glm_lbfgs.*`` phase
    (another scope or none; their ``source`` says where they come from);
    ``None`` where there is no device plane or no device operation."""
    host = [(plane["events"].get(meta, ("", {}))[0], start, duration)
            for plane in planes if plane["device"] is None
            for events in plane["lines"].values()
            for meta, start, duration in events if duration > 0]
    devices = [p for p in planes if p["device"] is not None
               and p["lines"].get(trace_reduce.OPS_LINE)]
    if not devices:
        return None
    spans = trace_reduce.search_spans(host)
    if spans:
        lo, hi = spans[0][0], spans[-1][1]
    else:
        every = [e for p in devices for e in p["lines"][trace_reduce.OPS_LINE]]
        lo = min(s for _, s, _ in every)
        hi = max(s + d for _, s, d in every)

    def busy(plane):
        return trace_reduce.busy_seconds(trace_reduce.clip(
            [("", s, d) for _, s, d in plane["lines"][trace_reduce.OPS_LINE]],
            lo, hi))

    fullest = max(devices, key=busy)
    # what the names say is worked out once an operation, not once an event
    described = {}
    for meta, (name, stats) in fullest["events"].items():
        short = trace_reduce.short_name(name)
        scope = scope_of(stats.get("tf_op"))
        described[meta] = (
            short.startswith(trace_reduce.CONTAINERS), scope,
            stats.get("hlo_category") or "uncategorized",
            None if scope.startswith(SOLVER_PREFIX) else
            (short, scope, str(stats.get("source") or ""),
             str(stats.get("tf_op") or "")))
    scopes, categories, outside, total = {}, {}, {}, 0.0
    for meta, start, duration in fullest["lines"][trace_reduce.OPS_LINE]:
        container, scope, category, key = described[meta]
        seconds = _clip(start, duration, lo, hi)
        if container or seconds <= 0.0:
            continue
        total += seconds
        scopes[scope] = scopes.get(scope, 0.0) + seconds
        categories[category] = categories.get(category, 0.0) + seconds
        if key is not None:
            outside[key] = outside.get(key, 0.0) + seconds
    spans_s = {}
    for name, start, duration in host:
        if name.startswith(HOST_PREFIX):
            seconds = _clip(start, duration, lo, hi)
            if seconds > 0.0:
                spans_s[name] = spans_s.get(name, 0.0) + seconds
    return {
        "window_s": hi - lo, "busy_s": busy(fullest), "ops_s": total,
        "device": fullest["device"], "n_devices": n_devices,
        "scopes": scopes, "categories": categories,
        "outside_solver_top": [
            {"op": op, "scope": scope, "source": source, "tf_op": tf_op,
             "s": seconds}
            for (op, scope, source, tf_op), seconds in
            sorted(outside.items(), key=lambda kv: -kv[1])[:5]],
        "host_spans": spans_s,
    }


def solver_seconds(reduced):
    return sum(s for name, s in reduced["scopes"].items()
               if name.startswith(SOLVER_PREFIX))


# ---------------------------------------------------------------------------
# the readers' entry point
# ---------------------------------------------------------------------------

_PARSED = {}


def trace_dir_of(ctx):
    """Where ``run.run_cell`` put the trace: the rehearsal's directory
    if the environment names one, else ``<root>/.bench_trace/<cell>``."""
    return os.environ.get("BENCH_TEST_TRACE_DIR") or os.path.join(
        os.path.dirname(HERE), ".bench_trace", ctx["cell"]["name"])


def say(msg):
    print(msg, flush=True)


def read(ctx):
    """The reduction of this run's trace, parsed once per process and
    printed on two earlier lines; ``None`` (and why) where the run has no
    device trace."""
    if ctx.get("trace") is None:
        return None
    path = trace_reduce.find_xplane(trace_dir_of(ctx))
    if path is None:
        say(f"scopes: no .xplane.pb under {trace_dir_of(ctx)}")
        return None
    if path not in _PARSED:
        reduced = reduce(read_planes(path), ctx["chips"])
        _PARSED[path] = reduced
        if reduced is not None:
            describe(reduced, ctx["trace"]["busy_s"] * ctx["chips"])
    return _PARSED[path]


def describe(reduced, busy_s):
    """The two earlier lines.  ``ops_s`` is scoped + unscoped, the sum of
    the operations that are no containers; ``busy_s`` the union of all
    device operations as ``trace_reduce`` has it (a loop's own interval
    also covers the time between its body's operations)."""
    scoped = sum(s for name, s in reduced["scopes"].items()
                 if name != UNSCOPED)
    unscoped = reduced["scopes"].get(UNSCOPED, 0.0)
    by_time = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
    say("scopes: " + json.dumps({
        "device": reduced["device"],
        "scoped_s": scoped, "unscoped_s": unscoped,
        "busy_s": busy_s,
        "off_busy": (scoped + unscoped) / busy_s - 1.0 if busy_s else None,
        "by_scope": by_time(reduced["scopes"]),
        "by_category": by_time(reduced["categories"]),
        "outside_solver_top": reduced["outside_solver_top"]}))
    say("host spans: " + json.dumps(by_time(reduced["host_spans"])))


if __name__ == "__main__":
    import sys

    found = reduce(read_planes(sys.argv[1]),
                   int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    if found is None:
        print("no device plane")
    else:
        describe(found, found["busy_s"])
