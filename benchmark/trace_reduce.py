"""From a profiler trace (``.xplane.pb``) to busy and idle time, time per
device operation, and the idle gaps named by what the host was doing.

The arithmetic works on plain lists of ``(name, start_s, duration_s)`` so
that it can be checked by hand; :func:`load` turns a trace file into
those lists with nothing but ``jax.profiler.ProfileData``.  A trace with
no device plane (XLA:CPU has none) reduces to ``None``: nothing is ever
reported for a device that was not there.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SHORT_GAP_S = 2e-3
#: the annotation the runner puts around every search of a traced window
SEARCH_SPAN = "bench.search"


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler.trace`` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


HLO_TEXT = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(name):
    """XLA:TPU names a device operation by its whole HLO line; keep the
    instruction's name and its (first) result shape:
    ``%fusion.88 = f32[16,70000,190]{...} fusion(...)`` ->
    ``fusion.88:f32[16,70000,190]``."""
    m = HLO_TEXT.match(name)
    return f"{m.group(1)}:{m.group(2)}" if m else name


def _events(line):
    return [(short_name(ev.name), ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
            for ev in line.events]


def load(path):
    """``{"devices": {ordinal: {"ops": [...]}}, "host": [...]}`` with every
    event a ``(name, start_s, duration_s)``.  Host events are those of
    every host thread that have a duration."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"].extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line) if e[2] > 0)
    return {"devices": devices, "host": host}


def clip(events, start, end):
    """The events' parts that lie inside ``[start, end]``."""
    out = []
    for name, s, d in events:
        lo, hi = max(s, start), min(s + d, end)
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def union_intervals(events):
    """Sorted, merged ``[start, end]`` intervals the events cover."""
    merged = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(events):
    return sum(e - s for s, e in union_intervals(events))


def gaps(events, start, end):
    """The parts of ``[start, end]`` that no event covers."""
    out, at = [], start
    for s, e in union_intervals(clip(events, start, end)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if end > at:
        out.append((at, end))
    return out


#: operations that only hold others (a loop's body runs inside its
#: ``while``); their time is their children's, counted there
CONTAINERS = ("while", "conditional", "call")


def top_ops(events, n=10):
    """``[[name, seconds], ...]``: the operations that took most time."""
    total = {}
    for name, _, d in events:
        if name.startswith(CONTAINERS):
            continue
        total[name] = total.get(name, 0.0) + d
    return [[name, secs] for name, secs in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def search_spans(host):
    return sorted((s, s + d) for name, s, d in host if name == SEARCH_SPAN)


def _overlap(a_start, a_end, b_start, b_end):
    return max(0.0, min(a_end, b_end) - max(a_start, b_start))


def name_gap(gap, spans, host):
    """What the host was doing in an idle gap: ``between_searches`` where
    most of it lies outside every search's span; otherwise the jax or XLA
    host event that covers most of it, or plain host code."""
    start, end = gap
    if end - start < SHORT_GAP_S:
        return "gaps_under_2_ms"
    inside = sum(_overlap(start, end, s, e) for s, e in spans)
    if inside < 0.5 * (end - start):
        return "between_searches"
    best, best_cover = None, 0.0
    for name, s, d in host:
        if name == SEARCH_SPAN or name.startswith("$"):
            continue        # the span itself; python frames of the tracer
        cover = _overlap(start, end, s, s + d)
        if cover > best_cover:
            best, best_cover = name, cover
    if best is not None and best_cover >= 0.5 * (end - start):
        return "inside_fit:" + re.sub(r"[^A-Za-z0-9_.:-]+", "_", best)[:80]
    return "inside_fit:host_code_outside_any_jax_call"


def idle_gaps(ops, spans, host, start, end, n=10):
    """``[[name, seconds], ...]``: idle time by what the host was doing."""
    total = {}
    for gap in gaps(ops, start, end):
        name = name_gap(gap, spans, host)
        total[name] = total.get(name, 0.0) + (gap[1] - gap[0])
    return [[name, secs] for name, secs in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace, n_devices):
    """The numbers the benchmark reads from one traced window, or ``None``
    where the trace holds no device plane.

    The window is the span of the searches' annotations on the host's
    clock, which the profiler puts on the same axis as the devices'; a
    trace with no annotation falls back to the first and last device
    operation.  ``busy_s`` is averaged over the devices used."""
    devices = trace["devices"]
    if not devices:
        return None
    spans = search_spans(trace["host"])
    all_ops = [e for dev in devices.values() for e in dev["ops"]]
    if not all_ops:
        return None
    if spans:
        start, end = spans[0][0], spans[-1][1]
    else:
        start = min(s for _, s, _ in all_ops)
        end = max(s + d for _, s, d in all_ops)
    per_device = {i: clip(dev["ops"], start, end)
                  for i, dev in devices.items()}
    busy = sum(busy_seconds(ops) for ops in per_device.values())
    fullest = max(per_device, key=lambda i: busy_seconds(per_device[i]))
    return {
        "window_s": end - start,
        "busy_s": busy / max(n_devices, 1),
        "devices_traced": len(devices),
        "ops": per_device[fullest],
        "device_ops": top_ops(per_device[fullest]),
        "idle_gaps": idle_gaps(per_device[fullest], spans, trace["host"],
                               start, end),
    }


def outline(path, per_line=4):
    """A look at a trace by hand: planes, lines, and the first events."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                print(f"      {ev.name[:90]!r} start_ns={ev.start_ns:.0f} "
                      f"dur_ns={ev.duration_ns:.0f}")


if __name__ == "__main__":
    import json
    import sys

    outline(sys.argv[1])
    reduced = reduce(load(sys.argv[1]),
                     int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    if reduced is not None:
        del reduced["ops"]
    print(json.dumps(reduced, indent=1))
