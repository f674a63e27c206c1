"""Reduce a profiler trace to device busy and idle time, time per op
name, and idle gaps attributed to the harness's own host spans.

The reduction works on plain ``(start_ns, duration_ns, name)`` tuples, so
it can be checked on hand-made events; :func:`load` reads them from the
``.xplane.pb`` that ``jax.profiler`` writes:

* device ops: the ``XLA Ops`` line of every ``/device:...`` plane (one
  plane per chip), or the lines named in ``op_lines`` for a trace recorded
  where there is no such plane;
* host spans: events of the host plane whose name is one of the harness's
  span names (``jax.profiler.TraceAnnotation``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SPANS = ("epoch_call", "next_batch", "step_call", "wait")


def union(intervals, lo=None, hi=None):
    """Merged ``[(start, end)]`` of ``(start, end)`` intervals, clipped to
    ``[lo, hi]`` when given."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(events, lo, hi):
    """Length of the union of the events' intervals inside the window."""
    return sum(e - s for s, e in union(
        [(s, s + d) for s, d, _ in events], lo, hi))


def gaps(events, lo, hi):
    """Idle intervals of one device inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union([(s, s + d) for s, d, _ in events], lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_time(events, lo, hi):
    """Seconds of device time per op name inside the window."""
    total = defaultdict(float)
    for s, d, name in events:
        e = min(s + d, hi)
        s = max(s, lo)
        if e > s:
            total[name] += (e - s) * 1e-9
    return dict(total)


def attribute(gap, spans):
    """Name of the host span that covers most of ``gap``; the innermost
    (shortest) wins a tie, and ``"none"`` when no span overlaps it."""
    best, best_key = "none", (0, 0)
    for s, d, name in spans:
        overlap = min(gap[1], s + d) - max(gap[0], s)
        if overlap > 0 and (overlap, -d) > best_key:
            best, best_key = name, (overlap, -d)
    return best


def reduce(device_events, host_spans, lo, hi, top=10, containers=None):
    """Per-device busy/idle, time per op and the longest idle gaps.

    ``device_events``: ``{device: [(start_ns, dur_ns, name)]}``;
    ``containers``: the same for control-flow ops (loops, conditionals)
    whose time is their children's: they count as busy, not as ops;
    ``host_spans``: ``[(start_ns, dur_ns, name)]``; the window is
    ``[lo, hi]`` in the same clock.  Returns seconds."""
    containers = containers or {}
    window = (hi - lo) * 1e-9
    every = {dev: evs + containers.get(dev, [])
             for dev, evs in device_events.items()}
    busy = [busy_ns(evs, lo, hi) * 1e-9 for evs in every.values()]
    ops = defaultdict(float)
    idle = []
    for dev, evs in device_events.items():
        for name, secs in op_time(evs, lo, hi).items():
            ops[name] += secs
        for g in gaps(every[dev], lo, hi):
            idle.append((g[1] - g[0], attribute(g, host_spans)))
    return {
        "window_s": window,
        "busy_s": sum(busy) / max(len(busy), 1),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "op_time": dict(ops),
        "op_count": _counts(device_events, lo, hi),
        "idle_gaps": [[name, dur * 1e-9] for dur, name
                      in sorted(idle, key=lambda x: -x[0])[:top]],
    }


def _counts(device_events, lo, hi):
    count = defaultdict(int)
    for evs in device_events.values():
        for s, d, name in evs:
            if s < hi and s + d > lo:
                count[name] += 1
    return dict(count)


def latest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_CONTAINER = re.compile(r"[\s)](while|conditional|call)\(")


def op_name(text):
    """``(instruction name, is control flow)`` of an op event, whose name
    on a TPU is the instruction's HLO text (``%fusion.3 = f32[...] ...``)."""
    head = text.split(" = ", 1)
    name = head[0].strip().lstrip("%")
    return name, len(head) > 1 and bool(_CONTAINER.search(head[1]))


def load(path, op_lines=("XLA Ops",), device_prefix="/device:",
         span_names=SPANS):
    """``(device_events, host_spans, containers)`` from an
    ``.xplane.pb``.  A plane counts as a device when its name starts with
    ``device_prefix``; where ``device_prefix`` is None, every plane is
    searched for ``op_lines`` (a trace recorded on a host).  Op events are
    named by their HLO instruction; loops and conditionals go to
    ``containers``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans, containers = {}, [], {}
    for plane in data.planes:
        is_device = device_prefix is None or \
            plane.name.startswith(device_prefix)
        for line in plane.lines:
            events = list(line.events)
            if is_device and (line.name in op_lines
                              or any(line.name.startswith(p)
                                     for p in op_lines)):
                ops = devices.setdefault(plane.name, [])
                flow = containers.setdefault(plane.name, [])
                for e in events:
                    name, is_flow = op_name(e.name)
                    (flow if is_flow else ops).append(
                        (e.start_ns, e.duration_ns, name))
            if not plane.name.startswith("/device:"):
                spans.extend((e.start_ns, e.duration_ns, e.name)
                             for e in events if e.name in span_names)
    return devices, spans, containers
