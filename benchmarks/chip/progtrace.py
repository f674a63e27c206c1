"""The program's own spans and scopes on the device trace's clock.

``devtrace`` reduces a trace with the harness's spans only.  This module
adds what the program puts into the same trace, for the per-layer
readings of the trainer's phases and of the LM step's parts:

* :func:`scope_map` names the program scope (``jax.named_scope``) of each
  instruction of a compiled program, from its HLO text;
* :func:`scoped_time` reduces device events to seconds per scope;
* :func:`load_host` reads the program's spans (``pop.<phase>`` from
  ``RunTelemetry.phase``, ``gc`` from ``RunTelemetry.gc_span``) and the
  runtime's host events, and :func:`idle_gaps` names each idle gap after
  the innermost of them, or of the harness's spans, that covers it;
* :func:`counter_delta` turns two ``RunTelemetry.totals()`` into the
  counters of the window between them.

Nothing here changes what ``devtrace`` computes: the window's bounds, busy
time and time per op come from the harness's spans and the device events
as before.
"""
from __future__ import annotations

import re
from collections import defaultdict

import devtrace

SCOPES = ("embed", "layers", "head", "optimizer")
PROGRAM_PREFIX = "pop."
GC_SPAN = "gc"

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"', re.M)
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")


def scope_of(op_name, scopes=SCOPES):
    """The program scope named in one ``op_name``, or None.

    An ``op_name`` is a ``/``-joined path such as
    ``jit(step)/vmap(transpose(jvp(layers)))/while/body/dot_general``.  A
    component may wrap its name in transformations (``jvp(``,
    ``transpose(``, ``vmap(`` and the like); the innermost name is the
    component's.  The outermost component whose name is one of ``scopes``
    gives the scope, so a scope opened inside another belongs to the
    outer one.  An ``op_name`` that XLA joined from several with ``;``
    takes the scope of the first of them that names one."""
    for path in op_name.split(";"):
        for part in path.split("/"):
            m = _WRAPPED.match(part.strip())
            if m and m.group(1) in scopes:
                return m.group(1)
    return None


def scope_map(hlo_text, scopes=SCOPES):
    """``{instruction name: scope}`` for every instruction of the compiled
    HLO text whose ``op_name`` metadata names one of ``scopes`` (the
    instructions of loop bodies included, which the trace shows as ops of
    their own)."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text or ""):
        scope = scope_of(op_name, scopes)
        if scope is not None:
            out[name] = scope
    return out


def _total(intervals):
    return sum(e - s for s, e in intervals)


def scoped_time(device_events, containers, scopes, lo, hi):
    """Seconds of device time per scope inside ``[lo, hi]``, averaged over
    devices.  A scope's time is the union of the intervals of its ops and
    of its loop containers, so a loop that the trace shows whole, without
    its body's ops, still counts.  ``"unscoped"`` is the part of the busy
    union (ops and containers) that no scoped interval covers; when the
    scopes do not overlap in time, the parts add up to the busy time.
    ``scopes`` is :func:`scope_map`'s result."""
    containers = containers or {}
    out = defaultdict(float)
    for dev, evs in device_events.items():
        every = evs + containers.get(dev, [])
        by_scope = defaultdict(list)
        for s, d, name in every:
            scope = scopes.get(name)
            if scope is not None:
                by_scope[scope].append((s, s + d))
        for scope, intervals in by_scope.items():
            out[scope] += _total(devtrace.union(intervals, lo, hi))
        scoped = _total(devtrace.union(
            [iv for ivs in by_scope.values() for iv in ivs], lo, hi))
        out["unscoped"] += devtrace.busy_ns(every, lo, hi) - scoped
    n = max(len(device_events), 1)
    return {k: v * 1e-9 / n for k, v in out.items()}


def scoped_ms(ctx, *names):
    """Device milliseconds per unit under the scopes ``names`` (summed),
    from a per-layer reader's context; None where the context holds no
    scoped time (``ctx.trace["scoped_s"]``, from :func:`scoped_time`)."""
    scoped = (ctx.trace or {}).get("scoped_s")
    if scoped is None or not ctx.units:
        return None
    return 1e3 * sum(scoped.get(n, 0.0) for n in names) / ctx.units


def load_host(path, op_lines=("XLA Ops",), device_prefix="/device:"):
    """``(program_spans, runtime_events)`` from an ``.xplane.pb``, each a
    list of ``(start_ns, duration_ns, name)`` on the trace's clock.

    Program spans are the host events named ``pop.<phase>`` or ``gc``.
    Runtime events are the other host events with a duration, less the
    harness's spans (``devtrace.SPANS``), the Python tracer's function
    events (named ``$...``) and the lines that ``devtrace.load`` reads as
    device ops (``op_lines``, which a trace recorded on a host has on a
    host plane)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    program, runtime = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        ops_here = device_prefix is None
        for line in plane.lines:
            if ops_here and any(line.name.startswith(p) for p in op_lines):
                continue
            for e in line.events:
                name = e.name
                if name.startswith(PROGRAM_PREFIX) or name == GC_SPAN:
                    program.append((e.start_ns, e.duration_ns, name))
                elif (e.duration_ns > 0 and name not in devtrace.SPANS
                      and not name.startswith("$")):
                    runtime.append((e.start_ns, e.duration_ns, name))
    return program, runtime


def attribute(gap, spans):
    """Name of the innermost span that covers ``gap``: the shortest span
    that overlaps at least half of it.  Where none does, the span that
    overlaps most of it (``devtrace.attribute``).  A gap inside a phase is
    then named after the phase, or after the runtime's event inside it,
    and not after the harness's span around both."""
    half = (gap[1] - gap[0]) / 2
    best = None
    for s, d, name in spans:
        overlap = min(gap[1], s + d) - max(gap[0], s)
        if overlap >= half and (best is None or d < best[0]):
            best = (d, name)
    return best[1] if best is not None else devtrace.attribute(gap, spans)


def idle_gaps(device_events, containers, spans, lo, hi, top=10):
    """The ``top`` longest idle gaps inside ``[lo, hi]``, as
    ``devtrace.reduce`` finds them, each named by :func:`attribute`:
    ``[[name, seconds]]``."""
    containers = containers or {}
    out = []
    for dev, evs in device_events.items():
        for g in devtrace.gaps(evs + containers.get(dev, []), lo, hi):
            out.append((g[1] - g[0], attribute(g, spans)))
    return [[name, d * 1e-9]
            for d, name in sorted(out, key=lambda x: -x[0])[:top]]


def counter_delta(before, after):
    """The counters of the window between two ``RunTelemetry.totals()``:
    ``{name: {"count", "wall_s", "cpu_s"}}`` for every name in ``after``."""
    zero = {"count": 0, "wall_s": 0.0, "cpu_s": 0.0}
    return {name: {k: v - before.get(name, zero)[k] for k, v in now.items()}
            for name, now in after.items()}
