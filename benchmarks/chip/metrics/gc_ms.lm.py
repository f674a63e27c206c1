"""Milliseconds per step of Python garbage-collection pauses over the
traced window, from the ``gc`` counter of ``RunTelemetry.gc_span``."""


def read(ctx):
    c = (getattr(ctx, "counters", None) or {}).get("gc")
    if c is None or not ctx.units:
        return None
    return 1e3 * c["wall_s"] / ctx.units
