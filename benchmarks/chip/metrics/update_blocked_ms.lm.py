"""Milliseconds per step that the trainer's thread waited, rather than
worked, in its ``update`` phase over the traced window: the phase's wall
time less its CPU time, from the ``RunTelemetry`` counters."""


def read(ctx):
    c = (getattr(ctx, "counters", None) or {}).get("update")
    if not c or not c["count"]:
        return None
    return 1e3 * (c["wall_s"] - c["cpu_s"]) / c["count"]
