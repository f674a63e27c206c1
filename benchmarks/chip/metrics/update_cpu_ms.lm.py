"""Host CPU milliseconds per step that the trainer's thread spent in its
``update`` phase (the executable call) over the traced window, from the
``RunTelemetry`` counters (``thread_time``)."""


def read(ctx):
    c = (getattr(ctx, "counters", None) or {}).get("update")
    if not c or not c["count"]:
        return None
    return 1e3 * c["cpu_s"] / c["count"]
