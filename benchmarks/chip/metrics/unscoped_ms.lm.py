"""Device milliseconds per step of busy time under no program scope: the
coverage check of the scoped readings, from ``progtrace.scoped_time``."""

from progtrace import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "unscoped")
