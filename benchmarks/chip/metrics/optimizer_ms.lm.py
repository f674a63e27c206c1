"""Device milliseconds per step under the LM's ``optimizer`` scope
(gradient clipping, AdamW and the parameter update), from
``progtrace.scoped_time``."""

from progtrace import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "optimizer")
