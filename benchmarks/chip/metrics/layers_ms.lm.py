"""Device milliseconds per step under the LM's ``layers`` scope (the
decoder layers' scans, forward and backward), from the trace's op and loop
intervals mapped to scopes through the compiled program
(``progtrace.scoped_time``)."""

from progtrace import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "layers")
