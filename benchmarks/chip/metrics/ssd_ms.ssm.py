"""Device milliseconds per step under the ``ssd`` scope: the Mamba-2
chunk scan inside the mixers, forward and backward
(``scope_ops.scope_ms``)."""

from scope_ops import scope_ms


def read(ctx):
    return scope_ms(ctx, "ssd")
