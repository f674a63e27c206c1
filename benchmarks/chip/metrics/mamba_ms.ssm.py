"""Device milliseconds per step under the ``mamba`` scope: the Mamba-2
mixers (projections, convolution, chunk scan, gated norm), forward and
backward (``scope_ops.scope_ms``)."""

from scope_ops import scope_ms


def read(ctx):
    return scope_ms(ctx, "mamba")
