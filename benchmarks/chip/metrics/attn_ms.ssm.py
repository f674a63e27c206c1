"""Device milliseconds per step under the ``attention`` scope: the
attention mixers of a hybrid stack (projections, scores, softmax),
forward and backward (``scope_ops.scope_ms``)."""

from scope_ops import scope_ms


def read(ctx):
    return scope_ms(ctx, "attention")
