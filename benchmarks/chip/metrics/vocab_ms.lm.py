"""Device milliseconds per step under the LM's ``embed`` and ``head``
scopes (the token gather, the final norm, the tied head and the
cross-entropy, forward and backward), from ``progtrace.scoped_time``."""

from progtrace import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "embed", "head")
