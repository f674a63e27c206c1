"""Device time of the ops under one program scope, for the per-layer
readers of a cell whose ``compiled_text()`` gives the compiled step.

A reader gets the compiled HLO text (``ctx.hlo_text``) and the trace's
seconds per op name (``ctx.trace["op_time"]``, from ``devtrace.reduce``).
``progtrace.scope_map`` names the scope of each instruction from its
``op_name``; the ops under a scope are summed.  Ops run one at a time on
a TPU core, so these sums of nested scopes nest too.
"""
from __future__ import annotations

from progtrace import scope_map


def scope_ms(ctx, scope):
    """Device milliseconds per unit of the ops under ``scope`` (an op of
    a scope opened inside it counts too); None where the context holds no
    compiled text or trace, or no instruction is under ``scope``."""
    if not ctx.hlo_text or ctx.trace is None or not ctx.units:
        return None
    ops = scope_map(ctx.hlo_text, scopes=(scope,))
    if not ops:
        return None
    return 1e3 * sum(t for op, t in ctx.trace["op_time"].items()
                     if op in ops) / ctx.units
