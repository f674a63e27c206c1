"""Operations and bytes of one kernel call, from its operand shapes in the
compiled program's text.

A Pallas kernel compiles to a ``tpu_custom_call`` instruction whose name
carries the kernel's name (``pop_matmul``, ``jvp_pop_matmul...``,
``pop_adam``).  Its HLO line gives the result and operand shapes, which is
all the counts below need.
"""
from __future__ import annotations

import math
import re

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8}
_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*custom-call\((.*)$")


def _shapes(text):
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def _bytes(shapes):
    return sum(_DTYPE_BYTES[dt] * math.prod(dims) for dt, dims in shapes)


def custom_calls(hlo_text: str, kernel: str):
    """``{instruction name: (result shapes, operand shapes)}`` of every
    ``tpu_custom_call`` whose instruction name contains ``kernel``."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if m is None or kernel not in m.group(1):
            continue
        # compiled HLO names operands without shapes; their shapes are in
        # the layout constraints
        rest = m.group(3)
        if "operand_layout_constraints={" in rest:
            operands = rest.split("operand_layout_constraints={", 1)[1]
            operands = operands.split("frontend_attributes=")[0]
            operands = operands.split(", metadata=")[0]
        else:
            operands = rest.split("), custom_call_target")[0]
        out[m.group(1)] = (_shapes(m.group(2)), _shapes(operands))
    return out


def pop_matmul_cost(results, operands):
    """(flops, bytes) of ``y[n] = act(x[n] @ w[n] + b[n])``: x (N, B, K),
    w (N, K, M), optional b; every operand read and the result written
    once."""
    (_, x), (_, w) = operands[0], operands[1]
    n, b, k = x
    m = w[-1]
    flops = 2 * n * b * k * m + (n * b * m if len(operands) > 2 else 0)
    return flops, _bytes(operands) + _bytes(results)


def pop_adam_cost(results, operands):
    """(flops, bytes) of the fused Adam pass over (N, P) params, grads and
    moments: 13 operations per element (two moment updates, two bias
    corrections, the root, the step), each array read or written once."""
    (_, p) = operands[3]
    return 13 * math.prod(p), _bytes(operands) + _bytes(results)


COSTS = {"pop_matmul": pop_matmul_cost, "pop_adam": pop_adam_cost}


def kernel_costs(hlo_text: str, kernel: str):
    """``{instruction name: (flops, bytes)}`` for one kernel."""
    cost = COSTS[kernel]
    return {name: cost(res, ops)
            for name, (res, ops) in custom_calls(hlo_text, kernel).items()}


def roofline_share(ctx, kernel: str):
    """Percent of the chip's roofline the kernel reached in the traced
    window: the least time its calls could take (each call bound by its
    FLOPs at the bf16 peak or its bytes at the HBM bandwidth, whichever is
    longer) over the device time its events took.  None where the window
    holds no such call."""
    if ctx.trace is None or ctx.hlo_text is None:
        return None
    costs = kernel_costs(ctx.hlo_text, kernel)
    least = spent = 0.0
    for name, (flops, nbytes) in costs.items():
        count = ctx.trace["op_count"].get(name, 0)
        least += count * max(flops / ctx.peaks["bf16_flops"],
                             nbytes / ctx.peaks["hbm_bytes_per_s"])
        spent += ctx.trace["op_time"].get(name, 0.0)
    if spent <= 0.0:
        return None
    return 100.0 * least / spent
