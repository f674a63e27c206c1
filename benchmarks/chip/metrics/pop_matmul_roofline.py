"""Least time over kernel time for every ``pop_matmul`` call in the traced
window: per call the larger of its FLOPs at the bf16 peak and its bytes at
the HBM bandwidth, counted from its operand shapes in the compiled
program; the kernel time is the summed device time of its events."""

from flops.kernels import roofline_share


def read(ctx):
    return roofline_share(ctx, "pop_matmul")
