"""Model FLOPs of the traced window's units (``flops.models``, from the
configuration's widths) over window seconds x devices x the bf16 peak."""


def read(ctx):
    if not ctx.units or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.flops_per_unit * ctx.units / (
        ctx.window_s * ctx.devices * ctx.peaks["bf16_flops"])
