"""Mean host milliseconds per fused epoch inside the harness's
``epoch_call`` span: ``run_env_loop`` dispatch plus its bookkeeping."""


def read(ctx):
    if not ctx.spans.count.get("epoch_call"):
        return None
    return 1e3 * ctx.spans.total["epoch_call"] / ctx.spans.count["epoch_call"]
