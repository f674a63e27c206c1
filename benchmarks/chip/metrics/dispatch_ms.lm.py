"""Mean host milliseconds per step in the harness's ``next_batch`` and
``step_call`` spans: token generation, the copy in and the step's
dispatch."""


def read(ctx):
    n = ctx.spans.count.get("step_call")
    if not n:
        return None
    return 1e3 * (ctx.spans.total.get("next_batch", 0.0)
                  + ctx.spans.total["step_call"]) / n
