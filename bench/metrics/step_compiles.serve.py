"""Serving steps that compiled when dispatched, after warm-up
(`ServeMetrics` counter `step_compiles`, all pools): a shape warm-up did
not cover, paid for inside the window.  None where the program does not
count them."""


def read(ctx):
    m = ctx.serve_metrics
    if m is None or not hasattr(m, "observe_step_compile"):
        return None
    return m.counters["step_compiles"]
