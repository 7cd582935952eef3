"""95th percentile of request latency over every request of the window,
timed from when it was due (open loop) or sent (closed loop); a request
that failed counts as late as the run waited for it."""
from bench.harness.serve import latency_p95_ms


def read(ctx):
    if ctx.served is None or not ctx.served.requests:
        return None
    return latency_p95_ms(ctx.served)
