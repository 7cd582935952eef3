"""The screen's 95th-percentile request latency, timed from each request's
due time, read per layer: the same number as the end-to-end
`latency_p95_ms`, which the screen does not report because its runs spread
too widely for any bound (PERF.md)."""
from bench.harness.serve import latency_p95_ms


def read(ctx):
    if ctx.served is None or not ctx.served.requests:
        return None
    return latency_p95_ms(ctx.served)
