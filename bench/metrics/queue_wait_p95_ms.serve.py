"""95th percentile of the scheduler's queue wait (submit to admit), from
`ServeMetrics.queue_wait_s`, over every admitted request of the run."""
from bench.harness.stats import percentile


def read(ctx):
    m = ctx.serve_metrics
    if m is None or not m.queue_wait_s:
        return None
    return percentile(m.queue_wait_s, 95) * 1e3
