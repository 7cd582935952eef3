"""Seconds, on the host's clock, the serving engine's warm-up took, summed
over its pools (`ServeMetrics.warmup_s`): each pool's autotune seeding and
step compile, the engine's autotune cache load with the first.  Part of
`setup_s`.  None where the program does not record it."""


def read(ctx):
    m = ctx.serve_metrics
    if m is None or getattr(m, "warmup_s", None) is None:
        return None
    return sum(m.warmup_s.values())
