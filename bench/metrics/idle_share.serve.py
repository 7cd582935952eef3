"""Share of the traced window in which the device ran no operation:
1 - busy / window, busy the union of device-op intervals, mean over the
cell's devices."""


def read(ctx):
    if ctx.trace is None or ctx.served is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
