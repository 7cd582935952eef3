"""Energy+force evaluations answered inside the window, per second of it."""


def read(ctx):
    if ctx.served is None:
        return None
    return ctx.served.completed_in_window() / ctx.window_s
