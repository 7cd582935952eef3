"""Structures consumed by the optimizer steps completed in the window, per
second from the window's open to the last completion."""


def read(ctx):
    if not ctx.train_steps:
        return None
    return ctx.train_structs / ctx.window_s
