"""Seconds from process start to the window's open: weights, traffic,
warm-up and any compilation."""


def read(ctx):
    return ctx.setup_s
