"""Compiles inside the measured window."""


def in_window(ctx):
    start, end = ctx.session.window
    return float(sum(1 for t in ctx.session.compiles if start <= t <= end))
