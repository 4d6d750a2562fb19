"""Readers of the device itself: idle share from the trace, peak memory."""


def idle_share(ctx):
    if ctx.trace is None:
        return None
    start, end = ctx.interval
    return 100.0 * (1.0 - ctx.trace["busy_s"] / (end - start))


def peak_hbm_gib(ctx):
    return ctx.memory_peak_bytes / 2 ** 30 if ctx.memory_peak_bytes else None
