"""A kernel's share of its roofline, from the device trace."""

from benchmark import trace_reduce


def roofline(ctx, patterns, work: str):
    """Least time the chip could take for the traced iteration's calls (the
    function ``work`` of the family's ``flops`` lists them) over the summed
    device durations of the events matching ``patterns``. Nothing where the
    trace holds no such event."""
    if ctx.trace is None:
        return None
    seconds = trace_reduce.kernel_seconds(ctx.trace["ops"], patterns)
    if not seconds:
        return None
    flops = ctx.family.flops
    calls = getattr(flops, work)(ctx.config, ctx.cell)
    least = flops.flash_min_seconds(ctx.config, calls, ctx.peaks)
    ctx.notes[f"{work}_bound"] = least["bound"]
    return 100.0 * least["seconds"] / seconds
