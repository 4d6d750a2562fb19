"""The whole step's share of the chip's peak."""


def mfu(ctx):
    """Model operations of the interval's whole iterations over what the
    chips could do in that time at their bf16 peak, in percent."""
    start, end = ctx.interval
    marks = ctx.session.marks  # the interval's ends are marks themselves
    iterations = sum(1 for a, b in zip(marks, marks[1:]) if a >= start and b <= end)
    if iterations == 0:
        return None
    needed = iterations * ctx.family.flops.iteration_flops(ctx.config, ctx.cell)["total"]
    return 100.0 * needed / ((end - start) * ctx.cell["chips"] * ctx.peaks["bf16_flops_per_s"])
