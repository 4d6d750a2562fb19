"""The end-to-end metrics. The harness takes them itself, on the host's clock."""


def tokens_per_s(ctx):
    """Experience tokens collected and trained in the window's whole
    iterations, over the window's whole wall time, over the cell's chips."""
    start, end = ctx.session.window
    tokens = ctx.session.iterations * ctx.family.flops.iteration_tokens(ctx.cell)
    return tokens / (end - start) / ctx.cell["chips"]


def setup_s(ctx):
    """Process start to window open."""
    return ctx.session.marks[0] - ctx.session.t0
