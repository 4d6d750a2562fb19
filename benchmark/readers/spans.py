"""Readers of the harness's own host spans."""


def time_share(ctx, span: str):
    """Percent of the measured interval spent inside spans named ``span``."""
    start, end = ctx.interval
    inside = sum(
        min(e, end) - max(s, start) for name, s, e in ctx.session.spans
        if name == span and e > start and s < end
    )
    return 100.0 * inside / (end - start) if inside > 0 else None
