"""Readers of a looped model's generator: the bytes its cache holds a token
(the program's ``loop/`` gauge) and the decode loop's share of its roofline
from the device trace. A program without looped layers, or a family whose
``flops`` does not say what a decode step needs, gives ``None`` everywhere."""

from benchmark.readers import program_trace
from benchmark.readers.moe import _gauges


def cache_bytes_per_token(ctx):
    """Bytes the rollout's cache holds for one token, over every (pass, layer)."""
    return _gauges("loop/").get("loop/cache_bytes_per_token")


def decode_roofline(ctx, program: str):
    """Least time the chip could take for the traced iteration's decode steps
    (the family's ``flops.decode_min_seconds``, for every call of the
    generator) over the summed device time of the outermost ``while`` ops
    inside those calls: what ``decode_step_ms`` reads."""
    flops = ctx.family.flops
    if ctx.trace is None or not hasattr(flops, "decode_min_seconds"):
        return None
    loaded = program_trace._program_trace(ctx)
    calls = program_trace.matching(loaded[0], program) if loaded else []
    loops = program_trace.outermost_whiles(ctx.trace["ops"], calls) if calls else []
    seconds = sum(t - s for _, s, t in loops) / 1e9
    if not seconds:
        return None
    least = flops.decode_min_seconds(ctx.config, ctx.cell, ctx.peaks)
    ctx.notes["decode_bound"] = least["bound"]
    return 100.0 * len(calls) * least["seconds"] / seconds
