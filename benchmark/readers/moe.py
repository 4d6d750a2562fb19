"""Readers of the expert layer and the latent cache: the grouped expert
products' share of their roofline from the device trace, and the program's own
``moe/`` and ``mla/`` gauges. A program without them (any commit before it had
experts) gives ``None`` everywhere."""

from benchmark import trace_reduce


def _gauges(prefix: str):
    try:
        from trlx_tpu.utils.metrics import gauges
    except ImportError:
        return {}
    return gauges.snapshot(prefix)


def gmm_roofline(ctx, patterns):
    """Least time the chip could take for the traced iteration's grouped
    expert products (the family's ``flops.gmm_calls``, over the share of the
    assignments that the ``moe/`` counters say fell to held experts) over the
    summed device durations of the events matching ``patterns``. The held
    share is the learner's, of the last optimizer step's microbatches (the
    rollout and the scoring pass sow no load): it scales the whole iteration's
    needed rows, the other passes' at the learner's share."""
    flops, counters = ctx.family.flops, _gauges("moe/")
    if ctx.trace is None or not hasattr(flops, "gmm_calls") or not counters.get("moe/assignments"):
        return None
    seconds = trace_reduce.kernel_seconds(ctx.trace["ops"], patterns)
    if not seconds:
        return None
    share = counters["moe/assignments_held"] / counters["moe/assignments"]
    least = flops.gmm_min_seconds(ctx.config, flops.gmm_calls(ctx.config, ctx.cell), ctx.peaks, share)
    ctx.notes["gmm_calls_bound"] = least["bound"]
    ctx.notes["moe_held_share"] = share
    return 100.0 * least["seconds"] / seconds


def load_max_over_mean(ctx):
    """The largest over the mean load of a held expert, in the last optimizer
    step's microbatches, summed over the expert layers."""
    counters = _gauges("moe/")
    if not counters.get("moe/load_mean"):
        return None
    return counters["moe/load_max"] / counters["moe/load_mean"]


def cache_bytes_per_token(ctx):
    """Bytes the rollout's cache holds for one token, over all layers."""
    return _gauges("mla/").get("mla/cache_bytes_per_token")
