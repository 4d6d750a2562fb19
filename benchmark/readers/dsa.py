"""Readers of learned sparse attention: the share of the causal (query, key)
pairs that the selection kept, from the program's ``dsa/`` counters (the
learner's last microbatch, summed over the layers). A program without learned
sparse attention sets none and gives ``None``."""

from benchmark.readers.moe import _gauges


def kept_pairs_share(ctx):
    """Pairs kept over the causal pairs there were: about ``topk`` over half the
    row's length where rows are longer than ``topk``; 1.0 would mean the
    selection keeps everything. The share is fixed by the traffic, so it is a
    check too: a run in which any query kept other than ``min(topk, allowed)``
    keys (the program's ``dsa/queries_off``) raises."""
    counters = _gauges("dsa/")
    if not counters.get("dsa/causal_pairs"):
        return None
    if counters.get("dsa/queries_off", 0.0):
        raise ValueError(f"{counters['dsa/queries_off']:.0f} queries kept other than min(topk, allowed) keys")
    return counters["dsa/kept_pairs"] / counters["dsa/causal_pairs"]
