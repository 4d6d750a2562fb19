"""Readers of a hybrid model's generator, whose cache holds two kinds of
state: the bytes its attention layers hold a token and the bytes its
convolution layers hold a row (the program's ``hybrid/`` gauges). A program
without convolution layers sets none, and both give ``None``."""

from benchmark.readers.moe import _gauges


def cache_bytes_per_token(ctx):
    """Bytes the rollout's cache holds for one token, over the attention layers."""
    return _gauges("hybrid/").get("hybrid/cache_bytes_per_token")


def state_bytes_per_row(ctx):
    """Bytes of convolution state the rollout's cache holds for one row, over the
    convolution layers, whatever the row's length."""
    return _gauges("hybrid/").get("hybrid/state_bytes_per_row")
