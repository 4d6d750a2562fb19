"""Operations and bytes an lfm2 cell's work needs, from its shapes alone
(``benchmark/flops.py`` does the same for models that attend in every layer
over a dense FFN, whose arithmetic does not count this work).

Needed work only, so no share of a peak worked out from these numbers can pass
100 %: causal attention is the lower triangle in the attention layers alone, a
convolution layer is its two products, its taps and its two gates, the routed
experts are counted at the assignments a chip's share expects, ``top_k * held /
experts`` a token, the vocabulary head only at the positions whose logits PPO
reads; padding, sorting and recomputation are not counted.

A matmul of ``[m, k] x [k, n]`` is ``2 m k n`` operations.
"""

from typing import Any, Dict, List

from benchmark.flops import flash_calls, iteration_tokens  # noqa: F401  (the traffic is the gpt2 cells')
from benchmark.flops_kimi_vl import gmm_calls  # noqa: F401  (an expert layer is called as kimi_vl's are)
from benchmark.reference_lfm2 import dims

BF16 = 2.0  # bytes


def expert_layers(config: Dict[str, Any]) -> int:
    s = dims(config)
    return s["layers"] - s["dense_layers"]


def expected_share(config: Dict[str, Any]) -> float:
    """The share of a token's assignments that falls to the experts held here
    under even routing."""
    s = dims(config)
    return s["held"] / s["experts"]


def conv_weights(config: Dict[str, Any]) -> int:
    """Parameters of one convolution mixer: the product to ``[b, c, x]``, the filter, the product back."""
    s = dims(config)
    return s["d"] * 3 * s["d"] + s["d"] * s["taps"] + s["d"] * s["d"]


def attention_weights(config: Dict[str, Any]) -> int:
    """Matrix parameters of one attention mixer: q and o at all heads, k and v at the kv heads."""
    s = dims(config)
    return 2 * s["d"] * s["heads"] * s["head_dim"] + 2 * s["d"] * s["kv_heads"] * s["head_dim"]


def trunk_flops_per_token(config: Dict[str, Any]) -> float:
    """Forward operations of the blocks for one token, attention scores apart."""
    s = dims(config)
    d = s["d"]
    conv = 4 * d * d + s["taps"] * d + d  # the two products; a multiply-add a tap; the two gates' products
    dense = 3 * d * s["ffn"]
    routed = s["top_k"] * expected_share(config) * 3 * d * s["expert_ffn"]
    experts = d * s["experts"] + routed
    return 2.0 * (s["conv_layers"] * conv + s["attn_layers"] * attention_weights(config)
                  + s["dense_layers"] * dense + expert_layers(config) * experts)


def head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * s["d"] * s["vocab"]


def value_head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * (s["d"] * 2 * s["d"] + 2 * s["d"])


def causal_attention_flops(config: Dict[str, Any], length: int) -> float:
    """Forward attention of one sequence in every attention layer: QK^T and PV
    are ``2 head_dim`` operations each per query head and (query, visible key) pair."""
    s = dims(config)
    pairs = length * (length + 1) / 2
    return 4.0 * s["heads"] * s["head_dim"] * pairs * s["attn_layers"]


def forward_flops(config: Dict[str, Any], length: int, head_positions: int, value_head: bool) -> float:
    """One cache-free forward of one sequence."""
    flops = length * trunk_flops_per_token(config) + causal_attention_flops(config, length)
    flops += head_positions * head_flops_per_token(config)
    if value_head:
        flops += head_positions * value_head_flops_per_token(config)
    return flops


def iteration_flops(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, float]:
    """Model operations one PPO iteration needs, by phase, as
    ``flops.iteration_flops`` counts them."""
    P, N = cell["prompt_len"], cell["new_tokens"]
    R = N + 1
    n = cell["num_rollouts"]
    rollout = n * forward_flops(config, P + N - 1, N, value_head=False)
    score = n * (forward_flops(config, P + R, R, True) + forward_flops(config, P + R, R, False))
    learn = cell["ppo_epochs"] * n * 3.0 * forward_flops(config, P + R, R, True)
    return {"rollout": rollout, "score": score, "learn": learn, "total": rollout + score + learn}


def flash_min_seconds(config: Dict[str, Any], calls: List[Dict[str, Any]], peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take for ``calls`` (``flops.flash_calls``)
    in every attention layer, as ``flops.flash_min_seconds`` reckons it, with
    grouped heads: the operations are the query heads', two matmuls per visible
    pair forward and five backward; the forward moves q and o at ``heads`` and
    k and v at ``kv_heads`` once, the backward q, o, dO, dq at ``heads`` and k,
    v, dk, dv at ``kv_heads``, all bf16."""
    s = dims(config)
    H, Hkv, D, L = s["heads"], s["kv_heads"], s["head_dim"], s["attn_layers"]
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for call in calls:
        T = call["length"]
        pairs = T * (T + 1) / 2
        matmuls, tensors = (2, 2) if call["kind"] == "forward" else (5, 4)
        flops = call["sequences"] * L * H * matmuls * 2.0 * D * pairs
        nbytes = call["sequences"] * L * tensors * (H + Hkv) * T * D * BF16
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}


def gmm_min_seconds(config: Dict[str, Any], calls: List[Dict[str, Any]], peak: Dict[str, float],
                    held_share: float) -> Dict[str, Any]:
    """The least time for ``calls`` in every expert layer, whatever implements
    the products, as ``flops_kimi_vl.gmm_min_seconds`` reckons it at this
    family's sizes. A forward call is three ``[rows, d] x [d, f]``-shaped
    products over the ``rows = tokens * top_k * held_share`` assignments that
    fell to held experts; it reads the held experts' weights once and moves
    the rows in and out. A backward call is twice that. bf16."""
    s = dims(config)
    d, f, held, layers = s["d"], s["expert_ffn"], s["held"], expert_layers(config)
    weights = 3 * held * d * f * BF16
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for call in calls:
        rows = call["tokens"] * s["top_k"] * held_share
        passes = 1 if call["kind"] == "forward" else 2
        flops = passes * 3 * 2.0 * rows * d * f
        nbytes = passes * weights + 2 * rows * d * BF16
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += call["calls"] * layers * max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += call["calls"] * layers * max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """bf16 keys and values of one token in every attention layer."""
    s = dims(config)
    return int(s["attn_layers"] * 2 * s["kv_heads"] * s["head_dim"] * BF16)


def state_bytes_per_row(config: Dict[str, Any]) -> int:
    """The bf16 gated inputs a row's next token reads, ``taps - 1`` of them, in every convolution layer."""
    s = dims(config)
    return int(s["conv_layers"] * (s["taps"] - 1) * s["d"] * BF16)


def decode_weights(config: Dict[str, Any]) -> int:
    """Matrix parameters a decode step reads: every mixer, the dense FFN, the
    routers, every held expert (128 rows of 4 assignments over 64 experts touch
    all 8 held) and the tied head's rows."""
    s = dims(config)
    d = s["d"]
    experts = d * s["experts"] + s["held"] * 3 * d * s["expert_ffn"]
    return (s["conv_layers"] * conv_weights(config) + s["attn_layers"] * attention_weights(config)
            + s["dense_layers"] * 3 * d * s["ffn"] + expert_layers(config) * experts + d * s["vocab"])


def decode_min_seconds(config: Dict[str, Any], cell: Dict[str, Any], peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time for the decode steps of one ``generate`` call: a batch of
    ``decode_batch_size`` rows, ``new_tokens - 1`` steps after a prefill of
    ``prompt_len`` slots (the first token is the prefill's). Step ``s`` (from
    1) reads the bf16 weights once, the ``prompt_len + s`` written slots of the
    attention layers for every row, unrounded, and reads and writes every
    row's convolution states; its operations are a token's trunk and head for
    every row and its attention over those slots. Per step the larger of
    operations over peak and bytes over peak bandwidth."""
    s = dims(config)
    P, N = cell["prompt_len"], cell["new_tokens"]
    rows = cell["decode_batch_size"] or cell["chunk_size"]
    weights = decode_weights(config) * BF16
    states = 2 * rows * state_bytes_per_row(config)
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for step in range(1, N):
        slots = P + step
        nbytes = weights + states + rows * slots * cache_bytes_per_token(config)
        flops = rows * (trunk_flops_per_token(config) + head_flops_per_token(config)
                        + 4.0 * s["heads"] * s["head_dim"] * slots * s["attn_layers"])
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}
