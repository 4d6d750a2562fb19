"""Operations and bytes a keye_vl cell's work needs, from its shapes alone
(``benchmark/flops.py`` does the same for models that attend densely over a
dense FFN, whose arithmetic does not count this work).

Needed work only, so no share of a peak worked out from these numbers can pass
100 %: attention is counted over the pairs the selection keeps, ``min(topk, t +
1)`` keys for the query at position t, and the indexer's scores over every
causal pair (its ``Hi`` heads of ``Di`` a pair); the routed experts are counted
at the assignments a chip's share expects, ``top_k * held / experts`` a token;
the vocabulary head only at the positions whose logits PPO reads; padding,
the selection's counting passes and recomputation are not counted.

A matmul of ``[m, k] x [k, n]`` is ``2 m k n`` operations.
"""

from typing import Any, Dict, List

from benchmark.flops import iteration_tokens  # noqa: F401  (the traffic is the gpt2 cells')
from benchmark.flops_kimi_vl import gmm_calls  # noqa: F401  (an expert layer is called as kimi_vl's are)
from benchmark.reference_keye_vl import dims

BF16 = 2.0  # bytes


def kept_pairs(config: Dict[str, Any], length: int) -> float:
    """(query, key) pairs the selection keeps in one sequence of ``length``
    real tokens: ``min(topk, t + 1)`` for the query at position t."""
    k = dims(config)["topk"]
    if length <= k:
        return length * (length + 1) / 2
    return k * (k + 1) / 2 + (length - k) * k


def causal_pairs(length: int) -> float:
    return length * (length + 1) / 2


def expected_share(config: Dict[str, Any]) -> float:
    """The share of a token's assignments that falls to the experts held here under even routing."""
    s = dims(config)
    return s["held"] / s["experts"]


def attention_weights(config: Dict[str, Any]) -> int:
    """Matrix parameters of one attention layer: q and o at all heads, k and v at the kv heads."""
    s = dims(config)
    return 2 * s["d"] * s["heads"] * s["head_dim"] + 2 * s["d"] * s["kv_heads"] * s["head_dim"]


def indexer_weights(config: Dict[str, Any]) -> int:
    """Matrix parameters of one indexer: its query heads, its key head, its per-head weights."""
    s = dims(config)
    return s["d"] * (s["index_heads"] * s["index_dim"] + s["index_dim"] + s["index_heads"])


def trunk_flops_per_token(config: Dict[str, Any]) -> float:
    """Forward operations of the layers for one token, attention and index scores apart."""
    s = dims(config)
    routed = s["top_k"] * expected_share(config) * 3 * s["d"] * s["expert_ffn"]
    per_layer = attention_weights(config) + indexer_weights(config) + s["d"] * s["experts"] + routed
    return 2.0 * s["layers"] * per_layer


def head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * s["d"] * s["vocab"]


def value_head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * (s["d"] * 2 * s["d"] + 2 * s["d"])


def attention_flops(config: Dict[str, Any], length: int) -> float:
    """Forward attention of one sequence in every layer: QK^T and PV, ``2
    head_dim`` operations each per query head and kept pair; the indexer's
    scores, ``2 Di`` a head and causal pair."""
    s = dims(config)
    attend = 4.0 * s["heads"] * s["head_dim"] * kept_pairs(config, length)
    index = 2.0 * s["index_heads"] * s["index_dim"] * causal_pairs(length)
    return s["layers"] * (attend + index)


def forward_flops(config: Dict[str, Any], length: int, head_positions: int, value_head: bool) -> float:
    """One cache-free forward of one sequence."""
    flops = length * trunk_flops_per_token(config) + attention_flops(config, length)
    flops += head_positions * head_flops_per_token(config)
    if value_head:
        flops += head_positions * value_head_flops_per_token(config)
    return flops


def iteration_flops(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, float]:
    """Model operations one PPO iteration needs, by phase, as
    ``flops.iteration_flops`` counts them. The indexer takes no gradient: its
    scores count once in a learner's pass, the rest three times."""
    P, N = cell["prompt_len"], cell["new_tokens"]
    R, T = N + 1, P + N + 1
    n = cell["num_rollouts"]
    s = dims(config)
    rollout = n * forward_flops(config, P + N - 1, N, value_head=False)
    score = n * (forward_flops(config, T, R, True) + forward_flops(config, T, R, False))
    index = s["layers"] * 2.0 * (s["index_heads"] * s["index_dim"] * causal_pairs(T) + T * indexer_weights(config))
    learn = cell["ppo_epochs"] * n * (3.0 * forward_flops(config, T, R, True) - 2.0 * index)
    return {"rollout": rollout, "score": score, "learn": learn, "total": rollout + score + learn}


def sparse_calls(config: Dict[str, Any], cell: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The sparse attention work one iteration needs: per kind of call, how
    many sequences go through every layer, at which length."""
    P, N = cell["prompt_len"], cell["new_tokens"]
    n, T = cell["num_rollouts"], P + N + 1
    passes = cell["ppo_epochs"]
    return [
        {"kind": "forward", "sequences": n, "length": P},  # prefill
        {"kind": "forward", "sequences": 2 * n, "length": T},  # score: policy, reference
        {"kind": "forward", "sequences": passes * n, "length": T},  # learn
        {"kind": "backward", "sequences": passes * n, "length": T},
    ]


def flash_min_seconds(config: Dict[str, Any], calls: List[Dict[str, Any]], peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take for ``calls`` (:func:`sparse_calls`)
    of attention over the kept pairs, in every layer, whatever implements it:
    the operations are the query heads', two matmuls per kept pair forward and
    five backward; the forward moves q and o at ``heads`` and k and v at
    ``kv_heads`` once, the backward q, o, dO, dq at ``heads`` and k, v, dk, dv
    at ``kv_heads``, all bf16. (The reader calls it by this name.)"""
    s = dims(config)
    H, Hkv, D, L = s["heads"], s["kv_heads"], s["head_dim"], s["layers"]
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for call in calls:
        T = call["length"]
        matmuls, tensors = (2, 2) if call["kind"] == "forward" else (5, 4)
        flops = call["sequences"] * L * H * matmuls * 2.0 * D * kept_pairs(config, T)
        nbytes = call["sequences"] * L * tensors * (H + Hkv) * T * D * BF16
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}


def gmm_min_seconds(config: Dict[str, Any], calls: List[Dict[str, Any]], peak: Dict[str, float],
                    held_share: float) -> Dict[str, Any]:
    """The least time for ``calls`` in every expert layer, whatever implements
    the products, as ``flops_kimi_vl.gmm_min_seconds`` reckons it at this
    family's sizes (no dense layer: every layer has experts). bf16."""
    s = dims(config)
    d, f, held, layers = s["d"], s["expert_ffn"], s["held"], s["layers"]
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
    """bf16 keys and values and the indexer's key of one token in every layer."""
    s = dims(config)
    return int(s["layers"] * (2 * s["kv_heads"] * s["head_dim"] + s["index_dim"]) * BF16)


def decode_weights(config: Dict[str, Any]) -> int:
    """Matrix parameters a decode step reads: attention, indexer and router of
    every layer, every held expert (16 rows of 8 assignments over 128 experts
    touch about 10 of the 16 held; all are counted, the least a step can read is
    what it needs) and the head's rows."""
    s = dims(config)
    d = s["d"]
    per_layer = attention_weights(config) + indexer_weights(config) + d * s["experts"]
    return s["layers"] * per_layer + s["layers"] * s["held"] * 3 * d * s["expert_ffn"] + d * s["vocab"]


def decode_min_seconds(config: Dict[str, Any], cell: Dict[str, Any], peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time for the decode steps of one ``generate`` call: a batch of
    ``decode_batch_size`` rows, ``new_tokens - 1`` steps after a prefill of
    ``prompt_len`` slots. Step ``s`` (from 1) reads the bf16 weights once,
    every written slot's indexer key (``prompt_len + s`` of them, a row and a
    layer) and the keys and values of the ``min(topk, prompt_len + s)`` slots
    selected, nothing else of the cache; its operations are a token's trunk and
    head for every row, the indexer's scores over the written slots and
    attention over the selected ones. Per step the larger of operations over
    peak and bytes over peak bandwidth."""
    s = dims(config)
    P, N = cell["prompt_len"], cell["new_tokens"]
    rows = cell["decode_batch_size"] or cell["chunk_size"]
    weights = decode_weights(config) * BF16
    kv = 2 * s["kv_heads"] * s["head_dim"] * BF16
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for step in range(1, N):
        slots = P + step
        kept = min(s["topk"], slots)
        nbytes = weights + rows * s["layers"] * (slots * s["index_dim"] * BF16 + kept * kv)
        flops = rows * (trunk_flops_per_token(config) + head_flops_per_token(config) + s["layers"] * (
            2.0 * s["index_heads"] * s["index_dim"] * slots + 4.0 * s["heads"] * s["head_dim"] * kept))
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}
