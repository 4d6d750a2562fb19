"""Operations and bytes a cell's work needs, from its shapes alone.

Everything here counts what the algorithm needs, never what an implementation
spends: causal attention is the lower triangle, the vocabulary head is counted
only at the positions whose logits PPO reads, padding to buckets and
recomputation are not counted. So a share of a peak worked out from these
numbers cannot pass 100 % whatever the implementation does.

A matmul of ``[m, k] x [k, n]`` is ``2 m k n`` operations.
"""

from typing import Any, Dict, List

from benchmark.reference import dims


def trunk_flops_per_token(config: Dict[str, Any]) -> float:
    """Forward matmuls of the blocks for one token, attention scores apart:
    q, k, v, o (4 d^2) and the MLP (2 d f) in every layer."""
    s = dims(config)
    return 2.0 * s["layers"] * (4 * s["d"] ** 2 + 2 * s["d"] * s["ffn"])


def head_flops_per_token(config: Dict[str, Any]) -> float:
    """The tied vocabulary head for one position."""
    s = dims(config)
    return 2.0 * s["d"] * s["vocab"]


def value_head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * (s["d"] * 2 * s["d"] + 2 * s["d"])


def causal_attention_flops(config: Dict[str, Any], length: int) -> float:
    """Forward attention of one sequence of ``length`` tokens in every layer:
    QK^T and PV are ``2 D`` operations each per (query, visible key) pair, and
    a causal query at position t sees t + 1 keys."""
    s = dims(config)
    pairs = length * (length + 1) / 2
    return 4.0 * s["d"] * pairs * s["layers"]


def forward_flops(config: Dict[str, Any], length: int, head_positions: int, value_head: bool) -> float:
    """One cache-free forward of one sequence."""
    flops = length * trunk_flops_per_token(config) + causal_attention_flops(config, length)
    flops += head_positions * head_flops_per_token(config)
    if value_head:
        flops += head_positions * value_head_flops_per_token(config)
    return flops


def iteration_flops(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, float]:
    """Model operations one PPO iteration needs, by phase. Sequences are
    ``prompt_len + new_tokens`` generated, and one more token (the eos the
    trainer appends) where they are scored and learnt."""
    P, N = cell["prompt_len"], cell["new_tokens"]
    R = N + 1
    n = cell["num_rollouts"]
    # rollout: every token but the last generated one is fed once; a head
    # evaluation per generated token
    rollout = n * forward_flops(config, P + N - 1, N, value_head=False)
    # score: the policy (with values) and the reference over prompt + response
    score = n * (forward_flops(config, P + R, R, True) + forward_flops(config, P + R, R, False))
    # learn: forward and backward (twice the forward) per pass over the store
    learn = cell["ppo_epochs"] * n * 3.0 * forward_flops(config, P + R, R, True)
    return {"rollout": rollout, "score": score, "learn": learn, "total": rollout + score + learn}


def iteration_tokens(cell: Dict[str, Any]) -> int:
    """Experience tokens one iteration collects and trains: prompt and
    generated tokens of every rollout, the re-appended eos not counted."""
    return cell["num_rollouts"] * (cell["prompt_len"] + cell["new_tokens"])


def flash_calls(config: Dict[str, Any], cell: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The flash-attention work one iteration needs: per kind of call, how
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
    """The least time the chip could take for ``calls``: per call the larger
    of operations over peak and bytes over peak bandwidth. The forward is two
    matmuls per visible pair (QK^T, PV) and moves q, k, v, o once; the backward
    is five (QK^T again, dO V^T, dS K, dS^T Q, P^T dO) and moves q, k, v, o, dO
    in and dq, dk, dv out, all bf16."""
    s = dims(config)
    heads, D, L = s["heads"], s["d"] // s["heads"], s["layers"]
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for call in calls:
        T = call["length"]
        pairs = T * (T + 1) / 2
        matmuls, tensors = (2, 4) if call["kind"] == "forward" else (5, 8)
        flops = call["sequences"] * L * heads * matmuls * 2.0 * D * pairs
        nbytes = call["sequences"] * L * heads * tensors * T * D * 2.0
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}
