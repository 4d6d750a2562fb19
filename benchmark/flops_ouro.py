"""Operations and bytes an ouro cell's work needs, from its shapes alone
(``benchmark/flops.py`` walks the layers once; a looped model's trunk is walked
``total_ut_steps`` times).

Needed work only, so no share of a peak worked out from these numbers can pass
100 %: every pass of the trunk counts (at the published ``early_exit_threshold``
of 1 every token takes them all), causal attention is the lower triangle in
each of ``passes x layers`` calls, the vocabulary head and the value head are
taken once, at the positions whose logits PPO reads; the exit gate, which is on
no path to the logits, padding to buckets and recomputation are not counted.

A matmul of ``[m, k] x [k, n]`` is ``2 m k n`` operations.
"""

from typing import Any, Dict, List

from benchmark.flops import flash_calls, iteration_tokens  # noqa: F401  (the traffic is the gpt2 cells')
from benchmark.reference_ouro import dims


def block_applications(config: Dict[str, Any]) -> int:
    """Layers a forward applies: every layer in every pass."""
    s = dims(config)
    return s["passes"] * s["layers"]


def layer_weights(config: Dict[str, Any]) -> int:
    """Matrix parameters of one layer: q, k, v, o and the gated FFN's three."""
    s = dims(config)
    return 4 * s["d"] * s["heads"] * s["head_dim"] + 3 * s["d"] * s["ffn"]


def trunk_flops_per_token(config: Dict[str, Any]) -> float:
    """Forward matmuls of the blocks for one token, attention scores apart."""
    return 2.0 * block_applications(config) * layer_weights(config)


def head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * s["d"] * s["vocab"]


def value_head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * (s["d"] * 2 * s["d"] + 2 * s["d"])


def causal_attention_flops(config: Dict[str, Any], length: int) -> float:
    """Forward attention of one sequence in every block application: QK^T and
    PV are ``2 head_dim`` operations each per head and (query, visible key) pair."""
    s = dims(config)
    pairs = length * (length + 1) / 2
    return 4.0 * s["heads"] * s["head_dim"] * pairs * block_applications(config)


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
    in every block application, as ``flops.flash_min_seconds`` reckons it, at
    this family's ``head_dim``: the forward is two matmuls per visible pair and
    moves q, k, v, o once; the backward is five and moves q, k, v, o, dO in and
    dq, dk, dv out, all bf16."""
    s = dims(config)
    heads, D, applications = s["heads"], s["head_dim"], block_applications(config)
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for call in calls:
        T = call["length"]
        pairs = T * (T + 1) / 2
        matmuls, tensors = (2, 4) if call["kind"] == "forward" else (5, 8)
        flops = call["sequences"] * applications * heads * matmuls * 2.0 * D * pairs
        nbytes = call["sequences"] * applications * heads * tensors * T * D * 2.0
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """bf16 keys and values of one token in every (pass, layer)."""
    s = dims(config)
    return block_applications(config) * 2 * s["heads"] * s["head_dim"] * 2


def decode_min_seconds(config: Dict[str, Any], cell: Dict[str, Any], peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time for the decode steps of one ``generate`` call: a batch of
    ``decode_batch_size`` rows, ``new_tokens - 1`` steps after a prefill of
    ``prompt_len`` slots (the first token is the prefill's). Step ``s`` (from
    1) reads the ``prompt_len + s`` written slots of every (pass, layer) for
    every row, unrounded, the layers' bf16 weights once a pass and the head's
    once; its operations are a token's trunk and head for every row and its
    attention over those slots. Per step the larger of operations over peak
    and bytes over peak bandwidth."""
    s = dims(config)
    P, N = cell["prompt_len"], cell["new_tokens"]
    rows = cell["decode_batch_size"] or cell["chunk_size"]
    weights = (block_applications(config) * layer_weights(config) + s["d"] * s["vocab"]) * 2.0
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for step in range(1, N):
        slots = P + step
        nbytes = weights + rows * slots * cache_bytes_per_token(config)
        flops = rows * (trunk_flops_per_token(config) + head_flops_per_token(config)
                        + 4.0 * s["heads"] * s["head_dim"] * slots * block_applications(config))
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}
