"""Operations and bytes a kimi_vl cell's work needs, from its shapes alone
(``benchmark/flops.py`` does the same for dense multi-head models, whose
arithmetic does not count this work).

Needed work only, so no share of a peak worked out from these numbers can pass
100 %: causal attention is the lower triangle at key width ``nope + rope`` and
value width ``v_head_dim`` (the absorbed decode spends ``rank + rope`` and
``rank`` a pair; that is the implementation's choice), the routed experts are
counted at the assignments a chip's share expects, ``top_k * held / experts`` a
token, the vocabulary head only at the positions whose logits PPO reads;
padding, sorting and recomputation are not counted.

A matmul of ``[m, k] x [k, n]`` is ``2 m k n`` operations.
"""

from typing import Any, Dict, List

from benchmark.flops import flash_calls, iteration_tokens  # noqa: F401  (the traffic is the gpt2 cells')
from benchmark.reference_kimi_vl import dims


def expert_layers(config: Dict[str, Any]) -> int:
    s = dims(config)
    return s["layers"] - s["dense_layers"]


def expected_share(config: Dict[str, Any]) -> float:
    """The share of a token's assignments that falls to the experts held here
    under even routing."""
    s = dims(config)
    return s["held"] / s["experts"]


def trunk_flops_per_token(config: Dict[str, Any]) -> float:
    """Forward matmuls of the blocks for one token, attention scores apart."""
    s = dims(config)
    d, H = s["d"], s["heads"]
    attention = (d * H * (s["nope"] + s["rope"]) + d * (s["latent"] + s["rope"])
                 + s["latent"] * H * (s["nope"] + s["vdim"]) + H * s["vdim"] * d)
    dense = 3 * d * s["ffn"]
    routed = s["top_k"] * expected_share(config) * 3 * d * s["expert_ffn"]
    experts = d * s["experts"] + 3 * d * s["shared"] * s["expert_ffn"] + routed
    return 2.0 * (s["layers"] * attention + s["dense_layers"] * dense + expert_layers(config) * experts)


def head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * s["d"] * s["vocab"]


def value_head_flops_per_token(config: Dict[str, Any]) -> float:
    s = dims(config)
    return 2.0 * (s["d"] * 2 * s["d"] + 2 * s["d"])


def causal_attention_flops(config: Dict[str, Any], length: int) -> float:
    """Forward attention of one sequence in every layer: QK^T is ``2 (nope +
    rope)`` and PV ``2 v_head_dim`` operations per head and (query, visible
    key) pair."""
    s = dims(config)
    pairs = length * (length + 1) / 2
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["vdim"]) * pairs * s["layers"]


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
    """The least time the chip could take for ``calls`` (``flops.flash_calls``):
    per call the larger of operations over peak and bytes over peak bandwidth,
    at key width ``D = nope + rope`` and value width ``Dv``. The forward is
    QK^T (D) and PV (Dv) per visible pair and moves q, k (D) and v, o (Dv)
    once; the backward is QK^T again (D), dO V^T (Dv), dS K and dS^T Q (D),
    P^T dO (Dv), and moves q, k, v, o, dO in and dq, dk, dv out, all bf16."""
    s = dims(config)
    heads, D, Dv, L = s["heads"], s["nope"] + s["rope"], s["vdim"], s["layers"]
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for call in calls:
        T = call["length"]
        pairs = T * (T + 1) / 2
        if call["kind"] == "forward":
            width, moved = D + Dv, 2 * D + 2 * Dv
        else:
            width, moved = 3 * D + 2 * Dv, 4 * D + 4 * Dv
        flops = call["sequences"] * L * heads * 2.0 * width * pairs
        nbytes = call["sequences"] * L * heads * moved * T * 2.0
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}


def gmm_calls(config: Dict[str, Any], cell: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The grouped expert products one iteration needs: per kind of call, how
    often an expert layer is called and with how many tokens."""
    P, N = cell["prompt_len"], cell["new_tokens"]
    n, T = cell["num_rollouts"], P + N + 1
    decode = cell["decode_batch_size"] or cell["chunk_size"]
    learn_rows = cell["minibatch_size"] or cell["batch_size"]
    learn_calls = cell["ppo_epochs"] * n // learn_rows
    return [
        {"kind": "forward", "calls": n // decode, "tokens": decode * P},  # prefill
        {"kind": "forward", "calls": n // decode * (N - 1), "tokens": decode},  # decode steps
        {"kind": "forward", "calls": 2 * n // cell["chunk_size"], "tokens": cell["chunk_size"] * T},  # score
        {"kind": "forward", "calls": learn_calls, "tokens": learn_rows * T},  # learn
        {"kind": "backward", "calls": learn_calls, "tokens": learn_rows * T},
    ]


def gmm_min_seconds(config: Dict[str, Any], calls: List[Dict[str, Any]], peak: Dict[str, float],
                    held_share: float) -> Dict[str, Any]:
    """The least time for ``calls`` in every expert layer, whatever implements
    the products. A forward call is three ``[rows, d] x [d, f]``-shaped
    products over the ``rows = tokens * top_k * held_share`` assignments that
    fell to held experts; it reads the held experts' weights once and moves
    the rows in and out. A backward call is twice that (each product's two
    gradients), reads the weights once and writes their gradients once. bf16."""
    s = dims(config)
    d, f, held, layers = s["d"], s["expert_ffn"], s["held"], expert_layers(config)
    weights = 3 * held * d * f * 2.0
    total, bound_by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for call in calls:
        rows = call["tokens"] * s["top_k"] * held_share
        passes = 1 if call["kind"] == "forward" else 2
        flops = passes * 3 * 2.0 * rows * d * f
        nbytes = passes * weights + 2 * rows * d * 2.0
        t_flops, t_bytes = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        total += call["calls"] * layers * max(t_flops, t_bytes)
        bound_by["flops" if t_flops >= t_bytes else "bytes"] += call["calls"] * layers * max(t_flops, t_bytes)
    return {"seconds": total, "bound": max(bound_by, key=bound_by.get)}
