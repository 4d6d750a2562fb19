"""The kimi_vl family: what a configuration's ``"family": "kimi_vl"`` brings
(the five names ``families/gpt2.py`` has).

- ``reference``: ``benchmark/reference_kimi_vl.py`` (latent attention, routed
  and shared experts, a leading dense layer; one chip's share);
- ``flops``: ``benchmark/flops_kimi_vl.py``, the operations and bytes that
  work needs (dense multi-head arithmetic does not count it);
- how the program under test is told the sizes (``MODEL_PATH``,
  :func:`program_overrides`) and how it names the weights (:func:`leaf_name`):
  names only, written down here by hand.
"""

from typing import Any, Dict, Optional, Tuple

from benchmark import flops_kimi_vl as flops  # noqa: F401  (read as family.flops)
from benchmark import reference_kimi_vl as reference  # noqa: F401  (read as family.reference)

MODEL_PATH = "kimi_vl"  # no such directory: the program's preset, random init
#: published ``config.json`` key -> the program's ``model_overrides`` key
PUBLISHED_TO_PROGRAM = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "intermediate_size": "intermediate_size",
    "max_position_embeddings": "max_position_embeddings", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "num_shared_experts", "moe_intermediate_size": "moe_intermediate_size",
    "first_k_dense_replace": "first_dense_layers", "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "initializer_range": "initializer_range",
}
#: program leaf (the end of its path) -> reference key of the stacked layers
_LAYER_LEAVES = {
    ("ln_1", "scale"): "ln_1.g", ("ln_2", "scale"): "ln_2.g",
    ("attn", "q_proj", "kernel"): "q.w", ("attn", "kv_a_proj", "kernel"): "kva.w",
    ("attn", "kv_a_norm", "scale"): "kva_norm.g", ("attn", "kv_b_proj", "kernel"): "kvb.w",
    ("attn", "o_proj", "kernel"): "o.w",
}
_DENSE_LEAVES = {("mlp", f"{n}_proj", "kernel"): f"dense.{n}.w" for n in ("gate", "up", "down")}
_EXPERT_LEAVES = {
    ("mlp", "router", "kernel"): "moe.router.w", ("mlp", "router", "bias"): "moe.router.b",
    **{("mlp", "experts", n): f"moe.experts.{n}" for n in ("gate", "up", "down")},
    **{("mlp", "shared", f"{n}_proj", "kernel"): f"moe.shared.{n}.w" for n in ("gate", "up", "down")},
}
_TOP_LEAVES = {
    ("transformer", "embed_tokens", "embedding"): "wte",
    ("transformer", "lm_head", "kernel"): "head.w",
    ("transformer", "ln_f", "scale"): "ln_f.g",
    **{("v_head", "value_head", fc, leaf): f"v.{fc}.{short}"
       for fc in ("fc_in", "fc_out") for leaf, short in (("kernel", "w"), ("bias", "b"))},
}
#: microbatches of 4 x 513 tokens beside 20 bytes a parameter: saving everything for the backward
#: reckons 16.8 GiB on the chip's 15.75, saving the matmuls' outputs alone 14.1 (PERF.md)
REMAT = "dots_saveable"
#: the family's leading dense layers (the published ``first_k_dense_replace``): ``leaf_name`` gets
#: a path alone, and an expert layer's index into its own stack counts from them
DENSE_LAYERS = 1


def program_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under the program's ``model_overrides`` keys.
    ``n_routed_experts`` counts the experts held here; the router's width is
    the published count. ``remat`` is what the learner's fit needs (PERF.md)."""
    overrides = {prog: config[pub] for pub, prog in PUBLISHED_TO_PROGRAM.items()}
    overrides["num_experts"] = config.get("published", {}).get("n_routed_experts", config["n_routed_experts"])
    overrides["experts_held"] = config["n_routed_experts"]
    overrides["expert_offset"] = config.get("expert_offset", 0)
    overrides["remat"] = REMAT
    if int(config["first_k_dense_replace"]) != DENSE_LAYERS:
        raise ValueError(f"the kimi_vl family has {DENSE_LAYERS} leading dense layer(s), which leaf_name counts "
                         f"from; the configuration gives first_k_dense_replace {config['first_k_dense_replace']}")
    return overrides


def leaf_name(path: Tuple[str, ...]) -> Tuple[str, Optional[int]]:
    """Program parameter path -> (reference key, index into that key's own
    stack or None). The attention and norm keys stack every layer, the dense
    FFN's the leading dense layers, the experts' the layers after them."""
    if path in _TOP_LEAVES:
        return _TOP_LEAVES[path], None
    if len(path) >= 3 and path[0] == "transformer" and path[1].startswith("layers_"):
        layer, rest = int(path[1][len("layers_"):]), tuple(path[2:])
        if rest in _LAYER_LEAVES:
            return "h." + _LAYER_LEAVES[rest], layer
        if rest in _DENSE_LEAVES:
            return "h." + _DENSE_LEAVES[rest], layer
        if rest in _EXPERT_LEAVES:
            return "h." + _EXPERT_LEAVES[rest], layer - DENSE_LAYERS
    raise KeyError(f"no reference weight for the program's parameter {'/'.join(path)}")
