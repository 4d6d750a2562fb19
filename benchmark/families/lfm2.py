"""The lfm2 family: what a configuration's ``"family": "lfm2"`` brings (the
five names ``families/gpt2.py`` has).

- ``reference``: ``benchmark/reference_lfm2.py`` (a mixer chosen layer by layer,
  gated short convolution or grouped-query attention with a norm on each head;
  leading dense layers, then routed experts with no shared one; one chip's share);
- ``flops``: ``benchmark/flops_lfm2.py``, the operations and bytes that work
  needs (attention in every layer does not count it);
- how the program under test is told the sizes (``MODEL_PATH``,
  :func:`program_overrides`) and how it names the weights (:func:`leaf_name`):
  names only, written down here by hand.
"""

from typing import Any, Dict, Optional, Tuple

from benchmark import flops_lfm2 as flops  # noqa: F401  (read as family.flops)
from benchmark import reference_lfm2 as reference  # noqa: F401  (read as family.reference)

MODEL_PATH = "lfm2_moe"  # no such directory: the program's preset, random init
#: published ``config.json`` key -> the program's ``model_overrides`` key
PUBLISHED_TO_PROGRAM = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "intermediate_size", "max_position_embeddings": "max_position_embeddings",
    "norm_eps": "norm_eps", "conv_L_cache": "conv_taps", "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "moe_intermediate_size", "num_dense_layers": "first_dense_layers",
    "routed_scaling_factor": "routed_scaling_factor", "norm_topk_prob": "norm_topk_prob",
    "initializer_range": "initializer_range",
}
#: published ``layer_types`` -> the program's ``layer_kinds``
KINDS = {"conv": "conv", "full_attention": "attention"}
#: program leaf (the end of its path) -> reference key of the stacked layers
_NORM_LEAVES = {("ln_1", "scale"): "ln_1.g", ("ln_2", "scale"): "ln_2.g"}
_CONV_LEAVES = {
    ("conv", "in_proj", "kernel"): "conv.in.w", ("conv", "conv", "kernel"): "conv.filter",
    ("conv", "out_proj", "kernel"): "conv.out.w",
}
_ATTENTION_LEAVES = {
    **{("attn", f"{n}_proj", "kernel"): f"attn.{n}.w" for n in "qkvo"},
    ("attn", "q_norm", "scale"): "attn.q_norm.g", ("attn", "k_norm", "scale"): "attn.k_norm.g",
}
_DENSE_LEAVES = {("mlp", f"{n}_proj", "kernel"): f"dense.{n}.w" for n in ("gate", "up", "down")}
_EXPERT_LEAVES = {
    ("mlp", "router", "kernel"): "moe.router.w", ("mlp", "router", "bias"): "moe.router.b",
    **{("mlp", "experts", n): f"moe.experts.{n}" for n in ("gate", "up", "down")},
}
_TOP_LEAVES = {
    ("transformer", "embed_tokens", "embedding"): "wte",
    ("transformer", "ln_f", "scale"): "ln_f.g",
    **{("v_head", "value_head", fc, leaf): f"v.{fc}.{short}"
       for fc in ("fc_in", "fc_out") for leaf, short in (("kernel", "w"), ("bias", "b"))},
}
#: microbatches of 8 x 513 tokens beside 20 bytes a parameter: PERF.md section 4 has what the
#: chip's compiler reckons for each choice
REMAT = "none"
#: the family's kept layers in their published order and its leading dense layers: ``leaf_name``
#: gets a path alone, and a layer's index into a stack of its own kind counts from these
LAYER_TYPES = ("conv", "full_attention", "conv", "conv", "conv")
DENSE_LAYERS = 1


def program_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under the program's ``model_overrides`` keys.
    ``num_experts`` counts the experts held here; the router's width is the
    published count. ``remat`` is what the learner's fit needs (PERF.md)."""
    overrides = {prog: config[pub] for pub, prog in PUBLISHED_TO_PROGRAM.items()}
    kinds = tuple(config["layer_types"])
    if kinds != LAYER_TYPES[: len(kinds)] or int(config["num_dense_layers"]) != DENSE_LAYERS:
        raise ValueError(
            f"the lfm2 family keeps layers of the kinds {LAYER_TYPES} (or the first of them) after {DENSE_LAYERS} "
            f"leading dense layer(s), which leaf_name counts from; the configuration gives layer_types {kinds} and "
            f"num_dense_layers {config['num_dense_layers']}")
    overrides["layer_kinds"] = tuple(KINDS[kind] for kind in kinds)
    overrides["rope_theta"] = float(config["rope_parameters"]["rope_theta"])
    overrides["num_experts"] = config.get("published", {}).get("num_experts", config["num_experts"])
    overrides["experts_held"] = config["num_experts"]
    overrides["expert_offset"] = config.get("expert_offset", 0)
    overrides["remat"] = REMAT
    return overrides


def leaf_name(path: Tuple[str, ...]) -> Tuple[str, Optional[int]]:
    """Program parameter path -> (reference key, index into that key's own
    stack or None). The norm keys stack every layer, a mixer's keys the layers
    of its kind, the dense FFN's the leading dense layers, the experts' the
    layers after them."""
    if path in _TOP_LEAVES:
        return _TOP_LEAVES[path], None
    if len(path) >= 3 and path[0] == "transformer" and path[1].startswith("layers_"):
        layer, rest = int(path[1][len("layers_"):]), tuple(path[2:])
        if rest in _NORM_LEAVES:
            return "h." + _NORM_LEAVES[rest], layer
        if rest in _CONV_LEAVES:
            return "h." + _CONV_LEAVES[rest], LAYER_TYPES[:layer].count("conv")
        if rest in _ATTENTION_LEAVES:
            return "h." + _ATTENTION_LEAVES[rest], LAYER_TYPES[:layer].count("full_attention")
        if rest in _DENSE_LEAVES:
            return "h." + _DENSE_LEAVES[rest], layer
        if rest in _EXPERT_LEAVES:
            return "h." + _EXPERT_LEAVES[rest], layer - DENSE_LAYERS
    raise KeyError(f"no reference weight for the program's parameter {'/'.join(path)}")
