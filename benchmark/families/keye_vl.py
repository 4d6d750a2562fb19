"""The keye_vl family: what a configuration's ``"family": "keye_vl"`` brings
(the five names ``families/gpt2.py`` has).

- ``reference``: ``benchmark/reference_keye_vl.py`` (grouped-query attention
  over the keys a learned indexer selects, softmax-routed experts with no
  shared one and no dense layer; one chip's share);
- ``flops``: ``benchmark/flops_keye_vl.py``, the operations and bytes that work
  needs (dense causal attention does not count it);
- how the program under test is told the sizes (``MODEL_PATH``,
  :func:`program_overrides`) and how it names the weights (:func:`leaf_name`):
  names only, written down here by hand.
"""

from typing import Any, Dict, Optional, Tuple

from benchmark import flops_keye_vl as flops  # noqa: F401  (read as family.flops)
from benchmark import reference_keye_vl as reference  # noqa: F401  (read as family.reference)

MODEL_PATH = "keye_vl"  # no such directory: the program's preset, random init
#: published ``config.json`` key -> the program's ``model_overrides`` key
PUBLISHED_TO_PROGRAM = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate_size", "max_position_embeddings": "max_position_embeddings",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "moe_intermediate_size", "norm_topk_prob": "norm_topk_prob",
    "initializer_range": "initializer_range",
}
#: published ``sa_config`` key -> the program's ``model_overrides`` key
SPARSE_TO_PROGRAM = {"topk": "index_topk", "indexer_num_heads": "index_heads", "indexer_head_dim": "index_head_dim"}
#: program leaf (the end of its path) -> reference key of the stacked layers
_LAYER_LEAVES = {
    ("ln_1", "scale"): "ln_1.g", ("ln_2", "scale"): "ln_2.g",
    **{("attn", f"{n}_proj", "kernel"): f"attn.{n}.w" for n in "qkvo"},
    ("attn", "q_norm", "scale"): "attn.q_norm.g", ("attn", "k_norm", "scale"): "attn.k_norm.g",
    ("attn", "indexer", "wq", "kernel"): "idx.q.w", ("attn", "indexer", "wk", "kernel"): "idx.k.w",
    ("attn", "indexer", "k_norm", "scale"): "idx.k_norm.g", ("attn", "indexer", "k_norm", "bias"): "idx.k_norm.b",
    ("attn", "indexer", "weights_proj", "kernel"): "idx.w.w",
    ("mlp", "router", "kernel"): "moe.router.w",
    **{("mlp", "experts", n): f"moe.experts.{n}" for n in ("gate", "up", "down")},
}
_TOP_LEAVES = {
    ("transformer", "embed_tokens", "embedding"): "wte",
    ("transformer", "lm_head", "kernel"): "head.w",
    ("transformer", "ln_f", "scale"): "ln_f.g",
    **{("v_head", "value_head", fc, leaf): f"v.{fc}.{short}"
       for fc in ("fc_in", "fc_out") for leaf, short in (("kernel", "w"), ("bias", "b"))},
}
#: one row of 8,193 tokens a microbatch beside 20 bytes a parameter: the learner keeps each layer's
#: input alone and recomputes the layer in the backward (PERF.md section 4 has the fit)
REMAT = "per_layer"


def program_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under the program's ``model_overrides`` keys.
    ``num_experts`` counts the experts held here; the router's width is the
    published count. ``remat`` is what the learner's fit needs (PERF.md)."""
    overrides = {prog: config[pub] for pub, prog in PUBLISHED_TO_PROGRAM.items()}
    overrides.update({prog: config["sa_config"][pub] for pub, prog in SPARSE_TO_PROGRAM.items()})
    overrides["index_rope_dim"] = config["sa_config"]["indexer_head_dim"] // 2
    overrides["rope_theta"] = float(config["rope_theta"])
    overrides["num_experts"] = config.get("published", {}).get("num_experts", config["num_experts"])
    overrides["experts_held"] = config["num_experts"]
    overrides["expert_offset"] = config.get("expert_offset", 0)
    overrides["remat"] = REMAT
    return overrides


def leaf_name(path: Tuple[str, ...]) -> Tuple[str, Optional[int]]:
    """Program parameter path -> (reference key, layer or None). Every layer
    has every stacked key."""
    if path in _TOP_LEAVES:
        return _TOP_LEAVES[path], None
    if len(path) >= 3 and path[0] == "transformer" and path[1].startswith("layers_"):
        key = _LAYER_LEAVES.get(tuple(path[2:]))
        if key is not None:
            return "h." + key, int(path[1][len("layers_"):])
    raise KeyError(f"no reference weight for the program's parameter {'/'.join(path)}")
