"""The gpt2 family: what a configuration's ``"family": "gpt2"`` brings.

- ``reference``: the family's plain reference (weights from the seed, forward,
  PPO step), which imports nothing of the program;
- ``flops``: the operations and bytes its work needs, from shapes;
- how the program under test is told the sizes (``MODEL_PATH``,
  :func:`program_overrides`) and how it names the weights (:func:`leaf_name`):
  names only, written down here by hand.

Another family is another file here, with the same five names, and a
reference of its own beside ``benchmark/reference.py``; nothing is edited.
"""

from typing import Any, Dict, Optional, Tuple

from benchmark import flops, reference  # noqa: F401  (read as family.flops, family.reference)

MODEL_PATH = "gpt2"  # no such directory: the program's preset, random init
#: published ``config.json`` key -> the program's ``model_overrides`` key
PUBLISHED_TO_PROGRAM = {
    "vocab_size": "vocab_size", "n_embd": "hidden_size", "n_layer": "num_layers",
    "n_head": "num_heads", "n_positions": "max_position_embeddings",
    "layer_norm_epsilon": "norm_eps", "initializer_range": "initializer_range",
}
#: program leaf (the end of its path) -> reference key of the stacked layers
_LAYER_LEAVES = {
    ("ln_1", "scale"): "ln_1.g", ("ln_1", "bias"): "ln_1.b",
    ("ln_2", "scale"): "ln_2.g", ("ln_2", "bias"): "ln_2.b",
    **{("attn", f"{n}_proj", leaf): f"{n}.{short}"
       for n in "qkvo" for leaf, short in (("kernel", "w"), ("bias", "b"))},
    **{("mlp", f"{n}_proj", leaf): f"{n}.{short}"
       for n in ("up", "down") for leaf, short in (("kernel", "w"), ("bias", "b"))},
}
_TOP_LEAVES = {
    ("transformer", "embed_tokens", "embedding"): "wte",
    ("transformer", "embed_positions", "embedding"): "wpe",
    ("transformer", "ln_f", "scale"): "ln_f.g", ("transformer", "ln_f", "bias"): "ln_f.b",
    **{("v_head", "value_head", fc, leaf): f"v.{fc}.{short}"
       for fc in ("fc_in", "fc_out") for leaf, short in (("kernel", "w"), ("bias", "b"))},
}


def program_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under the program's ``model_overrides`` keys."""
    overrides = {prog: config[pub] for pub, prog in PUBLISHED_TO_PROGRAM.items()}
    if config.get("n_inner"):
        overrides["intermediate_size"] = config["n_inner"]
    return overrides


def leaf_name(path: Tuple[str, ...]) -> Tuple[str, Optional[int]]:
    """Program parameter path -> (reference key, layer or None). The
    reference stacks the layers' weights under keys that start with ``h.``."""
    if path in _TOP_LEAVES:
        return _TOP_LEAVES[path], None
    if len(path) >= 3 and path[0] == "transformer" and path[1].startswith("layers_"):
        key = _LAYER_LEAVES.get(tuple(path[2:]))
        if key is not None:
            return "h." + key, int(path[1][len("layers_"):])
    raise KeyError(f"no reference weight for the program's parameter {'/'.join(path)}")
