"""The ouro family: what a configuration's ``"family": "ouro"`` brings (the
five names ``families/gpt2.py`` has).

- ``reference``: ``benchmark/reference_ouro.py`` (one stack of layers applied
  ``total_ut_steps`` times over the same weights, sandwich norms, the exit gate);
- ``flops``: ``benchmark/flops_ouro.py``, the operations and bytes that work
  needs (a single walk over the layers does not count it);
- how the program under test is told the sizes (``MODEL_PATH``,
  :func:`program_overrides`) and how it names the weights (:func:`leaf_name`):
  names only, written down here by hand.
"""

from typing import Any, Dict, Optional, Tuple

from benchmark import flops_ouro as flops  # noqa: F401  (read as family.flops)
from benchmark import reference_ouro as reference  # noqa: F401  (read as family.reference)

MODEL_PATH = "ouro"  # no such directory: the program's preset, random init
#: published ``config.json`` key -> the program's ``model_overrides`` key
PUBLISHED_TO_PROGRAM = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate_size", "max_position_embeddings": "max_position_embeddings",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "total_ut_steps": "loop_steps",
    "early_exit_threshold": "early_exit_threshold", "initializer_range": "initializer_range",
}
#: program leaf (the end of its path) -> reference key of the stacked layers
_LAYER_LEAVES = {
    **{(norm, "scale"): f"{norm}.g" for norm in ("ln_1", "ln_1_post", "ln_2", "ln_2_post")},
    **{("attn", f"{n}_proj", "kernel"): f"{n}.w" for n in "qkvo"},
    **{("mlp", f"{n}_proj", "kernel"): f"{n}.w" for n in ("gate", "up", "down")},
}
_TOP_LEAVES = {
    ("transformer", "embed_tokens", "embedding"): "wte",
    ("transformer", "lm_head", "kernel"): "head.w",
    ("transformer", "ln_f", "scale"): "ln_f.g",
    ("transformer", "exit_gate", "kernel"): "exit.w",
    ("transformer", "exit_gate", "bias"): "exit.b",
    **{("v_head", "value_head", fc, leaf): f"v.{fc}.{short}"
       for fc in ("fc_in", "fc_out") for leaf, short in (("kernel", "w"), ("bias", "b"))},
}
#: microbatches of 8 x 257 tokens through passes x layers = 20 block applications beside 20 bytes a
#: parameter fit the chip's 15.75 GiB with nothing recomputed (the train step reckons 12.81 GiB beside
#: 1.74 of reference model; 12.24 with "dots_saveable", which read 3.6 % slower: PERF.md section 4)
REMAT = "none"


def program_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under the program's ``model_overrides`` keys."""
    overrides = {prog: config[pub] for pub, prog in PUBLISHED_TO_PROGRAM.items()}
    overrides["remat"] = REMAT
    return overrides


def leaf_name(path: Tuple[str, ...]) -> Tuple[str, Optional[int]]:
    """Program parameter path -> (reference key, layer or None). A leaf is one
    leaf however many passes use it."""
    if path in _TOP_LEAVES:
        return _TOP_LEAVES[path], None
    if len(path) >= 3 and path[0] == "transformer" and path[1].startswith("layers_"):
        key = _LAYER_LEAVES.get(tuple(path[2:]))
        if key is not None:
            return "h." + key, int(path[1][len("layers_"):])
    raise KeyError(f"no reference weight for the program's parameter {'/'.join(path)}")
