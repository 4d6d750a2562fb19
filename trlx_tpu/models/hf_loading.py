"""HF checkpoint interop: load torch checkpoints into TransformerLM params and export
back (parity: ``PreTrainedModelWrapper.from_pretrained/save_pretrained`` incl. sharded
checkpoint merging, `/root/reference/trlx/models/modeling_base.py:44-374`).

Conversion is per model family (gpt2 / gptj / gpt_neox / opt / llama). All conversions
are bidirectional so ``save_pretrained_hf`` can export an HF-loadable directory, and a
roundtrip test validates both directions without network access by instantiating tiny
random HF torch models from config.

Offline behavior: when ``model_path`` is not a local directory with weights, we fall
back to a family preset with random init (tests/benchmarks in a zero-egress sandbox).
"""

import json
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from trlx_tpu.models.presets import from_hf_config, get_preset
from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


# --------------------------------------------------------------------------- io


def load_torch_state_dict(model_dir: str) -> Dict[str, np.ndarray]:
    """Load (possibly sharded) torch weights from a local HF model dir into numpy."""
    out: Dict[str, np.ndarray] = {}

    def _load_safetensors(path):
        from safetensors import safe_open

        with safe_open(path, framework="np") as f:
            for k in f.keys():
                out[k] = f.get_tensor(k)

    def _load_bin(path):
        import torch

        sd = torch.load(path, map_location="cpu", weights_only=True)
        for k, v in sd.items():
            out[k] = v.float().numpy() if v.dtype in (torch.bfloat16, torch.float16) else v.numpy()

    for index_name, loader in (
        ("model.safetensors.index.json", _load_safetensors),
        ("pytorch_model.bin.index.json", _load_bin),
    ):
        index_path = os.path.join(model_dir, index_name)
        if os.path.exists(index_path):
            with open(index_path) as f:
                index = json.load(f)
            for shard in sorted(set(index["weight_map"].values())):
                loader(os.path.join(model_dir, shard))
            return out
    for name, loader in (("model.safetensors", _load_safetensors), ("pytorch_model.bin", _load_bin)):
        path = os.path.join(model_dir, name)
        if os.path.exists(path):
            loader(path)
            return out
    raise FileNotFoundError(f"No weights found in {model_dir}")


# ------------------------------------------------------------------ conversions

# Each family: (hf_to_params, params_to_hf). Params trees are plain nested dicts of
# numpy arrays with TransformerLM naming.


def _ln(sd, prefix):
    d = {"scale": sd[f"{prefix}.weight"]}
    if f"{prefix}.bias" in sd:
        d["bias"] = sd[f"{prefix}.bias"]
    return d


def _linear(sd, prefix, transpose=True):
    d = {"kernel": sd[f"{prefix}.weight"].T if transpose else sd[f"{prefix}.weight"]}
    if f"{prefix}.bias" in sd:
        d["bias"] = sd[f"{prefix}.bias"]
    return d


def _gpt2_to_params(sd: Dict[str, np.ndarray], c: TransformerConfig) -> Dict[str, Any]:
    p: Dict[str, Any] = {
        "embed_tokens": {"embedding": sd["transformer.wte.weight"]},
        "embed_positions": {"embedding": sd["transformer.wpe.weight"]},
        "ln_f": _ln(sd, "transformer.ln_f"),
    }
    H = c.hidden_size
    for i in range(c.num_layers):
        pre = f"transformer.h.{i}"
        # HF Conv1D stores [in, out] — no transpose
        ck = sd[f"{pre}.attn.c_attn.weight"]
        cb = sd[f"{pre}.attn.c_attn.bias"]
        p[f"layers_{i}"] = {
            "ln_1": _ln(sd, f"{pre}.ln_1"),
            "ln_2": _ln(sd, f"{pre}.ln_2"),
            "attn": {
                "q_proj": {"kernel": ck[:, :H], "bias": cb[:H]},
                "k_proj": {"kernel": ck[:, H : 2 * H], "bias": cb[H : 2 * H]},
                "v_proj": {"kernel": ck[:, 2 * H :], "bias": cb[2 * H :]},
                "o_proj": _linear(sd, f"{pre}.attn.c_proj", transpose=False),
            },
            "mlp": {
                "up_proj": _linear(sd, f"{pre}.mlp.c_fc", transpose=False),
                "down_proj": _linear(sd, f"{pre}.mlp.c_proj", transpose=False),
            },
        }
    return p


def _gpt2_from_params(p: Dict[str, Any], c: TransformerConfig) -> Dict[str, np.ndarray]:
    sd = {
        "transformer.wte.weight": p["embed_tokens"]["embedding"],
        "transformer.wpe.weight": p["embed_positions"]["embedding"],
        "transformer.ln_f.weight": p["ln_f"]["scale"],
        "transformer.ln_f.bias": p["ln_f"]["bias"],
    }
    for i in range(c.num_layers):
        L = p[f"layers_{i}"]
        pre = f"transformer.h.{i}"
        sd[f"{pre}.ln_1.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.ln_1.bias"] = L["ln_1"]["bias"]
        sd[f"{pre}.ln_2.weight"] = L["ln_2"]["scale"]
        sd[f"{pre}.ln_2.bias"] = L["ln_2"]["bias"]
        sd[f"{pre}.attn.c_attn.weight"] = np.concatenate(
            [L["attn"][k]["kernel"] for k in ("q_proj", "k_proj", "v_proj")], axis=1
        )
        sd[f"{pre}.attn.c_attn.bias"] = np.concatenate(
            [L["attn"][k]["bias"] for k in ("q_proj", "k_proj", "v_proj")]
        )
        sd[f"{pre}.attn.c_proj.weight"] = L["attn"]["o_proj"]["kernel"]
        sd[f"{pre}.attn.c_proj.bias"] = L["attn"]["o_proj"]["bias"]
        sd[f"{pre}.mlp.c_fc.weight"] = L["mlp"]["up_proj"]["kernel"]
        sd[f"{pre}.mlp.c_fc.bias"] = L["mlp"]["up_proj"]["bias"]
        sd[f"{pre}.mlp.c_proj.weight"] = L["mlp"]["down_proj"]["kernel"]
        sd[f"{pre}.mlp.c_proj.bias"] = L["mlp"]["down_proj"]["bias"]
    return sd


def _llama_to_params(sd, c):
    p = {
        "embed_tokens": {"embedding": sd["model.embed_tokens.weight"]},
        "ln_f": {"scale": sd["model.norm.weight"]},
    }
    if not c.tie_word_embeddings:
        p["lm_head"] = _linear(sd, "lm_head")
    for i in range(c.num_layers):
        pre = f"model.layers.{i}"
        p[f"layers_{i}"] = {
            "ln_1": {"scale": sd[f"{pre}.input_layernorm.weight"]},
            "ln_2": {"scale": sd[f"{pre}.post_attention_layernorm.weight"]},
            "attn": {
                "q_proj": _linear(sd, f"{pre}.self_attn.q_proj"),
                "k_proj": _linear(sd, f"{pre}.self_attn.k_proj"),
                "v_proj": _linear(sd, f"{pre}.self_attn.v_proj"),
                "o_proj": _linear(sd, f"{pre}.self_attn.o_proj"),
            },
            "mlp": {
                "gate_proj": _linear(sd, f"{pre}.mlp.gate_proj"),
                "up_proj": _linear(sd, f"{pre}.mlp.up_proj"),
                "down_proj": _linear(sd, f"{pre}.mlp.down_proj"),
            },
        }
    return p


def _llama_from_params(p, c):
    sd = {
        "model.embed_tokens.weight": p["embed_tokens"]["embedding"],
        "model.norm.weight": p["ln_f"]["scale"],
    }
    if "lm_head" in p:
        sd["lm_head.weight"] = p["lm_head"]["kernel"].T
    for i in range(c.num_layers):
        L = p[f"layers_{i}"]
        pre = f"model.layers.{i}"
        sd[f"{pre}.input_layernorm.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.post_attention_layernorm.weight"] = L["ln_2"]["scale"]
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            sd[f"{pre}.self_attn.{name}.weight"] = L["attn"][name]["kernel"].T
        for name in ("gate_proj", "up_proj", "down_proj"):
            sd[f"{pre}.mlp.{name}.weight"] = L["mlp"][name]["kernel"].T
    return sd


def _neox_to_params(sd, c):
    p = {
        "embed_tokens": {"embedding": sd["gpt_neox.embed_in.weight"]},
        "ln_f": _ln(sd, "gpt_neox.final_layer_norm"),
        "lm_head": _linear(sd, "embed_out"),
    }
    heads, hd, H = c.num_heads, c.dim_per_head, c.hidden_size
    for i in range(c.num_layers):
        pre = f"gpt_neox.layers.{i}"
        qkv_w = sd[f"{pre}.attention.query_key_value.weight"]  # [3H, H], per-head interleave
        qkv_b = sd[f"{pre}.attention.query_key_value.bias"]
        w = qkv_w.reshape(heads, 3, hd, H)
        b = qkv_b.reshape(heads, 3, hd)
        mk_w = lambda j: w[:, j].reshape(heads * hd, H).T  # -> [H, H] kernel
        mk_b = lambda j: b[:, j].reshape(heads * hd)
        p[f"layers_{i}"] = {
            "ln_1": _ln(sd, f"{pre}.input_layernorm"),
            "ln_2": _ln(sd, f"{pre}.post_attention_layernorm"),
            "attn": {
                "q_proj": {"kernel": mk_w(0), "bias": mk_b(0)},
                "k_proj": {"kernel": mk_w(1), "bias": mk_b(1)},
                "v_proj": {"kernel": mk_w(2), "bias": mk_b(2)},
                "o_proj": _linear(sd, f"{pre}.attention.dense"),
            },
            "mlp": {
                "up_proj": _linear(sd, f"{pre}.mlp.dense_h_to_4h"),
                "down_proj": _linear(sd, f"{pre}.mlp.dense_4h_to_h"),
            },
        }
    return p


def _neox_from_params(p, c):
    sd = {
        "gpt_neox.embed_in.weight": p["embed_tokens"]["embedding"],
        "gpt_neox.final_layer_norm.weight": p["ln_f"]["scale"],
        "gpt_neox.final_layer_norm.bias": p["ln_f"]["bias"],
        "embed_out.weight": p["lm_head"]["kernel"].T,
    }
    heads, hd, H = c.num_heads, c.dim_per_head, c.hidden_size
    for i in range(c.num_layers):
        L = p[f"layers_{i}"]
        pre = f"gpt_neox.layers.{i}"
        sd[f"{pre}.input_layernorm.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.input_layernorm.bias"] = L["ln_1"]["bias"]
        sd[f"{pre}.post_attention_layernorm.weight"] = L["ln_2"]["scale"]
        sd[f"{pre}.post_attention_layernorm.bias"] = L["ln_2"]["bias"]
        ws = [L["attn"][k]["kernel"].T.reshape(heads, hd, H) for k in ("q_proj", "k_proj", "v_proj")]
        bs = [L["attn"][k]["bias"].reshape(heads, hd) for k in ("q_proj", "k_proj", "v_proj")]
        sd[f"{pre}.attention.query_key_value.weight"] = np.stack(ws, axis=1).reshape(3 * H, H)
        sd[f"{pre}.attention.query_key_value.bias"] = np.stack(bs, axis=1).reshape(3 * H)
        sd[f"{pre}.attention.dense.weight"] = L["attn"]["o_proj"]["kernel"].T
        sd[f"{pre}.attention.dense.bias"] = L["attn"]["o_proj"]["bias"]
        sd[f"{pre}.mlp.dense_h_to_4h.weight"] = L["mlp"]["up_proj"]["kernel"].T
        sd[f"{pre}.mlp.dense_h_to_4h.bias"] = L["mlp"]["up_proj"]["bias"]
        sd[f"{pre}.mlp.dense_4h_to_h.weight"] = L["mlp"]["down_proj"]["kernel"].T
        sd[f"{pre}.mlp.dense_4h_to_h.bias"] = L["mlp"]["down_proj"]["bias"]
    return sd


def _gptj_to_params(sd, c):
    p = {
        "embed_tokens": {"embedding": sd["transformer.wte.weight"]},
        "ln_f": _ln(sd, "transformer.ln_f"),
        "lm_head": _linear(sd, "lm_head"),
    }
    for i in range(c.num_layers):
        pre = f"transformer.h.{i}"
        p[f"layers_{i}"] = {
            "ln_1": _ln(sd, f"{pre}.ln_1"),
            "attn": {
                "q_proj": _linear(sd, f"{pre}.attn.q_proj"),
                "k_proj": _linear(sd, f"{pre}.attn.k_proj"),
                "v_proj": _linear(sd, f"{pre}.attn.v_proj"),
                "o_proj": _linear(sd, f"{pre}.attn.out_proj"),
            },
            "mlp": {
                "up_proj": _linear(sd, f"{pre}.mlp.fc_in"),
                "down_proj": _linear(sd, f"{pre}.mlp.fc_out"),
            },
        }
    return p


def _gptj_from_params(p, c):
    sd = {
        "transformer.wte.weight": p["embed_tokens"]["embedding"],
        "transformer.ln_f.weight": p["ln_f"]["scale"],
        "transformer.ln_f.bias": p["ln_f"]["bias"],
        "lm_head.weight": p["lm_head"]["kernel"].T,
    }
    if "bias" in p["lm_head"]:
        sd["lm_head.bias"] = p["lm_head"]["bias"]
    for i in range(c.num_layers):
        L = p[f"layers_{i}"]
        pre = f"transformer.h.{i}"
        sd[f"{pre}.ln_1.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.ln_1.bias"] = L["ln_1"]["bias"]
        for ours, theirs in (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"), ("o_proj", "out_proj")):
            sd[f"{pre}.attn.{theirs}.weight"] = L["attn"][ours]["kernel"].T
        sd[f"{pre}.mlp.fc_in.weight"] = L["mlp"]["up_proj"]["kernel"].T
        sd[f"{pre}.mlp.fc_in.bias"] = L["mlp"]["up_proj"]["bias"]
        sd[f"{pre}.mlp.fc_out.weight"] = L["mlp"]["down_proj"]["kernel"].T
        sd[f"{pre}.mlp.fc_out.bias"] = L["mlp"]["down_proj"]["bias"]
    return sd


def _opt_to_params(sd, c):
    prefix = "model.decoder" if "model.decoder.embed_tokens.weight" in sd else "decoder"
    p = {
        "embed_tokens": {"embedding": sd[f"{prefix}.embed_tokens.weight"]},
        "embed_positions": {"embedding": sd[f"{prefix}.embed_positions.weight"]},
    }
    if f"{prefix}.final_layer_norm.weight" in sd:
        p["ln_f"] = _ln(sd, f"{prefix}.final_layer_norm")
    for i in range(c.num_layers):
        pre = f"{prefix}.layers.{i}"
        p[f"layers_{i}"] = {
            "ln_1": _ln(sd, f"{pre}.self_attn_layer_norm"),
            "ln_2": _ln(sd, f"{pre}.final_layer_norm"),
            "attn": {
                "q_proj": _linear(sd, f"{pre}.self_attn.q_proj"),
                "k_proj": _linear(sd, f"{pre}.self_attn.k_proj"),
                "v_proj": _linear(sd, f"{pre}.self_attn.v_proj"),
                "o_proj": _linear(sd, f"{pre}.self_attn.out_proj"),
            },
            "mlp": {
                "up_proj": _linear(sd, f"{pre}.fc1"),
                "down_proj": _linear(sd, f"{pre}.fc2"),
            },
        }
    return p


def _opt_from_params(p, c):
    prefix = "model.decoder"
    sd = {
        f"{prefix}.embed_tokens.weight": p["embed_tokens"]["embedding"],
        f"{prefix}.embed_positions.weight": p["embed_positions"]["embedding"],
    }
    if "ln_f" in p:
        sd[f"{prefix}.final_layer_norm.weight"] = p["ln_f"]["scale"]
        sd[f"{prefix}.final_layer_norm.bias"] = p["ln_f"]["bias"]
    for i in range(c.num_layers):
        L = p[f"layers_{i}"]
        pre = f"{prefix}.layers.{i}"
        sd[f"{pre}.self_attn_layer_norm.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.self_attn_layer_norm.bias"] = L["ln_1"]["bias"]
        sd[f"{pre}.final_layer_norm.weight"] = L["ln_2"]["scale"]
        sd[f"{pre}.final_layer_norm.bias"] = L["ln_2"]["bias"]
        for ours, theirs in (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"), ("o_proj", "out_proj")):
            sd[f"{pre}.self_attn.{theirs}.weight"] = L["attn"][ours]["kernel"].T
            sd[f"{pre}.self_attn.{theirs}.bias"] = L["attn"][ours]["bias"]
        sd[f"{pre}.fc1.weight"] = L["mlp"]["up_proj"]["kernel"].T
        sd[f"{pre}.fc1.bias"] = L["mlp"]["up_proj"]["bias"]
        sd[f"{pre}.fc2.weight"] = L["mlp"]["down_proj"]["kernel"].T
        sd[f"{pre}.fc2.bias"] = L["mlp"]["down_proj"]["bias"]
    return sd


def _bloom_to_params(sd, c):
    """Bloom: ALiBi, embedding LN, per-head-interleaved fused qkv (like neox)."""
    pre0 = "transformer." if "transformer.word_embeddings.weight" in sd else ""
    p = {
        "embed_tokens": {"embedding": sd[f"{pre0}word_embeddings.weight"]},
        "embed_layernorm": _ln(sd, f"{pre0}word_embeddings_layernorm"),
        "ln_f": _ln(sd, f"{pre0}ln_f"),
    }
    heads, hd, H = c.num_heads, c.dim_per_head, c.hidden_size
    for i in range(c.num_layers):
        pre = f"{pre0}h.{i}"
        qkv_w = sd[f"{pre}.self_attention.query_key_value.weight"]  # [3H, H]
        qkv_b = sd[f"{pre}.self_attention.query_key_value.bias"]
        w = qkv_w.reshape(heads, 3, hd, H)
        b = qkv_b.reshape(heads, 3, hd)
        mk_w = lambda j: w[:, j].reshape(heads * hd, H).T
        mk_b = lambda j: b[:, j].reshape(heads * hd)
        p[f"layers_{i}"] = {
            "ln_1": _ln(sd, f"{pre}.input_layernorm"),
            "ln_2": _ln(sd, f"{pre}.post_attention_layernorm"),
            "attn": {
                "q_proj": {"kernel": mk_w(0), "bias": mk_b(0)},
                "k_proj": {"kernel": mk_w(1), "bias": mk_b(1)},
                "v_proj": {"kernel": mk_w(2), "bias": mk_b(2)},
                "o_proj": _linear(sd, f"{pre}.self_attention.dense"),
            },
            "mlp": {
                "up_proj": _linear(sd, f"{pre}.mlp.dense_h_to_4h"),
                "down_proj": _linear(sd, f"{pre}.mlp.dense_4h_to_h"),
            },
        }
    return p


def _bloom_from_params(p, c):
    sd = {
        "transformer.word_embeddings.weight": p["embed_tokens"]["embedding"],
        "transformer.word_embeddings_layernorm.weight": p["embed_layernorm"]["scale"],
        "transformer.word_embeddings_layernorm.bias": p["embed_layernorm"]["bias"],
        "transformer.ln_f.weight": p["ln_f"]["scale"],
        "transformer.ln_f.bias": p["ln_f"]["bias"],
    }
    heads, hd, H = c.num_heads, c.dim_per_head, c.hidden_size
    for i in range(c.num_layers):
        L = p[f"layers_{i}"]
        pre = f"transformer.h.{i}"
        sd[f"{pre}.input_layernorm.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.input_layernorm.bias"] = L["ln_1"]["bias"]
        sd[f"{pre}.post_attention_layernorm.weight"] = L["ln_2"]["scale"]
        sd[f"{pre}.post_attention_layernorm.bias"] = L["ln_2"]["bias"]
        ws = [L["attn"][k]["kernel"].T.reshape(heads, hd, H) for k in ("q_proj", "k_proj", "v_proj")]
        bs = [L["attn"][k]["bias"].reshape(heads, hd) for k in ("q_proj", "k_proj", "v_proj")]
        sd[f"{pre}.self_attention.query_key_value.weight"] = np.stack(ws, axis=1).reshape(3 * H, H)
        sd[f"{pre}.self_attention.query_key_value.bias"] = np.stack(bs, axis=1).reshape(3 * H)
        sd[f"{pre}.self_attention.dense.weight"] = L["attn"]["o_proj"]["kernel"].T
        sd[f"{pre}.self_attention.dense.bias"] = L["attn"]["o_proj"]["bias"]
        sd[f"{pre}.mlp.dense_h_to_4h.weight"] = L["mlp"]["up_proj"]["kernel"].T
        sd[f"{pre}.mlp.dense_h_to_4h.bias"] = L["mlp"]["up_proj"]["bias"]
        sd[f"{pre}.mlp.dense_4h_to_h.weight"] = L["mlp"]["down_proj"]["kernel"].T
        sd[f"{pre}.mlp.dense_4h_to_h.bias"] = L["mlp"]["down_proj"]["bias"]
    return sd


def _bigcode_to_params(sd, c):
    """GPTBigCode: multi-query attention — c_attn packs [q(H) | k(hd) | v(hd)].

    Only the MQA layout is supported: with ``multi_query=False`` HF interleaves
    q/k/v per head instead, which this flat slicing would scramble."""
    if c.kv_heads != 1:
        raise ValueError(
            "gpt_bigcode converter supports multi_query=True checkpoints only "
            f"(got kv_heads={c.kv_heads}); the non-MQA c_attn layout is per-head "
            "interleaved and not implemented"
        )
    p = {
        "embed_tokens": {"embedding": sd["transformer.wte.weight"]},
        "embed_positions": {"embedding": sd["transformer.wpe.weight"]},
        "ln_f": _ln(sd, "transformer.ln_f"),
    }
    H = c.hidden_size
    kv_dim = c.kv_heads * c.dim_per_head
    for i in range(c.num_layers):
        pre = f"transformer.h.{i}"
        cw = sd[f"{pre}.attn.c_attn.weight"]  # [H + 2*kv_dim, H] (nn.Linear layout)
        cb = sd[f"{pre}.attn.c_attn.bias"]
        p[f"layers_{i}"] = {
            "ln_1": _ln(sd, f"{pre}.ln_1"),
            "ln_2": _ln(sd, f"{pre}.ln_2"),
            "attn": {
                "q_proj": {"kernel": cw[:H].T, "bias": cb[:H]},
                "k_proj": {"kernel": cw[H : H + kv_dim].T, "bias": cb[H : H + kv_dim]},
                "v_proj": {"kernel": cw[H + kv_dim :].T, "bias": cb[H + kv_dim :]},
                "o_proj": _linear(sd, f"{pre}.attn.c_proj"),
            },
            "mlp": {
                "up_proj": _linear(sd, f"{pre}.mlp.c_fc"),
                "down_proj": _linear(sd, f"{pre}.mlp.c_proj"),
            },
        }
    return p


def _bigcode_from_params(p, c):
    if c.kv_heads != 1:
        raise ValueError("gpt_bigcode export supports multi_query=True configs only")
    sd = {
        "transformer.wte.weight": p["embed_tokens"]["embedding"],
        "transformer.wpe.weight": p["embed_positions"]["embedding"],
        "transformer.ln_f.weight": p["ln_f"]["scale"],
        "transformer.ln_f.bias": p["ln_f"]["bias"],
    }
    for i in range(c.num_layers):
        L = p[f"layers_{i}"]
        pre = f"transformer.h.{i}"
        sd[f"{pre}.ln_1.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.ln_1.bias"] = L["ln_1"]["bias"]
        sd[f"{pre}.ln_2.weight"] = L["ln_2"]["scale"]
        sd[f"{pre}.ln_2.bias"] = L["ln_2"]["bias"]
        sd[f"{pre}.attn.c_attn.weight"] = np.concatenate(
            [L["attn"][k]["kernel"].T for k in ("q_proj", "k_proj", "v_proj")], axis=0
        )
        sd[f"{pre}.attn.c_attn.bias"] = np.concatenate(
            [L["attn"][k]["bias"] for k in ("q_proj", "k_proj", "v_proj")]
        )
        sd[f"{pre}.attn.c_proj.weight"] = L["attn"]["o_proj"]["kernel"].T
        sd[f"{pre}.attn.c_proj.bias"] = L["attn"]["o_proj"]["bias"]
        sd[f"{pre}.mlp.c_fc.weight"] = L["mlp"]["up_proj"]["kernel"].T
        sd[f"{pre}.mlp.c_fc.bias"] = L["mlp"]["up_proj"]["bias"]
        sd[f"{pre}.mlp.c_proj.weight"] = L["mlp"]["down_proj"]["kernel"].T
        sd[f"{pre}.mlp.c_proj.bias"] = L["mlp"]["down_proj"]["bias"]
    return sd


def _rotary_halves(kernel: np.ndarray, rope: int) -> np.ndarray:
    """The last ``rope`` columns of each ``[in, ..., width]`` output group from
    the published checkpoint's interleaved rotary pairs (2i, 2i + 1) to this
    program's halves (i, i + rope / 2)."""
    order = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    head = kernel.shape[-1] - rope
    return np.concatenate([kernel[..., :head], kernel[..., head:][..., order]], axis=-1)


def _kimi_prefix(sd) -> str:
    """``""`` for a bare language model, ``"language_model."`` inside the
    vision-language checkpoint (whose tower and projector are not loaded)."""
    for key in sd:
        if key.endswith("model.embed_tokens.weight"):
            return key[: -len("model.embed_tokens.weight")]
    raise KeyError("no model.embed_tokens.weight in the checkpoint")


def _kimi_to_params(sd, c):
    """Kimi-VL-A3B's language model (DeepSeek-V3 names). Only the experts held
    (``experts_held`` from ``expert_offset``) and the first ``vocab_size`` rows
    of the vocabulary are taken; the rotary columns of ``q_proj`` and
    ``kv_a_proj_with_mqa`` go from interleaved pairs to halves."""
    root = _kimi_prefix(sd)
    H, rope, V = c.num_heads, c.qk_rope_head_dim, c.vocab_size
    p = {
        "embed_tokens": {"embedding": sd[f"{root}model.embed_tokens.weight"][:V]},
        "ln_f": {"scale": sd[f"{root}model.norm.weight"]},
    }
    if not c.tie_word_embeddings:
        p["lm_head"] = {"kernel": sd[f"{root}lm_head.weight"][:V].T}
    for i in range(c.num_layers):
        pre = f"{root}model.layers.{i}"
        q = sd[f"{pre}.self_attn.q_proj.weight"].T  # [d, H * (nope + rope)]
        q = _rotary_halves(q.reshape(q.shape[0], H, -1), rope).reshape(q.shape)
        layer = {
            "ln_1": {"scale": sd[f"{pre}.input_layernorm.weight"]},
            "ln_2": {"scale": sd[f"{pre}.post_attention_layernorm.weight"]},
            "attn": {
                "q_proj": {"kernel": q},
                "kv_a_proj": {"kernel": _rotary_halves(sd[f"{pre}.self_attn.kv_a_proj_with_mqa.weight"].T, rope)},
                "kv_a_norm": {"scale": sd[f"{pre}.self_attn.kv_a_layernorm.weight"]},
                "kv_b_proj": _linear(sd, f"{pre}.self_attn.kv_b_proj"),
                "o_proj": _linear(sd, f"{pre}.self_attn.o_proj"),
            },
        }
        if c.is_expert_layer(i):
            held = range(c.expert_offset, c.expert_offset + c.held_experts)
            stack = lambda name: np.stack([sd[f"{pre}.mlp.experts.{e}.{name}_proj.weight"].T for e in held])
            layer["mlp"] = {
                "router": {"kernel": sd[f"{pre}.mlp.gate.weight"].T,
                           "bias": sd[f"{pre}.mlp.gate.e_score_correction_bias"]},
                "experts": {name: stack(name) for name in ("gate", "up", "down")},
                "shared": {f"{name}_proj": _linear(sd, f"{pre}.mlp.shared_experts.{name}_proj")
                           for name in ("gate", "up", "down")},
            }
        else:
            layer["mlp"] = {f"{name}_proj": _linear(sd, f"{pre}.mlp.{name}_proj") for name in ("gate", "up", "down")}
        p[f"layers_{i}"] = layer
    return p


#: Ouro's published names of a block's norms and projections -> this program's
_OURO_NORMS = (
    ("ln_1", "input_layernorm"), ("ln_1_post", "input_layernorm_2"),
    ("ln_2", "post_attention_layernorm"), ("ln_2_post", "post_attention_layernorm_2"),
)
_OURO_LINEARS = (
    *((("attn", f"{n}_proj"), f"self_attn.{n}_proj") for n in "qkvo"),
    *((("mlp", f"{n}_proj"), f"mlp.{n}_proj") for n in ("gate", "up", "down")),
)


def _ouro_to_params(sd, c):
    """Ouro (a looped model: one stack of layers, so one set of names): four
    norms a block, no biases but the exit gate's."""
    p = {
        "embed_tokens": {"embedding": sd["model.embed_tokens.weight"]},
        "ln_f": {"scale": sd["model.norm.weight"]},
        "exit_gate": {"kernel": sd["model.early_exit_gate.weight"].T, "bias": sd["model.early_exit_gate.bias"]},
    }
    if not c.tie_word_embeddings:
        p["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(c.num_layers):
        pre = f"model.layers.{i}"
        layer = {ours: {"scale": sd[f"{pre}.{theirs}.weight"]} for ours, theirs in _OURO_NORMS}
        for (module, ours), theirs in _OURO_LINEARS:
            layer.setdefault(module, {})[ours] = _linear(sd, f"{pre}.{theirs}")
        p[f"layers_{i}"] = layer
    return p


def _ouro_from_params(p, c):
    sd = {
        "model.embed_tokens.weight": p["embed_tokens"]["embedding"],
        "model.norm.weight": p["ln_f"]["scale"],
        "model.early_exit_gate.weight": p["exit_gate"]["kernel"].T,
        "model.early_exit_gate.bias": p["exit_gate"]["bias"],
    }
    if "lm_head" in p:
        sd["lm_head.weight"] = p["lm_head"]["kernel"].T
    for i in range(c.num_layers):
        L, pre = p[f"layers_{i}"], f"model.layers.{i}"
        for ours, theirs in _OURO_NORMS:
            sd[f"{pre}.{theirs}.weight"] = L[ours]["scale"]
        for (module, ours), theirs in _OURO_LINEARS:
            sd[f"{pre}.{theirs}.weight"] = L[module][ours]["kernel"].T
    return sd


#: LFM2's published names of a layer's projections -> this program's (module, name)
_LFM2_MIXERS = {
    True: ((("conv", "in_proj"), "conv.in_proj"), (("conv", "out_proj"), "conv.out_proj")),
    False: tuple((("attn", ours), f"self_attn.{theirs}") for ours, theirs in (
        ("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"), ("o_proj", "out_proj"))),
}
_LFM2_FFN = (("gate", "w1"), ("up", "w3"), ("down", "w2"))


def _lfm2_to_params(sd, c):
    """LFM2-MoE (``lfm2_moe``): a convolution or an attention mixer a layer under
    its own names, the depthwise filter ``[d, 1, taps]`` there and ``[d, taps]``
    here, the head tied. Only the experts held (``experts_held`` from
    ``expert_offset``) and the first ``vocab_size`` rows of the vocabulary are taken."""
    p = {
        "embed_tokens": {"embedding": sd["model.embed_tokens.weight"][: c.vocab_size]},
        "ln_f": {"scale": sd["model.embedding_norm.weight"]},
    }
    if not c.tie_word_embeddings:
        p["lm_head"] = {"kernel": sd["lm_head.weight"][: c.vocab_size].T}
    for i in range(c.num_layers):
        pre, conv = f"model.layers.{i}", c.is_conv_layer(i)
        layer = {"ln_1": {"scale": sd[f"{pre}.operator_norm.weight"]}, "ln_2": {"scale": sd[f"{pre}.ffn_norm.weight"]}}
        for (module, ours), theirs in _LFM2_MIXERS[conv]:
            layer.setdefault(module, {})[ours] = _linear(sd, f"{pre}.{theirs}")
        if conv:
            layer["conv"]["conv"] = {"kernel": sd[f"{pre}.conv.conv.weight"][:, 0, :]}
        else:
            layer["attn"]["q_norm"] = {"scale": sd[f"{pre}.self_attn.q_layernorm.weight"]}
            layer["attn"]["k_norm"] = {"scale": sd[f"{pre}.self_attn.k_layernorm.weight"]}
        if c.is_expert_layer(i):
            held = range(c.expert_offset, c.expert_offset + c.held_experts)
            layer["mlp"] = {
                "router": {"kernel": sd[f"{pre}.feed_forward.gate.weight"].T,
                           "bias": sd[f"{pre}.feed_forward.expert_bias"]},
                "experts": {ours: np.stack([sd[f"{pre}.feed_forward.experts.{e}.{theirs}.weight"].T for e in held])
                            for ours, theirs in _LFM2_FFN},
            }
        else:
            layer["mlp"] = {f"{ours}_proj": _linear(sd, f"{pre}.feed_forward.{theirs}") for ours, theirs in _LFM2_FFN}
        p[f"layers_{i}"] = layer
    return p


def _lfm2_from_params(p, c):
    """The held experts go out under their published indices, from ``expert_offset`` on."""
    sd = {
        "model.embed_tokens.weight": p["embed_tokens"]["embedding"],
        "model.embedding_norm.weight": p["ln_f"]["scale"],
    }
    if "lm_head" in p:
        sd["lm_head.weight"] = p["lm_head"]["kernel"].T
    for i in range(c.num_layers):
        L, pre, conv = p[f"layers_{i}"], f"model.layers.{i}", c.is_conv_layer(i)
        sd[f"{pre}.operator_norm.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.ffn_norm.weight"] = L["ln_2"]["scale"]
        for (module, ours), theirs in _LFM2_MIXERS[conv]:
            sd[f"{pre}.{theirs}.weight"] = L[module][ours]["kernel"].T
        if conv:
            sd[f"{pre}.conv.conv.weight"] = L["conv"]["conv"]["kernel"][:, None, :]
        else:
            sd[f"{pre}.self_attn.q_layernorm.weight"] = L["attn"]["q_norm"]["scale"]
            sd[f"{pre}.self_attn.k_layernorm.weight"] = L["attn"]["k_norm"]["scale"]
        if c.is_expert_layer(i):
            sd[f"{pre}.feed_forward.gate.weight"] = L["mlp"]["router"]["kernel"].T
            sd[f"{pre}.feed_forward.expert_bias"] = L["mlp"]["router"]["bias"]
            for ours, theirs in _LFM2_FFN:
                for e, kernel in enumerate(L["mlp"]["experts"][ours], start=c.expert_offset):
                    sd[f"{pre}.feed_forward.experts.{e}.{theirs}.weight"] = kernel.T
        else:
            for ours, theirs in _LFM2_FFN:
                sd[f"{pre}.feed_forward.{theirs}.weight"] = L["mlp"][f"{ours}_proj"]["kernel"].T
    return sd


#: Keye-VL-2.0's language model (Qwen3-MoE's names): this program's attention module name -> the published one
_KEYE_ATTENTION = tuple((ours, f"self_attn.{ours}") for ours in ("q_proj", "k_proj", "v_proj", "o_proj"))
#: the indexer's names, which no published file of ours shows: assumed after DeepSeek-V3.2's (``wq``, ``wk``,
#: ``k_norm``, ``weights_proj``) under ``self_attn.indexer`` (benchmark/configs/keye-vl-2.0-30b-a3b.json: assumed)
_KEYE_INDEXER = ("wq", "wk", "weights_proj")
_KEYE_FFN = ("gate", "up", "down")


def _keye_to_params(sd, c):
    """Keye-VL-2.0's language model (``KeyeVL2``) under Qwen3-MoE's names: ``model.layers.N.self_attn``
    with q / k norms and the indexer, ``mlp.gate`` the softmax router, ``mlp.experts.E.{gate,up,down}_proj``.
    Only the experts held (``experts_held`` from ``expert_offset``) and the first ``vocab_size`` rows of
    the vocabulary are taken."""
    p = {
        "embed_tokens": {"embedding": sd["model.embed_tokens.weight"][: c.vocab_size]},
        "ln_f": {"scale": sd["model.norm.weight"]},
    }
    if not c.tie_word_embeddings:
        p["lm_head"] = {"kernel": sd["lm_head.weight"][: c.vocab_size].T}
    held = range(c.expert_offset, c.expert_offset + c.held_experts)
    for i in range(c.num_layers):
        pre = f"model.layers.{i}"
        attn = {ours: _linear(sd, f"{pre}.{theirs}") for ours, theirs in _KEYE_ATTENTION}
        attn["q_norm"] = {"scale": sd[f"{pre}.self_attn.q_norm.weight"]}
        attn["k_norm"] = {"scale": sd[f"{pre}.self_attn.k_norm.weight"]}
        attn["indexer"] = {name: _linear(sd, f"{pre}.self_attn.indexer.{name}") for name in _KEYE_INDEXER}
        attn["indexer"]["k_norm"] = {"scale": sd[f"{pre}.self_attn.indexer.k_norm.weight"],
                                     "bias": sd[f"{pre}.self_attn.indexer.k_norm.bias"]}
        p[f"layers_{i}"] = {
            "ln_1": {"scale": sd[f"{pre}.input_layernorm.weight"]},
            "ln_2": {"scale": sd[f"{pre}.post_attention_layernorm.weight"]},
            "attn": attn,
            "mlp": {
                "router": {"kernel": sd[f"{pre}.mlp.gate.weight"].T},
                "experts": {ours: np.stack([sd[f"{pre}.mlp.experts.{e}.{ours}_proj.weight"].T for e in held])
                            for ours in _KEYE_FFN},
            },
        }
    return p


def _keye_from_params(p, c):
    """The held experts go out under their published indices, from ``expert_offset`` on."""
    sd = {"model.embed_tokens.weight": p["embed_tokens"]["embedding"], "model.norm.weight": p["ln_f"]["scale"]}
    if "lm_head" in p:
        sd["lm_head.weight"] = p["lm_head"]["kernel"].T
    for i in range(c.num_layers):
        L, pre = p[f"layers_{i}"], f"model.layers.{i}"
        sd[f"{pre}.input_layernorm.weight"] = L["ln_1"]["scale"]
        sd[f"{pre}.post_attention_layernorm.weight"] = L["ln_2"]["scale"]
        for ours, theirs in _KEYE_ATTENTION:
            sd[f"{pre}.{theirs}.weight"] = L["attn"][ours]["kernel"].T
        sd[f"{pre}.self_attn.q_norm.weight"] = L["attn"]["q_norm"]["scale"]
        sd[f"{pre}.self_attn.k_norm.weight"] = L["attn"]["k_norm"]["scale"]
        for name in _KEYE_INDEXER:
            sd[f"{pre}.self_attn.indexer.{name}.weight"] = L["attn"]["indexer"][name]["kernel"].T
        sd[f"{pre}.self_attn.indexer.k_norm.weight"] = L["attn"]["indexer"]["k_norm"]["scale"]
        sd[f"{pre}.self_attn.indexer.k_norm.bias"] = L["attn"]["indexer"]["k_norm"]["bias"]
        sd[f"{pre}.mlp.gate.weight"] = L["mlp"]["router"]["kernel"].T
        for ours in _KEYE_FFN:
            for e, kernel in enumerate(L["mlp"]["experts"][ours], start=c.expert_offset):
                sd[f"{pre}.mlp.experts.{e}.{ours}_proj.weight"] = kernel.T
    return sd


CONVERTERS = {
    "gpt2": (_gpt2_to_params, _gpt2_from_params),
    "llama": (_llama_to_params, _llama_from_params),
    "gpt_neox": (_neox_to_params, _neox_from_params),
    "gptj": (_gptj_to_params, _gptj_from_params),
    "opt": (_opt_to_params, _opt_from_params),
    "bloom": (_bloom_to_params, _bloom_from_params),
    "gpt_bigcode": (_bigcode_to_params, _bigcode_from_params),
    # load only: a share of the experts and of the vocabulary is no whole checkpoint to export
    "kimi_vl": (_kimi_to_params, None),
    "ouro": (_ouro_to_params, _ouro_from_params),
    "lfm2_moe": (_lfm2_to_params, _lfm2_from_params),
    # the preset's family name and the published model_type
    "keye_vl": (_keye_to_params, _keye_from_params),
    "KeyeVL2": (_keye_to_params, _keye_from_params),
}
# "t5" is registered below once its converters are defined (seq2seq section)


def hf_state_dict_to_params(model_type: str, sd: Dict[str, np.ndarray], config: TransformerConfig) -> Dict[str, Any]:
    if model_type not in CONVERTERS:
        raise ValueError(f"No converter for model_type {model_type!r}")
    p = CONVERTERS[model_type][0](sd, config)
    return jax.tree.map(lambda x: np.asarray(x, dtype=np.float32), p)


def params_to_hf_state_dict(
    model_type: str, params: Dict[str, Any], config: TransformerConfig
) -> Dict[str, np.ndarray]:
    if model_type not in CONVERTERS or CONVERTERS[model_type][1] is None:
        raise ValueError(f"No converter for model_type {model_type!r}")
    params = jax.tree.map(lambda x: np.asarray(jax.device_get(x), dtype=np.float32), params)
    return CONVERTERS[model_type][1](params, config)


# ------------------------------------------------------------------- top level


def init_params(config: TransformerConfig, module=None, seed: int = 0) -> Dict[str, Any]:
    """Random-init trunk params (for offline runs and tests)."""
    module = module or TransformerLM(config)
    ids = jnp.zeros((1, 2), jnp.int32)
    return module.init(jax.random.PRNGKey(seed), ids, jnp.ones((1, 2), jnp.int32))["params"]


def _hf_load_retry_policy():
    """Retry policy for HF checkpoint reads: transient I/O faults (NFS blips,
    hub 5xx surfaced as OSError, injected chaos) are retried; a definitively
    missing file is an answer and fails immediately. Budget is overridable via
    TRLX_HF_LOAD_RETRIES for constrained CI."""
    from trlx_tpu.resilience.chaos import ChaosInjectedError
    from trlx_tpu.resilience.retry import RetryPolicy

    return RetryPolicy(
        max_retries=int(os.environ.get("TRLX_HF_LOAD_RETRIES", 2)),
        base_delay_s=float(os.environ.get("TRLX_HF_LOAD_RETRY_DELAY", 1.0)),
        max_delay_s=15.0,
        retry_on=(OSError, ChaosInjectedError),
        giveup_on=(FileNotFoundError, IsADirectoryError, NotADirectoryError),
    )


def _read_hf_checkpoint(model_path: str):
    """(AutoConfig, torch state dict) for a local HF dir, under the retry
    policy above, with the chaos ``hf-load`` fault site inside the retried
    body so injected faults exercise the same recovery path as real ones."""
    from trlx_tpu.resilience.chaos import chaos
    from trlx_tpu.resilience.retry import retry_call

    def read():
        chaos.fail_if_armed("hf-load", detail=model_path)
        import transformers

        hf_config = transformers.AutoConfig.from_pretrained(model_path)
        return hf_config, load_torch_state_dict(model_path)

    return retry_call(read, policy=_hf_load_retry_policy(), name=f"hf-load {model_path}")


def load_pretrained(
    model_path: str,
    overrides: Optional[Dict[str, Any]] = None,
    mesh=None,
) -> Tuple[TransformerConfig, Optional[Dict[str, Any]], str]:
    """Resolve (config, trunk params or None, model_type) for a model path.

    Local dir with config.json + weights → converted checkpoint. Otherwise a family
    preset with no params (caller random-inits) — the zero-egress fallback.
    With ``mesh``, a native pre-converted checkpoint restores directly into device
    shards (per-host partial reads); torch checkpoints always load host-side.
    """
    from trlx_tpu import checkpointing

    if checkpointing.is_native_checkpoint(model_path):
        # pre-converted chunked store: already in TransformerLM layout, restores
        # with per-host partial reads (see trlx_tpu/checkpointing.py)
        return checkpointing.restore_native(
            model_path, overrides, mesh=mesh, expect_seq2seq=False
        )
    config_path = os.path.join(model_path, "config.json")
    if os.path.isdir(model_path) and os.path.exists(config_path):
        hf_config, sd = _read_hf_checkpoint(model_path)
        config = from_hf_config(hf_config, overrides)
        params = hf_state_dict_to_params(hf_config.model_type, sd, config)
        return config, params, hf_config.model_type
    config = get_preset(model_path, overrides)
    model_type = _family_of(model_path)
    logger.warning(
        f"No local checkpoint at {model_path!r}; using random-init {model_type} preset "
        "(zero-egress environment)"
    )
    return config, None, model_type


def _family_of(name: str) -> str:
    key = name.lower().replace("-", "").replace("_", "")
    for family in ("gptbigcode", "gptneox", "gptj", "gpt2", "llama", "opt", "bloom", "kimivl", "ouro", "lfm2moe",
                   "keyevl"):
        if family in key:
            return {"gptneox": "gpt_neox", "gptbigcode": "gpt_bigcode", "kimivl": "kimi_vl",
                    "lfm2moe": "lfm2_moe", "keyevl": "keye_vl"}.get(family, family)
    if "pythia" in key or "neox" in key:
        return "gpt_neox"
    if "starcoder" in key or "santacoder" in key:
        return "gpt_bigcode"
    return "gpt2"


def save_pretrained_hf(
    out_dir: str,
    model_type: str,
    params: Dict[str, Any],
    config: TransformerConfig,
    hf_config=None,
) -> None:
    """Export trunk params as an HF-format directory (safetensors + config.json),
    parity with the reference's ``save_pretrained`` hf_model export
    (accelerate_base_trainer.py:284-307)."""
    os.makedirs(out_dir, exist_ok=True)
    sd = params_to_hf_state_dict(model_type, params, config)
    from safetensors.numpy import save_file

    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()}, os.path.join(out_dir, "model.safetensors"))
    if hf_config is None:
        hf_config = make_hf_config(model_type, config)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        f.write(hf_config.to_json_string())


def make_hf_config(model_type: str, c: TransformerConfig):
    import transformers

    if model_type == "gpt2":
        return transformers.GPT2Config(
            vocab_size=c.vocab_size, n_embd=c.hidden_size, n_layer=c.num_layers,
            n_head=c.num_heads, n_positions=c.max_position_embeddings,
            n_inner=c.ffn_dim, layer_norm_epsilon=c.norm_eps,
        )
    if model_type == "llama":
        return transformers.LlamaConfig(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size,
            num_hidden_layers=c.num_layers, num_attention_heads=c.num_heads,
            num_key_value_heads=c.kv_heads, intermediate_size=c.ffn_dim,
            max_position_embeddings=c.max_position_embeddings, rms_norm_eps=c.norm_eps,
            rope_theta=c.rope_theta, tie_word_embeddings=c.tie_word_embeddings,
        )
    if model_type == "gpt_neox":
        return transformers.GPTNeoXConfig(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size,
            num_hidden_layers=c.num_layers, num_attention_heads=c.num_heads,
            intermediate_size=c.ffn_dim, max_position_embeddings=c.max_position_embeddings,
            rotary_pct=c.rotary_pct, layer_norm_eps=c.norm_eps,
            use_parallel_residual=c.parallel_residual,
        )
    if model_type == "gptj":
        return transformers.GPTJConfig(
            vocab_size=c.vocab_size, n_embd=c.hidden_size, n_layer=c.num_layers,
            n_head=c.num_heads, n_positions=c.max_position_embeddings,
            rotary_dim=int(c.dim_per_head * c.rotary_pct), layer_norm_epsilon=c.norm_eps,
        )
    if model_type == "opt":
        return transformers.OPTConfig(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size,
            num_hidden_layers=c.num_layers, num_attention_heads=c.num_heads,
            ffn_dim=c.ffn_dim, max_position_embeddings=c.max_position_embeddings,
            do_layer_norm_before=True,
        )
    if model_type == "bloom":
        return transformers.BloomConfig(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size, n_layer=c.num_layers,
            n_head=c.num_heads, layer_norm_epsilon=c.norm_eps,
        )
    if model_type == "gpt_bigcode":
        return transformers.GPTBigCodeConfig(
            vocab_size=c.vocab_size, n_embd=c.hidden_size, n_layer=c.num_layers,
            n_head=c.num_heads, n_positions=c.max_position_embeddings,
            n_inner=c.ffn_dim, layer_norm_epsilon=c.norm_eps,
            multi_query=c.kv_heads == 1, activation_function="gelu_pytorch_tanh",
        )
    if model_type == "ouro":
        # transformers has no class for it (the published model brings its own code): the published keys
        published = type("OuroConfig", (transformers.PretrainedConfig,), {"model_type": "ouro"})
        return published(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size, num_hidden_layers=c.num_layers,
            num_attention_heads=c.num_heads, num_key_value_heads=c.kv_heads, head_dim=c.dim_per_head,
            intermediate_size=c.ffn_dim, max_position_embeddings=c.max_position_embeddings,
            rms_norm_eps=c.norm_eps, rope_theta=c.rope_theta, hidden_act="silu",
            tie_word_embeddings=c.tie_word_embeddings, total_ut_steps=c.loop_steps,
            early_exit_threshold=c.early_exit_threshold,
        )
    if model_type == "lfm2_moe":
        # the published keys, whether or not this transformers has the class
        published = type("Lfm2MoeConfig", (transformers.PretrainedConfig,), {"model_type": "lfm2_moe"})
        return published(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size, num_hidden_layers=c.num_layers,
            num_attention_heads=c.num_heads, num_key_value_heads=c.kv_heads, intermediate_size=c.ffn_dim,
            moe_intermediate_size=c.moe_intermediate_size, max_position_embeddings=c.max_position_embeddings,
            norm_eps=c.norm_eps, rope_parameters={"rope_theta": c.rope_theta, "rope_type": "default"},
            layer_types=["conv" if c.is_conv_layer(i) else "full_attention" for i in range(c.num_layers)],
            conv_L_cache=c.conv_taps, conv_bias=False, num_dense_layers=c.first_dense_layers,
            num_experts=c.num_experts, num_experts_per_tok=c.experts_per_token, norm_topk_prob=c.norm_topk_prob,
            routed_scaling_factor=c.routed_scaling_factor, use_expert_bias=True,
            tie_word_embeddings=c.tie_word_embeddings,
        )
    if model_type in ("keye_vl", "KeyeVL2"):
        # the published keys of the language model, whether or not this transformers has the class
        published = type("KeyeVL2Config", (transformers.PretrainedConfig,), {"model_type": "KeyeVL2"})
        return published(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size, num_hidden_layers=c.num_layers,
            num_attention_heads=c.num_heads, num_key_value_heads=c.kv_heads, head_dim=c.dim_per_head,
            intermediate_size=c.ffn_dim, moe_intermediate_size=c.moe_intermediate_size,
            max_position_embeddings=c.max_position_embeddings, rms_norm_eps=c.norm_eps, rope_theta=c.rope_theta,
            num_experts=c.num_experts, num_experts_per_tok=c.experts_per_token, norm_topk_prob=c.norm_topk_prob,
            decoder_sparse_step=1, mlp_only_layers=[], use_sliding_window=False,
            tie_word_embeddings=c.tie_word_embeddings, attention_bias=False, hidden_act="silu",
            sa_config={"indexer_head_dim": c.index_head_dim, "indexer_num_heads": c.index_heads,
                       "indexer_num_kv_heads": 1, "topk": c.index_topk},
        )
    if model_type == "t5":
        return transformers.T5Config(
            vocab_size=c.vocab_size, d_model=c.d_model, d_kv=c.d_kv, d_ff=c.d_ff,
            num_layers=c.num_layers, num_decoder_layers=c.num_decoder_layers,
            num_heads=c.num_heads,
            relative_attention_num_buckets=c.relative_attention_num_buckets,
            relative_attention_max_distance=c.relative_attention_max_distance,
            layer_norm_epsilon=c.layer_norm_epsilon,
            feed_forward_proj="gated-gelu" if c.is_gated else "relu",
            tie_word_embeddings=c.tie_word_embeddings,
            decoder_start_token_id=c.decoder_start_token_id,
        )
    raise ValueError(f"No HF config factory for {model_type!r}")


# ------------------------------------------------------------------- T5 (seq2seq)


def _t5_attn_to_params(sd, pre, has_bias):
    p = {
        "q": {"kernel": sd[f"{pre}.q.weight"].T},
        "k": {"kernel": sd[f"{pre}.k.weight"].T},
        "v": {"kernel": sd[f"{pre}.v.weight"].T},
        "o": {"kernel": sd[f"{pre}.o.weight"].T},
    }
    if has_bias:
        p["relative_attention_bias"] = {"embedding": sd[f"{pre}.relative_attention_bias.weight"]}
    return p


def _t5_ffn_to_params(sd, pre, gated):
    if gated:
        return {
            "wi_0": {"kernel": sd[f"{pre}.wi_0.weight"].T},
            "wi_1": {"kernel": sd[f"{pre}.wi_1.weight"].T},
            "wo": {"kernel": sd[f"{pre}.wo.weight"].T},
        }
    return {"wi": {"kernel": sd[f"{pre}.wi.weight"].T}, "wo": {"kernel": sd[f"{pre}.wo.weight"].T}}


def t5_state_dict_to_params(sd: Dict[str, np.ndarray], config) -> Dict[str, Any]:
    """HF T5 state dict -> T5LM params (cites modeling_base.py:124 from_pretrained)."""
    gated = config.is_gated
    p: Dict[str, Any] = {
        "shared": {"embedding": sd["shared.weight"]},
        "encoder_ln": {"scale": sd["encoder.final_layer_norm.weight"]},
        "decoder_ln": {"scale": sd["decoder.final_layer_norm.weight"]},
    }
    if not config.tie_word_embeddings and "lm_head.weight" in sd:
        p["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(config.num_layers):
        pre = f"encoder.block.{i}"
        p[f"encoder_blocks_{i}"] = {
            "ln_1": {"scale": sd[f"{pre}.layer.0.layer_norm.weight"]},
            "attn": _t5_attn_to_params(sd, f"{pre}.layer.0.SelfAttention", i == 0),
            "ln_2": {"scale": sd[f"{pre}.layer.1.layer_norm.weight"]},
            "mlp": _t5_ffn_to_params(sd, f"{pre}.layer.1.DenseReluDense", gated),
        }
    for i in range(config.num_decoder_layers):
        pre = f"decoder.block.{i}"
        p[f"decoder_blocks_{i}"] = {
            "ln_1": {"scale": sd[f"{pre}.layer.0.layer_norm.weight"]},
            "self_attn": _t5_attn_to_params(sd, f"{pre}.layer.0.SelfAttention", i == 0),
            "ln_cross": {"scale": sd[f"{pre}.layer.1.layer_norm.weight"]},
            "cross_attn": _t5_attn_to_params(sd, f"{pre}.layer.1.EncDecAttention", False),
            "ln_2": {"scale": sd[f"{pre}.layer.2.layer_norm.weight"]},
            "mlp": _t5_ffn_to_params(sd, f"{pre}.layer.2.DenseReluDense", gated),
        }
    return jax.tree.map(lambda x: np.asarray(x, np.float32), p)


def _t5_attn_from_params(p, pre, sd):
    for k in ("q", "k", "v", "o"):
        sd[f"{pre}.{k}.weight"] = p[k]["kernel"].T
    if "relative_attention_bias" in p:
        sd[f"{pre}.relative_attention_bias.weight"] = p["relative_attention_bias"]["embedding"]


def _t5_ffn_from_params(p, pre, sd):
    for name in ("wi", "wi_0", "wi_1", "wo"):
        if name in p:
            sd[f"{pre}.{name}.weight"] = p[name]["kernel"].T


def _t5_from_params(p: Dict[str, Any], c) -> Dict[str, np.ndarray]:
    """T5LM params -> HF T5 state dict (reverse of :func:`t5_state_dict_to_params`)."""
    sd = {
        "shared.weight": p["shared"]["embedding"],
        "encoder.embed_tokens.weight": p["shared"]["embedding"],
        "decoder.embed_tokens.weight": p["shared"]["embedding"],
        "encoder.final_layer_norm.weight": p["encoder_ln"]["scale"],
        "decoder.final_layer_norm.weight": p["decoder_ln"]["scale"],
    }
    if "lm_head" in p:
        sd["lm_head.weight"] = p["lm_head"]["kernel"].T
    for i in range(c.num_layers):
        pre = f"encoder.block.{i}"
        L = p[f"encoder_blocks_{i}"]
        sd[f"{pre}.layer.0.layer_norm.weight"] = L["ln_1"]["scale"]
        _t5_attn_from_params(L["attn"], f"{pre}.layer.0.SelfAttention", sd)
        sd[f"{pre}.layer.1.layer_norm.weight"] = L["ln_2"]["scale"]
        _t5_ffn_from_params(L["mlp"], f"{pre}.layer.1.DenseReluDense", sd)
    for i in range(c.num_decoder_layers):
        pre = f"decoder.block.{i}"
        L = p[f"decoder_blocks_{i}"]
        sd[f"{pre}.layer.0.layer_norm.weight"] = L["ln_1"]["scale"]
        _t5_attn_from_params(L["self_attn"], f"{pre}.layer.0.SelfAttention", sd)
        sd[f"{pre}.layer.1.layer_norm.weight"] = L["ln_cross"]["scale"]
        _t5_attn_from_params(L["cross_attn"], f"{pre}.layer.1.EncDecAttention", sd)
        sd[f"{pre}.layer.2.layer_norm.weight"] = L["ln_2"]["scale"]
        _t5_ffn_from_params(L["mlp"], f"{pre}.layer.2.DenseReluDense", sd)
    return sd


CONVERTERS["t5"] = (t5_state_dict_to_params, _t5_from_params)


def load_pretrained_seq2seq(
    model_path: str, overrides: Optional[Dict[str, Any]] = None, mesh=None
):
    """Resolve (T5Config, params or None) for a seq2seq model path."""
    from trlx_tpu import checkpointing
    from trlx_tpu.models.t5 import T5Config, from_hf_t5_config

    if checkpointing.is_native_checkpoint(model_path):
        config, params, _ = checkpointing.restore_native(
            model_path, overrides, mesh=mesh, expect_seq2seq=True
        )
        return config, params
    config_path = os.path.join(model_path, "config.json")
    if os.path.isdir(model_path) and os.path.exists(config_path):
        hf_config, sd = _read_hf_checkpoint(model_path)
        config = from_hf_t5_config(hf_config, overrides)
        return config, t5_state_dict_to_params(sd, config)
    config = T5Config()
    if overrides:
        config = config.replace(**overrides)
    logger.warning(
        f"No local checkpoint at {model_path!r}; using random-init T5 config (zero-egress)"
    )
    return config, None


def merge_loaded_params(init_tree: Dict[str, Any], loaded_tree: Dict[str, Any]) -> Dict[str, Any]:
    """Overlay checkpoint leaves onto an init tree, keeping init-only params (LoRA
    adapters, new heads) — the JAX analogue of HF's lenient state-dict load."""
    if not isinstance(init_tree, dict):
        return loaded_tree if loaded_tree is not None else init_tree
    out = {}
    for k, v in init_tree.items():
        if isinstance(loaded_tree, dict) and k in loaded_tree:
            out[k] = merge_loaded_params(v, loaded_tree[k])
        else:
            out[k] = v
    # keep any loaded-only keys too (e.g. optional biases)
    if isinstance(loaded_tree, dict):
        for k, v in loaded_tree.items():
            if k not in out:
                out[k] = v
    return out


# leaf param names that belong to peft adapters (LoRA / prefix / prompt)
ADAPTER_PARAM_NAMES = ("lora_a", "lora_b", "prefix_k", "prefix_v", "prompt_embeddings")


def extract_adapter_params(tree: Any) -> Optional[Dict[str, Any]]:
    """The adapter-only subtree of a params tree (None if no adapters).

    Parity: the reference saves peft adapters + heads only instead of the full
    model (modeling_base.py:347-353)."""
    if not isinstance(tree, dict):
        return None
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        if k in ADAPTER_PARAM_NAMES:
            out[k] = v
        elif isinstance(v, dict):
            sub = extract_adapter_params(v)
            if sub:
                out[k] = sub
    return out or None


def save_adapters(path: str, params: Dict[str, Any]) -> bool:
    """Write adapters.msgpack next to the export; returns False if no adapters."""
    from flax.serialization import to_bytes

    adapters = extract_adapter_params(params)
    if not adapters:
        return False
    with open(os.path.join(path, "adapters.msgpack"), "wb") as f:
        f.write(to_bytes(adapters))
    return True


def load_adapters(path: str, params: Dict[str, Any]) -> Dict[str, Any]:
    """Overlay adapters.msgpack leaves onto ``params`` (shapes must match)."""
    from flax.serialization import from_bytes

    with open(os.path.join(path, "adapters.msgpack"), "rb") as f:
        template = extract_adapter_params(params)
        adapters = from_bytes(template, f.read())
    return merge_loaded_params(params, adapters)


def peft_overrides(peft_config: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Map a reference-style peft config dict to TransformerConfig overrides
    (parity: modeling_base.py:162-240 — LORA, PREFIX_TUNING, PROMPT_TUNING)."""
    if not peft_config:
        return {}
    ptype = str(peft_config.get("peft_type", "LORA")).upper()
    if ptype == "LORA":
        out = {"lora_r": int(peft_config.get("r", 8)),
               "lora_alpha": float(peft_config.get("lora_alpha", peft_config.get("alpha", 16)))}
        targets = peft_config.get("target_modules")
        if targets:
            out["lora_targets"] = tuple(targets)
        return out
    if ptype in ("PREFIX_TUNING", "PREFIX"):
        return {"peft_type": "prefix",
                "num_virtual_tokens": int(peft_config.get("num_virtual_tokens", 8))}
    if ptype in ("PROMPT_TUNING", "PROMPT"):
        return {"peft_type": "prompt",
                "num_virtual_tokens": int(peft_config.get("num_virtual_tokens", 8))}
    raise ValueError(f"Unsupported peft_type {ptype!r} (LORA / PREFIX_TUNING / PROMPT_TUNING)")


T5_LORA_TARGETS = ("q", "k", "v", "o", "wi", "wi_0", "wi_1", "wo")


def t5_peft_overrides(peft_config: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Seq2seq variant of :func:`peft_overrides`: LoRA only, with T5 target-name
    validation — a causal-style target list (q_proj/v_proj) would otherwise
    silently build zero adapters and freeze the whole trunk."""
    peft = peft_overrides(peft_config)
    if not peft:
        return {}
    if "lora_r" not in peft:
        raise NotImplementedError(
            "seq2seq (T5) peft supports LORA adapters; prefix/prompt tuning "
            "is causal-only (T5Config has no virtual-token path)"
        )
    peft.setdefault("lora_targets", ("q", "v"))
    unknown = set(peft["lora_targets"]) - set(T5_LORA_TARGETS)
    if unknown:
        raise ValueError(
            f"peft target_modules {sorted(unknown)} match no T5 module; "
            f"valid T5 LoRA targets: {sorted(T5_LORA_TARGETS)}"
        )
    return peft
