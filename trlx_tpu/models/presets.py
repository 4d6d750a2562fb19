"""Architecture presets + HF-config conversion for the generic TransformerLM.

Replaces the reference's per-architecture model surgery (`hf_get_*` getters,
`/root/reference/trlx/utils/modeling.py:13-120`, and the per-arch hydra branches in
`modeling_ppo.py`): each supported family is a preset of TransformerConfig switches.
"""

from typing import Any, Dict, Optional

from trlx_tpu.models.transformer import TransformerConfig

# Tiny shape defaults used when no checkpoint is available (offline/random-init runs
# and tests); real dims come from HF configs via from_hf_config.
PRESETS: Dict[str, TransformerConfig] = {
    "gpt2": TransformerConfig(
        vocab_size=50257, hidden_size=768, num_layers=12, num_heads=12,
        max_position_embeddings=1024, pos_embedding="learned", norm="layernorm",
        activation="gelu_new", attn_bias=True, mlp_bias=True, tie_word_embeddings=True,
    ),
    "gptj": TransformerConfig(
        vocab_size=50400, hidden_size=4096, num_layers=28, num_heads=16,
        max_position_embeddings=2048, pos_embedding="rotary", rope_style="gptj",
        rotary_pct=64 / 256, norm="layernorm", activation="gelu_new",
        parallel_residual=True, shared_parallel_ln=True, attn_bias=False, mlp_bias=True,
        head_bias=True, tie_word_embeddings=False,
    ),
    "gpt_neox": TransformerConfig(
        vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
        max_position_embeddings=2048, pos_embedding="rotary", rope_style="neox",
        rotary_pct=0.25, norm="layernorm", activation="gelu", parallel_residual=True,
        shared_parallel_ln=False, attn_bias=True, mlp_bias=True, tie_word_embeddings=False,
    ),
    "opt": TransformerConfig(
        vocab_size=50272, hidden_size=768, num_layers=12, num_heads=12,
        max_position_embeddings=2048, pos_embedding="learned", pos_offset=2,
        norm="layernorm", activation="relu", attn_bias=True, mlp_bias=True,
        tie_word_embeddings=True,
    ),
    "llama": TransformerConfig(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32,
        intermediate_size=11008, max_position_embeddings=4096, pos_embedding="rotary",
        rope_style="neox", norm="rmsnorm", norm_eps=1e-6, activation="silu", glu=True,
        attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
    ),
    # reference parity: BloomModelBranch (modeling_ppo.py:816) — ALiBi positions,
    # embedding LayerNorm, fused per-head qkv, tied embeddings
    "bloom": TransformerConfig(
        vocab_size=250880, hidden_size=1024, num_layers=24, num_heads=16,
        max_position_embeddings=2048, pos_embedding="alibi", norm="layernorm",
        activation="gelu_new", attn_bias=True, mlp_bias=True, embed_ln=True,
        tie_word_embeddings=True,
    ),
    # reference parity: GPTBigCodeModelBranch (modeling_ppo.py:1079) — multi-query
    # attention (1 kv head), learned positions, tanh-gelu
    "gpt_bigcode": TransformerConfig(
        vocab_size=49152, hidden_size=2048, num_layers=24, num_heads=16,
        num_kv_heads=1, max_position_embeddings=2048, pos_embedding="learned",
        norm="layernorm", activation="gelu_new", attn_bias=True, mlp_bias=True,
        tie_word_embeddings=True,
    ),
    # Kimi-VL-A3B's language model (the vision tower is not run): latent
    # attention over a latent cache, one leading dense layer, then 64
    # sigmoid-routed experts (6 a token) beside 2 shared ones. experts_held /
    # expert_offset / vocab_size cut a chip's share of a layer (model_overrides).
    "kimi_vl": TransformerConfig(
        vocab_size=163840, hidden_size=2048, num_layers=27, num_heads=16,
        intermediate_size=11264, max_position_embeddings=131072, pos_embedding="rotary",
        rope_style="neox", rope_theta=800000.0, norm="rmsnorm", norm_eps=1e-5,
        activation="silu", glu=True, attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
        attention_kind="mla", kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_experts=64, experts_per_token=6, num_shared_experts=2,
        moe_intermediate_size=1408, first_dense_layers=1, routed_scaling_factor=2.446,
        norm_topk_prob=True,
    ),
    # Ouro-2.6B, a looped language model: the 48 layers are applied total_ut_steps = 4
    # times over the same weights, the final norm after every pass; sandwich norms
    # (four RMSNorms a block); a per-pass exit gate whose published threshold of 1
    # lets no token leave early.
    "ouro": TransformerConfig(
        vocab_size=49152, hidden_size=2048, num_layers=48, num_heads=16, head_dim=128,
        intermediate_size=5632, max_position_embeddings=65536, pos_embedding="rotary",
        rope_style="neox", rope_theta=1000000.0, norm="rmsnorm", norm_eps=1e-6,
        activation="silu", glu=True, attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
        loop_steps=4, sandwich_norms=True, exit_gate=True, early_exit_threshold=1.0,
    ),
    # LFM2-24B-A2B (model_type lfm2_moe), a hybrid: 30 of the 40 layers mix tokens by a
    # gated short convolution of 3 taps, 10 by grouped-query attention with a norm on
    # every query and key head; two leading dense layers, then 64 sigmoid-routed experts
    # (4 a token, weights over their sum + 1e-6) and no shared one; the head is tied.
    "lfm2_moe": TransformerConfig(
        vocab_size=65536, hidden_size=2048, num_layers=40, num_heads=32, num_kv_heads=8,
        intermediate_size=11776, max_position_embeddings=128000, pos_embedding="rotary",
        rope_style="neox", rope_theta=1000000.0, norm="rmsnorm", norm_eps=1e-5,
        activation="silu", glu=True, attn_bias=False, mlp_bias=False, tie_word_embeddings=True,
        layer_kinds=tuple("attention" if i % 4 == 2 else "conv" for i in range(40)),  # the published layer_types
        conv_taps=3, qk_norm=True, num_experts=64, experts_per_token=4, num_shared_experts=0,
        moe_intermediate_size=1536, first_dense_layers=2, routed_scaling_factor=1.0, norm_topk_prob=True,
        router_norm_eps=1e-6,
    ),
    # Keye-VL-2.0-30B-A3B's language model (model_type KeyeVL2; the vision tower is not run):
    # 48 alike layers of grouped-query attention (32 query heads over 4 kv heads of 128, a norm
    # on every query and key head) over the 2,048 earlier keys a learned indexer picks for each
    # query (16 heads of 64 over one key head), then 128 softmax-routed experts, 8 a token,
    # Qwen3-MoE's rule, no shared expert and no dense layer; the head is not tied.
    "keye_vl": TransformerConfig(
        vocab_size=151936, hidden_size=2048, num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
        intermediate_size=6144, max_position_embeddings=262144, pos_embedding="rotary",
        rope_style="neox", rope_theta=10000000.0, norm="rmsnorm", norm_eps=1e-6,
        activation="silu", glu=True, attn_bias=False, mlp_bias=False, tie_word_embeddings=False,
        qk_norm=True, num_experts=128, experts_per_token=8, num_shared_experts=0,
        moe_intermediate_size=768, first_dense_layers=0, routed_scaling_factor=1.0, norm_topk_prob=True,
        router_scoring="softmax", index_topk=2048, index_heads=16, index_head_dim=64, index_rope_dim=32,
    ),
}

#: the published ``layer_types`` -> ``TransformerConfig.layer_kinds``
LFM2_LAYER_KINDS = {"conv": "conv", "full_attention": "attention"}


def get_preset(name: str, overrides: Optional[Dict[str, Any]] = None) -> TransformerConfig:
    """Resolve a family preset by name (exact or prefix: "gpt2-imdb" -> gpt2)."""
    key = name.lower()
    config = None
    if key in PRESETS:
        config = PRESETS[key]
    else:
        for family in ("gpt_bigcode", "gpt_neox", "gptj", "gpt2", "llama", "opt", "bloom", "kimi_vl", "ouro",
                       "lfm2_moe", "keye_vl"):
            if family.replace("_", "") in key.replace("_", "").replace("-", ""):
                config = PRESETS[family]
                break
        if config is None and ("pythia" in key or "neox" in key):
            config = PRESETS["gpt_neox"]
        if config is None and ("starcoder" in key or "santacoder" in key):
            config = PRESETS["gpt_bigcode"]
    if config is None:
        raise ValueError(f"Unknown architecture preset for {name!r}; known: {sorted(PRESETS)}")
    if overrides:
        config = config.replace(**overrides)
    return config


def from_hf_config(hf_config, overrides: Optional[Dict[str, Any]] = None) -> TransformerConfig:
    """Convert a ``transformers`` config object to TransformerConfig."""
    mt = hf_config.model_type
    if mt == "gpt2":
        config = PRESETS["gpt2"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            intermediate_size=getattr(hf_config, "n_inner", None),
            max_position_embeddings=hf_config.n_positions,
            norm_eps=hf_config.layer_norm_epsilon,
        )
    elif mt == "gptj":
        config = PRESETS["gptj"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            max_position_embeddings=hf_config.n_positions,
            rotary_pct=hf_config.rotary_dim / (hf_config.n_embd // hf_config.n_head),
            norm_eps=hf_config.layer_norm_epsilon,
        )
    elif mt == "gpt_neox":
        config = PRESETS["gpt_neox"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers, num_heads=hf_config.num_attention_heads,
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            rotary_pct=hf_config.rotary_pct, norm_eps=hf_config.layer_norm_eps,
            parallel_residual=hf_config.use_parallel_residual,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        )
    elif mt == "opt":
        config = PRESETS["opt"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers, num_heads=hf_config.num_attention_heads,
            intermediate_size=hf_config.ffn_dim,
            max_position_embeddings=hf_config.max_position_embeddings,
            tie_word_embeddings=hf_config.tie_word_embeddings,
        )
    elif mt == "llama":
        config = PRESETS["llama"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers, num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            norm_eps=hf_config.rms_norm_eps,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        )
    elif mt == "bloom":
        config = PRESETS["bloom"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            norm_eps=hf_config.layer_norm_epsilon,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", True),
        )
    elif mt == "gpt_bigcode":
        config = PRESETS["gpt_bigcode"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            num_kv_heads=1 if getattr(hf_config, "multi_query", True) else None,
            intermediate_size=getattr(hf_config, "n_inner", None),
            max_position_embeddings=hf_config.n_positions,
            norm_eps=hf_config.layer_norm_epsilon,
        )
    elif mt == "kimi_vl":
        text = getattr(hf_config, "text_config", hf_config)  # kimi_vl nests the language model's
        unsupported = {
            "q_lora_rank": getattr(text, "q_lora_rank", None) is not None,
            "n_group > 1": getattr(text, "n_group", 1) != 1,
            "rope_scaling": getattr(text, "rope_scaling", None) is not None,
            "scoring_func other than sigmoid": getattr(text, "scoring_func", "sigmoid") != "sigmoid",
            "moe_layer_freq other than 1": getattr(text, "moe_layer_freq", 1) != 1,
        }
        if any(unsupported.values()):
            raise ValueError(f"{mt}: not supported: {[k for k, v in unsupported.items() if v]}")
        config = PRESETS["kimi_vl"].replace(
            vocab_size=text.vocab_size, hidden_size=text.hidden_size,
            num_layers=text.num_hidden_layers, num_heads=text.num_attention_heads,
            intermediate_size=text.intermediate_size,
            max_position_embeddings=text.max_position_embeddings,
            rope_theta=float(getattr(text, "rope_theta", 10000.0)), norm_eps=text.rms_norm_eps,
            tie_word_embeddings=getattr(text, "tie_word_embeddings", False),
            kv_lora_rank=text.kv_lora_rank, qk_nope_head_dim=text.qk_nope_head_dim,
            qk_rope_head_dim=text.qk_rope_head_dim, v_head_dim=text.v_head_dim,
            num_experts=text.n_routed_experts, experts_per_token=text.num_experts_per_tok,
            num_shared_experts=text.n_shared_experts, moe_intermediate_size=text.moe_intermediate_size,
            first_dense_layers=text.first_k_dense_replace,
            routed_scaling_factor=text.routed_scaling_factor, norm_topk_prob=text.norm_topk_prob,
        )
    elif mt == "ouro":
        unsupported = {
            "rope_scaling": getattr(hf_config, "rope_scaling", None) is not None,
            "use_sliding_window": bool(getattr(hf_config, "use_sliding_window", False)),
            "hidden_act other than silu": getattr(hf_config, "hidden_act", "silu") != "silu",
        }
        if any(unsupported.values()):
            raise ValueError(f"{mt}: not supported: {[k for k, v in unsupported.items() if v]}")
        config = PRESETS["ouro"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers, num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
            head_dim=getattr(hf_config, "head_dim", None), intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)), norm_eps=hf_config.rms_norm_eps,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
            loop_steps=hf_config.total_ut_steps,
            early_exit_threshold=float(getattr(hf_config, "early_exit_threshold", 1.0)),
        )
    elif mt == "lfm2_moe":
        unsupported = {
            "conv_bias": bool(getattr(hf_config, "conv_bias", False)),
            "use_expert_bias false": not getattr(hf_config, "use_expert_bias", True),
            "rope_type other than default": (getattr(hf_config, "rope_parameters", None) or {}).get(
                "rope_type", "default") != "default",
        }
        if any(unsupported.values()):
            raise ValueError(f"{mt}: not supported: {[k for k, v in unsupported.items() if v]}")
        config = PRESETS["lfm2_moe"].replace(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers, num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_key_value_heads, intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            rope_theta=float((getattr(hf_config, "rope_parameters", None) or {}).get("rope_theta", 1000000.0)),
            norm_eps=hf_config.norm_eps, tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", True),
            layer_kinds=tuple(LFM2_LAYER_KINDS[kind] for kind in hf_config.layer_types),
            conv_taps=hf_config.conv_L_cache, num_experts=hf_config.num_experts,
            experts_per_token=hf_config.num_experts_per_tok, moe_intermediate_size=hf_config.moe_intermediate_size,
            first_dense_layers=hf_config.num_dense_layers, routed_scaling_factor=hf_config.routed_scaling_factor,
            norm_topk_prob=hf_config.norm_topk_prob,
        )
    elif mt in ("KeyeVL2", "keye_vl2"):
        text = getattr(hf_config, "text_config", hf_config)  # the language model's keys, nested or not
        sa = dict(getattr(text, "sa_config", None) or {})
        unsupported = {
            "use_sliding_window": bool(getattr(text, "use_sliding_window", False)),
            "mlp_only_layers": bool(getattr(text, "mlp_only_layers", [])),
            "decoder_sparse_step other than 1": getattr(text, "decoder_sparse_step", 1) != 1,
            "indexer_num_kv_heads other than 1": sa.get("indexer_num_kv_heads", 1) != 1,
            "no sa_config": not sa,
        }
        if any(unsupported.values()):
            raise ValueError(f"{mt}: not supported: {[k for k, v in unsupported.items() if v]}")
        config = PRESETS["keye_vl"].replace(
            vocab_size=text.vocab_size, hidden_size=text.hidden_size, num_layers=text.num_hidden_layers,
            num_heads=text.num_attention_heads, num_kv_heads=text.num_key_value_heads, head_dim=text.head_dim,
            intermediate_size=text.intermediate_size, max_position_embeddings=text.max_position_embeddings,
            rope_theta=float(text.rope_theta), norm_eps=text.rms_norm_eps,
            tie_word_embeddings=getattr(text, "tie_word_embeddings", False),
            num_experts=text.num_experts, experts_per_token=text.num_experts_per_tok,
            moe_intermediate_size=text.moe_intermediate_size, norm_topk_prob=text.norm_topk_prob,
            index_topk=sa["topk"], index_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"],
            index_rope_dim=sa["indexer_head_dim"] // 2,
        )
    else:
        raise ValueError(f"Unsupported HF model_type {mt!r}")
    if overrides:
        config = config.replace(**overrides)
    return config
