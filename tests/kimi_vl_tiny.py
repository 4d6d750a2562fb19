"""The small kimi_vl configuration the CPU tests share: the published keys at
tiny sizes, as ``benchmark/configs/kimi-vl-a3b.json`` has them."""


def tiny_config(held=16, offset=0, experts=16, layers=3, **precision):
    """16 experts of width 32, 4 a token, 4 heads of 16 + 8 / 16, latent 32,
    one leading dense layer. ``held`` / ``offset`` cut a share of the experts."""
    return dict(
        name="tiny-kimi", source="tests", family="kimi_vl", model_type="kimi_vl",
        vocab_size=300, max_position_embeddings=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=layers, num_attention_heads=4,
        n_shared_experts=2, n_routed_experts=held, expert_offset=offset,
        published=dict(n_routed_experts=experts), routed_scaling_factor=2.446,
        kv_lora_rank=32, q_lora_rank=None, qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
        topk_method="noaux_tc", n_group=1, topk_group=1, num_experts_per_tok=4, moe_layer_freq=1,
        first_k_dense_replace=1, norm_topk_prob=True, scoring_func="sigmoid", hidden_act="silu",
        rms_norm_eps=1e-5, rope_theta=800000, rope_scaling=None, attention_bias=False,
        tie_word_embeddings=False, initializer_range=0.02, reduced=[], assumed={},
        precision=dict(param_dtype=precision.get("param_dtype", "float32"),
                       compute_dtype=precision.get("compute_dtype", "float32")),
    )
