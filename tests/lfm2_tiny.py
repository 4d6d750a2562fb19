"""The small lfm2 configuration the CPU tests share: the published keys at tiny
sizes, as ``benchmark/configs/lfm2-24b-a2b.json`` has them."""

KINDS = ("conv", "full_attention", "conv", "conv")


def tiny_config(held=8, offset=0, experts=8, layers=4, **precision):
    """d 64, kinds ``c a c c``, 4 query / 2 kv heads of 16 with a norm on each
    head, 3 taps, one leading dense layer of 160, then 8 experts of 48 with 2 a
    token and no shared one; vocabulary 300 (the tests' byte tokenizer needs 259
    ids). ``held`` / ``offset`` cut a share of the experts."""
    return dict(
        name="tiny-lfm2", source="tests", family="lfm2", model_type="lfm2_moe",
        vocab_size=300, max_position_embeddings=128, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_hidden_layers=layers, num_dense_layers=1, layer_types=list(KINDS[:layers]),
        num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3, conv_bias=False,
        num_experts=held, expert_offset=offset, published=dict(num_experts=experts), num_experts_per_tok=2,
        norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True, norm_eps=1e-5,
        rope_parameters=dict(rope_theta=1000000, rope_type="default"),
        initializer_range=0.02, reduced=[], assumed={},
        precision=dict(param_dtype=precision.get("param_dtype", "float32"),
                       compute_dtype=precision.get("compute_dtype", "float32")),
    )
