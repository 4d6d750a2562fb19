"""The small keye_vl configuration the CPU tests share: the published keys at
tiny sizes, as ``benchmark/configs/keye-vl-2.0-30b-a3b.json`` has them."""


def tiny_config(held=8, offset=0, experts=8, layers=2, topk=16, **precision):
    """d 64, 4 query / 2 kv heads of 16 with a norm on each head; an indexer of
    4 heads of 16 over one key head choosing ``topk`` keys a query; 8 experts of
    48, 2 a token, softmax-routed, no shared one; vocabulary 300 (the tests'
    byte tokenizer needs 259 ids). ``held`` / ``offset`` cut a share of the experts."""
    return dict(
        name="tiny-keye-vl", source="tests", family="keye_vl", model_type="KeyeVL2",
        vocab_size=300, max_position_embeddings=512, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_hidden_layers=layers, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=held, num_local_experts=experts, expert_offset=offset,
        published=dict(num_experts=experts), num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
        rope_theta=10000000, decoder_sparse_step=1, mlp_only_layers=[], use_sliding_window=False,
        sa_config=dict(indexer_head_dim=16, indexer_num_heads=4, indexer_num_kv_heads=1, topk=topk,
                       q_chunk_size=512, kv_chunk_size=512),
        initializer_range=0.02, reduced=[], assumed={},
        precision=dict(param_dtype=precision.get("param_dtype", "float32"),
                       compute_dtype=precision.get("compute_dtype", "float32")),
    )
