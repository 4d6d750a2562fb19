"""The small ouro configuration the CPU tests share: the published keys at tiny
sizes, as ``benchmark/configs/ouro-2.6b.json`` has them."""


def tiny_config(layers=3, passes=4, **precision):
    """3 layers run 4 times, d 64, 4 heads of 16, SwiGLU 176, vocabulary 300
    (the tests' byte tokenizer needs 259 ids)."""
    return dict(
        name="tiny-ouro", source="tests", family="ouro", model_type="ouro",
        vocab_size=300, max_position_embeddings=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=layers, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        hidden_act="silu", rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
        tie_word_embeddings=False, total_ut_steps=passes, early_exit_threshold=1,
        initializer_range=0.02, reduced=[], assumed={},
        precision=dict(param_dtype=precision.get("param_dtype", "float32"),
                       compute_dtype=precision.get("compute_dtype", "float32")),
    )
