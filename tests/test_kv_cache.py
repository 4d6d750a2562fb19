"""``ops/kv_cache.py``: the three formats of one layer of the contiguous cache."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.presets import PRESETS
from trlx_tpu.ops import kv_cache

B, HKV, S, D, RANK, ROPE = 2, 3, 16, 8, 12, 4
FORMATS = {
    # name: (layout, rows of a token in bytes, tolerance of a write-then-read)
    "per-head-bfloat16": (lambda: kv_cache.kv_cache_layout((B, HKV, S, D), jnp.bfloat16, False), 2 * HKV * D * 2, 0.0),
    "per-head-int8-with-scales": (lambda: kv_cache.kv_cache_layout((B, HKV, S, D), jnp.bfloat16, True),
                                  2 * HKV * (D + 4), 0.02),
    "latent": (lambda: kv_cache.latent_cache_layout(B, S, RANK, ROPE, jnp.bfloat16), (RANK + ROPE) * 2, 0.0),
}


@pytest.mark.parametrize("name", sorted(FORMATS))
def test_write_then_read_round_trip(name):
    """Two appends (a prefill of 5 tokens from slot 0, one token at slot 5) land
    where the index says, leave the other slots as they were and read back as
    written — exactly for the float formats, within a row's int8 step for the
    quantized one — and a layout says what it is and what a token costs."""
    make_layout, token_bytes, tol = FORMATS[name]
    layout = make_layout()
    cache = {key: jnp.zeros(shape, dtype) for key, (shape, dtype) in layout.items()}
    latent, quant = name == "latent", "int8" in name
    assert (kv_cache.is_latent(layout), kv_cache.has_row_scales(layout), kv_cache.is_paged(layout)) == (
        latent, quant, False)
    assert (kv_cache.is_latent(cache), kv_cache.has_row_scales(cache)) == (latent, quant)
    assert kv_cache.bytes_per_token(layout) == token_bytes

    rng = np.random.default_rng(0)

    def rows(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    written = {}
    for index, T in ((0, 5), (jnp.int32(5), 1)):
        if latent:
            c, k_rope = rows(B, T, RANK), rows(B, T, 1, ROPE)
            cache = kv_cache.write_latent_cache(cache, c, k_rope, index)
            written[int(index)] = (c, k_rope[:, :, 0])
        else:
            k, v = rows(B, HKV, T, D), rows(B, HKV, T, D)
            cache = kv_cache.write_kv_cache(cache, k, v, index)
            written[int(index)] = (k, v)
    # the loop's carry keeps its structure
    assert {key: (x.shape, x.dtype) for key, x in cache.items()} == {
        key: (shape, jnp.dtype(dtype)) for key, (shape, dtype) in layout.items()}

    got = (cache["c"], cache["k_rope"]) if latent else kv_cache.read_kv_cache(cache, jnp.bfloat16)
    slot_axis = 1 if latent else 2
    for index, rows in written.items():
        for g, want in zip(got, rows):
            g = jax.lax.slice_in_dim(g, index, index + want.shape[slot_axis], axis=slot_axis)
            np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(want, np.float32), atol=tol * 4, rtol=tol)
    for g in got:  # nothing past the sixth slot was touched
        assert not np.asarray(jax.lax.slice_in_dim(g, 6, S, axis=slot_axis), np.float32).any()


def test_a_model_states_its_layout_and_the_gauge_reads_it():
    """``TransformerConfig.cache_layout`` is what ``init_cache`` builds (prompt
    tuning's virtual rows included) and what ``mla/cache_bytes_per_token`` counts:
    1,152 bytes a layer for kimi-vl-a3b's latent, 5,760 over the cell's five layers."""
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.utils.metrics import gauges

    gpt2 = PRESETS["gpt2"].replace(num_layers=2, peft_type="prompt", num_virtual_tokens=3)
    cache = TransformerLM(gpt2).init_cache(4, 10, jnp.bfloat16)
    assert cache["k"][1].shape == gpt2.cache_layout(4, 10)["k"][0] == (4, 12, 13, 64)
    mla = PRESETS["gpt2"].replace(
        num_layers=5, hidden_size=2048, num_heads=16, attention_kind="mla", kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, compute_dtype=jnp.bfloat16)
    assert kv_cache.bytes_per_token(mla.cache_layout(1, 8)) == 1152
    TransformerLM(mla).init_cache(1, 8)
    assert gauges.get("mla/cache_bytes_per_token") == 5 * 1152
