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
    # three kv heads beside each row: six rows of one head, a token's bytes what they were
    "per-head-bfloat16-folded": (lambda: kv_cache.kv_cache_layout((B, HKV, S, D), jnp.bfloat16, False, fold=HKV),
                                 2 * HKV * D * 2, 0.0),
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
    assert kv_cache.bytes_per_token(layout, B) == token_bytes

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

    got = (cache["c"], cache["k_rope"]) if latent else kv_cache.read_kv_cache(cache, jnp.bfloat16, B)
    slot_axis = 1 if latent else 2
    for index, rows in written.items():
        for g, want in zip(got, rows):
            g = jax.lax.slice_in_dim(g, index, index + want.shape[slot_axis], axis=slot_axis)
            np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(want, np.float32), atol=tol * 4, rtol=tol)
    for g in got:  # nothing past the sixth slot was touched
        assert not np.asarray(jax.lax.slice_in_dim(g, 6, S, axis=slot_axis), np.float32).any()


@pytest.mark.parametrize("fold", [1, 2, 4])
def test_folded_rows_stand_where_the_layout_says(fold):
    """Row ``b * fold + i`` of a folded array holds row ``b``'s kv head ``hg * fold + i`` at head
    position ``hg``, a shard of the rows keeps whole rows, and unfolding gives the array back."""
    rows, heads, T = 3, 4, 5
    x = jnp.arange(rows * heads * T * D, dtype=jnp.float32).reshape(rows, heads, T, D)
    folded = kv_cache.fold_heads(x, fold)
    assert folded.shape == (rows * fold, heads // fold, T, D)
    for b in range(rows):
        for hg in range(heads // fold):
            for i in range(fold):
                np.testing.assert_array_equal(np.asarray(folded[b * fold + i, hg]), np.asarray(x[b, hg * fold + i]))
    np.testing.assert_array_equal(np.asarray(kv_cache.unfold_heads(folded, fold)), np.asarray(x))
    np.testing.assert_array_equal(  # the first row's shard, folded by itself
        np.asarray(folded[:fold]), np.asarray(kv_cache.fold_heads(x[:1], fold)))


@pytest.mark.parametrize("write", ["prefill", "one-slot-at-a-traced-index"])
@pytest.mark.parametrize("fold", [1, 2, 4])
def test_fold_then_unfold_of_a_write_and_a_read_is_the_identity(fold, write):
    """What ``write_kv_cache`` folds into a cache of ``fold`` kv heads a row, ``read_kv_cache``
    hands back as ``[B, Hkv, S, D]``: the same arrays as through an unfolded cache, bit for bit,
    for a prefill from slot 0 and for a decode step's one slot at an index the loop traces."""
    rows, heads = 2, 4
    rng = np.random.default_rng(fold)
    T = 6 if write == "prefill" else 1
    k, v = (jnp.asarray(rng.normal(size=(rows, heads, T, D)), jnp.bfloat16) for _ in range(2))

    def through(fold, index):
        layout = kv_cache.kv_cache_layout((rows, heads, S, D), jnp.bfloat16, False, fold)
        assert layout["k"][0] == (rows * fold, heads // fold, S, D)
        assert kv_cache.bytes_per_token(layout, rows) == 2 * heads * D * 2
        cache = {key: jnp.ones(shape, dtype) for key, (shape, dtype) in layout.items()}

        def write_then_read(cache, index):
            return kv_cache.read_kv_cache(kv_cache.write_kv_cache(cache, k, v, index), jnp.bfloat16, rows)

        if write == "prefill":
            return write_then_read(cache, index)
        return jax.jit(write_then_read)(cache, jnp.int32(index))

    index = 0 if write == "prefill" else 7
    got, want = through(fold, index), through(1, index)
    for g, w, x in zip(got, want, (k, v)):
        assert g.shape == (rows, heads, S, D)
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
        np.testing.assert_array_equal(np.asarray(g[:, :, index:index + T], np.float32), np.asarray(x, np.float32))


@pytest.mark.parametrize(
    "overrides,axes,batch,want",
    [
        (dict(attention_impl="flash"), None, 64, (128, 8)),  # gpt2-medium's cell: 2 kv heads beside each of 64 rows
        (dict(attention_impl="flash"), None, 128, (128, 16)),  # the lanes are full: as it was
        (dict(attention_impl="flash"), None, 96, (96, 16)),  # no second head fits beside 96 rows
        (dict(attention_impl="flash", num_kv_heads=8), None, 16, (128, 1)),  # grouped: all 8 kv heads beside 16 rows
        (dict(attention_impl="flash", num_kv_heads=8), None, 8, (64, 1)),  # the kv heads cap the fold
        (dict(attention_impl="xla"), None, 64, (64, 16)),  # the einsum reads rows as the model forms them
        (dict(attention_impl="flash", kv_cache_quant=True), None, 64, (64, 16)),
        (dict(attention_impl="flash", pos_embedding="alibi"), None, 64, (64, 16)),
        (dict(attention_impl="flash", peft_type="prefix", num_virtual_tokens=4), None, 64, (64, 16)),
        (dict(attention_impl="flash"), dict(data=4, model=2), 64, (512, 2)),  # a shard: 16 rows of 8 kv heads, 8 a row
        (dict(attention_impl="flash"), dict(data=1, model=8), 60, (120, 8)),  # a shard's 2 kv heads beside 60 rows
        (dict(attention_impl="flash"), dict(data=8, model=1), 60, (60, 16)),  # 60 rows over 8: the einsum, unfolded
    ],
)
def test_a_model_folds_its_cache_where_the_decode_kernel_will_read_it(overrides, axes, batch, want):
    """``TransformerConfig.cache_layout`` takes the fold from ``ops.attention.decode_cache_fold``: a
    function of the shapes, the attention path and the ambient mesh, visible in the layout's shapes alone;
    a token's bytes, the slots visited and who takes the kernel do not change with it."""
    import contextlib

    from trlx_tpu.ops import attention
    from trlx_tpu.parallel.mesh import make_mesh

    config = PRESETS["gpt2"].replace(hidden_size=1024, num_heads=16, num_layers=2, compute_dtype=jnp.bfloat16,
                                     **overrides)
    with make_mesh(**axes) if axes else contextlib.nullcontext():
        layout = config.cache_layout(batch, 576)
        rows, held = want
        assert layout["k"][0] == (rows, held, 576, 64)
        assert kv_cache.bytes_per_token(layout, batch) == kv_cache.bytes_per_token(
            kv_cache.kv_cache_layout((batch, config.kv_heads, 576, 64), config.compute_dtype, config.kv_cache_quant),
            batch)
        layer = {key: jax.ShapeDtypeStruct(shape, dtype) for key, (shape, dtype) in layout.items()}
        placed = attention.decode_kernel_placement(
            config.attention_impl, config.biased_attention, layer, config.num_heads, batch)[0]
        assert placed or rows == batch  # nothing is folded for the einsum



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
    assert kv_cache.bytes_per_token(mla.cache_layout(1, 8), 1) == 1152
    TransformerLM(mla).init_cache(1, 8)
    assert gauges.get("mla/cache_bytes_per_token") == 5 * 1152
