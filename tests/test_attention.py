"""Flash-attention and decode kernel tests (interpret mode on CPU) against the XLA reference."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import trlx_tpu.ops.attention as attn
from trlx_tpu.ops.attention import choose_tiles, flash_attention


def _heads_first(x):
    """``[B, T, H, D]``, the kernels' layout (a model's projections'), to the plain
    reference's ``[B, H, T, D]``; and back."""
    return x.transpose(0, 2, 1, 3)


def xla_attention(q, k, v, kv_valid, causal, scale):
    """The plain reference on operands in the kernels' layout."""
    return _heads_first(attn.xla_attention(*map(_heads_first, (q, k, v)), kv_valid, causal, scale))


def make_inputs(B=2, H=2, T=64, S=64, D=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(causal):
    q, k, v = make_inputs()
    kv_valid = jnp.ones((2, 64), jnp.int32)
    out = flash_attention(q, k, v, kv_valid, causal, None, True)
    ref = xla_attention(q, k, v, kv_valid, causal, 1.0 / 4.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5)


def test_flash_respects_padding_mask():
    q, k, v = make_inputs(seed=1)
    kv_valid = np.ones((2, 64), np.int32)
    kv_valid[0, :16] = 0  # left padding on sample 0
    kv_valid = jnp.asarray(kv_valid)
    out = flash_attention(q, k, v, kv_valid, True, None, True)
    ref = xla_attention(q, k, v, kv_valid, True, 1.0 / 4.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5)


def test_flash_gradients_match_xla():
    q, k, v = make_inputs(B=1, H=1, T=32, S=32, D=8, seed=2)
    kv_valid = jnp.ones((1, 32), jnp.int32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_valid, True, None, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, kv_valid, True, 1.0 / np.sqrt(8)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,S", [(24, 24), (144, 144), (10, 10), (72, 136)])
def test_flash_non_block_multiple_shapes(T, S):
    """The kernel's blocks overhang ragged T/S, so mixed P+R shapes (e.g.
    16+128=144) and odd prefill lengths take the flash path."""
    q, k, v = make_inputs(T=T, S=S, seed=3)
    kv_valid = np.ones((2, S), np.int32)
    kv_valid[0, : S // 4] = 0
    kv_valid = jnp.asarray(kv_valid)
    out = flash_attention(q, k, v, kv_valid, False, None, True)
    ref = xla_attention(q, k, v, kv_valid, False, 1.0 / 4.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5)


def test_flash_prefill_generation_matches_xla():
    """Greedy generation with attention_impl=flash (prefill via the kernel) must
    produce the same tokens as the XLA path."""
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.ops.generation import generate

    base = PRESETS["gpt2"].replace(
        vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 12), 2, 32)  # 12: not a block multiple
    mask = np.ones((2, 12), np.int32)
    mask[0, :5] = 0
    mask = jnp.asarray(mask)
    params = TransformerLM(base).init(rng, ids, mask)["params"]

    outs = {}
    for impl in ("xla", "flash"):
        model = TransformerLM(base.replace(attention_impl=impl))

        def step(params, t_ids, t_mask, positions, cache):
            logits, hidden, _, cache = model.apply(
                {"params": params}, t_ids, t_mask, positions, cache
            )
            return logits, hidden, cache

        outs[impl] = generate(
            step, params, lambda b, s: model.init_cache(b, s, jnp.float32),
            ids, mask, jax.random.PRNGKey(7), max_new_tokens=6,
            eos_token_id=None, pad_token_id=0, do_sample=False,
        )
    np.testing.assert_array_equal(
        np.asarray(outs["xla"]["sequences"]), np.asarray(outs["flash"]["sequences"])
    )


def test_model_flash_matches_xla_attention():
    """Full TransformerLM forward with attention_impl=flash equals the XLA path."""
    import jax
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM

    base = PRESETS["gpt2"].replace(
        vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 16), 1, 32)
    mask = np.ones((2, 16), np.int32)
    mask[0, :5] = 0  # left padding
    mask = jnp.asarray(mask)

    model_xla = TransformerLM(base)
    params = model_xla.init(rng, ids, mask)["params"]
    logits_xla, *_ = model_xla.apply({"params": params}, ids, mask)

    model_flash = TransformerLM(base.replace(attention_impl="flash"))
    logits_flash, *_ = model_flash.apply({"params": params}, ids, mask)
    valid = np.asarray(mask)[:, :, None]
    np.testing.assert_allclose(
        np.asarray(logits_flash) * valid, np.asarray(logits_xla) * valid, atol=2e-4, rtol=1e-4
    )


def test_gqa_decode_generation_matches_xla():
    """Greedy generation parity flash-vs-xla on a GQA config (kv_heads < heads):
    covers the GQA head-grouping over the [B,Hkv,S,D] cache on both paths."""
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.ops.generation import generate

    base = PRESETS["llama"].replace(
        vocab_size=32, hidden_size=16, num_layers=2, num_heads=4, num_kv_heads=2,
        intermediate_size=32, max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 7), 2, 32)
    mask = np.ones((2, 7), np.int32)
    mask[1, :3] = 0
    mask = jnp.asarray(mask)
    params = TransformerLM(base).init(rng, ids, mask)["params"]

    outs = {}
    for impl in ("xla", "flash"):
        model = TransformerLM(base.replace(attention_impl=impl))

        def step(params, t_ids, t_mask, positions, cache):
            logits, hidden, _, cache = model.apply(
                {"params": params}, t_ids, t_mask, positions, cache
            )
            return logits, hidden, cache

        outs[impl] = generate(
            step, params, lambda b, s: model.init_cache(b, s, jnp.float32),
            ids, mask, jax.random.PRNGKey(7), max_new_tokens=5,
            eos_token_id=None, pad_token_id=0, do_sample=False,
        )
    np.testing.assert_array_equal(
        np.asarray(outs["xla"]["sequences"]), np.asarray(outs["flash"]["sequences"])
    )


def make_gqa_inputs(B=2, H=4, Hkv=2, T=48, S=48, D=16, seed=5):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    return q, k, v


def test_flash_gqa_kernel_matches_xla():
    """The kernel consumes grouped K/V directly (no repeat): query head h reads
    kv head h // (H/Hkv) via the BlockSpec index map."""
    q, k, v = make_gqa_inputs()
    kv_valid = np.ones((2, 48), np.int32)
    kv_valid[1, :9] = 0
    kv_valid = jnp.asarray(kv_valid)
    out = flash_attention(q, k, v, kv_valid, True, None, True)
    ref = xla_attention(q, k, v, kv_valid, True, 1.0 / 4.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize(
    "B,H,Hkv,T,S,maskfrac",
    [
        (2, 2, 2, 64, 64, 0.0),
        (2, 2, 2, 40, 72, 0.25),  # ragged: the blocks overhang both T and S
        (1, 4, 2, 48, 48, 0.3),  # GQA: dk/dv sum over the query-head group
        (2, 4, 1, 33, 62, 0.2),  # MQA + ragged
    ],
)
def test_pallas_backward_matches_xla_backward(B, H, Hkv, T, S, maskfrac):
    """Grad parity: the Pallas dq/dkv kernels against the gradients of the plain
    XLA reference under the forward's cotangent, including left-padding masks,
    non-block-multiple shapes, and grouped heads."""
    q, k, v = make_gqa_inputs(B=B, H=H, Hkv=Hkv, T=T, S=S, seed=7)
    kv_valid = np.ones((B, S), np.int32)
    kv_valid[0, : int(S * maskfrac)] = 0
    kv_valid = jnp.asarray(kv_valid)

    out, flash_vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, kv_valid, True, None, True), q, k, v)
    # non-uniform cotangent exercises dO properly: that of sum(out * w) + sum(out ** 2)
    g = jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape) / out.size + 2 * out
    scale = 1.0 / np.sqrt(q.shape[-1])
    _, xla_vjp = jax.vjp(lambda q, k, v: xla_attention(q, k, v, kv_valid, True, scale), q, k, v)
    for a, b, name in zip(flash_vjp(g), xla_vjp(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-4, err_msg=f"d{name}"
        )


def test_pallas_backward_fully_masked_row_is_zero():
    """Rows with no valid keys (lse == -inf) must produce zero grads, not NaN."""
    q, k, v = make_gqa_inputs(B=1, H=2, Hkv=2, T=16, S=16, seed=9)
    kv_valid = jnp.zeros((1, 16), jnp.int32)  # everything masked

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_valid, True, None, True) ** 2)

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), 0.0, atol=1e-6)


def test_model_gqa_grouped_einsum_matches_repeat():
    """Full model forward on a GQA config: the grouped-einsum XLA path must match
    an explicit repeat-to-full-heads reference."""
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM

    base = PRESETS["llama"].replace(
        vocab_size=32, hidden_size=16, num_layers=2, num_heads=4, num_kv_heads=2,
        intermediate_size=32, max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 16), 1, 32)
    mask = np.ones((2, 16), np.int32)
    mask[0, :4] = 0
    mask = jnp.asarray(mask)
    model = TransformerLM(base)
    params = model.init(rng, ids, mask)["params"]
    logits, *_ = model.apply({"params": params}, ids, mask)

    # reference: same params, kv heads materialized at full count by repeating
    # the k/v projection kernels along the head axis
    import flax
    full = flax.core.unfreeze(params)
    import jax.numpy as jnp_

    def widen(leaf_name):
        for lname, layer in full.items():
            if not lname.startswith("layers_"):
                continue
            proj = layer["attn"][leaf_name]
            kern = proj["kernel"]  # [hid, Hkv*D]
            D = base.hidden_size // base.num_heads
            kern = kern.reshape(kern.shape[0], 2, D)
            kern = jnp_.repeat(kern, 2, axis=1).reshape(kern.shape[0], 4 * D)
            proj["kernel"] = kern
            if "bias" in proj:
                b = proj["bias"].reshape(2, D)
                proj["bias"] = jnp_.repeat(b, 2, axis=0).reshape(4 * D)

    widen("k_proj")
    widen("v_proj")
    model_full = TransformerLM(base.replace(num_kv_heads=4))
    logits_full, *_ = model_full.apply({"params": full}, ids, mask)
    valid = np.asarray(mask)[:, :, None]
    np.testing.assert_allclose(
        np.asarray(logits) * valid, np.asarray(logits_full) * valid, atol=2e-4, rtol=1e-4
    )


# ------------------------------------------------------------ the tile chooser

CELL_LENGTHS = (64, 512, 513, 576, 640)  # prefill, learner and scoring lengths of the benchmark's cells
# (T, S, D, rep, dtype, H): H None where a model has heads enough for any count a program may want
CHOOSER_SHAPES = [
    (T, T, 64, 1, jnp.bfloat16, None) for T in CELL_LENGTHS + (8, 96, 104, 130, 1024, 1100, 4096)
] + [
    (T, T, 128, rep, jnp.bfloat16, None) for T in (2048, 8192, 131072) for rep in (1, 4, 16)
] + [(72, 136, 16, 1, jnp.float32, 2), (33, 62, 16, 4, jnp.float32, 4), (513, 513, 128, 8, jnp.float32, None)]


@pytest.mark.parametrize("T,S,D,rep,dtype,H", CHOOSER_SHAPES)
def test_chooser_tiles_fit_the_chip(T, S, D, rep, dtype, H):
    """Lane side in multiples of 128, rows in the dtype's sublane multiple,
    overhang only up to the tile, a program's heads whole lane tiles of its
    blocks or all there are, the reckoned VMEM under the budget."""
    tiles = choose_tiles(T, S, D, rep, dtype, H=H)
    sublane = 32 // jnp.dtype(dtype).itemsize
    assert tiles.block_q % 128 == 0 and tiles.block_k % 128 == 0
    assert tiles.block_q not in (96, 104) and tiles.block_k not in (96, 104)
    assert (tiles.T, tiles.S) == (T, S)
    assert tiles.Tp % tiles.block_q == 0 and tiles.Sp % tiles.block_k == 0
    assert T <= tiles.Tp < T + tiles.block_q and S <= tiles.Sp < S + tiles.block_k
    assert tiles.Tr % sublane == 0 and T <= tiles.Tr <= tiles.Tp
    assert tiles.Sr % sublane == 0 and S <= tiles.Sr <= tiles.Sp
    assert tiles.sub in (128, 256, 512) and max(tiles.block_q, tiles.block_k) <= 8 * tiles.sub
    least = 128 // np.gcd(128, D)  # heads to a whole lane tile
    assert 1 <= tiles.heads <= 8 and (tiles.whole or tiles.heads == (H or least))
    kv_heads = max(1, tiles.heads // rep)
    assert tiles.heads == H or ((tiles.heads * D) % 128 == 0 and (kv_heads * D) % 128 == 0)
    assert 0 < tiles.vmem_bytes <= 12 * 2**20
    assert tiles == choose_tiles(T, S, D, rep, dtype, H=H)  # a pure function of the shape


@pytest.mark.parametrize("T", CELL_LENGTHS)
def test_chooser_takes_the_cells_sequences_whole(T):
    tiles = choose_tiles(T, T, 64, 1, jnp.bfloat16)
    assert tiles.whole and tiles.Tp == -(-T // 128) * 128
    assert tiles.heads >= 2  # several heads to a program: the grid is about (B, H / heads)


@pytest.mark.parametrize("rep", [1, 4, 16])
@pytest.mark.parametrize("T", [2048, 8192])
def test_chooser_walks_long_sequences_in_large_tiles(T, rep):
    tiles = choose_tiles(T, T, 128, rep, jnp.bfloat16)
    assert not tiles.whole and tiles.heads == 1
    assert 128 <= tiles.block_q <= 512 and 128 <= tiles.block_k <= 512
    assert tiles.Tp == T and tiles.Sp == T  # no overhang where the tile divides the length


def test_chooser_budget_decides_whole_or_walked():
    assert choose_tiles(513, 513, 64, 1, jnp.bfloat16).whole
    small = choose_tiles(513, 513, 64, 1, jnp.bfloat16, vmem_budget=2 * 2**20)
    assert not small.whole and small.vmem_bytes <= 2 * 2**20
    with pytest.raises(ValueError, match="no flash-attention tiling"):
        choose_tiles(513, 513, 64, 1, jnp.bfloat16, vmem_budget=2**16)


@pytest.mark.parametrize(
    "H,rep,most,want",
    [(12, 1, 4, 4), (12, 1, 8, 6), (16, 1, 8, 8), (16, 4, 8, 8), (16, 4, 6, 4), (16, 16, 4, 4), (48, 48, 8, 8),
     (7, 1, 4, 1)],
)
def test_heads_per_program_divides_heads_and_groups(H, rep, most, want):
    assert attn._heads_per_program(H, rep, most) == want


@pytest.mark.parametrize(
    "H,rep,most,lane,want",
    [
        (12, 1, 4, 2, 4), (12, 1, 8, 2, 6), (16, 1, 8, 2, 8),  # D = 64: an even number of heads
        (16, 4, 8, 2, 8),  # two kv heads to the program's eight query heads
        (16, 4, 6, 2, 8),  # four query heads would bring one kv head, half a lane tile: the fewest that may
        (7, 1, 4, 2, 7), (25, 1, 8, 2, 25),  # an odd head count: all the heads, the whole extent
        (4, 4, 8, 2, 4), (16, 16, 4, 2, 4),  # multi-query: the one kv head the whole extent, a part of the group even
        (16, 16, 4, 1, 4), (12, 1, 8, 1, 6),  # D = 128: any count
        (3, 1, 8, 16, 3), (4, 2, 8, 8, 4),  # the tests' small widths: all the heads
    ],
)
def test_heads_per_program_keeps_the_lane_rule(H, rep, most, lane, want):
    """A block's last dimension is a multiple of 128 or the array's whole
    extent: the program's query heads, and their kv heads, are a multiple of
    ``lane`` heads or all of them."""
    got = attn._heads_per_program(H, rep, most, lane)
    assert got == want
    kv_heads, kv_total = max(1, got // rep), H // rep
    assert H % got == 0 and (got % lane == 0 or got == H) and (kv_heads % lane == 0 or kv_heads == kv_total)


@pytest.mark.parametrize(
    "T,D,Dv,H,rep,lane,heads",
    [
        (513, 64, 64, 12, 1, 2, 4), (513, 64, 64, 16, 1, 2, 4), (64, 64, 64, 12, 1, 2, 6),  # the gpt2 cells
        (513, 192, 128, 16, 1, 2, 2),  # kimi-vl-a3b: 2 x 192 = 384 lanes
        (257, 128, 128, 16, 1, 1, 8),  # ouro-2.6b
        (513, 64, 64, 25, 1, 2, 25),  # gpt2-xl's 25 heads of 64: no even count divides them
        (513, 64, 64, 32, 4, 2, 8),  # grouped at D = 64: eight query heads bring two kv heads
        (4096, 64, 64, 16, 1, 2, 2),  # walked: the fewest heads the lanes allow
        (2048, 128, 128, 16, 4, 1, 1),
    ],
)
def test_chooser_learns_the_lane_rule_from_the_shape(T, D, Dv, H, rep, lane, heads):
    assert attn._lane_heads(D, Dv) == lane
    tiles = choose_tiles(T, T, D, rep, jnp.bfloat16, Dv=Dv, H=H)
    assert tiles.heads == heads and tiles.heads in attn._head_counts(H, rep, lane)
    assert tiles.vmem_bytes <= 12 * 2**20


# ------------------------------------- parity on both sides of the chooser's threshold


def _walked(T, S, D, rep, dtype, Dv=None, H=None):
    """Tiles of 128 walked over the grid, as a sequence too long for VMEM gets:
    the chooser with its budget shrunk to the least that still holds a tile."""
    for mib in (1, 1.5, 2, 3, 4, 6):
        try:
            tiles = choose_tiles(T, S, D, rep, dtype, vmem_budget=int(mib * 2**20), Dv=Dv, H=H)
        except ValueError:
            continue
        assert not tiles.whole and min(tiles.block_q, tiles.block_k) == 128
        return tiles
    raise AssertionError("no budget tried gives walked tiles")


PARITY_CASES = {
    # name: (B, H, Hkv, T, S, dtype, masking)
    "mha-left-padded": (2, 2, 2, 200, 200, jnp.float32, "left"),
    "ragged-T-S": (2, 2, 2, 140, 272, jnp.float32, "left"),
    "fully-masked-sample": (2, 2, 2, 160, 160, jnp.float32, "none-valid"),
    "gqa": (1, 4, 2, 150, 150, jnp.float32, "left"),
    "mqa": (2, 4, 1, 133, 262, jnp.float32, "left"),
    "bf16": (2, 2, 2, 200, 200, jnp.bfloat16, "left"),
    "bf16-gqa": (1, 4, 2, 150, 150, jnp.bfloat16, "left"),
    # keys 24 wide, values 16 (latent attention's 192 / 128 in small): a trailing (D, Dv)
    "value-width-apart": (2, 4, 4, 150, 150, jnp.float32, "left", 24, 16),
    "value-width-apart-bf16": (2, 4, 4, 140, 272, jnp.bfloat16, "left", 24, 16),
}


@pytest.mark.parametrize("regime", ["whole", "walked"])
@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_forward_and_gradient_match_xla_in_both_regimes(case, regime):
    """Forward and all three gradients against ``xla_attention`` on the same
    inputs, with the chooser's own tiles (the whole sequence in one program) and
    with tiles of 128 walked over the grid."""
    B, H, Hkv, T, S, dtype, masking = PARITY_CASES[case][:7]
    D, Dv = PARITY_CASES[case][7:] or (16, 16)
    rng = np.random.default_rng(11)
    q, k = (jnp.asarray(rng.normal(size=(B, n, heads, D)), dtype) for heads, n in ((H, T), (Hkv, S)))
    g, v = (jnp.asarray(rng.normal(size=(B, n, heads, Dv)), dtype) for heads, n in ((H, T), (Hkv, S)))
    kv_valid = np.ones((B, S), np.int32)
    kv_valid[0, : S // 3] = 0  # left padding: sample 0's first queries see no key at all
    if masking == "none-valid":
        kv_valid[-1, :] = 0
    kv_valid = jnp.asarray(kv_valid)
    scale = D ** -0.5
    rep = H // Hkv
    tiles = choose_tiles(T, S, D, rep, dtype, Dv=Dv, H=H) if regime == "whole" else _walked(T, S, D, rep, dtype, Dv, H)
    assert tiles.whole == (regime == "whole")

    out, lse = attn._flash_forward(q, k, v, kv_valid, True, scale, True, with_lse=True, tiles=tiles)
    assert lse.shape == (B, H, 1, tiles.Tp) and lse.dtype == jnp.float32  # T on the lanes, once
    got = (out,) + attn._flash_backward(q, k, v, kv_valid, out, lse, g, True, scale, True, tiles=tiles)
    want_out, vjp = jax.vjp(lambda q, k, v: xla_attention(q, k, v, kv_valid, True, scale), q, k, v)
    want = (want_out,) + vjp(g)
    tol = dict(atol=2e-4, rtol=2e-4) if dtype == jnp.float32 else dict(atol=6e-2, rtol=3e-2)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.dtype == b.dtype == dtype, name  # the backward writes the dtype of its inputs
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, err_msg=name, **tol)
    if masking == "none-valid":
        for a in got:
            np.testing.assert_array_equal(np.asarray(a[-1], np.float32), 0.0)


def _operands(B, T, S, H, Hkv, D, Dv, dtype, seed=17):
    """q, k, v, dO in the kernels' layout and a left-padded key mask."""
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(B, n, heads, D)), dtype) for heads, n in ((H, T), (Hkv, S)))
    g, v = (jnp.asarray(rng.normal(size=(B, n, heads, Dv)), dtype) for heads, n in ((H, T), (Hkv, S)))
    kv_valid = np.ones((B, S), np.int32)
    kv_valid[0, : S // 5] = 0
    return q, k, v, g, jnp.asarray(kv_valid)


def _assert_forward_and_gradients_match(q, k, v, g, kv_valid, got, dtype, causal=True):
    scale = q.shape[-1] ** -0.5
    want_out, vjp = jax.vjp(lambda q, k, v: xla_attention(q, k, v, kv_valid, causal, scale), q, k, v)
    tol = dict(atol=2e-4, rtol=2e-4) if dtype == jnp.float32 else dict(atol=6e-2, rtol=3e-2)
    for a, b, name in zip(got, (want_out,) + vjp(g), ("out", "dq", "dk", "dv")):
        assert a.shape == b.shape and a.dtype == dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, err_msg=name, **tol)


@pytest.mark.parametrize("regime", ["whole", "walked"])
@pytest.mark.parametrize(
    "T,S,H,Hkv,D,Dv",
    [(140, 140, 2, 2, 16, 16), (33, 190, 4, 2, 16, 16), (130, 72, 4, 1, 24, 16), (200, 200, 2, 2, 64, 64)],
)
def test_nothing_past_the_operands_end_reaches_an_output(T, S, H, Hkv, D, Dv, regime):
    """Ragged T and S, the blocks overhanging both: q, k, v and dO are sliced,
    under jit, from buffers with rows to spare that hold NaN past the end, so
    whatever an overhanging block finds there (on the chip the buffer's own
    rows, NaN in interpret mode either way) is NaN. The forward and all three
    gradients are finite and equal the plain reference's on the clean rows."""
    dtype = jnp.float32
    q, k, v, g, kv_valid = _operands(2, T, S, H, Hkv, D, Dv, dtype)
    rep = H // Hkv
    tiles = choose_tiles(T, S, D, rep, dtype, Dv=Dv, H=H) if regime == "whole" else _walked(T, S, D, rep, dtype, Dv, H)
    assert tiles.whole == (regime == "whole") and tiles.Tp > T and tiles.Sp > S

    def with_rows_to_spare(x, spare=200):
        return jnp.concatenate([x, jnp.full((x.shape[0], spare) + x.shape[2:], jnp.nan, x.dtype)], axis=1)

    @jax.jit
    def run(q_buf, k_buf, v_buf, g_buf):
        q, k, v, g = q_buf[:, :T], k_buf[:, :S], v_buf[:, :S], g_buf[:, :T]
        scale = D ** -0.5
        out, lse = attn._flash_forward(q, k, v, kv_valid, True, scale, True, with_lse=True, tiles=tiles)
        return (out, lse) + attn._flash_backward(q, k, v, kv_valid, out, lse, g, True, scale, True, tiles=tiles)

    out, lse, dq, dk, dv = run(*map(with_rows_to_spare, (q, k, v, g)))
    _assert_forward_and_gradients_match(q, k, v, g, kv_valid, (out, dq, dk, dv), dtype)
    # the row statistics are as long as the tiles: past the data they hold the constant the backward expects
    assert np.all(np.isfinite(np.asarray(lse)))
    if regime == "whole":  # rows past the data are not computed at all
        np.testing.assert_array_equal(np.asarray(lse[..., tiles.Tr:]), np.float32(attn.NEG_INF))


@pytest.mark.parametrize("D,Dv", [(64, 64), (128, 128), (192, 128)])
@pytest.mark.parametrize("T", [513, 257, 65])
def test_the_cells_ragged_lengths_at_their_widths(T, D, Dv):
    """The learner's lengths (the store's 513, ouro's 257, a short 65) at the
    cells' head widths, two heads as the lane rule wants at D = 64 and 192:
    forward and the three gradients through the public function."""
    dtype = jnp.float32
    q, k, v, g, kv_valid = _operands(1, T, T, 2, 2, D, Dv, dtype, seed=T + D)
    tiles = choose_tiles(T, T, D, 1, dtype, Dv=Dv, H=2)
    assert tiles.Tp - T == -T % tiles.block_q > 0 and (tiles.heads * D) % 128 == 0
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, kv_valid, True, None, True), q, k, v)
    _assert_forward_and_gradients_match(q, k, v, g, kv_valid, (out,) + vjp(g), dtype)


@pytest.mark.parametrize(
    "H,Hkv,D,heads",
    [(3, 3, 64, 3), (5, 5, 64, 5), (6, 3, 64, 6), (3, 1, 64, 3), (6, 6, 64, 6), (3, 3, 128, 3), (5, 1, 128, 5)],
)
def test_head_counts_the_lane_rule_cannot_halve(H, Hkv, D, heads):
    """Odd head counts at D = 64: no even number of heads divides them, so a
    program takes them all (the whole extent of the last dimension) — also
    where the kv heads are odd though the query heads are not. At D = 128 every
    count is whole lane tiles."""
    dtype = jnp.float32
    T = 72
    tiles = choose_tiles(T, T, D, H // Hkv, dtype, H=H)
    assert tiles.heads == heads
    q, k, v, g, kv_valid = _operands(2, T, T, H, Hkv, D, D, dtype, seed=H)
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, kv_valid, True, None, True), q, k, v)
    _assert_forward_and_gradients_match(q, k, v, g, kv_valid, (out,) + vjp(g), dtype)


@pytest.mark.parametrize("regime", ["whole", "walked"])
@pytest.mark.parametrize("T,S,H,Hkv,D,Dv,dtype", [
    (140, 140, 2, 2, 16, 16, jnp.float32), (150, 150, 4, 2, 24, 16, jnp.float32),
    (200, 200, 2, 2, 16, 16, jnp.bfloat16),
])
def test_delta_is_the_row_sum_of_dO_times_O(T, S, H, Hkv, D, Dv, dtype, regime):
    """``delta`` as the dq kernel forms it and the dkv kernel reads it: float32
    ``sum(dO * O)`` over the value width, a ``[B, H, 1, Tp]`` row, 0 past the data."""
    q, k, v, g, kv_valid = _operands(2, T, S, H, Hkv, D, Dv, dtype)
    rep, scale = H // Hkv, D ** -0.5
    tiles = choose_tiles(T, S, D, rep, dtype, Dv=Dv, H=H) if regime == "whole" else _walked(T, S, D, rep, dtype, Dv, H)
    out, lse = attn._flash_forward(q, k, v, kv_valid, True, scale, True, with_lse=True, tiles=tiles)
    _, delta = attn._flash_dq(q, k, v, kv_valid, out, lse, g, True, scale, True, tiles)
    assert delta.shape == (2, H, 1, tiles.Tp) and delta.dtype == jnp.float32
    want = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).transpose(0, 2, 1)  # [B, H, T]
    np.testing.assert_allclose(np.asarray(delta[:, :, 0, :T]), np.asarray(want), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(delta[:, :, 0, T:]), 0.0)


def test_chooser_choice_is_logged_once_per_shape(caplog):
    import logging

    q, k, v = make_inputs(B=1, H=3, T=40, S=40, D=8, seed=4)  # [1, 40, 3, 8]
    kv_valid = jnp.ones((1, 40), jnp.int32)
    attn._log_tiles.cache_clear()
    root = logging.getLogger("trlx_tpu")
    root.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="trlx_tpu"):
            for _ in range(2):
                jax.grad(lambda q: flash_attention(q, k, v, kv_valid, True, None, True).sum())(q)
    finally:
        root.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records if "flash attention q[1,40,3,8]" in r.getMessage()]
    assert len(lines) == 1, lines
    assert "tiles 128x128" in lines[0] and "3 head(s) a program" in lines[0] and "grid (1, 1, 1, 1)" in lines[0]
    # what crosses the kernels' boundary: the operands' layout, the rows that overhang, where delta is formed
    assert "blocks (1, rows, heads*D)" in lines[0] and "overhanging by 88x88 rows" in lines[0]
    assert "delta formed in the dq kernel" in lines[0]


# ------------------------------------------------------------ the decode kernel


def einsum_decode(q, k, v, mask_bias, scale):
    """The model's einsum path for a single-token step (grouped form): float32
    softmax over the whole cache behind an additive mask, the probabilities cast
    to the operands' dtype before they meet v."""
    B, H, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, 1, Hkv, H // Hkv, D)
    scores = jnp.einsum("btkrd,bksd->bkrts", qg, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(scores + mask_bias[:, :, None], axis=-1).astype(q.dtype)
    return jnp.einsum("bkrts,bksd->btkrd", probs, v).reshape(B, H, D)


# name: (B, H, Hkv, S, D, dtype, block or None for the chooser's)
DECODE_CASES = {
    "mha-float32": (3, 2, 2, 40, 16, jnp.float32, None),
    "grouped-float32": (2, 8, 2, 48, 16, jnp.float32, 16),
    "multi-query-bfloat16": (4, 4, 1, 64, 16, jnp.bfloat16, 8),
    "mha-bfloat16-576": (2, 2, 2, 576, 16, jnp.bfloat16, None),
    "mha-float32-block-does-not-divide": (2, 2, 2, 41, 8, jnp.float32, 8),
}


def _decode_tiles(case):
    B, H, Hkv, S, D, dtype, block = DECODE_CASES[case]
    tiles = attn.choose_decode_tiles(B, Hkv, H // Hkv, S, D, dtype)
    return tiles if block is None else tiles._replace(block=block)


@functools.lru_cache(maxsize=None)
def _decode_fns(case):
    """(kernel, einsum) for a case, jitted over a traced index as the decode loop has it."""
    B, H, Hkv, S, D, dtype, _ = DECODE_CASES[case]
    tiles = _decode_tiles(case)
    kernel = jax.jit(lambda q, k, v, bias, index: attn.decode_attention(q, k, v, bias, index, None, True, tiles))
    return kernel, jax.jit(lambda q, k, v, bias: einsum_decode(q, k, v, bias, 1.0 / np.sqrt(D)))


def _decode_index(case, where):
    S, block = DECODE_CASES[case][3], _decode_tiles(case).block
    return {"first": 0, "inside": block + 2, "block-end": block - 1, "block-start": block, "last": S - 1}[where]


@pytest.mark.parametrize("where", ["first", "inside", "block-end", "block-start", "last"])
@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_kernel_matches_einsum_and_never_reads_past_the_index(case, where):
    """Left-padded rows of different lengths; the slots past the index hold NaN
    for the kernel and zeros behind the mask for the einsum."""
    B, H, Hkv, S, D, dtype, _ = DECODE_CASES[case]
    index = _decode_index(case, where)
    rng = np.random.default_rng(index)
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    k, v = (rng.normal(size=(B, Hkv, S, D)).astype(np.float32) for _ in range(2))
    pads = [min(index, n) for n in [0, 3, 11, 20][:B]]  # row b's first slots are padding; the index is never
    valid = np.arange(S)[None, :] >= np.asarray(pads)[:, None]
    seen = valid & (np.arange(S)[None, :] <= index)
    bias = jnp.where(jnp.asarray(seen), 0.0, -1e9).astype(jnp.float32)[:, None, None, :]
    written = (np.arange(S) <= index)[None, None, :, None]
    kernel, einsum = _decode_fns(case)
    want = einsum(q, jnp.asarray(np.where(written, k, 0.0), dtype), jnp.asarray(np.where(written, v, 0.0), dtype), bias)
    got = kernel(
        q, jnp.asarray(np.where(written, k, np.nan), dtype), jnp.asarray(np.where(written, v, np.nan), dtype),
        bias, jnp.int32(index),
    )
    assert got.shape == (B, H, D) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got))
    tol = dict(atol=2e-5, rtol=1e-5) if dtype == jnp.float32 else dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(got, want, **tol)


# (B, Hkv, rep, S, D): the cells' decode steps, a grouped model at D = 128, a small model
DECODE_CHOOSER_SHAPES = [(128, 12, 1, 512, 64), (64, 16, 1, 576, 64), (32, 8, 4, 1024, 128), (128, 2, 1, 1024, 64)]


@pytest.mark.parametrize("B,Hkv,rep,S,D", DECODE_CHOOSER_SHAPES)
def test_decode_chooser_programs_move_a_megabyte_and_fit_the_chip(B, Hkv, rep, S, D):
    tiles = attn.choose_decode_tiles(B, Hkv, rep, S, D, jnp.bfloat16)
    assert tiles.rows == (128 if B % 128 == 0 else B) and tiles.block in (8, 16, 32)
    k_block = tiles.block * Hkv * D * max(tiles.rows, 128) * 2
    assert k_block >= 2**20 or tiles.block == 32
    assert tiles.vmem_bytes <= 16 * 2**20  # the call asks for twice what is reckoned, 32 MiB at least
    if tiles.block > 8:  # no smaller block would have reached a mebibyte
        assert (tiles.block // 2) * Hkv * D * max(tiles.rows, 128) * 2 < 2**20


def test_decode_chooser_keeps_to_its_budget():
    assert attn.choose_decode_tiles(128, 2, 1, 1024, 64, jnp.bfloat16).block == 32
    assert attn.choose_decode_tiles(128, 2, 1, 1024, 64, jnp.bfloat16, vmem_budget=3 * 2**20).block == 16
    assert attn.choose_decode_tiles(128, 2, 1, 20, 64, jnp.bfloat16).block == 16  # no longer than the cache


@pytest.mark.parametrize(
    "prompt,steps,cache,block,want",
    [
        (64, 447, 512, None, 1.0),  # the einsum path reads every slot at every step
        (64, 0, 512, 8, 1.0),  # no decode step ran
        (64, 447, 512, 8, sum(-(-(64 + t) // 8) * 8 for t in range(1, 448)) / (447 * 512)),
        (512, 63, 576, 8, sum(-(-(512 + t) // 8) * 8 for t in range(1, 64)) / (63 * 576)),
        (6, 3, 10, 8, (8 + 8 + 10) / 30),  # the last block ends with the cache
    ],
)
def test_cache_read_share_counts_visited_slots(prompt, steps, cache, block, want):
    got = attn.cache_read_share(prompt, steps, cache, block)
    assert got == pytest.approx(want)
    assert 0.0 < got <= 1.0
    if block and steps == 447:
        assert 0.56 < got < 0.58  # cell 1: 56 % of the slots hold a token on average


@pytest.mark.parametrize(
    "fold,said",
    [(1, "rows 2 block 16, fold 1, lanes filled 2/128, grid (1, 2), VMEM reckoned"),
     (3, "rows 6 block 16, fold 3, lanes filled 6/128, grid (1, 2), VMEM reckoned")],
)
def test_decode_chooser_choice_is_logged_once_per_shape(caplog, fold, said):
    """One line a traced shape, in the model's shapes whatever the cache's fold, with the fold and
    the lanes it fills."""
    import logging

    from trlx_tpu.ops import kv_cache

    q = jnp.ones((2, 3, 8), jnp.float32)
    k = v = kv_cache.fold_heads(jnp.ones((2, 3, 24, 8), jnp.float32), fold)
    bias = jnp.zeros((2, 1, 1, 24), jnp.float32)
    attn._log_decode_tiles.cache_clear()
    root = logging.getLogger("trlx_tpu")
    root.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="trlx_tpu"):
            for index in (3, 4):
                attn.decode_attention(q, k, v, bias, index, None, True)
    finally:
        root.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records if "decode attention q[2,3,8]" in r.getMessage()]
    assert len(lines) == 1, lines
    assert f"cache[2,3,24,8] float32: {said}" in lines[0]


# (B, Hkv) -> kv heads beside each row: the largest divisor of Hkv that fits the 128 lanes, 1 from 128 rows on
DECODE_FOLDS = {
    (8, 1): 1, (8, 8): 8, (8, 12): 12, (8, 16): 16,  # 8 and 12 kv heads cap the fold: 64 and 96 lanes filled
    (16, 1): 1, (16, 8): 8, (16, 12): 6, (16, 16): 8,
    (32, 1): 1, (32, 8): 4, (32, 12): 4, (32, 16): 4,
    (64, 1): 1, (64, 8): 2, (64, 12): 2, (64, 16): 2,
    (96, 1): 1, (96, 8): 1, (96, 12): 1, (96, 16): 1,  # no second head fits beside 96 rows
    (128, 1): 1, (128, 8): 1, (128, 12): 1, (128, 16): 1,
    (256, 1): 1, (256, 8): 1, (256, 12): 1, (256, 16): 1,
}


@pytest.mark.parametrize("B,Hkv", sorted(DECODE_FOLDS))
def test_decode_fold_chooser_fills_the_lanes_with_whole_kv_heads(B, Hkv):
    fold = attn.choose_decode_fold(B, Hkv)
    assert fold == DECODE_FOLDS[B, Hkv]
    assert Hkv % fold == 0 and (fold == 1 or fold * B <= 128)
    # the programs then take the folded rows, and what they fill is what the gauge reports
    tiles = attn.choose_decode_tiles(B * fold, Hkv // fold, 1, 576, 64, jnp.bfloat16)
    assert attn._lane_fill(tiles.rows) == (min(B * fold, 128), 128)


# ------------------------------------------------------- attend: the dispatch


def _attend_case(case, seed=11):
    """Operands of one ``attend`` call and the plain reference's answer for them.
    Multi-token forwards are cache-free over T tokens, the first rows left-padded;
    single-token steps attend over a cache whose slots 0..index hold tokens."""
    from trlx_tpu.ops import kv_cache

    impl, H, Hkv, decode, quant = {
        "flash-kernel": ("flash", 4, 2, False, False),
        "decode-kernel": ("flash", 4, 2, True, False),
        "grouped-einsum": ("xla", 4, 2, False, False),
        "multi-head-einsum": ("xla", 4, 4, False, False),
        "int8-einsum": ("flash", 4, 2, True, True),
    }[case]
    B, T, S, D, index = 2, 24, 32, 16, 19
    rng = np.random.default_rng(seed)
    scale = 1.0 / np.sqrt(D)
    valid = np.ones((B, S if decode else T), np.int32)
    valid[0, :5] = 0  # left padding
    if decode:
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        rows = [jnp.asarray(rng.normal(size=(B, Hkv, index + 1, D)), jnp.float32) for _ in range(2)]
        layout = kv_cache.kv_cache_layout((B, Hkv, S, D), jnp.float32, quant)
        cache = {key: jnp.zeros(shape, dtype) for key, (shape, dtype) in layout.items()}
        cache = kv_cache.write_kv_cache(cache, rows[0], rows[1], 0)
        k = v = jnp.zeros((B, 1, Hkv, D), jnp.float32)  # this step's rows are in the cache already
        seen = jnp.asarray(valid) * (jnp.arange(S)[None, :] <= index)
        mask_bias = jnp.where(seen[:, None, None, :] > 0, 0.0, -1e9).astype(jnp.float32)
        kh, vh = kv_cache.read_kv_cache(cache, jnp.float32)  # the int8 rows as they read back
        want = attn.xla_attention(q.transpose(0, 2, 1, 3), kh, vh, seen, False, scale)
        return dict(q=q, k=k, v=v, cache=cache, mask_bias=mask_bias, kv_valid=None, index=jnp.int32(index),
                    scale=scale, impl=impl), want.transpose(0, 2, 1, 3).reshape(B, 1, H * D)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32) for _ in range(2))
    kv_valid = jnp.asarray(valid)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None] & (kv_valid[:, None, None, :] > 0)
    mask_bias = jnp.where(causal, 0.0, -1e9).astype(jnp.float32)
    want = attn.xla_attention(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), kv_valid, True, scale)
    return dict(q=q, k=k, v=v, cache=None, mask_bias=mask_bias, kv_valid=kv_valid, index=None, scale=scale,
                impl=impl), want.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _spy_on_kernels(monkeypatch):
    taken = []
    for name in ("flash_attention", "decode_attention"):
        kernel = getattr(attn, name)
        monkeypatch.setattr(
            attn, name, lambda *a, _name=name, _kernel=kernel, **kw: (taken.append(_name), _kernel(*a, **kw))[1])
    return taken


@pytest.mark.parametrize(
    "case,kernel",
    [("flash-kernel", "flash_attention"), ("decode-kernel", "decode_attention"), ("grouped-einsum", None),
     ("multi-head-einsum", None), ("int8-einsum", None)],
)
def test_attend_reaches_each_path_and_agrees_with_the_plain_reference(case, kernel, monkeypatch):
    """The five outcomes ``attend`` can reach on the CPU — the flash kernel and
    the decode kernel (interpret mode), the grouped and the multi-head einsum,
    the einsum over an int8 cache with its row scales folded in — each taken
    for the operands that should take it, each equal to ``xla_attention``."""
    taken = _spy_on_kernels(monkeypatch)
    operands, want = _attend_case(case)
    got = attn.attend(**operands, biased=False, prefix=None)
    assert taken == ([kernel] if kernel else [])
    assert got.shape == want.shape and got.dtype == operands["q"].dtype
    # rows with no key to see (a padded query) are the reference's zeros and anyone's guess elsewhere
    rows = np.asarray(operands["kv_valid"] if operands["kv_valid"] is not None else np.ones(got.shape[:2]), bool)
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows], atol=3e-5, rtol=1e-5)


# name: (B, H, Hkv, the fold the chooser gives)
FOLDED_CASES = {"64-rows-16-heads": (64, 16, 16, 2), "16-rows-32-over-8-heads": (16, 32, 8, 8),
                "8-rows-the-heads-cap-the-fold": (8, 6, 3, 3)}


@pytest.mark.parametrize("where", ["inside", "ragged-last-block"])
@pytest.mark.parametrize("impl", ["flash", "xla"])
@pytest.mark.parametrize("case", sorted(FOLDED_CASES))
def test_attend_over_a_folded_cache_equals_the_einsum_over_the_unfolded_one(case, impl, where, monkeypatch):
    """A single-token step over a cache written through ``kv_cache.write_kv_cache`` with kv heads
    beside the rows — the decode kernel under ``"flash"``; under ``"xla"`` the einsum, which
    unfolds what it is handed — against the einsum over the same rows unfolded: left padding in
    the mask, the write index inside the cache and in a last block that ends with the cache
    (41 slots in blocks of 8: interpret mode fills what overhangs with NaN)."""
    from trlx_tpu.ops import kv_cache

    B, H, Hkv, fold = FOLDED_CASES[case]
    assert attn.choose_decode_fold(B, Hkv) == fold
    S, D = 41, 8
    index = {"inside": 19, "ragged-last-block": S - 1}[where]
    rng = np.random.default_rng(index + B)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    rows = [jnp.asarray(rng.normal(size=(B, Hkv, index + 1, D)), jnp.float32) for _ in range(2)]
    pads = rng.integers(0, index, size=B)  # row b's first slots are padding; the index never is
    seen = (np.arange(S)[None, :] >= pads[:, None]) & (np.arange(S)[None, :] <= index)
    mask_bias = jnp.where(jnp.asarray(seen)[:, None, None, :], 0.0, -1e9).astype(jnp.float32)

    def step(fold, impl):
        layout = kv_cache.kv_cache_layout((B, Hkv, S, D), jnp.float32, False, fold)
        # what no token was written to must not reach a result
        unwritten = jnp.nan if impl == "flash" else 0.0
        cache = {key: jnp.full(shape, unwritten, dtype) for key, (shape, dtype) in layout.items()}
        cache = kv_cache.write_kv_cache(cache, rows[0], rows[1], 0)
        k = v = jnp.zeros((B, 1, Hkv, D), jnp.float32)  # this step's rows are in the cache already
        return attn.attend(q, k, v, cache, mask_bias, None, jnp.int32(index), 1.0 / np.sqrt(D), impl, False, None)

    want = step(1, "xla")
    taken = _spy_on_kernels(monkeypatch)
    got = step(fold, impl)
    assert taken == (["decode_attention"] if impl == "flash" else [])
    assert got.shape == (B, 1, H * D) and np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-5)


@pytest.mark.parametrize("case", sorted(FOLDED_CASES))
def test_read_share_and_lane_fill_of_a_folded_layer(case):
    """The slots visited follow the block the chooser gives the shape the kernel sees — at 64 rows
    of 16 heads the same 8 slots folded or not, so the same share; a slot of fewer heads makes a
    longer block — and the lanes filled are what the fold is for: 0.5 -> 1.0 at 64 rows, 1.0
    where no kernel runs."""
    from trlx_tpu.ops import kv_cache

    B, H, Hkv, fold = FOLDED_CASES[case]
    shape, new_tokens, steps = (B, Hkv, 576, 64), 64, 63
    flat, folded = (kv_cache.kv_cache_layout(shape, jnp.bfloat16, False, g) for g in (1, fold))
    share = [attn.decode_cache_read_share("flash", False, H, layout, B, new_tokens, steps) for layout in (flat, folded)]
    blocks = [attn.choose_decode_tiles(B * g, Hkv // g, H // Hkv, 576, 64, jnp.bfloat16).block for g in (1, fold)]
    assert share == [attn.cache_read_share(512, steps, 576, block) for block in blocks]
    if fold == 2:
        assert blocks == [8, 8] and share[0] == share[1]
    assert attn.decode_cache_lane_fill("flash", False, H, flat, B) == B / 128
    assert attn.decode_cache_lane_fill("flash", False, H, folded, B) == B * fold / 128
    assert attn.decode_cache_lane_fill("xla", False, H, flat, B) == 1.0
    assert attn.decode_cache_read_share("xla", False, H, flat, B, new_tokens, steps) == 1.0


@pytest.mark.parametrize("what", ["alibi", "prefix"])
@pytest.mark.parametrize("case", ["flash-kernel", "decode-kernel"])
def test_a_biased_or_prefixed_forward_never_takes_a_kernel(case, what, monkeypatch):
    """Alibi (a bias on the scores beyond the mask) and prefix tuning (learned
    rows in front of the keys) keep the einsum whatever ``impl`` says, and the
    prefix rows are joined in front of the cache with a zero bias."""
    taken = _spy_on_kernels(monkeypatch)
    operands, plain = _attend_case(case)
    q, Hkv, D = operands["q"], operands["k"].shape[2], operands["q"].shape[-1]
    prefix = None
    if what == "alibi":
        slopes = jnp.asarray([0.5, 0.25, 0.125, 0.0625])[None, :, None, None]
        positions = jnp.arange(operands["mask_bias"].shape[-1], dtype=jnp.float32)[None, None, None, :]
        operands["mask_bias"] = operands["mask_bias"] + slopes * positions
    else:
        rng = np.random.default_rng(5)
        prefix = tuple(jnp.asarray(rng.normal(size=(3, Hkv, D)), jnp.float32) for _ in range(2))
    got = attn.attend(**operands, biased=True, prefix=prefix)
    assert taken == []

    # the same by hand in float32: [prefix; keys] under [0; bias]
    cache = operands["cache"]
    kh, vh = (cache["k"], cache["v"]) if cache is not None else (
        operands["k"].transpose(0, 2, 1, 3), operands["v"].transpose(0, 2, 1, 3))
    bias = operands["mask_bias"]
    if prefix is not None:
        B = q.shape[0]
        kh = jnp.concatenate([jnp.broadcast_to(prefix[0].transpose(1, 0, 2)[None], (B, Hkv, 3, D)), kh], axis=2)
        vh = jnp.concatenate([jnp.broadcast_to(prefix[1].transpose(1, 0, 2)[None], (B, Hkv, 3, D)), vh], axis=2)
        bias = jnp.concatenate([jnp.zeros(bias.shape[:-1] + (3,)), bias], axis=-1)
    rep = q.shape[2] // Hkv
    scores = jnp.einsum("bthd,bhsd->bhts", q, jnp.repeat(kh, rep, axis=1)) * operands["scale"] + bias
    want = jnp.einsum("bhts,bhsd->bthd", jax.nn.softmax(scores, axis=-1), jnp.repeat(vh, rep, axis=1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want.reshape(got.shape)), atol=3e-5, rtol=1e-5)
    assert not np.allclose(np.asarray(got), np.asarray(plain), atol=1e-3)  # the bias or the prefix was felt
