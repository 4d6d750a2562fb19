"""Model-layer tests (strategy mirrors reference tests/test_models.py: forward/
generate smoke for every family preset, hydra-vs-clean logits equivalence oracle,
cache-vs-full-forward consistency)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.heads import sync_target_q_heads
from trlx_tpu.models.policy import (
    CausalLMWithILQLHeads,
    CausalLMWithValueHead,
    apply_hydra_branch,
    branch_param_subtree,
)
from trlx_tpu.models.presets import PRESETS, get_preset
from trlx_tpu.models.transformer import TransformerConfig, TransformerLM

TINY = dict(
    vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
    max_position_embeddings=32, compute_dtype=jnp.float32,
)


def tiny_config(family: str) -> TransformerConfig:
    return PRESETS[family].replace(**TINY)


@pytest.mark.parametrize("family", sorted(PRESETS))
def test_forward_all_families(family):
    config = tiny_config(family)
    model = TransformerLM(config)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 8), 0, config.vocab_size)
    mask = jnp.ones((2, 8), jnp.int32)
    params = model.init(rng, ids, mask)["params"]
    logits, hidden, _, _ = model.apply({"params": params}, ids, mask)
    assert logits.shape == (2, 8, config.vocab_size)
    assert hidden.shape == (2, 8, config.hidden_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_left_padding_matches_unpadded():
    """A left-padded prompt must produce the same last-token logits as unpadded."""
    config = tiny_config("gpt2")
    model = TransformerLM(config)
    rng = jax.random.PRNGKey(1)
    ids = jax.random.randint(rng, (1, 6), 1, config.vocab_size)
    params = model.init(rng, ids, jnp.ones((1, 6), jnp.int32))["params"]
    logits_clean, *_ = model.apply({"params": params}, ids, jnp.ones((1, 6), jnp.int32))

    padded = jnp.concatenate([jnp.zeros((1, 3), ids.dtype), ids], axis=1)
    mask = jnp.concatenate([jnp.zeros((1, 3), jnp.int32), jnp.ones((1, 6), jnp.int32)], axis=1)
    logits_pad, *_ = model.apply({"params": params}, padded, mask)
    np.testing.assert_allclose(
        np.asarray(logits_clean[0, -1]), np.asarray(logits_pad[0, -1]), atol=1e-4
    )


@pytest.mark.parametrize("family", ["gpt2", "llama", "gpt_neox"])
def test_cache_decode_matches_full_forward(family):
    """Prefill + single-token cached decode == full forward at that position."""
    config = tiny_config(family)
    model = TransformerLM(config)
    rng = jax.random.PRNGKey(2)
    T = 5
    ids = jax.random.randint(rng, (2, T + 1), 1, config.vocab_size)
    params = model.init(rng, ids, jnp.ones((2, T + 1), jnp.int32))["params"]

    full_logits, *_ = model.apply({"params": params}, ids, jnp.ones((2, T + 1), jnp.int32))

    cache = model.init_cache(2, T + 4, dtype=jnp.float32)
    mask_prefill = jnp.concatenate([jnp.ones((2, T)), jnp.zeros((2, 4))], axis=1).astype(jnp.int32)
    prefill_logits, _, _, cache = model.apply(
        {"params": params}, ids[:, :T], mask_prefill, None, cache
    )
    np.testing.assert_allclose(
        np.asarray(full_logits[:, :T]), np.asarray(prefill_logits), atol=1e-4
    )

    mask_decode = jnp.concatenate([jnp.ones((2, T + 1)), jnp.zeros((2, 3))], axis=1).astype(jnp.int32)
    pos = jnp.full((2, 1), T, jnp.int32)
    step_logits, _, _, cache = model.apply(
        {"params": params}, ids[:, T : T + 1], mask_decode, pos, cache
    )
    np.testing.assert_allclose(
        np.asarray(full_logits[:, T]), np.asarray(step_logits[:, 0]), atol=1e-4
    )


def test_hydra_branch_equals_full_forward():
    """The frozen-branch forward from the branch activation must reproduce the full
    model's logits exactly (the reference's key oracle, tests/test_models.py:109-143)."""
    config = tiny_config("gpt2")
    model = CausalLMWithValueHead(config)
    rng = jax.random.PRNGKey(3)
    ids = jax.random.randint(rng, (2, 7), 1, config.vocab_size)
    mask = jnp.ones((2, 7), jnp.int32)
    params = model.init(rng, ids, mask)["params"]

    start = 1  # one unfrozen layer on a 2-layer model
    logits, values, branch_hidden, _ = model.apply(
        {"params": params}, ids, mask, branch_layer=start
    )
    assert values.shape == (2, 7)
    branch_params = branch_param_subtree(params["transformer"], start, config)
    ref_logits = apply_hydra_branch(model, branch_params, branch_hidden, mask, start)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)


def test_ilql_heads_shapes_and_sync():
    config = tiny_config("gpt2")
    model = CausalLMWithILQLHeads(config, two_qs=True)
    rng = jax.random.PRNGKey(4)
    ids = jax.random.randint(rng, (2, 9), 1, config.vocab_size)
    mask = jnp.ones((2, 9), jnp.int32)
    actions_ixs = jnp.array([[2, 3, 4], [1, 2, 3]])
    states_ixs = jnp.array([[2, 3, 4, 5], [1, 2, 3, 4]])
    params = model.init(rng, ids, mask, None, actions_ixs, states_ixs)["params"]
    logits, qs, tqs, vs, _ = model.apply(
        {"params": params}, ids, mask, None, actions_ixs, states_ixs
    )
    assert logits.shape == (2, 9, config.vocab_size)
    assert len(qs) == 2 and len(tqs) == 2
    assert qs[0].shape == (2, 3, config.vocab_size)
    assert vs.shape == (2, 4, 1)

    # Polyak sync: with alpha=1, target == q exactly
    heads = params["ilql_heads"]
    synced = sync_target_q_heads(heads, alpha=1.0)
    q0 = heads["q_heads_0"]["fc_in"]["kernel"]
    t0 = synced["target_q_heads_0"]["fc_in"]["kernel"]
    np.testing.assert_allclose(np.asarray(q0), np.asarray(t0))


def test_get_preset_prefix_matching():
    assert get_preset("gpt2-imdb").pos_embedding == "learned"
    assert get_preset("EleutherAI/pythia-160m").rope_style == "neox"
    assert get_preset("meta-llama/Llama-2-7b-hf").glu
    with pytest.raises(ValueError):
        get_preset("some-unknown-arch")


def test_value_branch():
    """num_value_layers > 0 gives the value fn its own trainable top-layer branch
    (parity: make_value_branch, modeling_ppo.py:255-263)."""
    config = tiny_config("gpt2")
    model = CausalLMWithValueHead(config, num_value_layers=1)
    rng = jax.random.PRNGKey(5)
    ids = jax.random.randint(rng, (2, 6), 1, config.vocab_size)
    mask = jnp.ones((2, 6), jnp.int32)
    params = model.init(rng, ids, mask)["params"]
    assert "value_blocks_0" in params and "value_ln" in params
    logits, values, branch_hidden, _ = model.apply({"params": params}, ids, mask, branch_layer=1)
    assert values.shape == (2, 6)
    assert branch_hidden is not None and branch_hidden.shape == (2, 6, config.hidden_size)
    # the value branch params receive gradients
    def loss(p):
        _, v, _, _ = model.apply({"params": p}, ids, mask)
        return jnp.sum(v**2)
    grads = jax.grad(loss)(params)
    g = np.abs(np.asarray(grads["value_blocks_0"]["attn"]["q_proj"]["kernel"])).sum()
    assert g > 0


def test_value_branch_inits_from_trunk():
    """Value branch starts from the pretrained top-layer weights (ModelBranch
    deepcopy parity), not random init."""
    from trlx_tpu.models.policy import init_value_branch_from_trunk

    config = tiny_config("gpt2")
    model = CausalLMWithValueHead(config, num_value_layers=1)
    rng = jax.random.PRNGKey(6)
    ids = jax.random.randint(rng, (1, 4), 1, config.vocab_size)
    params = dict(model.init(rng, ids, jnp.ones_like(ids))["params"])
    params = init_value_branch_from_trunk(params, config, 1)
    np.testing.assert_array_equal(
        np.asarray(params["value_blocks_0"]["attn"]["q_proj"]["kernel"]),
        np.asarray(params["transformer"]["layers_1"]["attn"]["q_proj"]["kernel"]),
    )
    np.testing.assert_array_equal(
        np.asarray(params["value_ln"]["scale"]),
        np.asarray(params["transformer"]["ln_f"]["scale"]),
    )


def test_value_branch_rejects_cache_and_overdepth():
    config = tiny_config("gpt2")
    import pytest as _pytest

    model = CausalLMWithValueHead(config, num_value_layers=5)  # > num_layers=2
    with _pytest.raises(ValueError):
        model.init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32))


def test_depth_scaled_residual_init():
    """Residual-out projections (o_proj/down_proj) must initialize at
    initializer_range/sqrt(2L) so the residual stream's variance stays
    depth-independent (HF GPT-2 _init_weights semantics, which the reference
    inherits via from_pretrained; flat 0.02 at depth 48 produced
    first-step loss spikes that depth-24 never showed). Other projections keep
    the flat std, and depth_scaled_init=False restores the old behavior."""
    import math

    def stds(depth, scaled):
        config = tiny_config("gpt2").replace(
            hidden_size=64, num_heads=4, num_layers=depth, depth_scaled_init=scaled
        )
        params = TransformerLM(config).init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32)
        )["params"]
        layer = params["layers_0"]
        return (
            float(np.std(np.asarray(layer["attn"]["o_proj"]["kernel"]))),
            float(np.std(np.asarray(layer["mlp"]["down_proj"]["kernel"]))),
            float(np.std(np.asarray(layer["attn"]["q_proj"]["kernel"]))),
        )

    for depth in (2, 32):
        expected = 0.02 / math.sqrt(2 * depth)
        o_std, down_std, q_std = stds(depth, scaled=True)
        assert abs(o_std - expected) / expected < 0.25, (depth, o_std, expected)
        assert abs(down_std - expected) / expected < 0.25, (depth, down_std, expected)
        assert abs(q_std - 0.02) / 0.02 < 0.25, (depth, q_std)

    o_std, down_std, _ = stds(32, scaled=False)
    assert abs(o_std - 0.02) / 0.02 < 0.25 and abs(down_std - 0.02) / 0.02 < 0.25
