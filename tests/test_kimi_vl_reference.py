"""The kimi_vl reference by itself: shapes, and the share tied to the model."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_kimi_vl as ref
from kimi_vl_tiny import tiny_config


def _layer(weights, i):
    return {k[2:]: v[i] for k, v in weights.items() if k.startswith("h.moe.")}


def test_forward_shapes_and_padding_is_ignored():
    config = tiny_config()
    w = ref.init_weights(config, 3)
    assert w["h.dense.gate.w"].shape == (1, 64, 96) and w["h.moe.experts.gate"].shape == (2, 16, 64, 32)
    assert w["h.kva.w"].shape == (3, 64, 32 + 8) and w["h.kvb.w"].shape == (3, 32, 4 * (16 + 16))
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 12), 3, 300)
    mask = jnp.ones((2, 12), jnp.int32)
    logits, values = ref.forward(w, config, ids, mask)
    assert logits.shape == (2, 12, 300) and values.shape == (2, 12)
    # left padding moves nothing of the real tokens' answers
    pad = jnp.concatenate([jnp.zeros((2, 3), jnp.int32), ids], 1)
    pmask = jnp.concatenate([jnp.zeros((2, 3), jnp.int32), mask], 1)
    plogits, _ = ref.forward(w, config, pad, pmask)
    np.testing.assert_allclose(plogits[:, 3:], logits, atol=2e-5)


def test_router_gives_top_k_normalised_and_scaled():
    config = tiny_config()
    s = ref.dims(config)
    lw = _layer(ref.init_weights(config, 5), 0)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 64))
    weights = ref.route(h, lw, s)
    assert weights.shape == (2, 7, 16)
    assert ((weights > 0).sum(-1) == 4).all()
    np.testing.assert_allclose(weights.sum(-1), 2.446, rtol=1e-5)
    # the bias chooses and does not weigh: a large bias on expert 0 selects it everywhere,
    # and its weight is still its own score's share
    biased = dict(lw, **{"moe.router.b": lw["moe.router.b"].at[0].set(10.0)})
    chosen = ref.route(h, biased, s)
    assert (chosen[..., 0] > 0).all()
    score = jax.nn.sigmoid(h @ lw["moe.router.w"])
    np.testing.assert_allclose(
        chosen[..., 0], 2.446 * score[..., 0] / (score * (chosen > 0)).sum(-1), rtol=1e-5)


def test_the_shares_add_up_to_the_whole_layer():
    """16 experts as two shares of 8: the two routed parts, and the shared
    experts counted once, are what the uncut layer gives."""
    whole_config = tiny_config()
    w = ref.init_weights(whole_config, 11)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    whole = sum(ref.moe_parts(h, _layer(w, 1), ref.dims(whole_config)))
    total, shared = 0.0, None
    for offset in (0, 8):
        share_config = tiny_config(held=8, offset=offset)
        lw = _layer(w, 1)
        for name in ("gate", "up", "down"):
            lw[f"moe.experts.{name}"] = lw[f"moe.experts.{name}"][offset : offset + 8]
        routed, shared = ref.moe_parts(h, lw, ref.dims(share_config))
        assert float(jnp.abs(routed).max()) > 0
        total = total + routed
    np.testing.assert_allclose(total + shared, whole, atol=1e-5)
