"""The lfm2 reference by itself: shapes, the convolution against
``jnp.convolve``, its gradients against finite differences, the router, and the
share tied to the model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_lfm2 as ref
from lfm2_tiny import tiny_config


def _layer(weights, config, i):
    return ref.layer_weights(weights, ref.dims(config), i)


def test_forward_shapes_and_padding_is_ignored():
    config = tiny_config()
    w = ref.init_weights(config, 3)
    assert w["h.conv.in.w"].shape == (3, 64, 192) and w["h.conv.filter"].shape == (3, 64, 3)
    assert w["h.attn.q.w"].shape == (1, 64, 64) and w["h.attn.k.w"].shape == (1, 64, 32)
    assert w["h.attn.q_norm.g"].shape == (1, 16) and w["h.dense.gate.w"].shape == (1, 64, 160)
    assert w["h.moe.experts.gate"].shape == (3, 8, 64, 48) and w["h.moe.router.w"].shape == (3, 64, 8)
    assert "head.w" not in w  # the head is the embedding's transpose
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 12), 3, 300)
    mask = jnp.ones((2, 12), jnp.int32)
    logits, values = ref.forward(w, config, ids, mask)
    assert logits.shape == (2, 12, 300) and values.shape == (2, 12)
    # left padding moves nothing of the real tokens' answers: the convolution's gated input is zero there
    pad = jnp.concatenate([jnp.full((2, 3), 7, jnp.int32), ids], 1)
    pmask = jnp.concatenate([jnp.zeros((2, 3), jnp.int32), mask], 1)
    plogits, _ = ref.forward(w, config, pad, pmask)
    np.testing.assert_allclose(plogits[:, 3:], logits, atol=2e-5)


def test_the_convolution_is_jnp_convolve_on_a_channel():
    """``v`` of one channel is ``jnp.convolve`` of that channel's gated input
    with its filter reversed (a convolution flips its kernel; the layer's last
    tap multiplies the token's own input), cut to the causal part."""
    config = tiny_config()
    s = ref.dims(config)
    lw = dict(_layer(ref.init_weights(config, 5), config, 0))
    d = s["d"]
    # W_in = [I, I, I] and W_out = I: b = c = x = h, so y = h * conv(h * h)
    lw["conv.in.w"] = jnp.concatenate([jnp.eye(d)] * 3, axis=1)
    lw["conv.out.w"] = jnp.eye(d)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 9, d))
    y = ref.short_conv(h, lw, s, jnp.ones((1, 9), jnp.int32))
    for channel in (0, 17):
        u = h[0, :, channel] ** 2
        v = jnp.convolve(u, lw["conv.filter"][channel][::-1])[:9]
        np.testing.assert_allclose(y[0, :, channel], h[0, :, channel] * v, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("leaf, at", [("h.conv.filter", (1, 5, 0)), ("h.moe.experts.up", (0, 2, 7, 3))])
def test_gradients_match_finite_differences(leaf, at):
    """Central differences in float64 on one entry of the filter and of one
    expert's weights against the reference's gradient. The router casts its
    input to float32 whatever the weights' type, which leaves 1e-11 of noise in
    the loss: a step of 1e-3 keeps it seven digits under the difference."""
    with jax.enable_x64(True), jax.default_matmul_precision("highest"):
        config = tiny_config()
        w = {k: v.astype(jnp.float64) for k, v in ref.init_weights(config, 9).items()}
        ids = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 3, 300)
        mask = jnp.ones((2, 10), jnp.int32).at[0, :2].set(0)

        def loss(w):
            logits, values = ref.forward(w, config, ids, mask)
            return (jax.nn.log_softmax(logits)[..., 5] * mask).sum() + (values * mask).sum()

        grad = jax.grad(loss)(w)[leaf][at]
        step = 1e-3
        bump = lambda sign: {**w, leaf: w[leaf].at[at].add(sign * step)}
        numeric = (loss(bump(1)) - loss(bump(-1))) / (2 * step)
        assert abs(float(grad)) > 1e-6
        np.testing.assert_allclose(float(grad), float(numeric), rtol=1e-4)


def test_router_gives_top_k_normalised_beside_the_published_epsilon():
    config = tiny_config()
    s = ref.dims(config)
    lw = _layer(ref.init_weights(config, 5), config, 1)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 64))
    weights = ref.route(h, lw, s)
    assert weights.shape == (2, 7, 8) and ((weights > 0).sum(-1) == 2).all()
    score = jax.nn.sigmoid(h @ lw["moe.router.w"])
    chosen_sum = (score * (weights > 0)).sum(-1)
    np.testing.assert_allclose(weights.sum(-1), chosen_sum / (chosen_sum + 1e-6), rtol=1e-6)
    # the bias chooses and does not weigh
    biased = dict(lw, **{"moe.router.b": lw["moe.router.b"].at[0].set(10.0)})
    chosen = ref.route(h, biased, s)
    assert (chosen[..., 0] > 0).all()
    np.testing.assert_allclose(chosen[..., 0], score[..., 0] / ((score * (chosen > 0)).sum(-1) + 1e-6), rtol=1e-5)


def test_the_shares_add_up_to_the_whole_layer():
    """8 experts as two shares of 4: the two routed parts (there is no shared
    expert) are what the uncut layer gives."""
    whole_config = tiny_config()
    w = ref.init_weights(whole_config, 11)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    whole = ref.experts(h, _layer(w, whole_config, 2), ref.dims(whole_config))
    total = 0.0
    for offset in (0, 4):
        share_config = tiny_config(held=4, offset=offset)
        lw = dict(_layer(w, whole_config, 2))
        for name in ("gate", "up", "down"):
            lw[f"moe.experts.{name}"] = lw[f"moe.experts.{name}"][offset : offset + 4]
        routed = ref.experts(h, lw, ref.dims(share_config))
        assert float(jnp.abs(routed).max()) > 0
        total = total + routed
    np.testing.assert_allclose(total, whole, atol=1e-6)
