"""The ouro reference by itself: shapes, padding, the loop, the gate's exit
distribution, and its gradient against finite differences."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_ouro as ref
from ouro_tiny import tiny_config


def _inputs(B=2, T=12):
    return jax.random.randint(jax.random.PRNGKey(0), (B, T), 3, 300), jnp.ones((B, T), jnp.int32)


def test_forward_shapes_and_padding_is_ignored():
    config = tiny_config()
    w = ref.init_weights(config, 3)
    assert w["h.q.w"].shape == (3, 64, 64) and w["h.gate.w"].shape == (3, 64, 176)
    assert w["h.ln_1_post.g"].shape == (3, 64) and w["exit.w"].shape == (64, 1) and w["head.w"].shape == (64, 300)
    ids, mask = _inputs()
    logits, values = ref.forward(w, config, ids, mask)
    assert logits.shape == (2, 12, 300) and values.shape == (2, 12)
    pad = jnp.concatenate([jnp.zeros((2, 3), jnp.int32), ids], 1)
    pmask = jnp.concatenate([jnp.zeros((2, 3), jnp.int32), mask], 1)
    plogits, _ = ref.forward(w, config, pad, pmask)
    np.testing.assert_allclose(plogits[:, 3:], logits, atol=2e-5)


def test_every_pass_moves_the_state_and_the_last_one_is_what_the_head_reads():
    """Four passes are not one, nor three: the logits differ by far more than
    round-off, and the state after pass t of a 4-pass forward is the t-pass
    forward's last."""
    config = tiny_config()
    w = ref.init_weights(config, 5)
    ids, mask = _inputs()
    states = ref.hidden_states(w, config, ids, mask)
    assert len(states) == 4
    full, _ = ref.forward(w, config, ids, mask)
    for passes in (1, 3):
        fewer, _ = ref.forward(w, config, ids, mask, passes=passes)
        assert float(jnp.abs(full - fewer).max()) > 0.05
        np.testing.assert_allclose(ref.hidden_states(w, config, ids, mask, passes)[-1], states[passes - 1], atol=1e-6)
    one_pass, _ = ref.forward(w, tiny_config(passes=1), ids, mask)
    np.testing.assert_allclose(one_pass, ref.forward(w, config, ids, mask, passes=1)[0], atol=1e-6)


def test_exit_distribution_is_one_and_follows_the_gate():
    config = tiny_config()
    w = dict(ref.init_weights(config, 7))
    ids, mask = _inputs()
    states = ref.hidden_states(w, config, ids, mask)
    p = ref.exit_distribution(w, states)
    assert p.shape == (4, 2, 12)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    # a gate that always says "leave" leaves at pass 1, one that never does at the last
    w["exit.w"] = jnp.zeros_like(w["exit.w"])
    for bias, at in ((30.0, 0), (-30.0, 3)):
        w["exit.b"] = jnp.full((1,), bias)
        np.testing.assert_allclose(ref.exit_distribution(w, states)[at], 1.0, atol=1e-6)


def test_an_early_exit_threshold_under_one_is_refused():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ref.dims(dict(tiny_config(), early_exit_threshold=0.9))


@pytest.mark.parametrize("leaf, at", [("h.q.w", (1, 5, 9)), ("h.ln_2_post.g", (2, 7)), ("ln_f.g", (11,))])
def test_gradient_of_a_shared_leaf_matches_finite_differences(leaf, at):
    """A leaf used in all four passes (and ``ln_f`` at the end of each): the
    gradient through the checkpointed passes against central differences, both
    in float64 so that the differences are not round-off: 1e-4 of the entry."""
    config = tiny_config()
    with jax.enable_x64(True):
        w = jax.tree.map(lambda x: x.astype(jnp.float64), ref.init_weights(config, 9))
        ids, mask = _inputs(T=8)
        loss = jax.jit(lambda w: (ref.forward(w, config, ids, mask)[0] ** 2).mean())
        grads = jax.grad(loss)(w)
        step = 1e-5
        up = float(loss({**w, leaf: w[leaf].at[at].add(step)}))
        down = float(loss({**w, leaf: w[leaf].at[at].add(-step)}))
        assert (up - down) / (2 * step) == pytest.approx(float(grads[leaf][at]), rel=1e-4)
        assert abs(float(grads[leaf][at])) > 1e-9
        # the exit gate is on no path to the logits
        assert float(jnp.abs(grads["exit.w"]).max()) == 0.0
