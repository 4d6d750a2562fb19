"""Ring attention vs single-device full attention, on the virtual 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.ops.attention import xla_attention
from trlx_tpu.ops.ring_attention import ring_attention
from trlx_tpu.parallel.mesh import MODEL_AXIS, make_mesh


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full_attention(causal):
    mesh = make_mesh(data=1, fsdp=1, model=8)
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 2, 64, 8  # S sharded 8 ways -> 8 tokens per device
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)

    out = jax.jit(
        lambda q, k, v: ring_attention(q, k, v, mesh, axis_name=MODEL_AXIS, causal=causal)
    )(q, k, v)
    ref = xla_attention(q, k, v, jnp.ones((B, S), jnp.int32), causal, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5)


def test_ring_respects_padding_mask():
    """kv_valid (left-padded prompts) rides the ring and masks padding keys."""
    mesh = make_mesh(data=1, fsdp=1, model=8)
    rng = np.random.default_rng(2)
    B, H, S, D = 2, 2, 64, 8
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    kv_valid = np.ones((B, S), np.int32)
    kv_valid[0, :20] = 0  # crosses shard boundaries (8-token shards)
    kv_valid = jnp.asarray(kv_valid)

    out = jax.jit(
        lambda q, k, v, m: ring_attention(q, k, v, mesh, "model", True, kv_valid=m)
    )(q, k, v, kv_valid)
    ref = xla_attention(q, k, v, kv_valid, True, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5)


def test_model_ring_matches_xla_attention():
    """Full TransformerLM forward with attention_impl='ring' under a model-axis
    mesh equals the XLA attention path (ring must be a capability, not a
    showcase)."""
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM

    mesh = make_mesh(data=2, fsdp=1, model=4)
    base = PRESETS["gpt2"].replace(
        vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 32), 1, 32)
    mask = np.ones((2, 32), np.int32)
    mask[0, :7] = 0  # left padding
    mask = jnp.asarray(mask)

    model_xla = TransformerLM(base)
    params = model_xla.init(rng, ids, mask)["params"]
    logits_xla, *_ = model_xla.apply({"params": params}, ids, mask)

    model_ring = TransformerLM(base.replace(attention_impl="ring"))
    with mesh:
        logits_ring, *_ = jax.jit(
            lambda p, i, m: model_ring.apply({"params": p}, i, m)
        )(params, ids, mask)
    valid = np.asarray(mask)[:, :, None]
    np.testing.assert_allclose(
        np.asarray(logits_ring) * valid, np.asarray(logits_xla) * valid, atol=2e-4, rtol=1e-4
    )


def test_ring_gradients_flow():
    mesh = make_mesh(data=1, fsdp=1, model=8)
    rng = np.random.default_rng(1)
    B, H, S, D = 1, 1, 32, 4
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, "model", True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            xla_attention(q, k, v, jnp.ones((B, S), jnp.int32), True, 1.0 / np.sqrt(D)) ** 2
        )

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_ring_grads_with_padding_and_nonuniform_cotangent():
    """Backward (custom VJP re-running the ring) vs the XLA reference, with a
    padding mask and a non-uniform cotangent through each of dq/dk/dv."""
    from trlx_tpu.ops.attention import xla_attention

    mesh = make_mesh(data=1, fsdp=1, model=8)
    rng = np.random.default_rng(11)
    B, H, S, D = 2, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    valid = np.ones((B, S), np.int32)
    valid[0, :24] = 0
    valid = jnp.asarray(valid)

    def weigh(out):
        w = jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape) / out.size
        return jnp.sum(out * w) + jnp.sum(out**2)

    def loss_ring(q, k, v):
        return weigh(ring_attention(q, k, v, mesh, "model", True, kv_valid=valid))

    def loss_ref(q, k, v):
        return weigh(xla_attention(q, k, v, valid, True, 1.0 / np.sqrt(D)))

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-4, err_msg=f"d{name}"
        )


def test_ring_backward_memory_scales_with_shard():
    """The point of ring attention: training-mode peak memory must scale with
    S/n, not S. Compare compiled per-device temp memory of grad(ring) at n=8
    against n=1 (same global shapes): residuals + workspace must shrink.

    Guards the custom-VJP property that only O(S_local) residuals are saved —
    autodiff through the ppermute loop would hoard every step's rotated K/V
    (O(S_full) per device) and show ~flat memory vs n."""
    rng = np.random.default_rng(3)
    B, H, S, D = 1, 2, 512, 16
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)

    def temp_bytes(n):
        # all 8 devices are always in the mesh; only the ring axis size varies
        mesh = make_mesh(data=8 // n, fsdp=1, model=n)

        def loss(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, "model", True) ** 2)

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()
        mem = compiled.memory_analysis()
        if mem is None:
            import pytest

            pytest.skip("backend exposes no memory analysis")
        return mem.temp_size_in_bytes

    t1, t8 = temp_bytes(1), temp_bytes(8)
    # per-device scratch at n=8 must be well under the single-device footprint;
    # the dominant O(S*S/n) score tile alone predicts ~8x — allow 3x for slack
    assert t8 < t1 / 3, f"ring backward temp does not shrink with the ring: n1={t1} n8={t8}"


def test_ring_gqa_native_heads():
    """Grouped K/V ride the ring at native head count (no repeat): forward AND
    grads must match full attention with repeated heads."""
    mesh = make_mesh(data=1, fsdp=1, model=8)
    rng = np.random.default_rng(3)
    B, H, Hkv, S, D = 2, 4, 2, 64, 8
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    kv_valid = jnp.asarray(rng.random((B, S)) > 0.2, jnp.int32)
    kv_valid = kv_valid.at[:, -8:].set(1)  # keep final shard non-degenerate
    scale = 1.0 / np.sqrt(D)

    def ring_loss(q, k, v):
        out = ring_attention(
            q, k, v, mesh, axis_name=MODEL_AXIS, causal=True, kv_valid=kv_valid
        )
        return (out.astype(jnp.float32) ** 2).sum(), out

    def ref_loss(q, k, v):
        rep = H // Hkv
        out = xla_attention(
            q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
            kv_valid, True, scale,
        )
        return (out.astype(jnp.float32) ** 2).sum(), out

    (_, out), grads = jax.value_and_grad(ring_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), ref_grads = jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=1e-4)
    for g, rg in zip(grads, ref_grads):
        assert g.shape == rg.shape  # dk/dv at native Hkv head count
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg), atol=3e-4, rtol=1e-3)
