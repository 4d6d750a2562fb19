"""Overlapped-collective FSDP learner tests (``train.learner_overlap``,
``trlx_tpu/parallel/fsdp.py``; docs/parallelism.md "Learner overlap & FSDP").

What the suite proves, per the PR's parity contract:

- grad-accum over N microbatches matches the whole-batch loss/grads/update
  numerically (both the GSPMD step and the overlapped step);
- with overlap OFF, ``make_grad_accum_step`` builds the exact pre-overlap
  program — asserted BITWISE against an independent reconstruction;
- the overlapped step's buffers are donated (``input_output_alias`` in the
  compiled HLO);
- the int8 sharded optimizer state tracks f32 Adam within tolerance;
- the lowered overlap step emits ``reduce-scatter:fsdp`` / ``all-gather:fsdp``
  and NO ``all-reduce:fsdp``, and the seeded regression
  (``TRLX_IR_SEED_REGRESSION=allreduce_under_fsdp``) restores the all-reduce
  the budget must reject;
- the committed IR budget pins the per-device memory drop of the sharded
  optimizer state vs the unsharded comparator entry (IR006).

Runs on the 8 virtual CPU devices from conftest; overlap meshes use 4 of
them (data=2 × fsdp=2 — the overlap path requires model == pipe == 1).
"""

import json
import os
import types

import numpy as np
import pytest

from tests.conftest import jax

import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from trlx_tpu.parallel import fsdp as fsdp_lib
from trlx_tpu.parallel.mesh import FSDP_AXIS, make_deviceless_mesh, make_mesh, put_batch
from trlx_tpu.parallel.sharding import in_manual_axes, manual_axes, shard_params

pytestmark = pytest.mark.learner_overlap

RULES = [
    (r".*dense/kernel$", P(FSDP_AXIS, None)),
    (r".*out/kernel$", P(None, FSDP_AXIS)),
    (r".*", P()),
]


def _make_params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "dense": {
            "kernel": jnp.asarray(rng.randn(16, 8), jnp.float32) * 0.1,
            "bias": jnp.zeros((8,), jnp.float32),
        },
        "out": {"kernel": jnp.asarray(rng.randn(8, 4), jnp.float32) * 0.1},
    }


def _loss_fn(p, mb):
    h = jnp.tanh(mb["x"] @ p["dense"]["kernel"] + p["dense"]["bias"])
    o = h @ p["out"]["kernel"]
    # per-example mean loss: invariant to how the batch is grouped into
    # microbatches or sharded across devices, so every path must agree
    loss = jnp.mean(jnp.square(o - mb["y"]))
    return loss, {"loss": loss}


def _make_batch(B=16, seed=1):
    rng = np.random.RandomState(seed)
    return {
        "x": np.asarray(rng.randn(B, 16), np.float32),
        "y": np.asarray(rng.randn(B, 4), np.float32),
    }


@pytest.fixture(scope="module")
def overlap_mesh():
    return make_mesh(data=2, fsdp=2, model=1, pipe=1, devices=jax.devices()[:4])


def _fake_trainer(tx, overlap=False, specs=None, mesh=None, max_grad_norm=None):
    """A minimal stand-in exposing exactly what ``make_grad_accum_step``
    reads, so the step builder is tested without a full trainer."""
    from trlx_tpu.trainer.mesh_trainer import MeshRLTrainer

    from trlx_tpu.data.configs import LearnerOverlapConfig

    self = types.SimpleNamespace(
        tx=tx,
        health=None,
        lr_schedule=lambda count: jnp.float32(1e-2),
        mesh=mesh,
        _overlap_specs=specs,
        _overlap_max_grad_norm=max_grad_norm,
        _learner_overlap_active=lambda: overlap,
        config=types.SimpleNamespace(
            train=types.SimpleNamespace(
                learner_overlap=LearnerOverlapConfig(enabled=overlap)
            )
        ),
    )
    self.make_grad_accum_step = types.MethodType(MeshRLTrainer.make_grad_accum_step, self)
    return self


# ----------------------------------------------------------- GSPMD step parity


def test_accum_n_matches_whole_batch():
    """accum=N and accum=1 agree on the resulting params (and the update
    equals a hand-computed whole-batch optax step)."""
    params = _make_params()
    batch = {k: jnp.asarray(v) for k, v in _make_batch().items()}
    tx = optax.adamw(1e-2)

    results = {}
    for num_mb in (1, 4):
        trainer = _fake_trainer(tx)
        step = trainer.make_grad_accum_step(_loss_fn, num_mb, donate=False)
        p, s, stats = step(params, tx.init(params), batch)
        results[num_mb] = jax.device_get(p)

    # whole-batch reference by hand
    (_, _), g = jax.value_and_grad(_loss_fn, has_aux=True)(params, batch)
    upd, _ = tx.update(g, tx.init(params), params)
    ref = jax.device_get(optax.apply_updates(params, upd))

    for a, b in zip(jax.tree.leaves(results[1]), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-7)
    for a, b in zip(jax.tree.leaves(results[4]), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_overlap_off_is_bit_identical_to_pre_overlap_program():
    """With learner_overlap off, make_grad_accum_step must build the exact
    pre-overlap program: compare against an independent reconstruction of the
    original step (scan + mean + tx.update), bit for bit, at accum=1."""
    params = _make_params()
    batch = {k: jnp.asarray(v) for k, v in _make_batch().items()}
    tx = optax.adamw(1e-2)
    opt_state = tx.init(params)

    trainer = _fake_trainer(tx)
    step = trainer.make_grad_accum_step(_loss_fn, 1, donate=False)
    p_new, s_new, stats = step(params, opt_state, batch)

    num_mb = 1

    def original_step(params, opt_state, batch):
        mbs = jax.tree.map(
            lambda x: x.reshape((num_mb, x.shape[0] // num_mb) + x.shape[1:]), batch
        )

        def body(grads_acc, mb):
            (loss, stats), grads = jax.value_and_grad(_loss_fn, has_aux=True)(params, mb)
            return jax.tree.map(jnp.add, grads_acc, grads), (loss, stats)

        zero = jax.tree.map(jnp.zeros_like, params)
        grads, (losses, stats) = jax.lax.scan(body, zero, mbs)
        grads = jax.tree.map(lambda g: g / num_mb, grads)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        mean_stats = jax.tree.map(lambda x: jnp.mean(x, axis=0), stats)
        mean_stats["learning_rate_group_0"] = jnp.float32(1e-2)
        return new_params, new_opt_state, mean_stats

    p_ref, s_ref, stats_ref = jax.jit(original_step)(params, opt_state, batch)

    for a, b in zip(jax.tree.leaves(jax.device_get(p_new)), jax.tree.leaves(jax.device_get(p_ref))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "params diverge bitwise"
    for a, b in zip(jax.tree.leaves(jax.device_get(s_new)), jax.tree.leaves(jax.device_get(s_ref))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "opt state diverges bitwise"
    assert np.array_equal(
        np.asarray(jax.device_get(stats["loss"])), np.asarray(jax.device_get(stats_ref["loss"]))
    )


# ------------------------------------------------------------- overlapped step


def test_overlap_matches_whole_batch_reference(overlap_mesh):
    """The overlapped shard_map step (accum=4, sharded state, shard-aware
    clip) matches a single-device whole-batch optax step numerically."""
    mesh = overlap_mesh
    params = _make_params()
    batch = _make_batch()
    tx = optax.adamw(1e-2)

    specs = fsdp_lib.make_overlap_specs(params, tx, mesh, RULES)
    sp = shard_params(params, mesh, RULES)
    opt_state = fsdp_lib.make_sharded_opt_init(tx, specs, mesh)(sp)
    step = fsdp_lib.make_overlapped_grad_accum_step(
        _loss_fn, tx, specs, mesh, num_mb=4, max_grad_norm=1.0,
        lr_schedule=lambda c: jnp.float32(1e-2), donate=False,
    )
    p2, s2, stats = step(sp, opt_state, put_batch(mesh, batch))

    ref_tx = optax.chain(optax.clip_by_global_norm(1.0), tx)
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    (_, _), g = jax.value_and_grad(_loss_fn, has_aux=True)(params, jbatch)
    upd, _ = ref_tx.update(g, ref_tx.init(params), params)
    ref = optax.apply_updates(params, upd)

    for a, b in zip(jax.tree.leaves(jax.device_get(p2)), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    assert "learning_rate_group_0" in stats
    assert np.isfinite(float(stats["loss"]))


def test_overlap_via_trainer_gate(overlap_mesh):
    """make_grad_accum_step routes to the overlapped builder when the gate is
    on, and the result still matches the GSPMD step numerically."""
    mesh = overlap_mesh
    params = _make_params()
    batch = _make_batch()
    tx = optax.adamw(1e-2)
    specs = fsdp_lib.make_overlap_specs(params, tx, mesh, RULES)

    on = _fake_trainer(tx, overlap=True, specs=specs, mesh=mesh, max_grad_norm=None)
    off = _fake_trainer(tx)
    step_on = on.make_grad_accum_step(_loss_fn, 2, donate=False)
    step_off = off.make_grad_accum_step(_loss_fn, 2, donate=False)

    sp = shard_params(params, mesh, RULES)
    opt_sharded = fsdp_lib.make_sharded_opt_init(tx, specs, mesh)(sp)
    p_on, _, _ = step_on(sp, opt_sharded, put_batch(mesh, batch))
    p_off, _, _ = step_off(params, tx.init(params), {k: jnp.asarray(v) for k, v in batch.items()})

    for a, b in zip(jax.tree.leaves(jax.device_get(p_on)), jax.tree.leaves(jax.device_get(p_off))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_overlap_donation_input_output_alias(overlap_mesh):
    """params and opt_state buffers are donated: the compiled overlap step
    must carry input_output_alias entries."""
    mesh = overlap_mesh
    params = _make_params()
    tx = optax.adamw(1e-2)
    specs = fsdp_lib.make_overlap_specs(params, tx, mesh, RULES)
    step = fsdp_lib.make_overlapped_grad_accum_step(
        _loss_fn, tx, specs, mesh, num_mb=2, donate=True,
    )
    abs_params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        params, specs.param_specs,
    )
    abs_opt = fsdp_lib.global_state_struct(specs, mesh)
    bsh = NamedSharding(mesh, P(("data", "fsdp"), None))
    abs_batch = {
        "x": jax.ShapeDtypeStruct((16, 16), jnp.float32, sharding=bsh),
        "y": jax.ShapeDtypeStruct((16, 4), jnp.float32, sharding=bsh),
    }
    hlo = step.lower(abs_params, abs_opt, abs_batch).compile().as_text()
    assert "input_output_alias" in hlo


def test_int8_opt_state_tracks_f32_adam(overlap_mesh):
    """The ZeRO int8 optimizer (blockwise-quantized moments over LOCAL
    shards) stays within tolerance of f32 Adam over several steps."""
    from trlx_tpu.ops.quantized_adam import adamw_8bit

    mesh = overlap_mesh
    params = _make_params()
    batch = _make_batch()
    tx8 = adamw_8bit(learning_rate=1e-2)
    specs = fsdp_lib.make_overlap_specs(params, tx8, mesh, RULES)

    # quantized-moment leaves shard over fsdp exactly when the param does
    flat = dict(
        (tuple(str(getattr(k, "key", k)) for k in path), spec)
        for path, spec in jax.tree_util.tree_flatten_with_path(
            specs.state_specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    )
    assert flat[("moments", "dense", "kernel", "m_q")] == P(FSDP_AXIS)
    assert flat[("moments", "dense", "bias", "m_q")] == P()
    assert flat[("count",)] == P()

    sp = shard_params(params, mesh, RULES)
    state8 = fsdp_lib.make_sharded_opt_init(tx8, specs, mesh)(sp)
    step8 = fsdp_lib.make_overlapped_grad_accum_step(
        _loss_fn, tx8, specs, mesh, num_mb=2, donate=False,
    )

    ref_tx = optax.adamw(1e-2)
    ref_state = ref_tx.init(params)
    p8, pref = sp, params
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    for _ in range(5):
        p8, state8, _ = step8(p8, state8, put_batch(mesh, batch))
        (_, _), g = jax.value_and_grad(_loss_fn, has_aux=True)(pref, jbatch)
        upd, ref_state = ref_tx.update(g, ref_state, pref)
        pref = optax.apply_updates(pref, upd)
    drift = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree.leaves(jax.device_get(p8)), jax.tree.leaves(pref))
    )
    assert drift < 5e-3, f"int8 state drifted {drift} from f32 Adam"


# ------------------------------------------------------------------ IR surface


def test_overlap_ir_reduce_scatter_not_allreduce(monkeypatch):
    """Deviceless lowering of the overlapped step shows the bandwidth-optimal
    schedule — reduce-scatter + all-gather over fsdp, NO all-reduce over
    fsdp — and the seeded regression restores the all-reduce."""
    from trlx_tpu.analysis.ir.lowering import parse_collectives

    mesh = make_deviceless_mesh(data=2, fsdp=2, pipe=1, model=1)
    params = _make_params()
    tx = optax.adamw(1e-2)
    specs = fsdp_lib.make_overlap_specs(params, tx, mesh, RULES)
    abs_params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        params, specs.param_specs,
    )
    abs_opt = fsdp_lib.global_state_struct(specs, mesh)
    bsh = NamedSharding(mesh, P(("data", "fsdp"), None))
    abs_batch = {
        "x": jax.ShapeDtypeStruct((16, 16), jnp.float32, sharding=bsh),
        "y": jax.ShapeDtypeStruct((16, 4), jnp.float32, sharding=bsh),
    }

    def lower(max_grad_norm=1.0):
        step = fsdp_lib.make_overlapped_grad_accum_step(
            _loss_fn, tx, specs, mesh, num_mb=2, max_grad_norm=max_grad_norm,
        )
        hlo = step.lower(abs_params, abs_opt, abs_batch).compile().as_text()
        return parse_collectives(hlo, mesh)

    monkeypatch.delenv("TRLX_IR_SEED_REGRESSION", raising=False)
    good = lower()
    assert any(k.startswith("reduce-scatter:") and "fsdp" in k for k in good), good
    assert any(k.startswith("all-gather:") and "fsdp" in k for k in good), good
    assert "all-reduce:fsdp" not in good, good

    monkeypatch.setenv("TRLX_IR_SEED_REGRESSION", "allreduce_under_fsdp")
    seeded = lower()
    assert "all-reduce:fsdp" in seeded, seeded
    assert not any(k.startswith("reduce-scatter:") for k in seeded), seeded


def test_committed_budget_shows_overlap_wins():
    """The committed IR budget is the acceptance record: the overlap entry
    must show reduce-scatter/allgather (no fsdp all-reduce) and strictly
    lower per-device memory than the unsharded-optimizer comparator (IR006)."""
    path = os.path.join(os.path.dirname(__file__), "..", "graftcheck-ir-budget.json")
    budget = json.load(open(path))
    overlap = budget["ppo_train_step_overlap@small"]
    unsharded = budget["ppo_train_step_unsharded_opt@small"]

    coll = overlap["collectives"]
    assert "reduce-scatter:fsdp" in coll, coll
    assert "all-gather:fsdp" in coll, coll
    assert "all-reduce:fsdp" not in coll, coll
    assert "all-reduce:fsdp" in unsharded["collectives"]

    assert overlap["memory_bytes"] < unsharded["memory_bytes"], (
        f"sharded-optimizer step must use less per-device memory: "
        f"{overlap['memory_bytes']} vs {unsharded['memory_bytes']}"
    )


# -------------------------------------------------------------- config/gating


def test_can_overlap_gating():
    assert fsdp_lib.can_overlap(make_deviceless_mesh(data=2, fsdp=2, pipe=1, model=1))
    assert fsdp_lib.can_overlap(make_deviceless_mesh(data=4, fsdp=2, pipe=1, model=1))
    assert not fsdp_lib.can_overlap(make_deviceless_mesh(data=2, fsdp=2, pipe=1, model=2))
    assert not fsdp_lib.can_overlap(make_deviceless_mesh(data=2, fsdp=2, pipe=2, model=1))


def test_learner_overlap_config_roundtrip():
    from trlx_tpu.data.configs import LearnerOverlapConfig, TrainConfig

    cfg = TrainConfig.from_dict(
        {"learner_overlap": {"enabled": True, "int8_opt_state": True,
                             "remat": "per_layer"}}
    )
    assert isinstance(cfg.learner_overlap, LearnerOverlapConfig)
    assert cfg.learner_overlap.enabled
    assert cfg.learner_overlap.int8_opt_state
    assert cfg.learner_overlap.remat == "per_layer"
    assert not TrainConfig.from_dict({}).learner_overlap.enabled
    # the flash backward is the kernels': a yml that still asks is told which field is unknown
    with pytest.raises(TypeError, match="flash_bwd"):
        TrainConfig.from_dict({"learner_overlap": {"enabled": True, "flash_bwd": "xla"}})


def test_per_layer_remat_policy_registered():
    from trlx_tpu.models.transformer import remat_policy

    assert remat_policy("per_layer") is None  # nn.remat with block-boundary saves
    assert remat_policy("nothing_saveable") is not None


def test_manual_axes_guard():
    """constrain helpers must no-op inside shard_map bodies (manual axes):
    the contextvar-style guard nests and restores."""
    assert not in_manual_axes()
    with manual_axes():
        assert in_manual_axes()
        with manual_axes():
            assert in_manual_axes()
        assert in_manual_axes()
    assert not in_manual_axes()
