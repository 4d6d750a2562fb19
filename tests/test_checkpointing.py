"""Native pre-converted checkpoints (trlx_tpu/checkpointing.py) — the analogue of
the reference's llama→NeMo converter (`examples/llama_nemo/convert_llama_to_nemo.py`),
made topology-independent: one converted store restores onto any mesh."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.test_hf_parity import make_hf_model
from trlx_tpu import checkpointing
from trlx_tpu.models.hf_loading import load_pretrained
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.parallel.mesh import make_mesh
from trlx_tpu.parallel.sharding import make_param_shardings


@pytest.fixture(scope="module")
def hf_dir(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf_gpt2")
    make_hf_model("gpt2").save_pretrained(path)
    return str(path)


@pytest.fixture(scope="module")
def native_dir(hf_dir, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("native"))
    checkpointing.main(["convert", hf_dir, out])
    return out


def test_convert_writes_metadata(native_dir):
    meta = checkpointing.load_native_config(native_dir)
    assert meta["model_type"] == "gpt2"
    assert meta["format_version"] == 1
    assert meta["config"]["hidden_size"] == 32


def test_load_pretrained_roundtrips_through_native(hf_dir, native_dir):
    config_hf, params_hf, type_hf = load_pretrained(
        hf_dir, {"compute_dtype": jnp.float32}
    )
    config_nat, params_nat, type_nat = load_pretrained(
        native_dir, {"compute_dtype": jnp.float32}
    )
    assert type_hf == type_nat == "gpt2"
    assert config_nat.hidden_size == config_hf.hidden_size
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params_hf,
        params_nat,
    )

    # and the restored params actually run
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 61, (2, 8)), jnp.int32)
    model = TransformerLM(config_nat)
    logits, *_ = model.apply({"params": params_nat}, ids, jnp.ones_like(ids))
    assert np.isfinite(np.asarray(logits)).all()


def test_restore_direct_to_mesh_shardings(native_dir):
    """Restore straight into NamedShardings on an 8-device mesh — the per-host
    partial-read path a pod would take (no host-replicated intermediate)."""
    mesh = make_mesh(data=2, fsdp=2, model=2)
    config, params_host, _ = checkpointing.restore_native(native_dir)
    shardings = make_param_shardings({"transformer": params_host}, mesh)["transformer"]
    config, params, model_type = checkpointing.restore_native(
        native_dir, shardings=shardings
    )
    assert model_type == "gpt2"
    leaves = jax.tree.leaves(params)
    assert all(isinstance(leaf, jax.Array) for leaf in leaves)
    spec_leaves = jax.tree.leaves(shardings)
    assert any(leaf.sharding.spec == s.spec and not leaf.is_fully_replicated
               for leaf, s in zip(leaves, spec_leaves))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        params_host,
    )


def test_restore_with_mesh_derives_shardings(native_dir):
    """The trainer-facing path: restore_native(mesh=...) derives shardings from
    the stored metadata (no host-replicated intermediate, no prior param tree)."""
    mesh = make_mesh(data=2, fsdp=2, model=2)
    _, params, _ = checkpointing.restore_native(native_dir, mesh=mesh)
    leaves = jax.tree.leaves(params)
    assert all(isinstance(leaf, jax.Array) for leaf in leaves)
    assert any(not leaf.is_fully_replicated for leaf in leaves)
    _, params_host, _ = checkpointing.restore_native(native_dir)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        params_host,
    )


def test_arch_mismatch_raises(native_dir):
    with pytest.raises(ValueError, match="causal"):
        checkpointing.restore_native(native_dir, expect_seq2seq=True)


def test_unknown_override_raises(native_dir):
    with pytest.raises(TypeError, match="Unknown config override"):
        checkpointing.restore_native(native_dir, {"hidden_sizee": 64})


def test_convert_dtype_cast(hf_dir, tmp_path):
    out = str(tmp_path / "bf16")
    checkpointing.convert_hf_to_native(hf_dir, out, dtype="bfloat16")
    _, params, _ = checkpointing.restore_native(out)
    dtypes = {np.asarray(x).dtype for x in jax.tree.leaves(params)}
    assert jnp.dtype(jnp.bfloat16) in {jnp.dtype(d) for d in dtypes}


def test_inspect_cli(native_dir, capsys):
    checkpointing.main(["inspect", native_dir])
    out = capsys.readouterr().out
    assert "gpt2" in out and "hidden_size" in out


def test_trainer_native_with_scan_layers(native_dir, tmp_path):
    """Stacked layout (scan_layers) forces the host-restore fallback
    (restore_mesh -> None): loaded shards must be host arrays so the [L, ...]
    restack works — then training proceeds normally."""
    import trlx_tpu
    from trlx_tpu.data.configs import (
        MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig,
        TokenizerConfig, TrainConfig, TRLConfig,
    )
    from trlx_tpu.methods.sft import SFTConfig

    config = TRLConfig(
        method=SFTConfig(gen_kwargs=dict(max_new_tokens=4)),
        train=TrainConfig(
            seq_length=16, epochs=2, total_steps=2, batch_size=4,
            checkpoint_interval=100, eval_interval=100,
            checkpoint_dir=str(tmp_path / "ckpts"),
            pipeline="PromptPipeline", trainer="SFTTrainer", tracker=None, seed=3,
        ),
        model=ModelConfig(model_path=native_dir, num_layers_unfrozen=-1,
                          model_overrides={"scan_layers": True}),
        tokenizer=TokenizerConfig(tokenizer_path="char://abcdefgh "),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)),
        mesh=MeshConfig(data=2, fsdp=2, model=2, compute_dtype="float32"),
    )
    trainer = trlx_tpu.train(
        samples=[["ab", "cd"], ["ef", "gh"]] * 2, eval_prompts=["ab"], config=config
    )
    assert trainer.iter_count >= 2


def test_trainer_runs_from_native_checkpoint(native_dir, tmp_path):
    """End-to-end: model_path pointing at a converted store trains PPO on the
    8-device mesh (restore → merge → shard → train)."""
    import trlx_tpu
    from trlx_tpu.data.configs import (
        MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig,
        TokenizerConfig, TrainConfig, TRLConfig,
    )
    from trlx_tpu.methods.ppo import PPOConfig

    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=4, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
            target=None,
            gen_kwargs=dict(max_new_tokens=4, do_sample=True, top_k=0, top_p=1.0),
        ),
        train=TrainConfig(
            seq_length=16, epochs=3, total_steps=2, batch_size=4,
            checkpoint_interval=100, eval_interval=100,
            checkpoint_dir=str(tmp_path / "ckpts"),
            pipeline="PromptPipeline", trainer="PPOTrainer", tracker=None, seed=3,
        ),
        model=ModelConfig(model_path=native_dir, num_layers_unfrozen=1),
        tokenizer=TokenizerConfig(tokenizer_path="char://abcdefgh "),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)),
        mesh=MeshConfig(data=2, fsdp=2, model=2, compute_dtype="float32"),
    )
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cd", "ef", "gh"],
        eval_prompts=["ab"],
        config=config,
    )
    assert trainer.iter_count >= 2


def test_convert_missing_weights_raises(tmp_path):
    """A preset name (no local weights) must NOT silently produce a random-init
    'native checkpoint': raising is the default, --allow-random the
    explicit opt-in."""
    from trlx_tpu import checkpointing

    with pytest.raises(FileNotFoundError, match="allow-random"):
        checkpointing.convert_hf_to_native("gpt2", str(tmp_path / "out"))
    out = checkpointing.convert_hf_to_native(
        "gpt2", str(tmp_path / "out2"), allow_random=True,
        overrides=dict(vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
                       max_position_embeddings=32),
    )
    cfg, params, model_type = checkpointing.restore_native(out)
    assert model_type == "gpt2" and params is not None


def test_restore_rejects_newer_format_version(tmp_path):
    from trlx_tpu import checkpointing

    out = checkpointing.convert_hf_to_native(
        "gpt2", str(tmp_path / "out"), allow_random=True,
        overrides=dict(vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
                       max_position_embeddings=32),
    )
    meta = checkpointing.load_native_config(out)
    meta["format_version"] = checkpointing.FORMAT_VERSION + 1
    import json as _json
    with open(out + "/" + checkpointing.NATIVE_CONFIG, "w") as f:
        _json.dump(meta, f)
    with pytest.raises(ValueError, match="format_version"):
        checkpointing.restore_native(out)


def test_native_config_tuple_fields_roundtrip(tmp_path):
    """lora_targets is a tuple; JSON stores a list; restore must hand back a
    tuple so config equality/replace semantics survive the round-trip."""
    from trlx_tpu import checkpointing

    out = checkpointing.convert_hf_to_native(
        "gpt2", str(tmp_path / "out"), allow_random=True,
        overrides=dict(vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
                       max_position_embeddings=32, lora_r=2,
                       lora_targets=("q_proj", "v_proj")),
    )
    cfg, _, _ = checkpointing.restore_native(out)
    assert cfg.lora_targets == ("q_proj", "v_proj")
    assert isinstance(cfg.lora_targets, tuple)
