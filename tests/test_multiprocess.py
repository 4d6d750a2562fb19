"""Real multi-process distributed training test.

The reference has NO distributed unit tests (SURVEY.md §4); its multi-node path
is exercised only by manual slurm runs. Here the full trainer runs as TWO jax
processes (Gloo over localhost, 4 virtual CPU devices each → one 8-device global
mesh), exercising ``initialize_distributed`` (the TRLX_* env contract),
``put_batch``'s multi-host ``make_array_from_callback`` assembly (each host
slices its devices' shards from its identical copy of the global batch), and
the SPMD train loop end-to-end. Both processes must report identical final
losses — the single-program property the whole backend design rests on."""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import json, os, sys
sys.path.insert(0, %r)
# platform comes from env alone: jax.distributed.initialize (called inside the
# trainer) must run before ANY backend-initializing jax call
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax

import trlx_tpu
from trlx_tpu.data.configs import (MeshConfig, ModelConfig, OptimizerConfig,
                                   SchedulerConfig, TokenizerConfig, TrainConfig, TRLConfig)
from trlx_tpu.methods.sft import SFTConfig

from trlx_tpu.methods.ppo import PPOConfig

ALPHABET = "abcdefgh "
mode = sys.argv[2]
if mode == "sft":
    method = SFTConfig(gen_kwargs=dict(max_new_tokens=4))
    trainer_name, total_steps = "SFTTrainer", 100
else:
    method = PPOConfig(num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01,
                       target=None,
                       overlap_reward_scoring=(mode == "ppo_rpz_overlap"),
                       gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0))
    trainer_name, total_steps = "PPOTrainer", 2
config = TRLConfig(
    method=method,
    train=TrainConfig(seq_length=16, epochs=1, total_steps=total_steps, batch_size=8,
                      checkpoint_interval=100000, eval_interval=100000,
                      checkpoint_dir=sys.argv[1], pipeline="PromptPipeline",
                      trainer=trainer_name, tracker=None, seed=3,
                      # ppo_rpz: explicit on. ppo_rpz_overlap: None exercises the
                      # auto default (multi-process => process-0 + broadcast).
                      # ppo: explicit off (the per-host scoring path).
                      reward_on_process_zero={"ppo_rpz": True,
                                              "ppo_rpz_overlap": None}.get(mode, False)),
    model=ModelConfig(model_path="gpt2", num_layers_unfrozen=1 if mode == "ppo" else -1,
                      model_overrides=dict(vocab_size=len(ALPHABET)+3, hidden_size=32,
                                           num_layers=2, num_heads=2,
                                           max_position_embeddings=64)),
    tokenizer=TokenizerConfig(tokenizer_path="char://" + ALPHABET),
    optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
    scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)),
    mesh=MeshConfig(data=4, fsdp=2, model=1, compute_dtype="float32"),
)
if mode == "sft":
    samples = [["ab", "cd"], ["ef", "gh"], ["a", "bc"], ["de", "fg"]] * 2
    trainer = trlx_tpu.train(samples=samples, config=config)
else:
    def reward_fn(samples, **kw):
        if mode.startswith("ppo_rpz"):
            # the process-0 + broadcast path must NEVER call reward_fn on
            # other hosts (the served-RM contract); crash loudly if it does —
            # including from the overlap worker thread (ppo_rpz_overlap)
            assert jax.process_index() == 0, "reward_fn called off process 0"
        return [float(s.count("a")) for s in samples]
    trainer = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=["ab", "cd ef", "gh", "a b c"] * 2, config=config,
    )
batch = next(iter(trainer.create_train_dataloader()))
stats = trainer.train_step(batch)
loss_key = next(k for k in stats if "loss" in k)
print("MP_RESULT " + json.dumps({
    "process": jax.process_index(), "world": jax.process_count(),
    "devices": jax.device_count(), "steps": trainer.iter_count,
    "final_loss": float(stats[loss_key]),
}), flush=True)
""" % (REPO_ROOT,)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["sft", "ppo", "ppo_rpz", "ppo_rpz_overlap"])
def test_two_process_training(tmp_path, mode):
    port = _free_port()
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            PYTHONPATH=REPO_ROOT,
            TRLX_NUM_PROCESSES="2",
            TRLX_COORDINATOR=f"127.0.0.1:{port}",
            TRLX_PROCESS_ID=str(pid),
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), str(tmp_path / f"ck{pid}"), mode],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
            )
        )
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            assert p.returncode == 0, out[-3000:]
            line = next(l for l in out.splitlines() if l.startswith("MP_RESULT "))
            results.append(json.loads(line[len("MP_RESULT "):]))
    finally:
        for p in procs:  # never leak a wedged jax process into later tests
            if p.poll() is None:
                p.kill()
                p.wait()
    assert [r["world"] for r in results] == [2, 2]
    assert [r["devices"] for r in results] == [8, 8]
    assert results[0]["steps"] == results[1]["steps"] > 0
    # the single-program property: both hosts computed the SAME loss
    assert results[0]["final_loss"] == results[1]["final_loss"]
