"""Tiny cell and configuration for the CPU rehearsals of the harness."""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

PPO = dict(lr=3e-5, b1=0.9, b2=0.95, eps=1e-8, weight_decay=1e-6, gamma=1.0, lam=0.95,
           cliprange=0.2, cliprange_value=0.2, vf_coef=1.0, init_kl_coef=0.001, cliprange_reward=10.0)
#: wide enough that every sound CPU run passes, far under what any fault reads
LIMITS = dict(loss_gap_1=2e-3, grad_gap=0.05, update_gap=0.05, rollout_gap=0.02,
              score_logprobs_gap=1e-3, score_values_gap=1e-4, score_rewards_gap=1e-5)


def tiny_config(param_dtype="float32", compute_dtype="float32"):
    return dict(
        name="tiny", source="benchmark/tests", family="gpt2", model_type="gpt2", vocab_size=300, n_positions=64,
        n_embd=32, n_layer=2, n_head=2, n_inner=None, activation_function="gelu_new",
        layer_norm_epsilon=1e-5, initializer_range=0.02, tie_word_embeddings=True, reduced=[],
        assumed={}, precision=dict(param_dtype=param_dtype, compute_dtype=compute_dtype),
    )


def tiny_cell():
    return dict(
        config="tiny", chips=1, who="tests", why="tests", prompt_len=8, new_tokens=8, num_rollouts=8,
        decode_batch_size=4, chunk_size=2, batch_size=4, minibatch_size=2, ppo_epochs=2,
        ppo=dict(PPO), limits=dict(LIMITS),
    )
