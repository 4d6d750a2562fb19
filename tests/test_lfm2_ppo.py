"""Whole PPO iterations of the small lfm2 configuration through
``trlx_tpu.train()`` as the benchmark's harness drives them, against the
reference: the rollout (prefill, then decode over the two-kind cache), scoring,
and three optimizer steps through ``make_grad_accum_step`` with two
microbatches; each step's loss, every leaf's gradient and the parameters'
change compared, and the ``moe/`` and ``hybrid/`` gauges read."""

import json
import os
import sys

import jax

from benchmark import harness
from lfm2_tiny import tiny_config
from trlx_tpu.utils.metrics import gauges

sys.path.insert(0, harness.HERE)
import run as bench_run  # noqa: E402

PPO = dict(lr=3e-5, b1=0.9, b2=0.95, eps=1e-8, weight_decay=1e-6, gamma=1.0, lam=0.95, cliprange=0.2,
           cliprange_value=0.2, vf_coef=1.0, init_kl_coef=0.001, cliprange_reward=10.0)
#: float32 compute: ``benchmark/tests/conftest.py``'s tight limits
LIMITS = dict(loss_gap_1=2e-3, grad_gap=0.05, update_gap=0.05, rollout_gap=0.02,
              score_logprobs_gap=1e-3, score_values_gap=1e-4, score_rewards_gap=1e-5)
CELL = dict(config="tiny-lfm2", chips=1, who="tests", why="tests", prompt_len=8, new_tokens=8, num_rollouts=8,
            decode_batch_size=4, chunk_size=2, batch_size=4, minibatch_size=2, ppo_epochs=2, ppo=PPO, limits=LIMITS)


def measure(tmp_path, monkeypatch, config):
    # the session has 8 virtual devices and the harness's mesh takes the chip's one
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    monkeypatch.setattr(jax, "device_count", lambda *a, **k: 1)
    with open(os.path.join(os.path.dirname(harness.HERE), "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for entry in benchmark["end_to_end"]:
        entry.pop("workloads", None)
    return bench_run.measure(
        "tiny-lfm2", CELL, config, 2 ** 31 + 5, 0.2, 0,
        {"platform": "cpu", "kind": "rehearsal", "count": 1}, harness.load_peaks("TPU v5 lite"), benchmark,
        out_dir=str(tmp_path / "out"))


def test_the_control_in_lower_precision_is_not_correct(tmp_path, monkeypatch):
    """bf16 master weights and moments, as ``configs/lfm2-24b-a2b.control-bf16-masters.json``
    states them: most weights of 0.02 cannot take a step of 3e-5, and ``update_gap`` says so."""
    result = measure(tmp_path, monkeypatch, tiny_config(held=4, param_dtype="bfloat16"))
    assert result["correct"] is False
    assert result["compared"]["update_gap"]["value"] > 3 * LIMITS["update_gap"], result["compared"]


def test_a_ppo_iteration_against_the_reference_and_its_counters(tmp_path, monkeypatch):
    gauges.clear("moe/")
    gauges.clear("hybrid/")
    result = measure(tmp_path, monkeypatch, tiny_config(held=4))
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["update_gap"]["value"] < 0.01
    assert result["info"]["leaves_left_out_of_update_gap"] <= 3  # only the selection biases take no gradient
    counters = gauges.snapshot("moe/")
    # a microbatch: 2 rows of 8 + 9 tokens, 2 experts a token, 3 expert layers
    assert counters["moe/assignments"] == 2 * 17 * 2 * 3
    assert 0 < counters["moe/assignments_held"] < counters["moe/assignments"]
    assert counters["moe/load_max"] >= counters["moe/load_mean"] > 0
    # one attention layer of (k + v) x 2 kv heads x 16 float32 values a token; three states of 2 x 64 a row
    assert gauges.get("hybrid/cache_bytes_per_token") == 2 * 2 * 16 * 4
    assert gauges.get("hybrid/state_bytes_per_row") == 3 * 2 * 64 * 4
    assert gauges.get("hybrid/attention_layers") == 1 and gauges.get("hybrid/conv_layers") == 3
