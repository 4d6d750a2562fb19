"""The ouro family's CPU rehearsal: a whole run at tiny widths (sound: ``correct``),
the same run with the control's lower precision and with each planted fault
(``correct`` false), the family's operation counts against numbers worked by
hand, and the decode loop's roofline reader on the recorded program trace."""

import json
import os
import sys

import pytest

import faults
from conftest import LIMITS, PPO, REPO_ROOT

sys.path.insert(0, os.path.join(REPO_ROOT, "benchmark"))
sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))
import run as bench_run  # noqa: E402
from ouro_tiny import tiny_config  # noqa: E402

from benchmark import flops_ouro, harness  # noqa: E402
from benchmark.readers import loop  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}
OURO = harness.load_json("configs", "ouro-2.6b.json")
CELL = harness.load_json("workloads", "ouro-2.6b.ppo-response-192.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell():
    return dict(
        config="tiny-ouro", chips=1, who="tests", why="tests", prompt_len=8, new_tokens=8, num_rollouts=8,
        decode_batch_size=4, chunk_size=2, batch_size=4, minibatch_size=2, ppo_epochs=2,
        ppo=dict(PPO), limits=dict(LIMITS),
    )


def measure(tmp_path, config=None):
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for section in ("end_to_end", "per_layer"):
        for entry in benchmark[section]:
            entry.pop("workloads", None)
    return bench_run.measure("tiny-ouro", tiny_cell(), config or tiny_config(), 2 ** 31 + 11, 0.2, 0, DEVICE,
                             harness.load_peaks("TPU v5 lite"), benchmark, out_dir=str(tmp_path / "out"))


def test_sound_run_is_correct(tmp_path):
    result = measure(tmp_path)
    assert result["correct"] is True, result["compared"]
    assert result["metrics"]["ppo_tokens_per_s"]["value"] > 0


def test_the_control_in_lower_precision_is_not_correct(tmp_path):
    result = measure(tmp_path, tiny_config(param_dtype="bfloat16"))
    assert result["correct"] is False
    assert result["compared"]["update_gap"]["value"] > 0.5


@pytest.mark.parametrize("fault, failed", [
    ("state_unchanged", ("update_gap",)), ("half_batch", ("grad_gap",)), ("token_altered", ("rollout_gap",)),
    ("score_policy_shifted", ("score_logprobs_gap", "score_values_gap", "loss_gap_1")),
    ("score_reference_shifted", ("score_rewards_gap",)),
])
def test_a_planted_fault_is_not_correct(tmp_path, fault, failed):
    with faults.FAULTS[fault]():
        result = measure(tmp_path)
    assert result["correct"] is False
    for name in failed:
        assert result["compared"][name]["value"] > result["compared"][name]["limit"], result["compared"]


def test_control_file_differs_from_its_cell_in_precision_alone():
    control = harness.load_json("configs", "ouro-2.6b.control-bf16-masters.json")
    assert control.pop("control_of") == "ouro-2.6b"
    assert control["precision"] == {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
    for key in OURO:
        if key not in ("precision", "assumed"):
            assert control[key] == OURO[key], key


def test_counts_by_hand():
    # a layer: 4 x 2048 x 2048 + 3 x 2048 x 5632; 4 passes of 5 layers
    assert flops_ouro.layer_weights(OURO) == 4 * 2048 * 2048 + 3 * 2048 * 5632 == 51_380_224
    assert flops_ouro.block_applications(OURO) == 20
    assert flops_ouro.trunk_flops_per_token(OURO) == 2 * 20 * 51_380_224
    assert flops_ouro.head_flops_per_token(OURO) == 2 * 2048 * 49152
    # T = 4: 10 visible pairs, 4 x 16 x 128 operations each, 20 block applications
    assert flops_ouro.causal_attention_flops(OURO, 4) == 4 * 2048 * 10 * 20
    assert flops_ouro.cache_bytes_per_token(OURO) == 20 * 8192 == 163_840
    it = flops_ouro.iteration_flops(OURO, CELL)
    T = 64 + 193
    forward = T * 2 * 20 * 51_380_224 + flops_ouro.causal_attention_flops(OURO, T)
    head, value = 193 * 2 * 2048 * 49152, 193 * flops_ouro.value_head_flops_per_token(OURO)
    assert it["learn"] == 4 * 128 * 3 * (forward + head + value)
    assert it["score"] == 128 * (2 * forward + 2 * head + value)
    assert 1.0e15 < it["total"] < 1.4e15  # about 1.2 PFLOP an iteration
    assert flops_ouro.iteration_tokens(CELL) == 128 * 256


def test_a_decode_step_is_bound_by_bytes_and_counted_by_hand():
    least = flops_ouro.decode_min_seconds(OURO, CELL, PEAK)
    weights = (20 * 51_380_224 + 2048 * 49152) * 2
    cache = sum(128 * (64 + s) * 163_840 for s in range(1, 192))
    assert least == {"seconds": pytest.approx((191 * weights + cache) / 819e9), "bound": "bytes"}
    assert 1.0 < least["seconds"] < 1.6  # about 7 ms a step
    # the flash calls at D = 128 and length 257: bound by bytes forward, as at gpt2's 513 x 64
    calls = flops_ouro.flash_calls(OURO, CELL)
    assert [c["length"] for c in calls] == [64, 257, 257, 257]
    one = flops_ouro.flash_min_seconds(OURO, [{"kind": "forward", "sequences": 1, "length": 257}], PEAK)
    assert one["seconds"] == pytest.approx(20 * 16 * 4 * 257 * 128 * 2 / 819e9) and one["bound"] == "bytes"


def test_readers_find_nothing_where_there_is_nothing_to_read():
    """A program without looped layers sets no gauge, and an untraced run has
    no decode loop to time: both readers return None and raise nothing."""
    from types import SimpleNamespace

    from trlx_tpu.utils.metrics import gauges

    gauges.clear("loop/")
    family = harness.family_of(OURO)
    ctx = SimpleNamespace(trace=None, family=family, config=OURO, cell=CELL, peaks=PEAK, notes={})
    assert loop.cache_bytes_per_token(ctx) is None
    assert loop.decode_roofline(ctx, "^jit_generate\\b") is None
    gpt2 = harness.family_of(harness.load_json("configs", "gpt2.json"))
    assert loop.decode_roofline(SimpleNamespace(trace={"ops": []}, family=gpt2), "^jit_generate\\b") is None
