"""The lfm2 family's CPU rehearsal: a whole run at tiny widths (sound: ``correct``),
the same run with the control's lower precision and with each planted fault
(``correct`` false), the family's operation counts against numbers worked by
hand, and the hybrid cache's readers."""

import json
import os
import sys

import pytest

import faults
from conftest import LIMITS, PPO, REPO_ROOT

sys.path.insert(0, os.path.join(REPO_ROOT, "benchmark"))
sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))
import run as bench_run  # noqa: E402
from lfm2_tiny import tiny_config  # noqa: E402

from benchmark import flops_lfm2, harness  # noqa: E402
from benchmark.readers import hybrid, loop, moe  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}
LFM2 = harness.load_json("configs", "lfm2-24b-a2b.json")
CELL = harness.load_json("workloads", "lfm2-24b-a2b.ppo-long-response.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell():
    return dict(
        config="tiny-lfm2", chips=1, who="tests", why="tests", prompt_len=8, new_tokens=8, num_rollouts=8,
        decode_batch_size=4, chunk_size=2, batch_size=4, minibatch_size=2, ppo_epochs=2,
        ppo=dict(PPO), limits=dict(LIMITS),
    )


def measure(tmp_path, config=None):
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for section in ("end_to_end", "per_layer"):
        for entry in benchmark[section]:
            entry.pop("workloads", None)
    return bench_run.measure("tiny-lfm2", tiny_cell(), config or tiny_config(held=4), 2 ** 31 + 11, 0.2, 0, DEVICE,
                             harness.load_peaks("TPU v5 lite"), benchmark, out_dir=str(tmp_path / "out"))


def test_sound_run_is_correct(tmp_path):
    result = measure(tmp_path)
    assert result["correct"] is True, result["compared"]
    assert result["metrics"]["ppo_tokens_per_s"]["value"] > 0


def test_the_control_in_lower_precision_is_not_correct(tmp_path):
    result = measure(tmp_path, tiny_config(held=4, param_dtype="bfloat16"))
    assert result["correct"] is False
    assert result["compared"]["update_gap"]["value"] > 3 * LIMITS["update_gap"], result["compared"]


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


def test_files_hold_the_published_widths_and_the_cut():
    control = harness.load_json("configs", "lfm2-24b-a2b.control-bf16-masters.json")
    assert control.pop("control_of") == "lfm2-24b-a2b"
    assert control["precision"] == {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
    for key in LFM2:
        if key not in ("precision", "assumed"):
            assert control[key] == LFM2[key], key
    # the published widths, uncut
    assert (LFM2["hidden_size"], LFM2["num_attention_heads"], LFM2["num_key_value_heads"]) == (2048, 32, 8)
    assert (LFM2["intermediate_size"], LFM2["moe_intermediate_size"], LFM2["num_experts_per_tok"]) == (11776, 1536, 4)
    assert LFM2["conv_L_cache"] == 3 and LFM2["rope_parameters"]["rope_theta"] == 1000000
    assert LFM2["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]
    published = LFM2["published"]
    assert sorted(published) == sorted(LFM2["reduced"])
    assert (published["num_hidden_layers"], published["num_dense_layers"], published["num_experts"],
            published["vocab_size"]) == (40, 2, 64, 65536)
    assert published["layer_types"].count("conv") == 30 and published["layer_types"][2] == "full_attention"
    assert LFM2["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    # the cell is cells 1 and 3's traffic but for the microbatch
    kimi = harness.load_json("workloads", "kimi-vl-a3b.ppo-long-response.json")
    for key in ("prompt_len", "new_tokens", "num_rollouts", "decode_batch_size", "chunk_size", "batch_size",
                "ppo_epochs", "ppo"):
        assert CELL[key] == kimi[key], key
    assert CELL["minibatch_size"] == 8


def test_counts_by_hand():
    d = 2048
    conv = d * 3 * d + d * 3 + d * d
    attention = 2 * d * 32 * 64 + 2 * d * 8 * 64
    assert flops_lfm2.conv_weights(LFM2) == conv == 16_783_360
    assert flops_lfm2.attention_weights(LFM2) == attention == 10_485_760
    assert flops_lfm2.expert_layers(LFM2) == 4 and flops_lfm2.expected_share(LFM2) == 0.125
    # a token: four convolutions (two products, three taps, two gates), one attention layer's projections,
    # the dense FFN, four routers of 64 and half an expert a layer (4 choices x 8 / 64)
    trunk = 2 * (4 * (4 * d * d + 3 * d + d) + attention + 3 * d * 11776 + 4 * (d * 64 + 0.5 * 3 * d * 1536))
    assert flops_lfm2.trunk_flops_per_token(LFM2) == trunk
    assert 0.33e9 < trunk < 0.36e9
    assert flops_lfm2.head_flops_per_token(LFM2) == 2 * d * 8192
    # T = 4: 10 visible pairs, 4 x 32 x 64 operations each, one attention layer
    assert flops_lfm2.causal_attention_flops(LFM2, 4) == 4 * 32 * 64 * 10
    assert flops_lfm2.cache_bytes_per_token(LFM2) == 2 * 8 * 64 * 2 == 2048
    assert flops_lfm2.state_bytes_per_row(LFM2) == 4 * 2 * d * 2 == 32768
    it = flops_lfm2.iteration_flops(LFM2, CELL)
    T = 64 + 449
    forward = T * trunk + flops_lfm2.causal_attention_flops(LFM2, T)
    head, value = 449 * 2 * d * 8192, 449 * flops_lfm2.value_head_flops_per_token(LFM2)
    assert it["learn"] == 4 * 128 * 3 * (forward + head + value)
    assert it["score"] == 128 * (2 * forward + 2 * head + value)
    assert 0.33e15 < it["total"] < 0.40e15  # about 0.37 PFLOP an iteration
    assert flops_lfm2.iteration_tokens(CELL) == 128 * 512


def test_the_kernels_least_times_are_counted_by_hand():
    # a decode step reads the bf16 weights, the states in and out, and the written slots: bound by bytes
    weights = 4 * 16_783_360 + 10_485_760 + 3 * 2048 * 11776 + 4 * (2048 * 64 + 8 * 3 * 2048 * 1536) + 2048 * 8192
    assert flops_lfm2.decode_weights(LFM2) == weights
    least = flops_lfm2.decode_min_seconds(LFM2, CELL, PEAK)
    cache = sum(128 * (64 + s) * 2048 for s in range(1, 448))
    states = 447 * 2 * 128 * 32768
    assert least == {"seconds": pytest.approx((447 * weights * 2 + states + cache) / 819e9), "bound": "bytes"}
    assert 0.5 < least["seconds"] < 0.6  # about 1.2 ms a step
    # the flash calls at 32 / 8 heads of 64 and length 513: q and o at 32 heads, k and v at 8
    calls = flops_lfm2.flash_calls(LFM2, CELL)
    assert [c["length"] for c in calls] == [64, 513, 513, 513]
    one = flops_lfm2.flash_min_seconds(LFM2, [{"kind": "forward", "sequences": 1, "length": 513}], PEAK)
    t_bytes, t_flops = 2 * (32 + 8) * 513 * 64 * 2 / 819e9, 32 * 2 * 2 * 64 * (513 * 514 / 2) / 197e12
    assert one["seconds"] == pytest.approx(max(t_bytes, t_flops)) and one["bound"] == "bytes"
    # the grouped products: a learner microbatch of 8 x 513 tokens brings 2,052 rows to the 8 held experts
    call = [{"kind": "forward", "calls": 1, "tokens": 8 * 513}]
    gmm = flops_lfm2.gmm_min_seconds(LFM2, call, PEAK, 0.125)
    rows = 8 * 513 * 4 * 0.125
    t_flops, t_bytes = 3 * 2 * rows * 2048 * 1536 / 197e12, (3 * 8 * 2048 * 1536 * 2 + 2 * rows * 2048 * 2) / 819e9
    assert gmm["seconds"] == pytest.approx(4 * max(t_flops, t_bytes)) and gmm["bound"] == "bytes"
    assert [c["tokens"] for c in flops_lfm2.gmm_calls(LFM2, CELL)] == [128 * 64, 128, 32 * 513, 8 * 513, 8 * 513]


def test_readers_find_nothing_where_there_is_nothing_to_read():
    """A program without convolution layers sets no ``hybrid/`` gauge, and an
    untraced run has no kernels to time: the readers return None and raise nothing."""
    from types import SimpleNamespace

    from trlx_tpu.utils.metrics import gauges

    gauges.clear("hybrid/")
    gauges.clear("moe/")
    family = harness.family_of(LFM2)
    ctx = SimpleNamespace(trace=None, family=family, config=LFM2, cell=CELL, peaks=PEAK, notes={})
    assert hybrid.cache_bytes_per_token(ctx) is None and hybrid.state_bytes_per_row(ctx) is None
    assert loop.decode_roofline(ctx, "^jit_generate\\b") is None
    assert moe.gmm_roofline(ctx, ["^%t?gmm[.0-9]* custom-call$"]) is None
    gauges.set("hybrid/cache_bytes_per_token", 2048)
    assert hybrid.cache_bytes_per_token(ctx) == 2048
    gauges.clear("hybrid/")
