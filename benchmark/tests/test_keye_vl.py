"""The keye_vl family's CPU rehearsal: a whole run at tiny widths (sound:
``correct``), the same run with the control's lower precision and with each of
the selection's planted faults (``correct`` false), the cell's files against the
catalog, the family's operation counts against numbers worked by hand, and the
selection's reader."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

import faults_keye_vl
from conftest import LIMITS, PPO, REPO_ROOT

sys.path.insert(0, os.path.join(REPO_ROOT, "benchmark"))
sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))
import run as bench_run  # noqa: E402
from keye_vl_tiny import tiny_config  # noqa: E402

from benchmark import flops_keye_vl as flops, harness  # noqa: E402
from benchmark.readers import dsa  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}
KEYE = harness.load_json("configs", "keye-vl-2.0-30b-a3b.json")
CELL = harness.load_json("workloads", "keye-vl-2.0-30b-a3b.ppo-long-prompt-8k.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell():
    return dict(
        config="tiny-keye-vl", chips=1, who="tests", why="tests", prompt_len=24, new_tokens=8, num_rollouts=8,
        decode_batch_size=4, chunk_size=2, batch_size=4, minibatch_size=2, ppo_epochs=2,
        ppo=dict(PPO), limits=dict(LIMITS),
    )


def measure(tmp_path, config=None):
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for section in ("end_to_end", "per_layer"):
        for entry in benchmark[section]:
            entry.pop("workloads", None)
    return bench_run.measure("tiny-keye-vl", tiny_cell(), config or tiny_config(held=4, topk=8), 2 ** 31 + 11, 0.2,
                             0, DEVICE, harness.load_peaks("TPU v5 lite"), benchmark, out_dir=str(tmp_path / "out"))


def test_sound_run_is_correct(tmp_path):
    """The whole PPO path through ``trlx_tpu.train()`` (rollout over the indexer
    cache, scoring, three optimizer steps through ``make_grad_accum_step``), and the
    learner's and the generator's ``dsa/`` and ``moe/`` counters."""
    from trlx_tpu.utils.metrics import gauges

    gauges.clear("dsa/")
    gauges.clear("moe/")
    result = measure(tmp_path)
    assert result["correct"] is True, result["compared"]
    assert 0.3 < result["metrics"]["ppo_tokens_per_s"]["value"]
    assert result["info"]["leaves_left_out_of_update_gap"] == 0  # the indexer's leaves are not among the trained
    counters = gauges.snapshot("dsa/")
    # a microbatch: 2 rows of 24 + 9 tokens (prompts unpadded), min(8, t + 1) kept of t + 1, in each of 2 layers
    kept = 2 * 2 * sum(min(8, t + 1) for t in range(33))
    assert counters["dsa/kept_pairs"] == kept and counters["dsa/causal_pairs"] == 2 * 2 * 33 * 34 / 2
    assert counters["dsa/queries_off"] == 0
    # 4 rows of 7 decode steps after the prompt's 32 slots (24 tokens, left-padded to the ladder's rung), 2 layers:
    # 16 float32 indexer dims a written slot, 8 selected slots of (k + v) x 2 heads x 16
    written = range(33, 40)
    assert counters["dsa/decode_index_bytes_per_step"] == 2 * sum(4 * s * 16 * 4 for s in written) / 7
    assert counters["dsa/decode_kv_bytes_per_step"] == 2 * 4 * 8 * 2 * 2 * 16 * 4
    assert gauges.snapshot("moe/")["moe/assignments"] == 2 * 2 * 33 * 2


def test_the_control_in_lower_precision_is_not_correct(tmp_path):
    result = measure(tmp_path, tiny_config(held=4, topk=8, param_dtype="bfloat16"))
    assert result["correct"] is False
    assert result["compared"]["update_gap"]["value"] > 3 * LIMITS["update_gap"], result["compared"]


@pytest.mark.parametrize("fault", sorted(faults_keye_vl.FAULTS))
def test_a_planted_fault_of_the_selection_is_not_correct(tmp_path, fault):
    with faults_keye_vl.FAULTS[fault]():
        result = measure(tmp_path)
    assert result["correct"] is False
    for name in ("score_logprobs_gap", "grad_gap"):  # as on the chip; loss_gap_1 does not separate them (PERF.md §6)
        assert result["compared"][name]["value"] > result["compared"][name]["limit"], result["compared"]


def test_files_hold_the_published_widths_and_the_cut():
    control = harness.load_json("configs", "keye-vl-2.0-30b-a3b.control-bf16-masters.json")
    assert control.pop("control_of") == "keye-vl-2.0-30b-a3b"
    assert control["precision"] == {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
    for key in KEYE:
        if key not in ("precision", "assumed"):
            assert control[key] == KEYE[key], key
    assert (KEYE["hidden_size"], KEYE["num_attention_heads"], KEYE["num_key_value_heads"], KEYE["head_dim"]) == (
        2048, 32, 4, 128)
    assert KEYE["sa_config"]["topk"] == 2048 and KEYE["sa_config"]["indexer_num_heads"] == 16
    assert (KEYE["moe_intermediate_size"], KEYE["num_experts_per_tok"], KEYE["rope_theta"]) == (768, 8, 10000000)
    assert KEYE["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert KEYE["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert (KEYE["num_hidden_layers"], KEYE["num_experts"], KEYE["vocab_size"]) == (4, 16, 151936 // 8)
    assert (CELL["prompt_len"], CELL["new_tokens"], CELL["num_rollouts"], CELL["decode_batch_size"]) == (
        7680, 512, 16, 16)
    assert (CELL["batch_size"], CELL["minibatch_size"], CELL["ppo_epochs"]) == (16, 1, 4)


def test_counts_by_hand():
    d = 2048
    # T = 8,193: the first 2,048 queries keep all they see, the rest 2,048 each
    assert flops.kept_pairs(KEYE, 8193) == 2048 * 2049 / 2 + 6145 * 2048 == 14_683_136
    assert flops.causal_pairs(8193) == 33_566_721
    assert flops.kept_pairs(KEYE, 100) == 5050
    assert flops.attention_weights(KEYE) == 2 * d * 4096 + 2 * d * 512 == 18_874_368
    assert flops.indexer_weights(KEYE) == d * (1024 + 64 + 16) == 2_260_992
    routed = 8 * 16 / 128 * 3 * d * 768
    assert flops.trunk_flops_per_token(KEYE) == 2 * 4 * (18_874_368 + 2_260_992 + d * 128 + routed)
    T = 8193
    assert flops.attention_flops(KEYE, T) == 4 * (4 * 32 * 128 * 14_683_136 + 2 * 16 * 64 * 33_566_721)
    assert flops.cache_bytes_per_token(KEYE) == 4 * (2 * 4 * 128 + 64) * 2
    it = flops.iteration_flops(KEYE, CELL)
    assert 0.6e15 < it["total"] < 0.7e15  # about 0.66 PFLOP an iteration
    assert flops.iteration_tokens(CELL) == 16 * 8192


def test_the_kernels_least_times_are_counted_by_hand():
    # a decode step reads the bf16 weights, every written slot's indexer key and the selected keys and values
    weights = 4 * (18_874_368 + 2_260_992 + 2048 * 128) + 4 * 16 * 3 * 2048 * 768 + 2048 * 18992
    assert flops.decode_weights(KEYE) == weights
    least = flops.decode_min_seconds(KEYE, CELL, PEAK)
    reads = sum(weights * 2 + 16 * 4 * ((7680 + s) * 64 * 2 + 2048 * 2 * 4 * 128 * 2) for s in range(1, 512))
    assert least == {"seconds": pytest.approx(reads / 819e9), "bound": "bytes"}
    one = flops.flash_min_seconds(KEYE, [{"kind": "forward", "sequences": 1, "length": 8193}], PEAK)
    t_flops = 4 * 32 * 2 * 2 * 128 * 14_683_136 / 197e12
    t_bytes = 4 * 2 * (32 + 4) * 8193 * 128 * 2 / 819e9
    assert one["seconds"] == pytest.approx(max(t_flops, t_bytes)) and one["bound"] == "flops"
    assert [c["length"] for c in flops.sparse_calls(KEYE, CELL)] == [7680, 8193, 8193, 8193]


def test_the_selections_reader_finds_nothing_where_there_is_nothing_to_read():
    from trlx_tpu.utils.metrics import gauges

    gauges.clear("dsa/")
    ctx = SimpleNamespace(trace=None, family=harness.family_of(KEYE), config=KEYE, cell=CELL, peaks=PEAK, notes={})
    assert dsa.kept_pairs_share(ctx) is None
    gauges.set("dsa/kept_pairs", 14_683_136.0)
    gauges.set("dsa/causal_pairs", 33_566_721.0)
    gauges.set("dsa/queries_off", 0.0)
    assert dsa.kept_pairs_share(ctx) == pytest.approx(0.43743, abs=1e-5)
    gauges.set("dsa/queries_off", 3.0)  # a selection that kept too few or too many is no reading
    with pytest.raises(ValueError, match="3 queries"):
        dsa.kept_pairs_share(ctx)
    gauges.clear("dsa/")
