"""The CPU rehearsal of a whole run at tiny widths: the window, the token
count, ``correct`` on a sound run; then the same run with the timed path broken
underneath, once for each fault the cells can have, and with the control's
lower-precision configuration: ``correct`` has to come out false."""

import json
import os
import sys
import pytest

import faults
from conftest import REPO_ROOT, tiny_cell, tiny_config

sys.path.insert(0, os.path.join(REPO_ROOT, "benchmark"))
import run as bench_run  # noqa: E402

from benchmark import flops, harness  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}


def measure(tmp_path, cell=None, config=None, seed=2 ** 31 + 11, seconds=0.2):
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for section in ("end_to_end", "per_layer"):  # the tiny cell reports what the real ones do
        for entry in benchmark[section]:
            entry.pop("workloads", None)
    peaks = harness.load_peaks("TPU v5 lite")
    return bench_run.measure("tiny", cell or tiny_cell(), config or tiny_config(), seed, seconds, 0,
                             DEVICE, peaks, benchmark, out_dir=str(tmp_path / "out"))


def test_sound_run_window_tokens_and_correct(tmp_path):
    result = measure(tmp_path)
    assert result["correct"] is True, result["compared"]
    n = result["attempted"]
    assert n >= 1 and result["failed"] == 0
    # whole iterations: the rate is exactly their tokens over their summed time
    times = result["info"]["iteration_s"]
    assert len(times) == n and sum(times) >= 0.2
    cell = tiny_cell()
    tokens = n * cell["num_rollouts"] * (cell["prompt_len"] + cell["new_tokens"])
    assert tokens == n * flops.iteration_tokens(cell)
    rate = result["metrics"]["ppo_tokens_per_s"]["value"]
    assert rate == pytest.approx(tokens / sum(times), rel=1e-9)
    assert result["metrics"]["setup_s"]["value"] > 0
    assert list(result)[-1] == "compared"
    assert not os.path.exists(tmp_path / "out" / "ckpts")  # the closed window wrote no checkpoint


def test_a_traced_run_profiles_one_iteration_and_closes(tmp_path):
    from benchmark import trace_reduce

    session = harness.run_cell(tiny_cell(), tiny_config(), 5, 1000.0, True, 0.0, str(tmp_path / "out"))
    harness.free_program_state(session)
    assert session.iterations == 1 and session.traced == session.window
    _, host = trace_reduce.load(trace_reduce.find_xplane(session.trace_dir), ("rollout", "score", "learn"))
    cell = tiny_cell()
    steps = cell["ppo_epochs"] * cell["num_rollouts"] // cell["batch_size"]
    assert sum(1 for name, _, _ in host if name == "learn") == steps


def test_state_left_unchanged_is_not_correct(tmp_path):
    with faults.state_unchanged():
        result = measure(tmp_path)
    assert result["correct"] is False
    assert result["compared"]["update_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(tmp_path):
    with faults.half_batch():
        result = measure(tmp_path)
    assert result["correct"] is False
    assert result["compared"]["grad_gap"]["value"] > result["compared"]["grad_gap"]["limit"]
    assert set(result["info"]["not_compared"]) == {"loss_gap_2", "loss_gap_3"}


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path):
    with faults.token_altered():
        result = measure(tmp_path)
    assert result["correct"] is False
    assert result["compared"]["rollout_gap"]["value"] > result["compared"]["rollout_gap"]["limit"]


@pytest.mark.parametrize("fault, failed", [
    ("score_policy_shifted", ("score_logprobs_gap", "score_values_gap", "loss_gap_1")),
    ("score_reference_shifted", ("score_rewards_gap",)),
])
def test_a_scoring_answer_altered_is_not_correct(tmp_path, fault, failed):
    with faults.FAULTS[fault]():
        result = measure(tmp_path)
    assert result["correct"] is False
    for name in failed:
        assert result["compared"][name]["value"] > result["compared"][name]["limit"], result["compared"]


def test_the_control_in_lower_precision_is_not_correct(tmp_path):
    """bf16 master weights and moments, the program's own lower-precision
    path, as ``configs/*.control-bf16-masters.json`` state it."""
    result = measure(tmp_path, config=tiny_config(param_dtype="bfloat16"))
    assert result["correct"] is False
    assert result["compared"]["update_gap"]["value"] > 0.5


def test_control_files_differ_from_their_cells_in_precision_alone():
    for name in ("gpt2", "gpt2-medium"):
        cell_config = harness.load_json("configs", f"{name}.json")
        control = harness.load_json("configs", f"{name}.control-bf16-masters.json")
        assert control.pop("control_of") == name
        assert control["precision"] == {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
        for key in cell_config:
            if key not in ("precision", "assumed"):
                assert control[key] == cell_config[key], key
