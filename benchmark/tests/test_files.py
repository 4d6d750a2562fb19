"""The data files: what is refused, and that BENCHMARK.json and the files agree."""

import json
import os
from unittest import mock

import pytest

from conftest import REPO_ROOT

from benchmark import harness


def test_unknown_workload_key_raises():
    cell = harness.load_json("workloads", "gpt2.ppo-long-response.json")
    cell["beam_width"] = 4
    with mock.patch.object(harness, "load_json", lambda *parts: cell):
        with pytest.raises(ValueError, match="beam_width"):
            harness.load_cell("gpt2.ppo-long-response")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        harness.load_peaks("TPU v9")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_benchmark_json_and_files_agree():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    assert benchmark["paths"] == ["benchmark"]
    names = {c["name"] for c in benchmark["configs"]}
    for cell in benchmark["workloads"]:
        workload, config = harness.load_cell(cell["name"])
        assert workload["config"] == cell["config"] and cell["config"] in names
        assert workload["chips"] == cell["chips"] and workload["why"] == cell["why"]
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert set(workload["limits"]) == {
            "loss_gap_1", "grad_gap", "update_gap", "rollout_gap",
            "score_logprobs_gap", "score_values_gap", "score_rewards_gap"}
    for section in ("end_to_end", "per_layer"):
        for entry in benchmark[section]:
            spec = harness.load_json("metrics", f"{entry['name']}.json")
            for key, value in entry.items():
                if key != "workloads":  # the cells that report it: BENCHMARK.json alone lists them
                    assert spec[key] == value, (entry["name"], key)
            module, function = spec["reader"].rsplit(".", 1)
            assert os.path.exists(os.path.join(harness.HERE, "readers", f"{module}.py"))


def test_the_reference_covers_every_program_leaf():
    """Every parameter the program makes at gpt2's layout has a reference
    weight of its shape, and no reference weight is left over."""
    import jax
    import jax.numpy as jnp

    from conftest import tiny_config
    from trlx_tpu.models.policy import CausalLMWithValueHead
    from trlx_tpu.models.presets import get_preset

    config = tiny_config()
    family = harness.family_of(config)
    module = CausalLMWithValueHead(get_preset(family.MODEL_PATH, family.program_overrides(config)))
    like = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32))
    )["params"]
    weights = family.reference.init_weights(config, 7)
    tree = harness.to_program_tree(family, weights, like, jnp.float32)
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(lambda a: a.shape, like)
    used = {family.leaf_name(path)[0] for path, _ in harness._paths(like)}
    assert used == set(weights)
