"""``flops.py`` against numbers worked by hand for gpt2."""

import pytest

from benchmark import flops, harness

GPT2 = harness.load_json("configs", "gpt2.json")
CELL = dict(prompt_len=64, new_tokens=448, num_rollouts=128, ppo_epochs=4)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_per_token_counts():
    # 12 layers x (4 x 768^2 + 2 x 768 x 3072) weights, 2 operations each
    assert flops.trunk_flops_per_token(GPT2) == 2 * 12 * (4 * 768 ** 2 + 2 * 768 * 3072) == 169_869_312
    assert flops.head_flops_per_token(GPT2) == 2 * 768 * 50257 == 77_194_752
    assert flops.value_head_flops_per_token(GPT2) == 2 * (768 * 1536 + 1536)


def test_causal_attention_is_the_lower_triangle():
    # T = 4: 1 + 2 + 3 + 4 = 10 visible pairs, 4 d operations each, 12 layers
    assert flops.causal_attention_flops(GPT2, 4) == 4 * 768 * 10 * 12
    full = 4 * 768 * 512 * 512 * 12
    assert flops.causal_attention_flops(GPT2, 512) == pytest.approx(full / 2, rel=0.01)


def test_iteration_by_phase():
    it = flops.iteration_flops(GPT2, CELL)
    T = 64 + 449
    forward = T * 169_869_312 + flops.causal_attention_flops(GPT2, T)
    head = 449 * 77_194_752
    value = 449 * flops.value_head_flops_per_token(GPT2)
    assert it["score"] == 128 * (2 * forward + 2 * head + value)
    assert it["learn"] == 4 * 128 * 3 * (forward + head + value)
    rollout = 511 * 169_869_312 + flops.causal_attention_flops(GPT2, 511) + 448 * 77_194_752
    assert it["rollout"] == 128 * rollout
    assert it["total"] == it["rollout"] + it["score"] + it["learn"]
    assert 2.0e14 < it["total"] < 2.6e14  # about 230 TFLOP an iteration
    assert flops.iteration_tokens(CELL) == 128 * 512


def test_flash_forward_at_512_is_bound_by_bytes_on_v5e():
    # one sequence, one layer's worth scaled by 12 layers x 12 heads of 64:
    # 2 matmuls x 2 x 64 x (512 x 513 / 2) operations against 4 x 512 x 64 x 2 bytes
    calls = [{"kind": "forward", "sequences": 1, "length": 512}]
    least = flops.flash_min_seconds(GPT2, calls, PEAK)
    ops = 12 * 12 * 2 * 2 * 64 * (512 * 513 / 2)
    nbytes = 12 * 12 * 4 * 512 * 64 * 2
    assert nbytes / 819e9 > ops / 197e12
    assert least == {"seconds": pytest.approx(nbytes / 819e9), "bound": "bytes"}
    back = flops.flash_min_seconds(GPT2, [{"kind": "backward", "sequences": 1, "length": 2048}], PEAK)
    assert back["bound"] == "flops"
