"""``chip_smoke.py`` rehearsed on the CPU: its kernel, PPO and sharded phase
functions at tiny widths (Pallas interpreted), and its refusal to report
anything from a device that is not a TPU. The chip run itself is the driver's;
a pass here says the script's paths, arguments and assertions hold together."""

import os
import sys

import pytest

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    # vocab above the byte tokenizer's 259, as gpt2's 50257 is: ids it cannot
    # decode must be survivable
    model_overrides=dict(
        vocab_size=300, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position_embeddings=64,
    ),
    compute_dtype="float32",
    prompt_len=8, new_tokens=8, batch=4, steps=3,
    flash_batch=2, flash_len=128,
    slots=4, num_blocks=40, block_size=4, max_blocks=4,
    interpret=True,
)


def test_kernels_phase_at_tiny_widths(capsys):
    chip_smoke.phase_kernels(TINY)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("[kernels]")]
    assert len(lines) == 8  # flash out/dq/dk/dv + paged {bf16,int8} x {decode,verify}
    assert all("max|diff|=" in l for l in lines)


@pytest.mark.parametrize("serving", [False, True], ids=["one_shot", "serving"])
def test_ppo_phase_at_tiny_widths(tmp_path, capsys, serving):
    """Through ``trlx_tpu.train()`` on a one-device mesh, as on the chip."""
    one_device = jax.devices()[:1]
    with _only_devices(one_device):
        trainer = chip_smoke.phase_ppo(TINY, str(tmp_path), serving=serving)
    assert trainer.iter_count == TINY.steps
    assert (trainer._serving_client is not None) == serving
    if not serving:  # set after every one-shot generate; at these sizes one block holds the whole cache
        from trlx_tpu.utils.metrics import gauges

        assert gauges.snapshot("rollout/").get("rollout/cache_read_share") == 1.0
        # 4 rows with their 2 kv heads beside them: 8 of a program's 128 lanes hold a (row, kv head) pair
        assert gauges.snapshot("rollout/").get("rollout/cache_lane_fill") == 8 / 128
    out = capsys.readouterr().out
    assert "0 after it (CompileWatcher)" in out
    assert f"step {TINY.steps}/{TINY.steps}:" in out
    if serving:
        assert "resolved paged impl: xla" in out  # the CPU's own path


def test_ppo_moe_phase_at_tiny_widths(tmp_path, capsys):
    """The sparse-expert family's phase: latent attention and routed experts
    through ``trlx_tpu.train()`` on a one-device mesh."""
    import dataclasses

    tiny_moe = dataclasses.replace(
        TINY, model_path=chip_smoke.MOE.model_path, steps=chip_smoke.MOE.steps,
        model_overrides=dict(
            vocab_size=300, hidden_size=32, num_layers=2, num_heads=2, intermediate_size=64,
            max_position_embeddings=64, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, num_experts=8, experts_held=4, experts_per_token=2, moe_intermediate_size=16,
        ),
    )
    with _only_devices(jax.devices()[:1]):
        trainer = chip_smoke.phase_ppo(tiny_moe, str(tmp_path), phase="ppo_moe")
    assert trainer.model_config.attention_kind == "mla" and trainer.model_config.is_expert_layer(1)
    out = capsys.readouterr().out
    assert "[ppo_moe] step 1/1:" in out and "0 after it (CompileWatcher)" in out


def test_sharded_phase_on_four_virtual_devices(tmp_path, capsys):
    with _only_devices(jax.devices()[:4]):
        chip_smoke.phase_sharded(TINY, str(tmp_path))
    out = capsys.readouterr().out
    assert out.count("  device ") == 4
    assert "first optimizer step health/grad_norm" in out


def test_main_refuses_a_cpu_device(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out and "[" not in captured.out  # no phase ran
    assert "not a TPU" in captured.err


class _only_devices:
    """The test session has 8 virtual CPU devices; the chip has 1 (or 4). Show
    the code under test that many: every mesh it builds from ``jax.devices()``
    is then the mesh it would build there."""

    def __init__(self, devices):
        self.devices = list(devices)

    def __enter__(self):
        self._patch = pytest.MonkeyPatch()
        self._patch.setattr(jax, "devices", lambda *a, **k: self.devices)
        self._patch.setattr(jax, "device_count", lambda *a, **k: len(self.devices))
        return self

    def __exit__(self, *exc):
        self._patch.undo()
