"""Scale-proof guarantee (scripts/scale_proof.py): the large-model configs
must keep PROVING they place on their target TPU slices. The slow test re-runs
the deviceless TPU AOT compile for the 7B config end-to-end (local libtpu; no
chip) and asserts the v5e verdict from scratch.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GIB = 1024 ** 3
# GiB per DEVICE, public specs: v5e chip = one 16 GiB device
V5E_BUDGET_GIB = 16.0


@pytest.mark.slow
def test_7b_v5e_compile_from_scratch():
    """Deviceless TPU AOT compile of the 7B tp4/fsdp4 config (train step +
    cached-decode generation) must fit 16 v5e chips. ~6-8 min on one CPU core."""
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO_ROOT,
        "JAX_PLATFORMS": "cpu",
        "TPU_ACCELERATOR_TYPE": "v5litepod-16",
        "TPU_WORKER_HOSTNAMES": "localhost",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "scale_proof.py"),
         "--child", "--config",
         os.path.join(REPO_ROOT, "configs", "ppo_llama2_7b_tp4_fsdp4.yml"),
         "--topology", "v5e:4x4"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=3600,
    )
    assert proc.returncode == 0, (proc.stderr or "")[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("SCALE_PROOF_RESULT ")]
    assert line, proc.stdout[-2000:]
    leg = json.loads(line[-1][len("SCALE_PROOF_RESULT "):])
    budget = V5E_BUDGET_GIB * GIB
    assert leg["train_step"]["peak_bytes"] <= budget
    assert leg["generation_step"]["peak_bytes"] <= budget
    assert leg["n_params_b"] > 6.5  # genuinely 7B-scale
