"""Sweep CLI unit tests: trial generation strategies, the parallel/ASHA
executor (against a fake trial script), and report writing."""

import json
import time

from trlx_tpu.sweep import AshaScheduler, generate_trials, run_trials

# The fake trials never touch jax; keep them off any accelerator all the same.
NO_TPU_ENV = {"JAX_PLATFORMS": "cpu"}

FAKE_TRIAL = '''
import json, os, sys, time
hp = json.loads(sys.argv[1])
stop = os.environ.get("TRLX_SWEEP_STOP_FILE")
q = hp["method.q"]
delay = hp.get("delay", 0.05)
last = 0.0
for step in range(1, 6):
    last = q * step
    print("SWEEP_METRIC " + json.dumps({"step": step, "reward/mean": last}), flush=True)
    time.sleep(delay)
    if stop and os.path.exists(stop):
        break
print("SWEEP_RESULT " + json.dumps({"reward/mean": last}), flush=True)
'''


def test_grid_trials():
    cfg = {
        "tune_config": {"search_alg": "grid"},
        "train.seed": {"strategy": "choice", "values": [1, 2]},
        "method.gamma": {"strategy": "choice", "values": [0.9, 0.99]},
    }
    trials = generate_trials(cfg)
    assert len(trials) == 4
    assert {json.dumps(t, sort_keys=True) for t in trials} == {
        json.dumps(t, sort_keys=True)
        for t in (
            {"train.seed": 1, "method.gamma": 0.9},
            {"train.seed": 1, "method.gamma": 0.99},
            {"train.seed": 2, "method.gamma": 0.9},
            {"train.seed": 2, "method.gamma": 0.99},
        )
    }


def test_asha_executor_stops_bad_trials(tmp_path):
    """Sequential ASHA: trials worse than the incumbent at a rung are stopped
    through the stop-file protocol (no signals), and the report records it."""
    script = tmp_path / "fake_trial.py"
    script.write_text(FAKE_TRIAL)
    trials = [{"method.q": 2.0}, {"method.q": 1.0}, {"method.q": 0.1}]
    sched = AshaScheduler("reward/mean", "max", grace_steps=1, eta=2)
    out = str(tmp_path / "res.jsonl")
    report = str(tmp_path / "report.md")
    results = run_trials(
        str(script), trials, out, "reward/mean", "max",
        max_concurrent=1, scheduler=sched, report_path=report, extra_env=NO_TPU_ENV,
    )
    assert [r["returncode"] for r in results] == [0, 0, 0]
    assert not results[0]["early_stopped"]
    assert results[1]["early_stopped"] and results[2]["early_stopped"]
    best = max((r for r in results if "metrics" in r), key=lambda r: r["metrics"]["reward/mean"])
    assert best["hparams"]["method.q"] == 2.0
    text = open(report).read()
    assert "Sweep report" in text and "early-stopped" in text
    lines = open(out).read().strip().splitlines()
    assert len(lines) == 3


def test_parallel_executor_overlaps_trials(tmp_path):
    script = tmp_path / "fake_trial.py"
    script.write_text(FAKE_TRIAL)
    trials = [{"method.q": float(i), "delay": 0.2} for i in range(4)]  # ~1s each
    t0 = time.time()
    results = run_trials(
        str(script), trials, str(tmp_path / "res.jsonl"), "reward/mean", "max",
        max_concurrent=4, extra_env=NO_TPU_ENV,
    )
    wall = time.time() - t0
    assert all(r["returncode"] == 0 for r in results)
    assert all(r["num_reports"] == 5 for r in results)  # no scheduler: full runs
    assert wall < 3.0, f"4 x ~1s trials took {wall:.1f}s; not overlapping"


def test_random_trials_strategies():
    cfg = {
        "tune_config": {"search_alg": "random", "num_samples": 16},
        "method.init_kl_coef": {"strategy": "loguniform", "values": [1e-4, 1e-1]},
        "optimizer.kwargs.lr": {"strategy": "uniform", "values": [1e-5, 1e-3]},
        "train.seed": {"strategy": "int", "values": [0, 100]},
        "train.batch_size": {"strategy": "choice", "values": [8, 16]},
    }
    trials = generate_trials(cfg, seed=1)
    assert len(trials) == 16
    for t in trials:
        assert 1e-4 <= t["method.init_kl_coef"] <= 1e-1
        assert 1e-5 <= t["optimizer.kwargs.lr"] <= 1e-3
        assert 0 <= t["train.seed"] <= 100
        assert t["train.batch_size"] in (8, 16)
    # reproducible
    assert generate_trials(cfg, seed=1) == trials
