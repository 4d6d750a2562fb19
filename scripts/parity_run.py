"""Reward-parity evidence runner: converge the example tasks and record the
reward curves in a PARITY json file.

The reference's headline artifact is quality results — reward curves for its
examples (`/root/reference/examples/hh/README.md` W&B runs; randomwalks is its
deterministic, fully-offline benchmark task, reference
examples/randomwalks/randomwalks.py:29). This runs each trainer to its task
target and captures steps -> reward, so convergence is seen and not only unit
tests and throughput.

Each run executes in a subprocess (fresh jax runtime; a failure fails one leg,
not the whole collection). Curves are parsed from the jsonl tracker. Results
MERGE into the output file one leg at a time, so an interrupted collection
keeps what finished.

Usage: python scripts/parity_run.py [--out PARITY.json]
           [--legs ppo_randomwalks,ilql_randomwalks,...] [--cpu]
"""

import glob
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_leg(name, script, hparams, log_dir, timeout_s=5400, env=None):
    """Run one example to convergence; return (curve_dict, error|None)."""
    t0 = time.time()
    run_env = dict(os.environ)
    if env:
        run_env.update(env)
    proc = subprocess.run(
        [sys.executable, script, json.dumps(hparams)],
        cwd=REPO, capture_output=True, text=True, timeout=timeout_s, env=run_env,
    )
    err = None
    if proc.returncode != 0:
        err = (proc.stderr or "").strip().splitlines()[-1:] or ["no stderr"]
        err = f"rc={proc.returncode}: {err[-1]}"
    curve = parse_jsonl_curve(log_dir)
    curve["wall_s"] = round(time.time() - t0, 1)
    return curve, err


def iter_tracker_rows(log_dir):
    """Parsed rows of the NEWEST jsonl tracker under ``log_dir`` (the single
    place that knows the tracker layout — curve parsing and the hh KL
    accounting both consume it)."""
    files = sorted(glob.glob(os.path.join(log_dir, "logs", "*.jsonl")), key=os.path.getmtime)
    if not files:
        return
    for line in open(files[-1]):
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        yield row


def parse_jsonl_curve(log_dir):
    """Extract rollout/eval reward curves from the newest jsonl tracker file."""
    out = {"rollout_curve": [], "eval_curve": []}
    for row in iter_tracker_rows(log_dir):
        step = row.get("step")
        if step is None:
            continue
        if "rollout_scores/mean" in row:
            out["rollout_curve"].append([step, round(row["rollout_scores/mean"], 4)])
        eval_val = row.get("metrics/optimality", row.get("reward/mean"))
        if eval_val is not None:
            out["eval_curve"].append([step, round(eval_val, 4)])
    # thin the rollout curve for the artifact (keep every step for short runs)
    rc = out["rollout_curve"]
    if len(rc) > 120:
        out["rollout_curve"] = rc[:: len(rc) // 100]
        if out["rollout_curve"][-1] != rc[-1]:
            out["rollout_curve"].append(rc[-1])
    ec = out["eval_curve"]
    if ec:
        out["start"] = ec[0][1]
        out["final"] = ec[-1][1]
        out["best"] = max(v for _, v in ec)
    return out


def platform_info(env=None):
    code = (
        "import json, jax; d = jax.devices()[0]; "
        "print(json.dumps({'platform': jax.default_backend(), 'device': d.device_kind, "
        "'n_devices': jax.device_count()}))"
    )
    run_env = dict(os.environ)
    if env:
        run_env.update(env)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=300, env=run_env,
        )
        for line in proc.stdout.splitlines():
            if line.startswith("{"):
                return json.loads(line)
    except Exception:
        pass
    return {"platform": "unknown", "device": "unknown"}


# Leg table. Targets: the randomwalks oracle tops out at 1.0 — PPO reliably
# exceeds 0.9 (measured 0.988 on one TPU chip, round 3); ILQL is offline
# learning from random-walk data only and plateaus ~0.82-0.85, so its bar is
# 0.8. Sentiment legs use the lexicon reward in [-1, 1] from the SFT'd offline
# base (practical ceiling ~0.9 causal / ~0.7 seq2seq; round-3 measured curves).
def _legs():
    def ck(name):
        return os.path.join(REPO, "ckpts", name)

    return {
        "ppo_randomwalks": dict(
            script=os.path.join(REPO, "examples", "randomwalks", "ppo_randomwalks.py"),
            hparams={"train.total_steps": 100, "train.eval_interval": 10},
            log_dir=ck("parity_ppo_rw"), target=0.9,
        ),
        "ilql_randomwalks": dict(
            script=os.path.join(REPO, "examples", "randomwalks", "ilql_randomwalks.py"),
            # 1000 steps, the round-3 budget: the 600-step trim undershot on
            # TPU (best 0.756@600, takeoff ~150 steps later than the round-1
            # curve; the task plateau ~0.82-0.85 needs the full budget)
            hparams={"train.total_steps": 1000, "train.eval_interval": 50},
            log_dir=ck("parity_ilql_rw"), target=0.8,
        ),
        "ppo_sentiments": dict(
            script=os.path.join(REPO, "examples", "ppo_sentiments.py"),
            hparams={"train.total_steps": 500, "train.eval_interval": 50},
            log_dir=ck("parity_ppo_sent"), target=0.7,
        ),
        "ppo_sentiments_t5": dict(
            script=os.path.join(REPO, "examples", "ppo_sentiments_t5.py"),
            hparams={"train.total_steps": 700, "train.eval_interval": 50},
            log_dir=ck("parity_ppo_t5"), target=0.5,
        ),
        "ppo_xl": dict(
            script=os.path.join(REPO, "examples", "randomwalks", "ppo_randomwalks.py"),
            # >=1B-parameter leg: gpt2-xl shaped policy
            # (48 x 1600, ~1.47B trunk params at the walk vocab) with
            # scan_layers + remat + bf16 params + 8-bit Adam moments. The
            # convergence bar is the task's PPO bar scaled to the small step
            # budget this size affords: a clearly rising curve toward ~0.7+.
            hparams={
                "pretrain_steps": 120,
                "pretrain_lr": 1e-4,  # 1e-3 (tiny-model default) spikes at 1.47B
                "optimizer.kwargs.lr": 1e-4,
                "optimizer.kwargs.max_grad_norm": 1.0,
                "scheduler.name": "cosine_warmup",
                "scheduler.kwargs.warmup_steps": 10,
                "scheduler.kwargs.total_steps": 400,
                "scheduler.kwargs.eta_min": 1e-5,
                "train.total_steps": 25, "train.eval_interval": 3,
                "train.batch_size": 16,
                "model.model_overrides.num_layers": 48,
                "model.model_overrides.hidden_size": 1600,
                "model.model_overrides.num_heads": 25,
                "model.model_overrides.intermediate_size": 6400,
                "model.model_overrides.scan_layers": True,
                "model.model_overrides.remat": "nothing_saveable",
                "optimizer.name": "adamw_8bit_bnb",
                # host-offloaded full KL reference — the memory option this
                # model size exists to exercise (ModelConfig.offload_ref)
                "model.offload_ref": True,
                "mesh.param_dtype": "bfloat16",
                "mesh.compute_dtype": "bfloat16",
                "method.num_rollouts": 16,
                "method.chunk_size": 16,
                "method.ppo_epochs": 2,
            },
            # CPU fallback runs a SINGLE virtual device: 8-way layouts either
            # hold 8 param copies (data: OOM'd the 125GB host) or run
            # collectives inside the scanned stack, which XLA CPU's
            # InProcessCommunicator hard-aborts after a 40s rendezvous skew —
            # one physical core cannot land 8 heavy threads inside the window.
            # Sharded-at-scale evidence stays with dryrun_multichip + the TPU
            # queue variant of this leg (single chip, default mesh).
            # CPU overlay (scripts/xl_microbench.py is the committed evidence):
            # f32 compute (XLA CPU emulates bf16 matmuls 5.3x slower: 1.78s vs
            # 9.36s for 1600x6400x1600) and plain adamw (the 8-bit update's
            # per-element log/exp quantization costs 429s/step on one core vs
            # 44s for the whole fwd+bwd — trivial on the TPU VPU, prohibitive
            # here). bf16 param storage, scan, remat and offload_ref — the
            # memory machinery — stay on. Step budget trimmed to what ~85s/step
            # affords; the full config runs on the TPU queue variant.
            hparams_cpu={"mesh.data": 1, "mesh.fsdp": 1,
                         "mesh.compute_dtype": "float32",
                         # f32 masters on CPU: plain optax.adamw keeps moments
                         # in the PARAM dtype, and bf16 masters+moments at
                         # depth 48 destabilize the first updates (loss 3.3->7
                         # at both lr 1e-3 and 1e-4); the TPU variant keeps
                         # bf16 params with the 8-bit optimizer's f32 math
                         "mesh.param_dtype": "float32",
                         "optimizer.name": "adamw",
                         "pretrain_steps": 60,
                         "train.total_steps": 18,
                         "train.eval_interval": 5},
            env_cpu={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
            log_dir=ck("parity_ppo_xl"), target=0.7, timeout_s=14400,
        ),
        "ppo_350m": dict(
            script=os.path.join(REPO, "examples", "randomwalks", "ppo_randomwalks.py"),
            # gpt2-medium-shaped (~354M) convergence leg: the largest size a
            # single CPU core turns around inside a round (measured: 1.47B is
            # ~5 min/step — scripts/xl_microbench.py — so the >=1B convergence
            # claim is TPU-queue-only). Same memory machinery as ppo_xl:
            # scan_layers + full remat + host-offloaded KL ref + warmup/clip.
            hparams={
                "pretrain_steps": 50,
                "pretrain_lr": 1e-4,
                "optimizer.kwargs.lr": 1e-4,
                "optimizer.kwargs.max_grad_norm": 1.0,
                "scheduler.name": "cosine_warmup",
                "scheduler.kwargs.warmup_steps": 8,
                "scheduler.kwargs.total_steps": 300,
                "scheduler.kwargs.eta_min": 1e-5,
                "train.total_steps": 15, "train.eval_interval": 3,
                "train.batch_size": 16,
                # small fixed KL anchor: randomwalks' default init_kl_coef=0
                # lets a 354M policy over-optimize and wobble late in the run
                # (first r4 attempt: rollout 0.713 @ step 12 -> 0.479 @ 15)
                "method.init_kl_coef": 0.02,
                "model.model_overrides.num_layers": 24,
                "model.model_overrides.hidden_size": 1024,
                "model.model_overrides.num_heads": 16,
                "model.model_overrides.intermediate_size": 4096,
                "model.model_overrides.scan_layers": True,
                "model.model_overrides.remat": "nothing_saveable",
                "model.offload_ref": True,
                "method.num_rollouts": 16,
                "method.chunk_size": 16,
                "method.ppo_epochs": 2,
            },
            hparams_cpu={"mesh.data": 1, "mesh.fsdp": 1,
                         "mesh.compute_dtype": "float32",
                         "mesh.param_dtype": "float32",
                         "optimizer.name": "adamw"},
            env_cpu={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
            log_dir=ck("parity_ppo_350m"), target=0.6, timeout_s=9000,
        ),
    }


DEFAULT_LEGS = ["ppo_randomwalks", "ilql_randomwalks", "ppo_sentiments", "ppo_sentiments_t5"]


def main():
    out_path = os.path.join(REPO, "PARITY.json")
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    names = DEFAULT_LEGS
    if "--legs" in sys.argv:
        names = sys.argv[sys.argv.index("--legs") + 1].split(",")
    env = None
    if "--cpu" in sys.argv:
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "PYTHONPATH": REPO,
        }

    try:
        with open(out_path) as f:
            result = json.load(f)
    except (OSError, json.JSONDecodeError):
        result = {}
    result.setdefault(
        "task",
        "per-leg convergence vs offline oracles (randomwalks optimality / lexicon sentiment)",
    )
    plat = platform_info(env)
    legs = _legs()
    targets = result.setdefault("target", {})
    failed = []

    for name in names:
        spec = legs[name]
        log_dir = spec["log_dir"]
        targets[name] = spec["target"]
        hparams = dict(spec["hparams"])
        leg_env = env
        if env is not None:  # --cpu: apply the leg's virtual-mesh overrides
            hparams.update(spec.get("hparams_cpu", {}))
            leg_env = {**env, **spec.get("env_cpu", {})}
        hparams.setdefault("train.checkpoint_dir", log_dir)
        hparams.setdefault("train.checkpoint_interval", 100000)
        curve, err = run_leg(
            name, spec["script"], hparams, log_dir,
            timeout_s=spec.get("timeout_s", 5400), env=leg_env,
        )
        prior = result.get(name)
        if isinstance(prior, dict):
            # never clobber non-reproducible hand-recorded evidence: a failed
            # re-run keeps the prior entry and only annotates the attempt
            if err and not curve.get("eval_curve") and not curve.get("rollout_curve"):
                prior["last_attempt_error"] = err
                prior["last_attempt_at"] = time.time()
                result["measured_at"] = time.time()
                with open(out_path, "w") as f:
                    json.dump(result, f, indent=1)
                print(json.dumps({name: {"kept_prior": True, "error": err}}))
                failed.append(name)
                continue
            for keep in ("cpu_infeasibility_record", "model"):
                if keep in prior and keep not in curve:
                    curve[keep] = prior[keep]
        curve["converged"] = bool(curve.get("best", -1e9) >= spec["target"])
        curve["platform"] = f"{plat.get('platform')} ({plat.get('device')})"
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if cache_dir and os.path.isdir(cache_dir):
            entries = [os.path.join(cache_dir, e) for e in os.listdir(cache_dir)]
            curve["compile_cache"] = {
                "entries": len(entries),
                "mb": round(sum(os.path.getsize(e) for e in entries if os.path.isfile(e)) / 1e6, 1),
            }
        if err:
            curve["error"] = err
            failed.append(name)
        result[name] = curve
        result["measured_at"] = time.time()
        with open(out_path, "w") as f:  # persist after EVERY leg
            json.dump(result, f, indent=1)
        print(json.dumps({name: {k: curve.get(k) for k in ("start", "final", "best", "converged", "error")}}))

    print(json.dumps({"out": out_path, "legs_done": names, "failed": failed}))
    # a failed leg must fail the invocation: callers gate on rc=0
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
