"""hh served-reward convergence runner → an HH_RPC json file.

Shape of the hh evidence leg: a BPE-tokenized
policy (from-scratch byte-level BPE trained on the hh corpus —
trlx_tpu/pipeline/bpe.py; ``--size tiny`` keeps the round-4 byte-level
recipe, ``--size 125m`` is the gpt2-124M-shaped TPU-queue variant), a pairwise
ranking RM whose held-out accuracy is recorded (design target ~(0.7, 0.95);
the BPE-tokenized RM separates the graded pairs a bit more cleanly and can
land just above — the disjoint-seed guard RM is what makes the evidence
robust to an easy served RM), PPO with
sustained delta-vs-chosen growth, AND overoptimization guards that
distinguish learning from reward hacking:

- a SECOND ranking RM (disjoint training seed/data) scores the final policy's
  outputs — a hacked policy overfits the served RM's quirks and scores low on
  the held-out RM;
- win-rate of PPO outputs vs the SFT base's outputs under that held-out RM;
- KL-to-base spent per unit of reward gained (parsed from the tracker).

Chain: sft_hh.ensure_hh_base (offline SFT base; a random init never discovers
whole reward words by exploration) -> train_tiny_rm.py x2 (served + held-out)
-> serve_reward.py (HTTP, Triton shape) -> ppo_hh.py (TRLX_REWARD_URL,
overlap scoring, final checkpoint exported) -> guards subprocess.

Usage: python scripts/hh_rpc_run.py [--out HH_RPC.json] [--cpu]
           [--steps 350] [--size small|tiny|125m] [--rm-dir ckpts/...]
"""

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
sys.path.insert(0, REPO)  # examples.* imports (HH_SIZES)

from parity_run import iter_tracker_rows, parse_jsonl_curve, platform_info  # noqa: E402

CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": REPO,
}
SERVER_ENV = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO, "XLA_FLAGS": ""}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def ensure_rm(rm_dir: str, tokenizer_path: str, seed: int = 0) -> dict:
    from examples.hh.train_tiny_rm import tokenizer_content_sha

    meta_path = os.path.join(rm_dir, "rm_meta.json")
    if os.path.exists(meta_path):
        # a cached RM keyed to a DIFFERENT tokenizer (by path OR by merge-table
        # content — the same bpe:// path can hold a retrained table) reads
        # different token ids for the same text, and one trained with a
        # different SEED voids the disjoint-data guarantee the held-out guard
        # RM exists for — retrain rather than serve garbage/cloned scores
        with open(meta_path) as f:
            meta = json.load(f)
        stale = (
            meta.get("tokenizer", "bytes") != tokenizer_path
            or meta.get("seed") != seed
            or meta.get("tokenizer_content_sha") != tokenizer_content_sha(tokenizer_path)
        )
        if stale:
            import shutil

            shutil.rmtree(rm_dir, ignore_errors=True)
    if not os.path.exists(meta_path):
        proc = subprocess.run(
            [sys.executable, "examples/hh/train_tiny_rm.py", "--out", rm_dir,
             "--tokenizer", tokenizer_path, "--seed", str(seed)],
            cwd=REPO, env={**os.environ, **SERVER_ENV}, timeout=3600,
            capture_output=True, text=True,
        )
        if proc.returncode != 0:
            raise RuntimeError(f"RM training failed: {(proc.stderr or '')[-500:]}")
    with open(meta_path) as f:
        return json.load(f)


GUARDS_CHILD = r"""
import json, sys
sys.path.insert(0, ".")
import numpy as np
spec = json.loads(sys.argv[1])
from examples.hh.train_tiny_rm import load_ranking_rm
from examples.hh.ppo_hh import PROMPTS, CHOSEN
from examples.summarize_rlhf.rouge_eval import generate_summaries

score_fn = load_ranking_rm(spec["heldout_rm_dir"])
chosen_scores = score_fn(CHOSEN)

outs = {}
for name in ("sft", "ppo"):
    texts = []
    for seed in range(spec["n_seeds"]):
        preds = generate_summaries(
            spec[name + "_model"], spec["tokenizer"], PROMPTS,
            max_new_tokens=spec["max_new_tokens"], seed=seed, greedy=False,
        )
        texts.extend(preds)
    outs[name] = texts

sft_scores = np.asarray(score_fn(outs["sft"]), np.float64)
ppo_scores = np.asarray(score_fn(outs["ppo"]), np.float64)
chosen_mean = float(np.mean(chosen_scores))
print("GUARDS " + json.dumps({
    "n_outputs_per_policy": len(outs["ppo"]),
    "heldout_rm_sft_mean": float(sft_scores.mean()),
    "heldout_rm_ppo_mean": float(ppo_scores.mean()),
    "heldout_rm_chosen_mean": chosen_mean,
    "heldout_rm_ppo_delta_vs_chosen": float(ppo_scores.mean() - chosen_mean),
    "ppo_vs_sft_win_rate": float(np.mean(ppo_scores > sft_scores)),
    "sample_ppo_outputs": outs["ppo"][:3],
}))
"""


def run_guards(env, heldout_rm_dir, sft_model, ppo_model, tokenizer, max_new_tokens):
    """Held-out-RM scoring of SFT-base vs final-PPO generations (subprocess:
    needs its own CPU jax runtime)."""
    spec = {
        "heldout_rm_dir": heldout_rm_dir, "sft_model": sft_model,
        "ppo_model": ppo_model, "tokenizer": tokenizer,
        "max_new_tokens": max_new_tokens, "n_seeds": 4,
    }
    proc = subprocess.run(
        [sys.executable, "-c", GUARDS_CHILD, json.dumps(spec)],
        cwd=REPO, env={**env, "XLA_FLAGS": ""}, timeout=3600,
        capture_output=True, text=True,
    )
    for line in (proc.stdout or "").splitlines():
        if line.startswith("GUARDS "):
            return json.loads(line[len("GUARDS "):])
    return {"error": f"rc={proc.returncode}: " + (proc.stderr or "").strip()[-300:]}


def kl_per_reward(log_dir):
    """Parse KL spend vs reward gain from the run's jsonl tracker: the
    reference anchors its hh claims to reward curves ALONE, which cannot
    distinguish optimization from drift — KL-per-reward is the price tag."""
    kls, rewards = [], []
    for row in iter_tracker_rows(log_dir):
        if "policy/sqrt_kl" in row:
            kls.append(float(row["policy/sqrt_kl"]) ** 2)
        if "rollout_scores/mean" in row:
            rewards.append(float(row["rollout_scores/mean"]))
    if not kls or len(rewards) < 2:
        return {}
    # gain = late-window mean minus early-window mean (same convention as the
    # curve's late_minus_early): a peak-based gain would make a spike-then-
    # collapse hacked run look like cheap optimization — the exact failure
    # mode this price tag exists to expose
    w = max(1, len(rewards) // 10)
    gain = sum(rewards[-w:]) / w - sum(rewards[:w]) / w
    mean_kl = sum(kls) / len(kls)
    return {
        "mean_seq_kl_to_base": round(mean_kl, 4),
        "reward_gain": round(gain, 4),
        "reward_gain_peak": round(max(rewards) - rewards[0], 4),
        "kl_per_unit_reward": round(mean_kl / gain, 4) if gain > 1e-6 else None,
    }


def main():
    out_path = os.path.join(REPO, "HH_RPC.json")
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    size = "small"
    if "--size" in sys.argv:
        size = sys.argv[sys.argv.index("--size") + 1]
    steps = 350
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    env = dict(os.environ)
    if "--cpu" in sys.argv:
        env.update(CPU_ENV)

    from examples.hh.sft_hh import HH_SIZES

    spec = HH_SIZES[size]
    # the BPE tokenizer must exist before RM training; ensure_hh_base builds it
    # too, but the RM runs first
    if spec["bpe"]:
        bpe_proc = subprocess.run(
            [sys.executable, "-c",
             "import sys; sys.path.insert(0, '.'); "
             f"from examples.hh.sft_hh import ensure_hh_bpe; print(ensure_hh_bpe({spec['bpe']}))"],
            cwd=REPO, env={**os.environ, **SERVER_ENV}, timeout=1800,
            capture_output=True, text=True,
        )
        if bpe_proc.returncode != 0:
            raise RuntimeError(f"BPE training failed: {(bpe_proc.stderr or '')[-500:]}")
        tokenizer_path = bpe_proc.stdout.strip().splitlines()[-1]
    else:
        tokenizer_path = "bytes"

    rm_dir = f"ckpts/hh_rm_{size}" if "--rm-dir" not in sys.argv else (
        sys.argv[sys.argv.index("--rm-dir") + 1])
    rm_dir = os.path.join(REPO, rm_dir)
    heldout_rm_dir = rm_dir + "_heldout"
    rm_meta = ensure_rm(rm_dir, tokenizer_path, seed=0)
    heldout_meta = ensure_rm(heldout_rm_dir, tokenizer_path, seed=1000)
    acc = rm_meta.get("heldout_pairwise_acc")

    # offline SFT base (cached + fingerprinted), subprocess for its own runtime
    base_proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); "
         f"from examples.hh.sft_hh import ensure_hh_base; print(ensure_hh_base(size={size!r}))"],
        cwd=REPO, env=env,
        timeout=7200, capture_output=True, text=True,
    )
    if base_proc.returncode != 0:
        raise RuntimeError(f"hh base SFT failed: {(base_proc.stderr or '')[-500:]}")
    hh_model = base_proc.stdout.strip().splitlines()[-1]

    port = _free_port()
    server = subprocess.Popen(
        [sys.executable, "examples/hh/serve_reward.py", "--port", str(port),
         "--model-dir", rm_dir],
        cwd=REPO, env={**os.environ, **SERVER_ENV},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    url = f"http://127.0.0.1:{port}/v2/models/reward/infer"
    try:
        import urllib.request

        for _ in range(120):
            try:
                urllib.request.urlopen(
                    urllib.request.Request(
                        url, data=json.dumps({"inputs": [
                            {"name": "outputs", "datatype": "BYTES", "shape": [1],
                             "data": ["probe"]}]}).encode(),
                        headers={"Content-Type": "application/json"}),
                    timeout=5,
                )
                break
            except Exception:
                if server.poll() is not None:
                    raise RuntimeError("reward server died during startup")
                time.sleep(1)
        else:
            raise RuntimeError("reward server never came up")

        log_dir = os.path.join(REPO, "ckpts", f"hh_rpc_r5_{size}")
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "examples/hh/ppo_hh.py", json.dumps({
                "train.total_steps": steps, "train.eval_interval": 25,
                "train.checkpoint_dir": log_dir,
                # export hf_model at the FINAL step: the guards generate from it
                "train.checkpoint_interval": steps,
                "train.seq_length": spec["seq_length"],
                "method.gen_kwargs.max_new_tokens": min(32, spec["seq_length"] // 2),
                "tokenizer.tokenizer_path": tokenizer_path,
            })],
            cwd=REPO, env={**env, "TRLX_REWARD_URL": url, "HH_MODEL": hh_model},
            capture_output=True, text=True, timeout=4 * 3600,
        )
        err = None
        if proc.returncode != 0:
            tail = (proc.stderr or "").strip().splitlines()[-1:] or ["no stderr"]
            err = f"rc={proc.returncode}: {tail[-1]}"
        curve = parse_jsonl_curve(log_dir)
        curve["wall_s"] = round(time.time() - t0, 1)
    finally:
        server.terminate()
        try:
            server.wait(timeout=10)
        except subprocess.TimeoutExpired:
            server.kill()

    plat = platform_info(CPU_ENV if "--cpu" in sys.argv else None)
    rc = curve.get("rollout_curve") or []

    def _mean(vals):
        return sum(vals) / max(len(vals), 1)

    early = [v for s, v in rc if 25 <= s <= 100]
    late = [v for s, v in rc if s >= max(s for s, _ in rc) - 100] if rc else []
    if not early or not late:
        early = late = []

    result = {
        "flow": (
            "hh RPC recipe (parity: reference examples/hh/ppo_hh.py): "
            f"{size} policy ({'bpe ' + str(spec['bpe']) if spec['bpe'] else 'byte'}-"
            "tokenized) offline SFT base -> pairwise ranking RM (served, Triton "
            "HTTP shape) -> PPO delta-vs-chosen -> held-out-RM guards"
        ),
        "size": size,
        "base_model": hh_model,
        "tokenizer": tokenizer_path,
        "platform": f"{plat.get('platform')} ({plat.get('device')})",
        "reward_is": "RM_scalar(output) - RM_scalar(chosen) from the served ranking RM",
        "rm_heldout_pairwise_acc": acc,
        "rm_acc_by_margin": rm_meta.get("heldout_acc_by_margin"),
        "heldout_rm_pairwise_acc": heldout_meta.get("heldout_pairwise_acc"),
        "steps": steps,
        **curve,
        "late_minus_early": round(_mean(late) - _mean(early), 4) if early else None,
        "kl_accounting": kl_per_reward(log_dir),
        "measured_at": time.time(),
    }
    if err:
        result["error"] = err
    else:
        ppo_export = os.path.join(log_dir, "hf_model")
        if os.path.exists(os.path.join(ppo_export, "config.json")):
            result["overoptimization_guards"] = run_guards(
                env, heldout_rm_dir, hh_model, ppo_export, tokenizer_path,
                min(32, spec["seq_length"] // 2),
            )
        else:
            result["overoptimization_guards"] = {"error": "no PPO hf_model export"}
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: result.get(k) for k in (
        "start", "final", "best", "late_minus_early", "rm_heldout_pairwise_acc",
        "overoptimization_guards", "error")}))
    return 1 if err else 0


if __name__ == "__main__":
    sys.exit(main())
