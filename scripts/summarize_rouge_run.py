"""summarize_rlhf quality-evidence runner: 3-stage chain + ROUGE table.

The reference's only published quality numbers are the summarize_rlhf ROUGE /
reward table (`/root/reference/examples/summarize_rlhf/README.md`: avg ROUGE
SFT 0.240 / PPO 0.223, RM reward 2.729 / 3.291 — PPO trades a little ROUGE for
reward, as RLHF should). This runs the repo's 3-stage chain (SFT → pairwise RM
→ PPO with live ROUGE metric_fn), then evaluates BOTH the SFT and the PPO
checkpoints with the rouge_eval harness on the held-out split, writing the
same-shaped table to SUMM_ROUGE_r{N}.json. At full scale (local gpt-j + TL;DR
checkpoints) the identical chain reproduces the reference's setup; the
zero-egress default runs the synthetic TL;DR task at tiny scale, where the
expected signature is the same: SFT ROUGE high, PPO reward >= SFT reward.

Usage: python scripts/summarize_rouge_run.py [--out SUMM_ROUGE_r5.json]
           [--cpu] [--sft-steps N] [--rm-steps N] [--ppo-steps N]
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

from parity_run import parse_jsonl_curve, platform_info  # noqa: E402

CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": REPO,
}


def main():
    out_path = os.path.join(REPO, "SUMM_ROUGE_r5.json")
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]

    def arg(flag, default):
        return int(sys.argv[sys.argv.index(flag) + 1]) if flag in sys.argv else default

    sft_steps = arg("--sft-steps", 150)
    rm_steps = arg("--rm-steps", 150)
    ppo_steps = arg("--ppo-steps", 300)
    base_dir = os.path.join(REPO, "ckpts", "summ_rouge_r5")

    env = dict(os.environ)
    if "--cpu" in sys.argv:
        env.update(CPU_ENV)
    plat = platform_info(CPU_ENV if "--cpu" in sys.argv else None)

    t0 = time.time()
    hparams = {"train.total_steps": ppo_steps, "train.eval_interval": max(25, ppo_steps // 8)}
    proc = subprocess.run(
        [sys.executable, "examples/summarize_rlhf/trlx_gptj_text_summarization.py",
         json.dumps(hparams), "--base-dir", base_dir,
         "--sft-steps", str(sft_steps), "--rm-steps", str(rm_steps)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=14000,
    )
    result = {
        "task": "3-stage summarize chain + held-out ROUGE/reward table "
                "(reference table: avg ROUGE SFT 0.240 / PPO 0.223, reward 2.729/3.291)",
        "platform": f"{plat.get('platform')} ({plat.get('device')})",
        "chain_rc": proc.returncode,
        "steps": {"sft": sft_steps, "rm": rm_steps, "ppo": ppo_steps},
    }
    if proc.returncode != 0:
        result["error"] = (proc.stderr or "").strip().splitlines()[-1:]
    else:
        # live eval curve (metrics/rouge_avg + reward/mean per eval)
        curve = parse_jsonl_curve(os.path.join(base_dir, "ppo"))
        result["ppo_eval_curve"] = curve.get("eval_curve")
        # held-out table for both checkpoints via the rouge_eval harness
        for name, ckpt in (("sft", f"{base_dir}/sft_model"), ("ppo", f"{base_dir}/ppo_model")):
            ev = subprocess.run(
                [sys.executable, "examples/summarize_rlhf/rouge_eval.py", ckpt,
                 "--max-new-tokens", "8", "--limit", "36"],
                cwd=REPO, env=env, capture_output=True, text=True, timeout=3000,
            )
            try:
                line = [l for l in ev.stdout.splitlines() if l.startswith("{")][-1]
                result[name] = json.loads(line)
            except (IndexError, json.JSONDecodeError):
                result[name] = {"error": (ev.stderr or "").strip().splitlines()[-1:]}
    result["wall_s"] = round(time.time() - t0, 1)
    result["measured_at"] = time.time()
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: result.get(k) for k in ("platform", "chain_rc", "sft", "ppo")}))
    ok = proc.returncode == 0 and "error" not in result.get("ppo", {"error": 1})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
