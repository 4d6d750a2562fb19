"""Single-step cost probe for the >=1B (gpt2-xl-shaped) training config.

Round-4 record: the CPU fallback for the `ppo_xl` parity leg is measured
infeasible on this box, and this script is the evidence (committed so the
numbers are reproducible):

- 8 virtual CPU devices (any sharded layout): XLA CPU's InProcessCommunicator
  enforces a 40s rendezvous-skew abort on collectives; one physical core
  cannot land 8 heavy all-reduce participants inside the window -> SIGABRT
  ("Termination timeout ... Expected 8 threads ... only 7 arrived").
- 1 virtual device, f32 compute, bf16 params, 8-bit Adam, scan+full remat:
  measured steady-state train step 927s at B=16,T=10 (2026-07-30, this box)
  -> a 120-SFT + 25-PPO convergence run would take ~2 days of wall clock.

On the chip these steps are bench.py's xl_train leg (not measured yet).

Usage: PYTHONPATH=/root/repo JAX_PLATFORMS=cpu python scripts/xl_microbench.py
           [--layers 48] [--hidden 1600] [--batch 16] [--seq 10]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import optax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=48)
    ap.add_argument("--hidden", type=int, default=1600)
    ap.add_argument("--heads", type=int, default=25)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=10)
    args = ap.parse_args()

    from trlx_tpu.models.policy import CausalLMWithValueHead
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.ops.quantized_adam import adamw_8bit

    config = PRESETS["gpt2"].replace(
        vocab_size=21, hidden_size=args.hidden, num_layers=args.layers,
        num_heads=args.heads, intermediate_size=4 * args.hidden,
        max_position_embeddings=max(32, args.seq),
        compute_dtype=jnp.float32, param_dtype=jnp.bfloat16,
        scan_layers=True, remat="nothing_saveable")
    module = CausalLMWithValueHead(config)
    out = {"layers": args.layers, "hidden": args.hidden,
           "batch": args.batch, "seq": args.seq}

    t0 = time.time()
    params = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32)
    )["params"]
    jax.block_until_ready(params)
    out["params_m"] = round(sum(x.size for x in jax.tree.leaves(params)) / 1e6, 1)
    out["init_s"] = round(time.time() - t0, 1)

    ids = jnp.ones((args.batch, args.seq), jnp.int32)
    mask = jnp.ones((args.batch, args.seq), jnp.int32)

    def loss_fn(p):
        logits, _, _, _ = module.apply({"params": p}, ids, mask)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    tx = adamw_8bit(1e-4)
    opt = jax.jit(tx.init)(params)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(loss_fn)(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    t0 = time.time()
    p2, o2, _ = step(params, opt)
    jax.block_until_ready(p2)
    out["compile_plus_first_step_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    p2, o2, _ = step(p2, o2)
    jax.block_until_ready(p2)
    out["steady_step_s"] = round(time.time() - t0, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
