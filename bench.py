"""Benchmark: PPO rollout+update throughput on the randomwalks task (the reference's
CI benchmark workload, `scripts/benchmark.sh:47`) plus per-subsystem legs, on
the TPU that ``jax.devices()`` provides. Prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...leg keys}.

One process, no child: the process that measures is the one that holds the
chip. It exits non-zero without a result line when the first device is not a
TPU, and non-zero after the line when any leg recorded an ``*_error`` key.

The reference publishes no throughput numbers (BASELINE.md), so vs_baseline is
the ratio against a fixed anchor constant (BASELINE_SAMPLES_PER_SEC below).
"""

import json
import os
import sys
import tempfile
import time
from functools import partial

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO_ROOT)

# The reference publishes no samples/sec; this constant anchors vs_baseline across
# rounds (round-1 measurement on one TPU v5e chip, so later rounds show progress).
BASELINE_SAMPLES_PER_SEC = 31.825


# approximate bf16 peak FLOP/s per chip, keyed by substrings of device_kind
PEAK_FLOPS = (("v6e", 918e12), ("v5p", 459e12), ("v5e", 197e12), ("v5lite", 197e12), ("v4", 275e12))
# approximate HBM bandwidth per chip (bytes/s), same keys
PEAK_HBM_BW = (("v6e", 1640e9), ("v5p", 2765e9), ("v5e", 819e9), ("v5lite", 819e9), ("v4", 1228e9))


def _chip_const(device_kind: str, table):
    kind = device_kind.lower().replace(" ", "")
    for key, val in table:
        if key in kind:
            return val
    raise ValueError(
        f"no published peak for device_kind {device_kind!r}: add it to the "
        "table with its source rather than borrow another chip's"
    )


def _peak_flops(device_kind: str) -> float:
    return _chip_const(device_kind, PEAK_FLOPS)


def _peak_bw(device_kind: str) -> float:
    return _chip_const(device_kind, PEAK_HBM_BW)


def _fwd_flops_tok_fn(config):
    """FLOPs of one token's forward at context length ctx (matmuls + attention)."""
    d, L, V = config.hidden_size, config.num_layers, config.vocab_size
    return lambda ctx: L * (24 * d * d + 4 * ctx * d) + 2 * d * V


def _rollout_flops(fwd_flops_tok, B, P, N):
    """FLOPs of one full rollout: prefill over P prompt tokens + N decode steps."""
    return B * (P * fwd_flops_tok(P // 2) + N * fwd_flops_tok(P + N // 2))


def _kv_step_bytes(config, B, P, N, kv_dtype_bytes):
    """Mean KV-cache bytes read from HBM per decode step (context P + N/2).
    ``kv_dtype_bytes=None`` means the int8 cache: 1 byte per element plus one
    f32 scale per dim_per_head-element row (kv_cache_quant layout)."""
    elems = 2 * config.num_layers * config.kv_heads * config.dim_per_head * (P + N // 2) * B
    if kv_dtype_bytes is None:
        return elems + elems * 4 // config.dim_per_head
    return elems * kv_dtype_bytes


def _time_decode(jax, trunk, trunk_params, B, P, N, reps, seed=0, top_k=0, top_p=1.0,
                 top_k_impl="approx"):
    """Seconds per full rollout (prefill + N decode steps) at batch B: compile
    once, then average reps timed runs. ``top_k``/``top_p`` time the candidate-
    space filtered-sampling path (ops/sampling.py::sample_token), with
    ``top_k_impl`` choosing approx_max_k vs exact lax.top_k selection."""
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.ops.generation import generate

    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(1, trunk.config.vocab_size, (B, P)), jnp.int32)
    mask = jnp.ones((B, P), jnp.int32)

    def dstep(p, t_ids, t_mask, positions, cache):
        logits, hidden, _, cache = trunk.apply({"params": p}, t_ids, t_mask, positions, cache)
        return logits, hidden, cache

    decode_fn = jax.jit(
        lambda p, i, m, r: generate(
            dstep, p, lambda bb, s: trunk.init_cache(bb, s), i, m, r,
            max_new_tokens=N, eos_token_id=None, pad_token_id=0, do_sample=True,
            top_k=top_k, top_p=top_p, top_k_impl=top_k_impl,
        )["sequences"]
    )
    res = decode_fn(trunk_params, ids, mask, jax.random.PRNGKey(1))
    jax.block_until_ready(res)  # compile
    t0 = time.time()
    for i in range(reps):
        res = decode_fn(trunk_params, ids, mask, jax.random.PRNGKey(2 + i))
    jax.block_until_ready(res)
    return (time.time() - t0) / reps


def _time_ppo_train_step(jax, module, params, tx, B, P, R, steps, seed=0,
                         breakdown_prefix=None):
    """Seconds per PPO fwd+bwd+update step over [B, P+R] (compile excluded).
    Returns (dt, params, opt_state, phases) — params are donated each step;
    ``phases`` is the per-phase breakdown dict (``<prefix>_fwd_s`` /
    ``_bwd_s`` / ``_opt_s`` / ``_collective_s``), empty unless
    ``breakdown_prefix`` is set."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from trlx_tpu.methods.ppo import PPOConfig
    from trlx_tpu.utils.modeling import logprobs_of_labels

    method = PPOConfig()
    rng = np.random.default_rng(seed)
    V = module.config.vocab_size
    seq = jnp.asarray(rng.integers(1, V, (B, P + R)), jnp.int32)
    full_mask = jnp.ones((B, P + R), jnp.int32)
    old_lp = jnp.asarray(rng.normal(size=(B, R)), jnp.float32)
    old_v = jnp.asarray(rng.normal(size=(B, R)), jnp.float32)
    rew = jnp.asarray(rng.normal(size=(B, R)), jnp.float32)
    r_mask = jnp.ones((B, R), jnp.int32)
    opt_state = jax.jit(tx.init)(params)
    jax.block_until_ready(opt_state)

    def loss_fn(p):
        logits, values_pred, _, _ = module.apply({"params": p}, seq, full_mask)
        logprobs = logprobs_of_labels(logits[:, :-1], seq[:, 1:])
        start = P - 1
        logprobs = logprobs[:, start : start + R]
        values_pred = values_pred[:, start : start + R].astype(jnp.float32)
        adv, ret = method.get_advantages_and_returns(old_v, rew, r_mask)
        loss, _ = method.loss(logprobs, values_pred, old_lp, old_v, adv, ret, r_mask)
        return loss

    # donate params/opt state like the real trainer's train_step does — without
    # donation XLA copies the full param tree every step
    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(p, s):
        grads = jax.grad(loss_fn)(p)
        updates, s2 = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s2

    # compile-ledger instrumentation (graftcheck-rt): the warmup call may
    # compile, the measured loop must not — the same zero-recompile promise
    # the committed graftcheck-rt-budget.json pins for the real entrypoints
    from trlx_tpu.analysis.rt.watcher import CompileWatcher

    prefix = breakdown_prefix or "ppo_train"
    entry = f"{prefix}_step"
    watcher = CompileWatcher().install()
    try:
        watcher.track(entry, train_step)
        with watcher.attributed(entry):
            params, opt_state = train_step(params, opt_state)
            jax.block_until_ready(params)  # compile
        watcher.mark_steady()
        t0 = time.time()
        for _ in range(steps):
            with watcher.attributed(entry):
                params, opt_state = train_step(params, opt_state)
        jax.block_until_ready(params)
        dt = (time.time() - t0) / steps
    finally:
        watcher.uninstall()
    led = watcher.ledger()[entry]
    phases = {
        f"{prefix}_compile_count_steady": int(led["steady_compiles"]),
        f"{prefix}_compile_time_warmup_s": round(led["compile_time_warmup_s"], 4),
    }
    if breakdown_prefix is not None:
        phases.update(_ppo_phase_breakdown(
            jax, loss_fn, tx, params, opt_state, steps, dt, breakdown_prefix
        ))
    return dt, params, opt_state, phases


def _ppo_phase_breakdown(jax, loss_fn, tx, params, opt_state, steps, step_dt, prefix):
    """Split the measured train step into forward / backward / optimizer /
    collective+dispatch residue.

    ``fwd`` times the jitted loss alone; ``bwd`` is the full grad program
    minus that; ``opt`` times the optimizer update on a fixed gradient tree.
    Whatever the donated full step spends beyond grad+opt — cross-replica
    collectives, dispatch, fusion seams the isolated programs don't pay —
    lands in ``*_collective_s`` (a residue, so it also absorbs timing noise;
    floored at 0). Each timed block runs under an ``obs.spans`` span, so a
    trace of the bench shows the same phases the keys report."""
    import optax

    from trlx_tpu.obs.spans import tracer

    def timed(name, fn, *args):
        r = fn(*args)  # compile excluded
        jax.block_until_ready(r)
        t0 = time.time()
        with tracer.span(name):
            for _ in range(steps):
                r = fn(*args)
            jax.block_until_ready(r)
        return (time.time() - t0) / steps

    t_fwd = timed(f"bench.{prefix}.fwd", jax.jit(loss_fn), params)
    grad_fn = jax.jit(jax.grad(loss_fn))
    t_grad = timed(f"bench.{prefix}.fwd_bwd", grad_fn, params)
    grads = jax.block_until_ready(grad_fn(params))

    def opt_step(g, s, p):
        updates, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s2

    t_opt = timed(f"bench.{prefix}.opt", jax.jit(opt_step), grads, opt_state, params)
    return {
        f"{prefix}_fwd_s": round(t_fwd, 4),
        f"{prefix}_bwd_s": round(max(t_grad - t_fwd, 0.0), 4),
        f"{prefix}_opt_s": round(t_opt, 4),
        f"{prefix}_collective_s": round(max(step_dt - t_grad - t_opt, 0.0), 4),
    }


def _gpt2_perf(jax):
    """gpt2-124M perf with the flash kernel (XLA attention on the CPU, where
    the kernel would only be interpreted). A kernel that fails, fails the leg."""
    return _gpt2_perf_impl(jax, "xla" if jax.default_backend() == "cpu" else "flash")


def _gpt2_perf_impl(jax, impl):
    """Decode + train tokens/sec and MFU on real gpt2-small (124M) shapes.

    Measures the two hot paths on a non-toy model: the jitted KV-cache rollout
    decode loop and the PPO fwd+bwd train step."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from trlx_tpu.models.policy import CausalLMWithValueHead
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM

    out = {}
    on_cpu = jax.default_backend() == "cpu"
    config = PRESETS["gpt2"].replace(
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16, attention_impl=impl
    )
    fwd_flops_tok = _fwd_flops_tok_fn(config)
    kind = jax.devices()[0].device_kind
    peak, bw = _peak_flops(kind), _peak_bw(kind)

    # a CPU call of this leg (a smoke of the code path, never a measurement)
    # cannot turn 124M shapes around; scale down
    B, P, N = (2, 32, 8) if on_cpu else (256, 128, 128)
    reps = 1 if on_cpu else 3
    rng = np.random.default_rng(0)
    V = config.vocab_size

    module = CausalLMWithValueHead(config)
    init_ids = jnp.asarray(rng.integers(1, V, (1, 8)), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), init_ids, jnp.ones((1, 8), jnp.int32))["params"]
    params = jax.device_put(jax.tree.map(lambda x: np.asarray(x), params))
    trunk = TransformerLM(config)

    trunk_params = params["transformer"]
    dtype_bytes = 2 if config.compute_dtype == jnp.bfloat16 else 4  # KV-cache dtype
    # size params by their STORED dtype — that is what streams from HBM each
    # decode step (param_dtype may be f32 while compute_dtype is bf16)
    param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(trunk_params))

    # decode batch decoupled from the reward chunk (PPOConfig.decode_batch_size):
    # the weights stream from HBM every step regardless of batch, so tok/s scales
    # nearly linearly with B until the KV cache saturates memory. tok/s counts
    # NEW tokens (operational rollout rate); MFU counts ALL FLOPs in the window
    # (prefill + decode).
    dt = _time_decode(jax, trunk, trunk_params, B, P, N, reps)
    out["gpt2_rollout_new_tok_s"] = round(B * N / dt, 1)
    out["gpt2_rollout_mfu"] = round(_rollout_flops(fwd_flops_tok, B, P, N) / (dt * peak), 4)
    out["gpt2_rollout_batch"] = B
    # HBM roofline for the decode loop: every step reads all params plus the
    # mean-context KV slice; the bound is what zero-overhead decode would sustain
    kv_bytes = _kv_step_bytes(config, B, P, N, dtype_bytes)
    bound_tok_s = bw / (param_bytes + kv_bytes) * B
    out["gpt2_rollout_bw_bound_tok_s"] = round(bound_tok_s, 1)
    out["gpt2_rollout_frac_of_bw_bound"] = round(out["gpt2_rollout_new_tok_s"] / bound_tok_s, 4)
    if not on_cpu:
        dt32 = _time_decode(jax, trunk, trunk_params, 32, P, N, reps)
        out["gpt2_rollout_new_tok_s_b32"] = round(32 * N / dt32, 1)
        # int8 KV cache: at wide batch the KV cache dominates decode HBM traffic,
        # so halving its bytes raises the roofline (TransformerConfig.kv_cache_quant)
        qtrunk = TransformerLM(config.replace(kv_cache_quant=True))
        dt_q = _time_decode(jax, qtrunk, trunk_params, B, P, N, reps)
        out["gpt2_rollout_new_tok_s_int8kv"] = round(B * N / dt_q, 1)
        kv_q_bytes = _kv_step_bytes(config, B, P, N, None)  # int8 layout
        out["gpt2_rollout_bw_bound_tok_s_int8kv"] = round(bw / (param_bytes + kv_q_bytes) * B, 1)
        # fused top-k/top-p sampling (HF gpt2 defaults top_k=50): the nucleus
        # cutoff sorts k values instead of the 50257-wide vocab each step
        dt_k = _time_decode(jax, trunk, trunk_params, B, P, N, reps, top_k=50, top_p=0.95)
        out["gpt2_rollout_new_tok_s_topk50_topp95"] = round(B * N / dt_k, 1)
        dt_ke = _time_decode(jax, trunk, trunk_params, B, P, N, reps, top_k=50, top_p=0.95,
                             top_k_impl="exact")
        out["gpt2_rollout_new_tok_s_topk50_topp95_exact"] = round(B * N / dt_ke, 1)
        # bf16 rollout param copy (train.rollout_param_dtype): decode streams
        # every weight per token, so f32 masters pay 2x weight bandwidth
        bf16_params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            trunk_params,
        )
        bf16_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(bf16_params))
        dt_b = _time_decode(jax, qtrunk, bf16_params, B, P, N, reps)
        out["gpt2_rollout_new_tok_s_bf16params_int8kv"] = round(B * N / dt_b, 1)
        out["gpt2_rollout_bw_bound_tok_s_bf16params_int8kv"] = round(
            bw / (bf16_bytes + kv_q_bytes) * B, 1
        )

    # PPO train step: fwd+bwd over [B, P+R]; round-2 shapes for comparability.
    # At S=256 the flash backward runs XLA-recompute (materialized O(T·S)
    # scores are cheap here; ops/attention.py BACKWARD_IMPL). Which backward
    # is faster at this length is not measured (ROADMAP S2). Long-context legs
    # keep pallas.
    from trlx_tpu.ops import attention as _attn

    Bt = B if on_cpu else 32
    prev_bwd = _attn.set_flash_backward("xla") if impl == "flash" else None
    try:
        dt, _p, _s, phases = _time_ppo_train_step(
            jax, module, params, optax.adamw(1e-5), Bt, P, N, steps=1 if on_cpu else 5,
            breakdown_prefix="gpt2_train",
        )
    finally:
        if prev_bwd is not None:
            _attn.set_flash_backward(prev_bwd)
    if impl == "flash":
        out["gpt2_train_flash_bwd"] = "xla"
    train_tok_s = Bt * (P + N) / dt
    out["gpt2_train_tok_s"] = round(train_tok_s, 1)
    out["gpt2_train_mfu"] = round(train_tok_s * 3 * fwd_flops_tok((P + N) // 2) / peak, 4)
    out.update(phases)
    out["gpt2_attention_impl"] = impl
    return out


def _serving_perf(jax):
    """Continuous-batching serving engine vs the one-shot rollout decode.

    Mirrors the gpt2 leg's model and shapes so ``serving_new_tok_s`` is
    directly comparable to ``gpt2_rollout_new_tok_s``: same trunk, same
    prompt/new-token envelope. The workload is the one continuous batching
    exists for — more requests than decode slots, a shared prompt prefix, and
    per-request token budgets spread across [N/4, N] so sequences finish at
    different steps and freed slots refill mid-flight (the one-shot path pays
    the full padded batch until the last straggler finishes)."""
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.serving.engine import ServingEngine

    out = {}
    on_cpu = jax.default_backend() == "cpu"
    kind = jax.devices()[0].device_kind
    bw = _peak_bw(kind)
    base = PRESETS["gpt2"].replace(
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16
    )

    S, P, N = (4, 32, 8) if on_cpu else (256, 128, 128)  # slots, prompt cap, max new
    n_req = 3 * S
    rng = np.random.default_rng(0)
    shared = rng.integers(1, base.vocab_size, P // 2)
    prompts = [
        np.concatenate(
            [shared, rng.integers(1, base.vocab_size, 1 + int(rng.integers(0, P // 2)))]
        ).astype(np.int32).tolist()
        for _ in range(n_req)
    ]
    budgets = [N // 4 + (i * (3 * N // 4)) // n_req for i in range(n_req)]
    mean_ctx = sum(len(p) for p in prompts) / n_req + sum(budgets) / n_req / 2

    trunk0 = TransformerLM(base)
    params = trunk0.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32),
    )["params"]
    param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))

    from trlx_tpu.analysis.rt.watcher import CompileWatcher

    def run_once(quant, run_budgets=None, watcher=None, **spec):
        trunk = TransformerLM(base.replace(kv_cache_quant=quant))
        engine = ServingEngine(
            trunk, params, num_slots=S, max_seq_len=P + N,
            gen_kwargs=dict(do_sample=False), seed=0, **spec,
        )
        if watcher is not None:
            # fresh engine, fresh jit caches: its compiles are a new warmup
            watcher.mark_warmup()
            watcher.track("serving_decode_step", engine._decode_step)
            watcher.track("serving_prefill", engine._prefill)
            watcher.track("serving_pack_step", engine._pack)
            if spec.get("spec_k"):
                watcher.track("serving_verify_step", engine._verify_step)
            if spec.get("prefill_chunk"):
                watcher.track("serving_chunk_step", engine._chunk_step)

        def one_pass():
            uids = [engine.submit(p, n) for p, n in zip(prompts, run_budgets or budgets)]
            done = engine.run(uids)
            delivered = sum(len(done[u].generated) for u in uids)
            for u in uids:
                engine.scheduler.requests.pop(u, None)
            return delivered

        one_pass()  # warmup: compiles every prefill bucket + the decode step
        if watcher is not None:
            watcher.mark_steady()
        t0 = time.time()
        delivered = one_pass()
        return delivered / (time.time() - t0), engine

    # compile ledger across all three legs (graftcheck-rt): each leg's first
    # pass is its warmup, the measured pass must be zero-recompile — the
    # promise the committed graftcheck-rt-budget.json pins
    watcher = CompileWatcher().install()
    try:
        tok_s, engine = run_once(quant=False, watcher=watcher)
        out["serving_new_tok_s"] = round(tok_s, 1)
        tok_s_q, engine_q = run_once(quant=True, watcher=watcher)
        out["serving_new_tok_s_int8kv"] = round(tok_s_q, 1)
        # the spec leg runs every request at the full decode budget: a 2-token
        # budget caps that slot's lifetime multiplier by construction, and the
        # leg exists to measure accepted-tokens-per-weight-read, not the budget
        # mix (the baseline legs above keep the mixed-budget turnover workload)
        tok_s_s, engine_s = run_once(
            quant=True, run_budgets=[N] * n_req, spec_k=4, prefill_chunk=P // 2,
            watcher=watcher,
        )
        out["serving_new_tok_s_spec"] = round(tok_s_s, 1)
    finally:
        watcher.uninstall()
    ledger = watcher.ledger()
    out["compile_ledger"] = ledger
    out["serving_compile_count_steady"] = int(
        sum(led["steady_compiles"] for led in ledger.values())
    )
    out["serving_compile_time_warmup_s"] = round(
        sum(led["compile_time_warmup_s"] for led in ledger.values()), 4
    )

    summary = engine_q.summary()
    out["serving_prefix_cache_hit_rate"] = round(summary["prefix_cache_hit_rate"], 4)
    out["serving_mean_slot_occupancy"] = round(summary["mean_slot_occupancy"], 4)
    spec_summary = engine_s.summary()
    out["serving_accepted_tok_per_round"] = round(
        spec_summary["accepted_tok_per_round"], 4
    )
    out["serving_spec_accept_rate"] = round(spec_summary["spec_accept_rate"], 4)

    # HBM roofline at each engine's operating point: every decode round
    # streams all params plus the live slots' mean-context int8 KV, and the
    # achievable delivered tok/s scales with how full the engine kept its
    # slots. The bound is the SINGLE-token-per-round roofline — speculative
    # verify streams the same bytes per round but validates up to K+1 tokens,
    # so the spec leg's fraction can exceed what one-token decode tops out at.
    def frac_of_bound(tok_s_leg, leg_summary, mean_context):
        kv_bytes = _kv_step_bytes(base, S, int(mean_context), 0, None)
        bound = bw / (param_bytes + kv_bytes) * S * leg_summary["mean_slot_occupancy"]
        return tok_s_leg / bound

    mean_ctx_full = sum(len(p) for p in prompts) / n_req + N / 2
    out["serving_frac_of_bw_bound"] = round(
        max(
            frac_of_bound(tok_s_q, summary, mean_ctx),
            frac_of_bound(tok_s_s, spec_summary, mean_ctx_full),
        ),
        4,
    )
    out["serving_num_slots"] = S
    return out


def _serving_chaos_perf(jax):
    """Chaos-armed serving load leg: request-latency tail and shed rate with
    the fault-tolerance layer on (docs/serving.md "Fault tolerance").

    The workload over-subscribes a deliberately tight engine — more requests
    than the pending bound (drives watermark shedding), a KV pool smaller
    than the worst case (drives optimistic admission + preemption) — while
    all four serving chaos sites are armed (one prefill crash, one decode
    crash, alloc-pressure injections, one wedge), so the measured p50/p99
    request latency includes supervised restart + replay overhead. Every
    submitted request must still reach exactly one accountable terminal
    state; anything unaccounted fails the leg."""
    import numpy as np

    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.resilience.chaos import chaos
    from trlx_tpu.serving import (
        ServingEngine,
        ServingResiliencePolicy,
        ServingSupervisor,
    )
    from trlx_tpu.serving.scheduler import FINISH_SHED

    import jax.numpy as jnp

    on_cpu = jax.default_backend() == "cpu"
    base = PRESETS["gpt2"].replace(
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16
    )
    S, P, N = (4, 32, 8) if on_cpu else (64, 128, 64)
    n_req = 8 * S
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, base.vocab_size, 1 + int(rng.integers(0, P - 1)))
        .astype(np.int32).tolist()
        for _ in range(n_req)
    ]
    budgets = [N // 4 + (i * (3 * N // 4)) // n_req for i in range(n_req)]

    trunk = TransformerLM(base)
    params = trunk.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32),
    )["params"]

    policy = ServingResiliencePolicy(
        request_ttl_s=300.0,
        max_pending=4 * S,  # < n_req pending at once -> watermark shedding
        high_watermark=1.0,
        low_watermark=0.5,
        preemption=True,
    )
    bs = 16
    supervisor = ServingSupervisor(
        # pool ~half the worst case: optimistic admission must preempt
        lambda: ServingEngine(
            trunk, params, num_slots=S, max_seq_len=P + N, block_size=bs,
            num_blocks=1 + max(2 * S, S * -(-(P + N) // bs) // 2),
            gen_kwargs=dict(do_sample=False), seed=0, policy=policy,
        ),
        max_restarts=8, backoff_base_s=0.01, wedge_timeout_s=2.0,
    )
    try:
        chaos.configure("serving-prefill:1,serving-decode:1,serving-alloc:2,serving-wedge:1")
        t0 = time.time()
        uids = [supervisor.submit(p, n) for p, n in zip(prompts, budgets)]
        done = supervisor.run(uids)
        elapsed = time.time() - t0
    finally:
        chaos.configure(None)
        supervisor.close()
    unaccounted = set(uids) - set(done)
    if unaccounted:
        raise RuntimeError(f"chaos load leg lost requests: {sorted(unaccounted)}")
    lat = np.array([done[u].latency_s for u in uids], np.float64)
    shed = sum(1 for u in uids if done[u].finish_reason == FINISH_SHED)
    counts = supervisor.scheduler.outcome_counts()
    return {
        "serving_chaos_p50_latency_s": round(float(np.percentile(lat, 50)), 4),
        "serving_chaos_p99_latency_s": round(float(np.percentile(lat, 99)), 4),
        "serving_chaos_shed_rate": round(shed / n_req, 4),
        "serving_chaos_preempted": int(counts["preempted"]),
        "serving_chaos_restarts": int(supervisor.restarts),
        "serving_chaos_req_s": round(n_req / elapsed, 2),
    }


def _serving_tenant_perf(jax):
    """Multi-tenant chaos-soak leg: per-SLO-class latency tails, shed rates,
    and fairness under sustained mixed-class traffic with every serving chaos
    site armed (docs/serving.md "Multi-tenancy and SLO classes").

    Two low-class tenants oversubscribe the engine while two high-class
    tenants run near capacity, all through the deterministic scenario
    harness: class-priority admission with aging, per-tenant KV quotas, and
    class-ordered shedding, surviving supervised restarts mid-stream. The
    quota-violation count is a hard bar — any value above zero fails the
    run's fairness contract."""
    import numpy as np

    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.serving import (
        ServingEngine,
        ServingResiliencePolicy,
        TenantRegistry,
        TenantTraffic,
        run_scenario,
    )
    from trlx_tpu.serving.scheduler import FINISH_SHED

    import jax.numpy as jnp

    on_cpu = jax.default_backend() == "cpu"
    base = PRESETS["gpt2"].replace(
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16
    )
    S, P, N, n_lo, n_hi = (3, 12, 8, 12, 6) if on_cpu else (16, 64, 32, 64, 32)
    bs = 4 if on_cpu else 16
    max_len = P + N + 4  # +4: the pro1 stream prepends a shared prefix
    blocks_per_req = -(-max_len // bs)

    trunk = TransformerLM(base)
    params = trunk.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32),
    )["params"]

    reg = TenantRegistry(class_ttl_s={0: 8.0, 1: 16.0})
    reg.register("free1", slo_class=0, kv_block_quota=blocks_per_req)
    reg.register("free2", slo_class=0, kv_block_quota=blocks_per_req)
    reg.register("pro1", slo_class=1)
    reg.register("pro2", slo_class=1)
    policy = ServingResiliencePolicy(
        max_pending=8, high_watermark=0.75, low_watermark=0.5, preemption=True
    )

    def factory():
        return ServingEngine(
            trunk, params, num_slots=S, max_seq_len=max_len, block_size=bs,
            num_blocks=1 + 2 * S * blocks_per_req // 3, eos_token_id=None,
            pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
            policy=policy, prefix_caching=True, tenants=reg,
        )

    traffic = [
        TenantTraffic("free1", num_requests=n_lo, arrivals_per_round=2.0,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
        TenantTraffic("free2", num_requests=n_lo, arrivals_per_round=2.0,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
        TenantTraffic("pro1", num_requests=n_hi, arrivals_per_round=0.5,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size,
                      shared_prefix=4),
        TenantTraffic("pro2", num_requests=n_hi, arrivals_per_round=0.5,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
    ]
    t0 = time.time()
    report = run_scenario(
        factory, reg, traffic,
        chaos_spec="serving-prefill:1,serving-decode:1,serving-alloc:2,serving-wedge:1",
        dt_s=0.05, max_rounds=800, seed=7, wedge_timeout_s=2.0 if not on_cpu else 0.25,
    )
    elapsed = time.time() - t0
    submitted_by_class = {}
    shed_by_class = {}
    for req in report.requests.values():
        submitted_by_class[req.slo_class] = submitted_by_class.get(req.slo_class, 0) + 1
    for uid, reason in report.terminal.items():
        if reason == FINISH_SHED:
            cls = report.requests[uid].slo_class
            shed_by_class[cls] = shed_by_class.get(cls, 0) + 1

    def _rate(cls):
        return round(shed_by_class.get(cls, 0) / max(1, submitted_by_class.get(cls, 0)), 4)

    return {
        "serving_tenant_p99_latency_s_by_class": {
            str(c): round(v, 4) for c, v in sorted(report.p99_by_class.items())
        },
        "serving_tenant_shed_rate_low": _rate(0),
        "serving_tenant_shed_rate_high": _rate(1),
        "serving_tenant_quota_violations": int(report.quota_violations),
        "serving_tenant_fairness_jain": round(float(report.fairness_jain), 4),
        "serving_tenant_restarts": int(report.restarts),
        "serving_tenant_req_s": round(report.submitted / elapsed, 2),
    }


def _fleet_perf(jax):
    """Serving-fleet leg: fleet-wide throughput, per-SLO-class latency tails,
    prefix-affinity hit rate and autoscale/kill churn over N engine replicas
    behind the FleetRouter (docs/serving.md "Fleet serving").

    The same mixed-class tenant traffic as the tenants leg, but spread over a
    3-replica fleet through the fleet scenario harness with the gauge-driven
    autoscaler live and the fleet chaos sites armed: one hard replica kill
    (cross-replica re-route) plus deliberate mis-routes, then an idle tail so
    the scale-down drain fires inside the measured window. The affinity hit
    rate is the routing-quality headline — it must beat the uniform-random
    baseline or the prefix-affinity scoring is not paying for itself."""
    from trlx_tpu.fleet import run_fleet_scenario
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.serving import (
        ServingEngine,
        ServingResiliencePolicy,
        TenantRegistry,
        TenantTraffic,
    )

    import jax.numpy as jnp

    on_cpu = jax.default_backend() == "cpu"
    base = PRESETS["gpt2"].replace(
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16
    )
    S, P, N, n_lo, n_hi = (3, 12, 8, 12, 6) if on_cpu else (16, 64, 32, 64, 32)
    bs = 4 if on_cpu else 16
    max_len = P + N + 4  # +4: the pro streams prepend a shared prefix
    blocks_per_req = -(-max_len // bs)

    trunk = TransformerLM(base)
    params = trunk.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32),
    )["params"]

    reg = TenantRegistry(class_ttl_s={0: 8.0, 1: 16.0})
    reg.register("free1", slo_class=0, kv_block_quota=blocks_per_req)
    reg.register("free2", slo_class=0, kv_block_quota=blocks_per_req)
    reg.register("pro1", slo_class=1)
    reg.register("pro2", slo_class=1)
    policy = ServingResiliencePolicy(
        max_pending=16, high_watermark=1.0, low_watermark=0.5, preemption=True
    )

    def factory(seat):
        return ServingEngine(
            trunk, params, num_slots=S, max_seq_len=max_len, block_size=bs,
            num_blocks=1 + 2 * S * blocks_per_req // 3, eos_token_id=None,
            pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=seat,
            policy=policy, prefix_caching=True, tenants=reg,
        )

    traffic = [
        TenantTraffic("free1", num_requests=n_lo, arrivals_per_round=2.0,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
        TenantTraffic("free2", num_requests=n_lo, arrivals_per_round=2.0,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
        TenantTraffic("pro1", num_requests=n_hi, arrivals_per_round=0.5,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size,
                      shared_prefix=4),
        TenantTraffic("pro2", num_requests=n_hi, arrivals_per_round=0.5,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size,
                      shared_prefix=4),
    ]
    t0 = time.time()
    report = run_fleet_scenario(
        factory, reg, traffic, num_replicas=3,
        chaos_spec="fleet-replica-kill:1,fleet-route:2",
        dt_s=0.05, max_rounds=800, seed=7,
        wedge_timeout_s=2.0 if not on_cpu else 0.25,
        autoscale=True, min_replicas=1, max_replicas=4,
        scale_down_occupancy=0.3, breach_rounds=3, cooldown_rounds=4,
        idle_tail_rounds=30,
    )
    elapsed = time.time() - t0
    return {
        "fleet_req_s": round(report.submitted / elapsed, 2),
        "fleet_p99_latency_s_by_class": {
            str(c): round(v, 4) for c, v in sorted(report.p99_by_class.items())
        },
        "fleet_affinity_hit_rate": round(float(report.affinity_hit_rate), 4),
        "fleet_random_hit_rate": round(float(report.random_hit_rate), 4),
        "fleet_autoscale_events": len(report.autoscale_events),
        "fleet_replica_kills": int(report.replica_kills),
        "fleet_quota_violations": int(report.quota_violations),
        "fleet_restarts": int(report.restarts),
    }


def _online_grpo_perf(jax):
    """Online GRPO loop leg (docs/online.md "The closed loop"): a sampling
    fleet serves grouped traffic, the PreferenceCollector harvests labeled
    groups, and a GRPO learner steps on the drained experience. Headlines:
    labels/s harvested through the fleet, learner steps/s on the harvested
    groups, and slo_held — whether the fleet ledger burned zero SLO error
    budget while the loop ran (serving and learning sharing a box must not
    cost the servers their SLO)."""
    from trlx_tpu.fleet import FleetRouter
    from trlx_tpu.methods.grpo import GRPOConfig
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.online import OnlineExperienceBuffer, PreferenceCollector
    from trlx_tpu.serving import ServingEngine
    from trlx_tpu.utils.modeling import logprobs_of_labels

    import numpy as np
    import jax.numpy as jnp

    on_cpu = jax.default_backend() == "cpu"
    base = PRESETS["gpt2"].replace(
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16,
        **(dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                max_position_embeddings=64) if on_cpu else {}),
    )
    G, P, N, n_waves, n_prompts = (2, 4, 6, 4, 2) if on_cpu else (4, 16, 16, 8, 4)
    learn_steps = 10 if on_cpu else 30

    model = TransformerLM(base)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32),
    )["params"]

    def factory(seat):
        return ServingEngine(
            model, params, num_slots=4, max_seq_len=P + N + 2, block_size=4,
            num_blocks=0, eos_token_id=None, pad_token_id=0,
            gen_kwargs=dict(do_sample=True), seed=seat + 1,
        )

    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, base.vocab_size, size=P).tolist()
               for _ in range(n_prompts)]

    def reward_fn(prompt, completions):
        return [float(np.mean(c)) / base.vocab_size for c in completions]

    router = FleetRouter(factory, 2, wedge_timeout_s=None, backoff_base_s=0.01)
    buf = OnlineExperienceBuffer(capacity=256, max_staleness=8)
    col = PreferenceCollector(buf, group_size=G, reward_fn=reward_fn)
    t0 = time.time()
    try:
        for _ in range(n_waves):
            uids = [router.submit(list(p), N) for p in prompts for _ in range(G)]
            got = 0
            while got < len(uids):
                router.step()
                got += col.harvest(router, policy_version=0)
        harvest_s = time.time() - t0
        labels = col.stats()["labels_harvested"]

        # GRPO learner over the harvested groups (fixed-length sequences:
        # the leg measures step rate, not ragged padding)
        groups = buf.drain(256, learner_version=0)
        method = GRPOConfig(name="GRPOConfig", num_rollouts=G, chunk_size=G,
                            group_size=G)
        ids = jnp.asarray(
            [list(g.prompt) + list(c) for g in groups for c in g.completions],
            jnp.int32,
        )
        scores = np.concatenate([g.scores for g in groups])
        adv = jnp.asarray(
            np.repeat(method.group_normalize(scores)[:, None], N, axis=1)
        )
        mask = jnp.ones((ids.shape[0], N), jnp.float32)
        zeros = jnp.zeros_like(mask)

        def comp_logprobs(p):
            logits, _, _, _ = model.apply({"params": p}, ids, jnp.ones_like(ids))
            return logprobs_of_labels(logits[:, :-1], ids[:, 1:])[:, P - 1:]

        old_lp = jax.lax.stop_gradient(comp_logprobs(params))

        def loss_fn(p):
            loss, _ = method.loss(comp_logprobs(p), zeros, old_lp, zeros,
                                  adv, zeros, mask)
            return loss

        step = jax.jit(jax.value_and_grad(loss_fn))
        step(params)[0].block_until_ready()  # compile outside the timing
        t1 = time.time()
        learned = params
        for _ in range(learn_steps):
            _, grads = step(learned)
            learned = jax.tree_util.tree_map(
                lambda w, g: w - 0.1 * g, learned, grads
            )
        jax.tree_util.tree_leaves(learned)[0].block_until_ready()
        train_s = time.time() - t1

        # republish + one more served wave under the updated policy
        router.set_params(learned)
        extra = [router.submit(list(prompts[0]), N) for _ in range(G)]
        router.run(extra)
        burn = router.ledger.burn_rates()
    finally:
        router.close()
    return {
        "online_labels_per_s": round(labels / max(harvest_s, 1e-9), 2),
        "online_learner_steps_per_s": round(learn_steps / max(train_s, 1e-9), 2),
        "online_groups_harvested": len(groups),
        "online_slo_held": bool(burn["firing"] == 0.0),
    }


def _serving_flight_perf(jax):
    """Request-flight telemetry leg (docs/observability.md "Request flights"):
    the per-phase latency decomposition of the multi-tenant chaos soak, plus
    the fleet SLO burn rate over the same terminal stream.

    The flight recorder journals every request's lifecycle through the soak
    (admissions, chunked prefill, decode rounds, preemptions, supervised
    restarts) and reduces it to nearest-rank phase percentiles — the numbers
    that say WHERE the tail latency of the tenants leg actually goes
    (queue wait vs prefill vs replay tax). A FleetLedger replays the terminal
    outcomes to report the fast-window SLO burn rate the alerting layer
    would have seen."""
    from trlx_tpu.fleet.ledger import FleetLedger
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.obs.flight import flight
    from trlx_tpu.serving import (
        ServingEngine,
        ServingResiliencePolicy,
        TenantRegistry,
        TenantTraffic,
        run_scenario,
    )

    import jax.numpy as jnp

    on_cpu = jax.default_backend() == "cpu"
    base = PRESETS["gpt2"].replace(
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16
    )
    S, P, N, n_lo, n_hi = (3, 12, 8, 12, 6) if on_cpu else (16, 64, 32, 64, 32)
    bs = 4 if on_cpu else 16
    max_len = P + N + 4
    blocks_per_req = -(-max_len // bs)

    trunk = TransformerLM(base)
    params = trunk.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32),
    )["params"]

    reg = TenantRegistry(class_ttl_s={0: 8.0, 1: 16.0})
    reg.register("free1", slo_class=0, kv_block_quota=blocks_per_req)
    reg.register("free2", slo_class=0, kv_block_quota=blocks_per_req)
    reg.register("pro1", slo_class=1)
    reg.register("pro2", slo_class=1)
    policy = ServingResiliencePolicy(
        max_pending=8, high_watermark=0.75, low_watermark=0.5, preemption=True
    )

    def factory():
        return ServingEngine(
            trunk, params, num_slots=S, max_seq_len=max_len, block_size=bs,
            num_blocks=1 + 2 * S * blocks_per_req // 3, eos_token_id=None,
            pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
            policy=policy, prefix_caching=True, tenants=reg,
        )

    traffic = [
        TenantTraffic("free1", num_requests=n_lo, arrivals_per_round=2.0,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
        TenantTraffic("free2", num_requests=n_lo, arrivals_per_round=2.0,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
        TenantTraffic("pro1", num_requests=n_hi, arrivals_per_round=0.5,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size,
                      shared_prefix=4),
        TenantTraffic("pro2", num_requests=n_hi, arrivals_per_round=0.5,
                      prompt_len=(4, P - 2), max_new=(4, N), vocab=base.vocab_size),
    ]
    flight.reset()
    flight.configure(enabled=True)
    try:
        report = run_scenario(
            factory, reg, traffic,
            chaos_spec="serving-prefill:1,serving-decode:1,serving-alloc:2,serving-wedge:1",
            dt_s=0.05, max_rounds=800, seed=7,
            wedge_timeout_s=2.0 if not on_cpu else 0.25,
        )
        pct = flight.phase_percentiles()
        # a 99%-of-terminals SLO on the soak's outcome stream: the fast-window
        # burn rate the fleet alerting would page on (shed/expired burn budget)
        ledger = FleetLedger(slo_target=0.99, fast_window=32, slow_window=256)
        for uid in report.terminal:
            ledger.record(report.requests[uid])
        burn = ledger.burn_rates()
        completed = len(flight.completed())
    finally:
        flight.configure(enabled=False)
        flight.reset()
    return {
        "serving_queue_wait_p99_s": round(pct["queue_wait_p99"], 4),
        "serving_prefill_p99_s": round(pct["prefill_p99"], 4),
        "serving_decode_p99_s": round(pct["decode_p99"], 4),
        "serving_preempt_replay_p99_s": round(pct["preempt_replay_p99"], 4),
        "serving_flight_completed": int(completed),
        "serving_flight_restarts": int(report.restarts),
        "fleet_alert_fast_burn": round(burn["fast_burn"], 4),
        "fleet_alert_firing": int(burn["firing"]),
    }


def _serving_overlap_perf(jax):
    """Stream-overlapped PPO leg (docs/serving.md "Stream-overlapped PPO"):
    how much of the decode window the streaming pipeline fills with
    reward/score/learn-stage work, and what bubble remains.

    A tiny char-LM PPO trainer runs one serving rollout phase twice — a
    compile warmup, then a measured phase — with 2 decode slots over 8
    prompts so completions stagger into waves and each wave's reward calls
    (a deliberate 30 ms stand-in for a reward RPC) land while later waves
    are still decoding. Keys:

    - ``serving_overlap_fraction``: overlapped work time / decode-busy time
      from the engine's summary delta (the same ledger the
      ``serving/overlap_fraction`` gauge exports; can exceed 1.0 with
      multiple reward workers). The CPU-soak acceptance bar is >= 0.5.
    - ``ppo_step_bubble_s``: reward+score+stage seconds that did NOT overlap
      decode — the serial residue a bigger model would expose.
    - ``ppo_step_time_s_overlap``: wall time of the streamed experience
      phase plus one PPO epoch consuming the staged learner batches.
    """
    import numpy as np

    from trlx_tpu.data.configs import (
        MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig,
        ServingConfig, TokenizerConfig, TrainConfig, TRLConfig,
    )
    from trlx_tpu.methods.ppo import PPOConfig
    from trlx_tpu.obs.spans import tracer
    from trlx_tpu.parallel import mesh as mesh_lib
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.utils.loading import get_trainer

    alphabet = "abcdefgh "
    tmp = tempfile.mkdtemp(prefix="trlx-overlap-bench-")
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=8, ppo_epochs=1, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=12, do_sample=False),
        ),
        train=TrainConfig(
            seq_length=32, epochs=1, total_steps=1, batch_size=4, minibatch_size=2,
            checkpoint_interval=100, eval_interval=100,
            checkpoint_dir=os.path.join(tmp, "ckpts"), pipeline="PromptPipeline",
            trainer="PPOTrainer", tracker=None, seed=2,
            serving=ServingConfig(
                enabled=True, num_slots=2, block_size=4, stream_overlap=True,
                overlap_microbucket=2, overlap_reward_workers=2,
            ),
        ),
        model=ModelConfig(
            model_path="gpt2", num_layers_unfrozen=-1,
            model_overrides=dict(
                vocab_size=len(alphabet) + 3, hidden_size=32, num_layers=2,
                num_heads=2, intermediate_size=64, max_position_embeddings=64,
            ),
        ),
        tokenizer=TokenizerConfig(tokenizer_path=f"char://{alphabet}"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(
            name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)
        ),
        mesh=MeshConfig(data=1, fsdp=1, model=1, compute_dtype="float32"),
    )

    def reward_fn(samples, **kw):
        time.sleep(0.03 * len(samples))  # stand-in for a reward-model RPC
        return [float(s.count("a")) for s in samples]

    # serving (and thus the streamed path) requires a single-device mesh
    real_mesh_from_config = mesh_lib.mesh_from_config
    mesh_lib.mesh_from_config = lambda cfg, devices=None: mesh_lib.make_mesh(
        data=1, fsdp=1, model=1, devices=jax.devices()[:1]
    )
    try:
        trainer = get_trainer("PPOTrainer")(config=config, reward_fn=reward_fn)
        prompts = ["ab", "cd ef", "gh", "a b c", "ba", "fe dc", "hg", "c b a"]
        trainer.add_prompt_pipeline(PromptPipeline(prompts, 12, trainer.tokenizer))
        trainer._resolve_serving()
        if trainer._serving_client is None:
            return {"serving_overlap_perf_error": "serving fell back to generate path"}

        # warmup: compiles every prefill bucket, the decode step, the bucketed
        # score fn, and the train step (first-compile must not pollute the
        # overlap ledger delta)
        trainer.prepare_learning()
        trainer.store.clear_history()
        trainer.make_experience(8, 0)
        for b in trainer.create_train_dataloader():
            trainer.train_step(b)

        before = trainer._serving_engine.summary()
        tracer.configure(enabled=True)
        tracer.drain_step_times()
        t0 = time.time()
        trainer.store.clear_history()
        trainer.make_experience(8, 1)
        for b in trainer.create_train_dataloader():
            trainer.train_step(b)
        step_wall = time.time() - t0
        spans = tracer.drain_step_times()
        tracer.configure(enabled=False)
        after = trainer._serving_engine.summary()

        decode_s = after["overlap_decode_s"] - before["overlap_decode_s"]
        overlapped_s = after["overlap_overlapped_s"] - before["overlap_overlapped_s"]
        work_s = sum(
            v for k, v in spans.items()
            if k.split("time/span/")[-1] in
            ("reward", "decode.score", "decode.learn_stage", "score", "learn_stage")
        )
        return {
            "serving_overlap_fraction": round(overlapped_s / max(1e-9, decode_s), 4),
            "ppo_step_bubble_s": round(max(0.0, work_s - overlapped_s), 4),
            "ppo_step_time_s_overlap": round(step_wall, 4),
        }
    finally:
        mesh_lib.mesh_from_config = real_mesh_from_config


def _island_perf(jax):
    """Disaggregated-island leg (docs/parallelism.md "Islands"): with the
    generation island driving real continuous-batching decode rounds and the
    learner island publishing chunked weight broadcasts between fake
    optimizer steps, how big is each island's idle bubble and how much of
    the broadcast hid under decode?

    A tiny char-LM serving engine runs saturated (slots kept full by the
    driver thread, every round touching the island's gate and polling for
    committed broadcasts) while a learner thread alternates a jitted
    parameter-update step with a chunked publish through the shared round
    gate. Keys:

    - ``island_gen_idle_frac`` / ``island_learn_idle_frac``: the per-island
      idle-bubble fractions from the interval ledgers (target < 0.1 on both;
      the same measurement tests/test_islands.py gates under the seeded
      blocking regression).
    - ``island_broadcast_hidden_frac``: broadcast-chunk time that ran inside
      decode-busy intervals / total broadcast time.
    - ``island_version_lag_steps``: versions behind the publisher the engine
      was at its last swap (1 = swapping every commit).
    """
    import threading

    import jax.numpy as jnp

    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.rollout import ChunkedParameterPublisher
    from trlx_tpu.serving import GenerationIsland, ServingEngine

    config = PRESETS["gpt2"].replace(
        vocab_size=37, hidden_size=32, num_layers=4, num_heads=2,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    model = TransformerLM(config)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    engine = ServingEngine(
        model, params, num_slots=4, max_seq_len=32, block_size=4,
        eos_token_id=None, pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
    )
    island = GenerationIsland(engine)
    publisher = ChunkedParameterPublisher(
        chunk_layers=2, chunk_pause_s=0.002, round_gate=island.round_gate
    )
    island.bind_publisher(publisher)
    publisher.publish(params)

    fake_update = jax.jit(lambda t: jax.tree.map(lambda x: x * 0.999, t))

    def drain_finished():
        for uid, _req in engine.scheduler.pop_finished().items():
            engine.scheduler.pop_request(uid)
            live.discard(uid)

    # warmup: compile prefill buckets, the decode step, and the update step
    live = set()
    for p in ([5, 9, 11], [2, 30, 7, 1], [1, 2]):
        live.add(engine.submit(p, 8))
    while engine.scheduler.has_work:
        engine.step()
        drain_finished()
    jax.block_until_ready(fake_update(params))

    stop = threading.Event()

    def decode_driver():
        i = 0
        while not stop.is_set():
            while len(live) < 4:
                live.add(engine.submit([3 + (i % 29), 7, 11], 8))
                i += 1
            engine.step()
            drain_finished()

    def learner_loop():
        nonlocal params
        while not stop.is_set():
            t0 = time.monotonic()
            params = jax.block_until_ready(fake_update(params))
            island.note_learn(t0, time.monotonic())
            t1 = time.monotonic()
            publisher.publish(params)
            island.note_learn(t1, time.monotonic())

    island.open_window()
    threads = [
        threading.Thread(target=decode_driver, daemon=True),
        threading.Thread(target=learner_loop, daemon=True),
    ]
    for t in threads:
        t.start()
    time.sleep(1.2)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    summary = island.summary()
    bytes_s = publisher.stats()["last_bytes_s"]
    island.close()
    return {
        "island_gen_idle_frac": round(summary["gen_idle_frac"], 4),
        "island_learn_idle_frac": round(summary["learn_idle_frac"], 4),
        "island_broadcast_hidden_frac": round(summary["broadcast_hidden_frac"], 4),
        "island_version_lag_steps": round(summary["version_lag"], 1),
        "island_swaps": int(summary["swaps"]),
        "island_broadcast_bytes_s": round(bytes_s, 1),
    }


def _xl_config(jnp):
    """The gpt2-xl-shaped (~1.56B param) config both xl legs share: bf16
    params, scan_layers, selective remat — the memory machinery on (the
    reference's envelope is ~20B across a node, README.md:7)."""
    from trlx_tpu.models.presets import PRESETS

    return PRESETS["gpt2"].replace(
        hidden_size=1600, num_layers=48, num_heads=25, intermediate_size=6400,
        max_position_embeddings=1024,
        compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        attention_impl="flash", scan_layers=True, remat="nothing_saveable",
    )


# a transient remote-compile 500 resolves in seconds; a hard-down helper
# should surface within the parent's leg deadline, not stall under it
_XL_COMPILE_RETRY = dict(
    max_retries=4, base_delay_s=5.0, max_delay_s=60.0, deadline_s=600.0
)


def _big_rollout_perf(jax):
    """xl rollout leg: KV-cache decode on the gpt2-xl trunk.

    Its own leg, so a train-side failure does not take the rollout numbers
    down with it. Every compile-heavy call runs under
    ``resilience.retry_call``; the retry count lands in the leg result."""
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.resilience.retry import RetryPolicy, retry_call
    from trlx_tpu.utils.metrics import gauges

    compile_retry = RetryPolicy(**_XL_COMPILE_RETRY)
    retries_before = gauges.get("resilience/retries")

    out = {}
    config = _xl_config(jnp)
    fwd_flops_tok = _fwd_flops_tok_fn(config)
    kind = jax.devices()[0].device_kind
    peak, bw = _peak_flops(kind), _peak_bw(kind)

    trunk = TransformerLM(config)
    init_ids = jnp.asarray(
        np.random.default_rng(0).integers(1, config.vocab_size, (1, 8)), jnp.int32
    )
    # init directly on device in bf16 (a host round-trip of 3GB is pointless)
    def _compiled_init():
        params = jax.jit(trunk.init)(jax.random.PRNGKey(0), init_ids)["params"]
        jax.block_until_ready(params)
        return params

    params = retry_call(_compiled_init, policy=compile_retry, name="xl-init-compile")
    n_params = sum(x.size for x in jax.tree.leaves(params))
    out["xl_params_m"] = round(n_params / 1e6, 1)

    B, P, N = 64, 128, 128
    dt = retry_call(
        _time_decode, jax, trunk, params, B, P, N, reps=2,
        policy=compile_retry, name="xl-decode-compile",
    )
    out["xl_rollout_new_tok_s"] = round(B * N / dt, 1)
    out["xl_rollout_mfu"] = round(_rollout_flops(fwd_flops_tok, B, P, N) / (dt * peak), 4)
    param_bytes = n_params * 2
    bound_tok_s = bw / (param_bytes + _kv_step_bytes(config, B, P, N, 2)) * B
    out["xl_rollout_frac_of_bw_bound"] = round(out["xl_rollout_new_tok_s"] / bound_tok_s, 4)
    out["xl_rollout_compile_retries"] = int(gauges.get("resilience/retries") - retries_before)
    return out


def _big_train_perf(jax):
    """xl train leg: the overlapped-collective FSDP PPO step at gpt2-xl scale.

    This is the learner hot path the trainer actually runs under
    ``train.learner_overlap`` — microbatch grad accumulation as a scan,
    per-leaf fsdp all-gather in the forward (whose AD transpose reduce-
    scatters the gradient during the backward), and ZeRO-sharded blockwise-
    int8 Adam state born shard-local via ``make_sharded_opt_init``. The step
    is AOT-lowered (``.lower().compile()``) under ``retry_call`` so a
    compile failure surfaces here, once, with backoff — not
    mid-measurement."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.methods.ppo import PPOConfig
    from trlx_tpu.models.policy import CausalLMWithValueHead
    from trlx_tpu.ops.quantized_adam import adamw_8bit
    from trlx_tpu.parallel import fsdp as fsdp_lib
    from trlx_tpu.parallel.mesh import BATCH_AXES, make_mesh
    from trlx_tpu.resilience.retry import RetryPolicy, retry_call
    from trlx_tpu.utils.metrics import gauges
    from trlx_tpu.utils.modeling import logprobs_of_labels

    compile_retry = RetryPolicy(**_XL_COMPILE_RETRY)
    retries_before = gauges.get("resilience/retries")

    out = {}
    config = _xl_config(jnp)
    fwd_flops_tok = _fwd_flops_tok_fn(config)
    kind = jax.devices()[0].device_kind
    peak = _peak_flops(kind)

    ndev = jax.device_count()
    mesh = make_mesh(data=1, fsdp=ndev, model=1, pipe=1)
    module = CausalLMWithValueHead(config)
    method = PPOConfig()
    tx = adamw_8bit(1e-5)

    init_ids = jnp.asarray(
        np.random.default_rng(0).integers(1, config.vocab_size, (1, 8)), jnp.int32
    )

    def _init_fn(key):
        return module.init(key, init_ids, jnp.ones((1, 8), jnp.int32))["params"]

    params_shape = jax.eval_shape(_init_fn, jax.random.PRNGKey(0))
    specs = fsdp_lib.make_overlap_specs(params_shape, tx, mesh)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs.param_specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    out["xl_params_m"] = round(
        sum(x.size for x in jax.tree.leaves(params_shape)) / 1e6, 1
    )

    # init directly into the fsdp layout: no device ever holds the full tree
    def _compiled_init():
        p = jax.jit(_init_fn, out_shardings=param_shardings)(jax.random.PRNGKey(0))
        jax.block_until_ready(p)
        return p

    params = retry_call(_compiled_init, policy=compile_retry, name="xl-train-init-compile")
    opt_state = retry_call(
        lambda: jax.block_until_ready(
            fsdp_lib.make_sharded_opt_init(tx, specs, mesh)(params)
        ),
        policy=compile_retry, name="xl-opt-init-compile",
    )

    # global batch scales with the fsdp width (per-device microbatch of 4 at
    # seq 256, num_mb=2 — grad-accum scales this; per-token cost is what matters)
    num_mb = 2
    Bt, T = 8 * ndev, 256
    P, R = T // 2, T - T // 2
    rng = np.random.default_rng(0)
    bsh = lambda x: jax.device_put(
        x, NamedSharding(mesh, PartitionSpec(BATCH_AXES, *([None] * (x.ndim - 1))))
    )
    batch = {
        "seq": bsh(jnp.asarray(rng.integers(1, config.vocab_size, (Bt, T)), jnp.int32)),
        "mask": bsh(jnp.ones((Bt, T), jnp.int32)),
        "old_lp": bsh(jnp.asarray(rng.normal(size=(Bt, R)), jnp.float32)),
        "old_v": bsh(jnp.asarray(rng.normal(size=(Bt, R)), jnp.float32)),
        "rew": bsh(jnp.asarray(rng.normal(size=(Bt, R)), jnp.float32)),
        "r_mask": bsh(jnp.ones((Bt, R), jnp.int32)),
    }

    def loss_fn(p, mb):
        logits, values_pred, _, _ = module.apply({"params": p}, mb["seq"], mb["mask"])
        logprobs = logprobs_of_labels(logits[:, :-1], mb["seq"][:, 1:])
        start = P - 1
        logprobs = logprobs[:, start : start + R]
        values_pred = values_pred[:, start : start + R].astype(jnp.float32)
        adv, ret = method.get_advantages_and_returns(mb["old_v"], mb["rew"], mb["r_mask"])
        loss, _ = method.loss(
            logprobs, values_pred, mb["old_lp"], mb["old_v"], adv, ret, mb["r_mask"]
        )
        return loss

    step = fsdp_lib.make_overlapped_grad_accum_step(
        loss_fn, tx, specs, mesh, num_mb, has_aux=False, max_grad_norm=1.0
    )
    # AOT: lower+compile explicitly so the one compile-heavy call sits under
    # the retry policy, then execute the Compiled object directly (it does not
    # populate jit's cache; donation from the builder's donate_argnums holds)
    compiled = retry_call(
        lambda: step.lower(params, opt_state, batch).compile(),
        policy=compile_retry, name="xl-train-aot-compile",
    )

    steps = 3
    params, opt_state, _ = compiled(params, opt_state, batch)  # warm
    jax.block_until_ready(params)
    t0 = time.time()
    for _ in range(steps):
        params, opt_state, _ = compiled(params, opt_state, batch)
    jax.block_until_ready(params)
    dt = (time.time() - t0) / steps

    train_tok_s = Bt * T / dt
    out["xl_train_tok_s"] = round(train_tok_s, 1)
    out["xl_train_mfu"] = round(train_tok_s * 3 * fwd_flops_tok(T // 2) / (peak * ndev), 4)
    out["xl_train_fsdp"] = ndev
    out["xl_train_num_microbatches"] = num_mb
    out["xl_train_sharded_opt_state"] = True
    out["xl_train_compile_retries"] = int(gauges.get("resilience/retries") - retries_before)
    return out


def _attn_mem_probe(jax):
    """Compile-only probe: peak temp memory of the attention *backward* at
    S=2048, Pallas flash (block-recompute dq/dkv kernels) vs plain-XLA attention
    (materializes the [B,H,T,S] f32 score matrix). Records the measured memory
    story behind selective checkpointing (the reference trains with fused
    CUDA attention, SURVEY.md §2.4.5)."""
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import flash_attention, xla_attention

    B, H, T, D = 1, 16, 2048, 64
    shapes = [jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)] * 3 + [
        jax.ShapeDtypeStruct((B, T), jnp.int32)
    ]

    def flash_loss(q, k, v, valid):
        return flash_attention(q, k, v, valid, True, None).astype(jnp.float32).sum()

    def xla_loss(q, k, v, valid):
        return xla_attention(q, k, v, valid, True, 1.0 / (D**0.5)).astype(jnp.float32).sum()

    out = {}
    for name, fn in (("flash", flash_loss), ("xla", xla_loss)):
        compiled = jax.jit(jax.grad(fn, argnums=(0, 1, 2))).lower(*shapes).compile()
        mem = compiled.memory_analysis()
        temp = getattr(mem, "temp_size_in_bytes", None)
        if temp is not None:
            out[f"attn_bwd_temp_mb_{name}_s2048"] = round(temp / 1e6, 1)
    if len(out) == 2:
        # On TPU the Pallas kernel's scratch lives in VMEM, so its HBM temp can
        # be exactly 0; floor at 1 MB so the ratio stays meaningful (">=537x"
        # rather than a divide-by-~0 artifact).
        out["attn_bwd_mem_ratio_xla_over_flash"] = round(
            out["attn_bwd_temp_mb_xla_s2048"] / max(out["attn_bwd_temp_mb_flash_s2048"], 1.0), 1
        )
    return out


def _ir_audit_probe():
    """Per-step collective census + compiled memory of the registered hot
    entrypoints, in exactly the graftcheck-ir budget's shape
    (``<kind>:<mesh-axes>`` -> count/bytes, plus ``memory_bytes``) so a bench
    artifact is directly diffable against ``graftcheck-ir-budget.json``. Runs
    the deviceless auditor in a child process — it forces its own virtual-CPU
    platform, so the child never asks for the chip this process holds."""
    import subprocess
    import tempfile

    out_path = os.path.join(tempfile.gettempdir(), f"trlx_ir_bench_{os.getpid()}.json")
    cmd = [sys.executable, "-m", "trlx_tpu.analysis.ir", "--no-baseline", "--json", out_path]
    try:
        # rc deliberately ignored: the probe records the measured profile even
        # when it deviates from the committed budget (that is CI's job to fail)
        subprocess.run(cmd, cwd=REPO_ROOT, timeout=900, capture_output=True)
        with open(out_path) as f:
            measurements = json.load(f)["measurements"]
    except Exception as e:
        return {"ir_audit_error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        try:
            os.remove(out_path)
        except OSError:
            pass
    out = {}
    for key, m in sorted(measurements.items()):
        name = key.split("@")[0]
        out[f"ir_{name}_collectives"] = m["collectives"]
        out[f"ir_{name}_memory_bytes"] = m["memory_bytes"]
    return out


def _primary_perf(jax):
    """The primary leg: PPO rollout+update samples/sec on randomwalks."""
    from examples.randomwalks import generate_random_walks
    from examples.randomwalks.ppo_randomwalks import default_config
    from trlx_tpu.utils.loading import get_pipeline, get_trainer

    platform = jax.default_backend()

    metric_fn, prompts, *_rest, alphabet = generate_random_walks(seed=1002)
    config = default_config(alphabet)
    config = config.evolve(
        train={"tracker": None, "total_steps": 8, "eval_interval": 10000,
               "checkpoint_interval": 10000, "epochs": 1},
        mesh={"compute_dtype": "bfloat16" if platform != "cpu" else "float32"},
    )

    reward_fn = lambda samples, **kw: metric_fn(samples)["optimality"]

    trainer = get_trainer(config.train.trainer)(config=config, reward_fn=reward_fn)
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, config.train.seq_length - 9, trainer.tokenizer
    )
    trainer.add_prompt_pipeline(pipeline)

    # warmup: one FULL cycle (experience phase + ppo_epochs over it). A single
    # train_step is not enough — the post-experience batches pad to a different
    # shape than the first batch, and the recompile they trigger then lands in
    # the measured window (observed: 4-step epoch 11.8s with recompile vs 0.3s
    # steady-state on one v5e chip).
    trainer.prepare_learning()
    trainer.store.clear_history()
    trainer.make_experience(config.method.num_rollouts, 0)
    for b in trainer.create_train_dataloader():
        trainer.train_step(b)

    # measure: steady-state over full cycles (what a long run actually sustains;
    # first-compile is one-off and amortized by the persistent compile cache)
    reps = 1 if platform == "cpu" else 3
    n_steps = 0
    t0 = time.time()
    for _ in range(reps):
        trainer.store.clear_history()
        trainer.make_experience(config.method.num_rollouts, 0)
        for b in trainer.create_train_dataloader():
            trainer.train_step(b)
            n_steps += 1
    elapsed = (time.time() - t0) / reps
    n_steps = n_steps // reps

    # samples processed: rollouts generated + samples passed through optimizer
    n_samples = config.method.num_rollouts + n_steps * config.train.batch_size
    per_chip = n_samples / elapsed / jax.device_count()

    return {
        "metric": "ppo_rollout_update_samples_per_sec_per_chip",
        "value": round(per_chip, 3),
        "unit": "samples/s/chip",
        # the anchor is a TPU-chip measurement; a CPU-fallback number must not
        # masquerade as a speedup over it
        "vs_baseline": (
            round(per_chip / BASELINE_SAMPLES_PER_SEC, 3) if platform == "tpu" else None
        ),
        "platform": platform,
    }


def measure():
    """Run every leg on the device jax provides. A leg that raises leaves an
    ``*_error`` key in the result; :func:`main` turns any such key into a
    non-zero exit."""
    import jax

    from trlx_tpu.utils.compilation_cache import configure_compilation_cache

    configure_compilation_cache()  # before the first compile: the check is latched

    result = _primary_perf(jax)
    legs = [
        ("gpt2_perf", lambda: _gpt2_perf(jax)),
        ("serving_perf", lambda: _serving_perf(jax)),
        ("serving_chaos_perf", lambda: _serving_chaos_perf(jax)),
        ("serving_tenant_perf", lambda: _serving_tenant_perf(jax)),
        ("fleet_perf", lambda: _fleet_perf(jax)),
        ("serving_flight_perf", lambda: _serving_flight_perf(jax)),
        ("serving_overlap_perf", lambda: _serving_overlap_perf(jax)),
        ("online_grpo_perf", lambda: _online_grpo_perf(jax)),
        ("island_perf", lambda: _island_perf(jax)),
        ("ir_audit", _ir_audit_probe),
        ("xl_rollout", lambda: _big_rollout_perf(jax)),
        ("xl_train", lambda: _big_train_perf(jax)),
        ("attn_mem", lambda: _attn_mem_probe(jax)),
    ]
    for name, leg in legs:
        try:
            result.update(leg())
        except Exception as e:  # the other legs still run; main() fails the run
            result[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]
    return result


def main():
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(
            f"bench.py measures a TPU; jax found {device.platform!r} "
            f"({device.device_kind}). No result."
        )
    result = measure()
    result["device"] = {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count(),
    }
    print(json.dumps(result))
    errors = sorted(key for key in result if key.endswith("_error"))
    if errors:
        sys.exit(f"bench.py: legs failed: {', '.join(errors)}")


if __name__ == "__main__":
    main()
