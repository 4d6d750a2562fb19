"""Deviceless scale proof: AOT-compile the large-model PPO configs for REAL
TPU topologies and record per-chip HBM accounting.

The reference demonstrates its big-model story by having *run* at 6B/20B
(`/root/reference/examples/hh/README.md` 8xA100 GPT-J;
`/root/reference/configs/nemo_configs/megatron_20b.yaml:53-85`). This proves
the same placement claim without touching a chip: the
locally-installed libtpu compiles for an abstract TPU topology
(`jax.experimental.topologies.get_topology_desc`), so for each large config we
build the REAL model/optimizer/step functions (the same construction
PPOTrainer performs — loss, grad-accum scan, optax multi_transform freeze
masking, cached-decode generation), lower them against fully abstract
`jax.ShapeDtypeStruct` inputs carrying the config's NamedShardings over the
config's exact mesh topology, run the TPU compiler's whole-program compile,
and record `compiled.memory_analysis()` — the ACTUAL buffer assignment the
chip would use, including temp arenas and generated code. A config "proves"
if its per-chip peak fits the target TPU generation's HBM.

Nothing is materialized: params never exist, so a 20B proof runs on a laptop.
Each leg runs in a subprocess (libtpu initializes per-process state; a failed
leg fails that leg only).

Usage:  python scripts/scale_proof.py [--out SCALE_PROOF.json] [--legs a,b]
        python scripts/scale_proof.py --child --config configs/... --topology v5e:4x4
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GIB = 1024 ** 3

# Per-DEVICE HBM budgets (public specs): a v5e chip is one device with 16 GiB
# HBM2; a v4 chip has 32 GiB shared by TWO TensorCores, and libtpu's topology
# exposes each core as a device — so the per-device budget is 16 GiB there too.
HBM = {"v5e": 16 * GIB, "v4-core": 16 * GIB}

# Each leg: config file, the TPU topology its mesh implies (data kept minimal —
# more data parallelism only replicates), and the HBM budget it must fit.
# accel_type quiets libtpu's host-introspection probes.
LEGS = {
    "ppo_llama2_7b_tp4_fsdp4": dict(
        config="configs/ppo_llama2_7b_tp4_fsdp4.yml",
        topology="v5e:4x4", accel_type="v5litepod-16", budget="v5e", data=1,
        slice_desc="16 x v5e chips (fsdp=4 x model=4, data=1)",
    ),
    "ppo_llama2_7b_pp4_tp2_fsdp2": dict(
        config="configs/ppo_llama2_7b_pp4_tp2_fsdp2.yml",
        topology="v5e:4x4", accel_type="v5litepod-16", budget="v5e", data=1,
        slice_desc="16 x v5e chips (fsdp=2 x pipe=4 x model=2, data=1)",
    ),
    "ppo_gpt_neox_20b_tp4_sp": dict(
        config="configs/ppo_gpt_neox_20b_tp4_sp.yml",
        topology="v4:4x4x2", accel_type="v4-64", budget="v4-core", data=2,
        slice_desc="v4-64 slice: 32 chips / 64 core-devices (data=2 x fsdp=8 x model=4)",
    ),
}


def _ma_dict(ma):
    """Per-chip byte accounting from the TPU compiler's CompiledMemoryStats.
    ``peak_memory_in_bytes`` is the HBM high-water mark of one program
    execution under XLA's buffer assignment (arguments + outputs + temp arena
    − donation aliases, plus program code)."""
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
        "peak_bytes": int(ma.peak_memory_in_bytes),
        "peak_gib": round(ma.peak_memory_in_bytes / GIB, 3),
    }


def _child(config_path, topology, data=1):
    """Build one config's train and generation steps and AOT-compile them for
    the given TPU topology. Runs with JAX_PLATFORMS=cpu (the host backend is
    irrelevant — shardings reference the abstract TPU devices)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.data.ppo_types import PPORLBatch
    from trlx_tpu.methods.ppo import PPOConfig  # noqa: F401 (registry import)
    from trlx_tpu.models.hf_loading import load_pretrained
    from trlx_tpu.models.policy import CausalLMWithValueHead
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.ops.generation import generate as generate_op
    from trlx_tpu.parallel.mesh import BATCH_AXES, MESH_AXES
    from trlx_tpu.parallel.sharding import make_param_shardings
    from trlx_tpu.utils import get_optimizer_class, get_scheduler_class
    from trlx_tpu.utils.modeling import logprobs_of_labels

    config = TRLConfig.load_yaml(config_path)
    mc = config.mesh
    pipe = getattr(mc, "pipe", 1)
    n_devices = data * mc.fsdp * pipe * mc.model

    topo = topologies.get_topology_desc(topology, "tpu")
    assert len(topo.devices) == n_devices, (topology, len(topo.devices), n_devices)
    mesh = Mesh(
        np.array(topo.devices).reshape(data, mc.fsdp, pipe, mc.model), MESH_AXES
    )

    # --- model config: the same override assembly as PPOTrainer.setup_model
    # (trlx_tpu/trainer/ppo_trainer.py:63-93), minus checkpoint weights
    overrides = dict(config.model.model_overrides or {})
    overrides.setdefault("param_dtype", jnp.dtype(mc.param_dtype))
    overrides.setdefault("compute_dtype", jnp.dtype(mc.compute_dtype))
    overrides.setdefault("remat", mc.remat)
    overrides.setdefault("sequence_sharding", mc.sequence_shard)
    if pipe > 1:
        overrides["pipeline_stages"] = pipe
        overrides["pipeline_microbatches"] = mc.pipeline_microbatches
        overrides["sequence_sharding"] = False
    model_config, _, model_type = load_pretrained(config.model.model_path, overrides)
    module = CausalLMWithValueHead(
        model_config,
        num_value_layers=getattr(config.method, "num_value_layers_unfrozen", 0),
    )
    trunk = TransformerLM(model_config)

    # --- abstract sharded params: eval_shape instead of init (nothing allocated)
    params_shape = jax.eval_shape(
        lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32)
        )
    )["params"]
    shardings = make_param_shardings(params_shape, mesh)
    abs_params = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, shardings,
    )
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params_shape))

    # --- optimizer: mirror MeshRLTrainer.setup_optimizer (mesh_trainer.py:222-241)
    opt_kwargs = dict(config.optimizer.kwargs)
    lr = opt_kwargs.pop("lr", 1e-5)
    sched_kwargs = dict(config.scheduler.kwargs)
    sched_lr = sched_kwargs.pop("learning_rate", lr)
    lr_schedule = get_scheduler_class(config.scheduler.name)(
        learning_rate=sched_lr, **sched_kwargs
    )
    max_grad_norm = opt_kwargs.pop("max_grad_norm", None)
    tx_inner = get_optimizer_class(config.optimizer.name)(
        learning_rate=lr_schedule, **opt_kwargs
    )
    if max_grad_norm:
        tx_inner = optax.chain(optax.clip_by_global_norm(max_grad_norm), tx_inner)

    n_unfrozen = config.model.num_layers_unfrozen
    num_layers = model_config.num_layers

    def trainable(path):  # mirror trainable_path_predicate (mesh_trainer.py:185-212)
        if n_unfrozen < 0:
            return True
        if "transformer" not in path:
            return True
        if "layers_" in path and "layers_scan" not in path:
            layer = int(path.split("layers_")[1].split("/")[0])
            return layer >= num_layers - n_unfrozen
        return False

    def build_labels(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: build_labels(v, f"{prefix}/{k}" if prefix else str(k)) for k, v in tree.items()}
        return "train" if trainable(prefix) else "freeze"

    tx = optax.multi_transform(
        {"train": tx_inner, "freeze": optax.set_to_zero()}, build_labels(params_shape)
    )

    # opt-state shardings: the same explicit path-rule placement the trainer
    # applies (mesh_trainer.setup_optimizer via make_state_shardings — GSPMD
    # propagation would replicate the moments, 54G/device for full-finetune 7B)
    from trlx_tpu.parallel.sharding import make_state_shardings

    opt_shapes = jax.eval_shape(tx.init, abs_params)
    opt_shardings = make_state_shardings(opt_shapes, mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    abs_opt = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        opt_shapes, opt_shardings,
    )

    # --- abstract PPO batch at the config's real shapes: B x (P + R) tokens,
    # P maxed so P + max_new == seq_length (the worst case the config admits)
    B = config.train.batch_size
    R = int(config.method.gen_kwargs.get("max_new_tokens", 16))
    P = config.train.seq_length - R
    bsh = NamedSharding(mesh, PartitionSpec(BATCH_AXES, None))

    def babs(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=bsh)

    abs_batch = PPORLBatch(
        query_tensors=babs((B, P), jnp.int32),
        response_tensors=babs((B, R), jnp.int32),
        logprobs=babs((B, R), jnp.float32),
        values=babs((B, R), jnp.float32),
        rewards=babs((B, R), jnp.float32),
        attention_mask=babs((B, P), jnp.int32),
        response_mask=babs((B, R), jnp.int32),
    )

    method = config.method
    num_mb = max(1, B // (config.train.minibatch_size or B))

    # --- the PPO train step: same loss as PPOTrainer._get_train_step
    # (ppo_trainer.py:687-706) inside the same grad-accum scan + masked optax
    # update as make_grad_accum_step (mesh_trainer.py:261-288)
    def loss_fn(params, mb):
        seq = jnp.concatenate([mb.query_tensors, mb.response_tensors], axis=1)
        mask = jnp.concatenate([mb.attention_mask, mb.response_mask], axis=1)
        logits, values_pred, _, _ = module.apply({"params": params}, seq, mask)
        logprobs = logprobs_of_labels(logits[:, :-1], seq[:, 1:])
        start = mb.query_tensors.shape[1] - 1
        Rr = mb.response_tensors.shape[1]
        logprobs = logprobs[:, start:start + Rr]
        values_pred = values_pred[:, start:start + Rr].astype(jnp.float32)
        advantages, returns = method.get_advantages_and_returns(
            mb.values, mb.rewards, mb.response_mask
        )
        loss, _ = method.loss(
            logprobs, values_pred, mb.logprobs, mb.values, advantages, returns,
            mb.response_mask,
        )
        return loss

    def train_step(params, opt_state, batch):
        mbs = jax.tree.map(
            lambda x: x.reshape((num_mb, x.shape[0] // num_mb) + x.shape[1:]), batch
        )

        def body(grads_acc, mb):
            grads = jax.grad(loss_fn)(params, mb)
            return jax.tree.map(jnp.add, grads_acc, grads), None

        grads, _ = jax.lax.scan(body, jax.tree.map(jnp.zeros_like, params), mbs)
        grads = jax.tree.map(lambda g: g / num_mb, grads)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt_state

    result = {
        "config": os.path.relpath(config_path, REPO),
        "model_type": model_type,
        "topology": topology,
        "n_params": n_params,
        "n_params_b": round(n_params / 1e9, 3),
        "devices": n_devices,
        "mesh": {"data": data, "fsdp": mc.fsdp, "pipe": pipe, "model": mc.model},
        "dtypes": {"param": str(mc.param_dtype), "compute": str(mc.compute_dtype)},
        "remat": mc.remat,
        "sequence_shard": bool(overrides.get("sequence_sharding", False)),
        "num_layers_unfrozen": n_unfrozen,
        "train_shape": {"batch": B, "prompt": P, "response": R, "num_microbatches": num_mb},
    }

    t0 = time.time()
    with mesh:
        train_compiled = (
            jax.jit(train_step, donate_argnums=(0, 1))
            .lower(abs_params, abs_opt, abs_batch)
            .compile()
        )
    result["train_step"] = _ma_dict(train_compiled.memory_analysis())
    result["train_step"]["compile_s"] = round(time.time() - t0, 1)
    del train_compiled

    # --- the generation step: the same jitted callable MeshRLTrainer.generate
    # builds (mesh_trainer.py:373-386) — generate_op over the trunk's cached
    # decode, replicated outputs. Prompt length = largest power-of-two bucket
    # that keeps P + max_new within the model's positions (the buckets
    # generate() itself pads to).
    B_gen = method.decode_batch_size or method.chunk_size
    gen_kwargs = dict(method.gen_kwargs)
    max_new = int(gen_kwargs.pop("max_new_tokens", 16))
    gen_kwargs.pop("eos_token_id", None), gen_kwargs.pop("pad_token_id", None)
    P_gen = 8
    while P_gen * 2 + max_new <= model_config.max_position_embeddings:
        P_gen *= 2

    def step_fn(params, ids, mask, positions, cache):  # gen_step_fn (ppo_trainer.py:321-331)
        logits, hidden, _, cache = trunk.apply(
            {"params": params["transformer"]}, ids, mask, positions, cache
        )
        return logits, hidden, cache

    def gen_fn(params, ids, mask, rng):
        return generate_op(
            step_fn, params, lambda b, s: trunk.init_cache(b, s), ids, mask, rng,
            max_new_tokens=max_new, eos_token_id=0, pad_token_id=0, **gen_kwargs,
        )

    abs_ids = jax.ShapeDtypeStruct((B_gen, P_gen), jnp.int32, sharding=bsh)
    abs_rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    # generation runs on the trainer's rollout params: a low-precision cast of
    # the masters when train.rollout_param_dtype is set (generation_params(),
    # mesh_trainer.py:308-328)
    gen_params = abs_params
    rollout_dtype = config.train.rollout_param_dtype
    if rollout_dtype is not None:
        rd = jnp.dtype(rollout_dtype)
        gen_params = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape,
                rd if jnp.issubdtype(l.dtype, jnp.floating) else l.dtype,
                sharding=l.sharding,
            ),
            abs_params,
        )
    t0 = time.time()
    with mesh:
        gen_compiled = (
            jax.jit(gen_fn, out_shardings=replicated)
            .lower(gen_params, abs_ids, abs_ids, abs_rng)
            .compile()
        )
    result["generation_step"] = _ma_dict(gen_compiled.memory_analysis())
    result["generation_step"]["compile_s"] = round(time.time() - t0, 1)
    result["gen_shape"] = {"batch": B_gen, "prompt": P_gen, "max_new_tokens": max_new}

    print("SCALE_PROOF_RESULT " + json.dumps(result))


def main():
    if "--child" in sys.argv:
        config_path = sys.argv[sys.argv.index("--config") + 1]
        topology = sys.argv[sys.argv.index("--topology") + 1]
        data = int(sys.argv[sys.argv.index("--data") + 1]) if "--data" in sys.argv else 1
        _child(config_path, topology, data)
        return 0

    out_path = os.path.join(REPO, "SCALE_PROOF.json")
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    names = list(LEGS)
    if "--legs" in sys.argv:
        names = sys.argv[sys.argv.index("--legs") + 1].split(",")

    try:
        with open(out_path) as f:
            result = json.load(f)
    except (OSError, json.JSONDecodeError):
        result = {}
    result["task"] = (
        "AOT compile-only placement proof: deviceless TPU compilation "
        "(jax.experimental.topologies + local libtpu) of the full PPO train "
        "step and cached-decode generation step at each config's exact mesh "
        "topology; peak_bytes is the TPU compiler's per-chip HBM high-water "
        "mark (no weights materialized, no chip needed)"
    )
    result["budgets_gib"] = {k: v / GIB for k, v in HBM.items()}
    failed = []

    for name in names:
        spec = LEGS[name]
        config_path = os.path.join(REPO, spec["config"])
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            # deviceless compile never talks to a chip; these quiet libtpu's
            # host-introspection warnings and pin the topology target
            "TPU_ACCELERATOR_TYPE": spec["accel_type"],
            "TPU_WORKER_HOSTNAMES": "localhost",
        })
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child",
                 "--config", config_path, "--topology", spec["topology"],
                 "--data", str(spec.get("data", 1))],
                cwd=REPO, env=env, capture_output=True, text=True, timeout=5400,
            )
        except subprocess.TimeoutExpired:
            result[name] = {"ok": False, "error": "compile timeout > 5400s"}
            failed.append(name)
            continue
        leg = None
        for line in (proc.stdout or "").splitlines():
            if line.startswith("SCALE_PROOF_RESULT "):
                leg = json.loads(line[len("SCALE_PROOF_RESULT "):])
        if proc.returncode != 0 or leg is None:
            tail = (proc.stderr or "").strip().splitlines()[-3:]
            result[name] = {"ok": False, "error": f"rc={proc.returncode}: " + " | ".join(tail)}
            failed.append(name)
            continue
        budget = HBM[spec["budget"]]
        peak = max(leg["train_step"]["peak_bytes"], leg["generation_step"]["peak_bytes"])
        leg["slice"] = spec["slice_desc"]
        leg["hbm_budget"] = {"generation": spec["budget"], "per_chip_gib": budget / GIB}
        leg["peak_per_chip_gib"] = round(peak / GIB, 3)
        leg["fits"] = bool(peak <= budget)
        leg["ok"] = leg["fits"]
        leg["wall_s"] = round(time.time() - t0, 1)
        result[name] = leg
        if not leg["ok"]:
            failed.append(name)
        result["measured_at"] = time.time()
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps({name: {
            "ok": leg["ok"], "peak_per_chip_gib": leg["peak_per_chip_gib"],
            "budget_gib": budget / GIB, "params_b": leg["n_params_b"],
        }}))

    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"out": out_path, "legs": names, "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
