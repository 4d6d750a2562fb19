"""The quickest proof that the system still starts on the chip: PPO through
``trlx_tpu.train()`` at the published gpt2 widths on one TPU chip.

    python chip_smoke.py             # one chip: device, kernels, ppo, ppo_serving, ppo_moe
    python chip_smoke.py --chips 4   # four chips: the sharded learner and its
                                     # one-device comparison, no other phase

One process, no child, and the script sets no platform: it exits non-zero at
once when jax's first device is not a TPU. Every phase raises on failure. The
last line of standard output is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

Weights are random, made from ``SEED``; nothing is downloaded. What the phases
print about time and memory is information from a cold run on the host's
clock, under ``info_*`` names: it is not a benchmark and is never to be
quoted as one (ROADMAP S0 builds that).

The phase functions take a :class:`Sizes`, so ``tests/test_chip_smoke.py``
rehearses them on the CPU at tiny widths; ``main`` always uses :data:`FULL`.
"""

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO_ROOT)

SEED = 1234
#: bf16 inputs, f32 accumulation on both sides of every comparison: outputs
#: differ by a few bf16 roundings (2^-8 relative) of O(1)-to-O(10) values
KERNEL_ATOL = 5e-2
#: the sharded learner against one device, same seed: the same bf16 program
#: with another reduction order. Log-probabilities and values, absolute; the
#: step's loss and gradient norm, relative
SHARDED_ATOL = 5e-2
SHARDED_RTOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    model_overrides: Dict[str, Any]  # on top of PRESETS["gpt2"]; {} = published widths
    compute_dtype: str
    prompt_len: int
    new_tokens: int
    batch: int
    steps: int
    flash_batch: int
    flash_len: int
    slots: int
    num_blocks: int
    block_size: int
    max_blocks: int
    interpret: bool  # Pallas interpret mode: the CPU rehearsal only
    model_path: str = "gpt2"  # the program's preset: no such directory, random init


FULL = Sizes(
    model_overrides={}, compute_dtype="bfloat16",
    prompt_len=64, new_tokens=64, batch=32, steps=4,
    flash_batch=8, flash_len=512,
    slots=32, num_blocks=1024, block_size=16, max_blocks=8,
    interpret=False,
)


def say(phase: str, message: str) -> None:
    print(f"[{phase}] {message}", flush=True)


# ---------------------------------------------------------------- device


def phase_device(cache_dir: Optional[str]) -> Dict[str, Any]:
    from importlib import metadata

    import jax
    import jaxlib

    from trlx_tpu import native

    device = jax.devices()[0]
    info = {"platform": device.platform, "kind": device.device_kind, "count": jax.device_count()}
    say("device", f"platform={info['platform']} device_kind={info['kind']!r} count={info['count']}")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("device", f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say("device", f"compilation cache: {cache_dir or 'off'}")
    plane = "C++ data plane" if native.get_lib() is not None else "numpy stand-in"
    say("device", f"trlx_tpu.native: {plane}")
    return info


# --------------------------------------------------------------- kernels


def _max_abs_diff(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _check(phase: str, name: str, diff: float, bound: float) -> None:
    say(phase, f"{name}: max|diff|={diff:.3e} (bound {bound:.1e})")
    if not diff <= bound:  # also catches NaN
        raise AssertionError(f"{name}: max|diff| {diff} exceeds {bound}")


def phase_kernels(sizes: Sizes) -> None:
    """Flash forward + Pallas backward against the XLA attention path, and
    paged decode / verify (bf16 and int8 pools) against the XLA gather path,
    compiled and executed on the device jax provides."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.presets import get_preset
    from trlx_tpu.ops import attention
    from trlx_tpu.ops.kv_cache import quantize_kv_rows
    from trlx_tpu.ops.paged_attention import (
        paged_pool_layout,
        paged_verify_attention_pallas,
        paged_verify_attention_xla,
    )

    c = get_preset("gpt2", sizes.model_overrides)
    dtype = jnp.dtype(sizes.compute_dtype)
    H, Hkv, D = c.num_heads, c.kv_heads, c.dim_per_head
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    # flash attention: forward, and the Pallas backward kernels
    B, T = sizes.flash_batch, sizes.flash_len
    # [B, T, heads, D]: the layout a model's projections leave q, k, v in, and the kernels take
    q, k, v, g = (
        jax.random.normal(next(keys), (B, T, n, D), jnp.float32).astype(dtype)
        for n in (H, Hkv, Hkv, H)
    )
    # ragged right padding, as the scoring forward sees it
    kv_valid = (jnp.arange(T)[None, :] < T - 7 * jnp.arange(B)[:, None]).astype(jnp.int32)
    scale = D ** -0.5

    def flash(q, k, v):
        return attention.flash_attention(
            q, k, v, kv_valid, True, scale, sizes.interpret
        )

    def plain(q, k, v):  # the plain reference holds the heads before the rows
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        return attention.xla_attention(q, k, v, kv_valid, True, scale).transpose(0, 2, 1, 3)

    def out_and_grads(fn):
        def run(q, k, v, g):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)

        return jax.jit(run)(q, k, v, g)  # arguments, not constants for XLA to fold

    got = out_and_grads(flash)
    want = out_and_grads(plain)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        _check("kernels", f"flash {name} [B={B} H={H} T={T} D={D} {dtype.name}]",
               _max_abs_diff(a, b), KERNEL_ATOL)

    # paged attention: decode is the Q=1 verify, so both run the one kernel
    S, NB, BS, MB = sizes.slots, sizes.num_blocks, sizes.block_size, sizes.max_blocks
    tables = jax.random.permutation(next(keys), jnp.arange(1, NB))[: S * MB].reshape(S, MB)
    for quant in (False, True):
        layout = paged_pool_layout(NB, BS, Hkv, D, dtype, quant)
        shape, _ = layout["k"]
        pools = {
            key: jax.random.normal(next(keys), shape, jnp.float32) for key in ("k", "v")
        }
        scales = {}
        if quant:
            for key in ("k", "v"):
                pools[key], row_scale = quantize_kv_rows(pools[key])
                scales[f"{key}_scale"] = row_scale[..., 0]
        else:
            pools = {key: pool.astype(dtype) for key, pool in pools.items()}
        for q_len in (1, 4):
            qp = jax.random.normal(next(keys), (S, q_len, H, D), jnp.float32).astype(dtype)
            # every context length from 1 token to a full table less the append
            lens = 1 + (jnp.arange(S) * 37) % (MB * BS - q_len)
            args = (qp, pools["k"], pools["v"], tables, lens)
            got = jax.jit(
                lambda *a: paged_verify_attention_pallas(
                    *a, interpret=sizes.interpret, **scales
                )
            )(*args)
            want = jax.jit(lambda *a: paged_verify_attention_xla(*a, **scales))(*args)
            pool_name = "int8" if quant else dtype.name
            _check("kernels",
                   f"paged {'decode' if q_len == 1 else f'verify Q={q_len}'} "
                   f"[{pool_name} pool, slots={S} Hkv={Hkv} D={D} block={BS}]",
                   _max_abs_diff(got, want), KERNEL_ATOL)


# ------------------------------------------------------------------- ppo


def make_prompts(sizes: Sizes, count: int):
    """``count`` deterministic ASCII prompts of exactly ``prompt_len`` bytes."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
    return [
        bytes(rng.choice(letters, sizes.prompt_len)).decode("ascii") for _ in range(count)
    ]


def reward_fn(samples, prompts, outputs, **kwargs):
    """Cheap and deterministic: the share of vowels in each output."""
    return [sum(ch in "aeiou" for ch in out) / max(1, len(out)) for out in outputs]


#: the sparse-expert family at its published widths and a small depth (one dense and one expert
#: layer, 8 of the 64 experts held, an eighth of the vocabulary), one PPO iteration
MOE = dataclasses.replace(
    FULL, model_path="kimi_vl", steps=1,
    model_overrides=dict(num_layers=2, experts_held=8, vocab_size=20480, max_position_embeddings=1024),
)


def ppo_config(sizes: Sizes, out_dir: str, serving: bool = False, fsdp: int = 1,
               trainer: str = "ObservedPPOTrainer", self_healing: bool = False):
    from trlx_tpu.data.configs import (
        MeshConfig,
        ModelConfig,
        OptimizerConfig,
        SchedulerConfig,
        SelfHealingConfig,
        ServingConfig,
        TokenizerConfig,
        TrainConfig,
        TRLConfig,
    )
    from trlx_tpu.methods.ppo import PPOConfig

    never = 10 ** 9
    return TRLConfig(
        train=TrainConfig(
            seq_length=sizes.prompt_len + sizes.new_tokens,
            # one optimizer step per epoch: the store holds exactly one batch
            epochs=sizes.steps, total_steps=sizes.steps, batch_size=sizes.batch,
            checkpoint_interval=never, eval_interval=never,
            checkpoint_dir=os.path.join(out_dir, "ckpts"),
            logging_dir=os.path.join(out_dir, "logs"),
            pipeline="PromptPipeline", trainer=trainer, tracker="jsonl", seed=SEED,
            serving=ServingConfig(enabled=serving),
            self_healing=SelfHealingConfig(enabled=self_healing),
        ),
        model=ModelConfig(
            model_path=sizes.model_path,
            num_layers_unfrozen=-1,  # full reference copy
            model_overrides={**sizes.model_overrides, "attention_impl": "flash"},
        ),
        tokenizer=TokenizerConfig(tokenizer_path="bytes"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=3e-5)),
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=1000, eta_min=3e-5)),
        method=PPOConfig(
            num_rollouts=sizes.batch, chunk_size=sizes.batch, ppo_epochs=1,
            init_kl_coef=0.01, target=None,
            # min == max: every response has new_tokens tokens, so no shape moves
            gen_kwargs=dict(max_new_tokens=sizes.new_tokens, min_new_tokens=sizes.new_tokens,
                            do_sample=True, top_k=0, top_p=1.0),
        ),
        mesh=MeshConfig(data=1, fsdp=fsdp, model=1, compute_dtype=sizes.compute_dtype),
    )


def _register_observed_trainer():
    """PPOTrainer plus observation at its own per-step hook; what it trains,
    and how, is untouched. Registered by name so that ``trlx_tpu.train()``
    builds it as it builds any trainer."""
    import jax
    import numpy as np

    from trlx_tpu.trainer import _TRAINERS, register_trainer
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    if "observedppotrainer" in _TRAINERS:
        return

    @register_trainer
    class ObservedPPOTrainer(PPOTrainer):
        watcher = None  # the caller's CompileWatcher

        def sample_params(self):
            """The first 64 values of every parameter leaf that takes a gradient,
            on the host, by path. Two take none: an expert router's selection
            bias, which only chooses, and the first rows of an embedding that no
            head is tied to, which belong to tokens the traffic never holds."""
            still = ["router/bias"] + ([] if self.model_config.tie_word_embeddings else ["embed_tokens/embedding"])
            named = [
                ("/".join(str(getattr(k, "key", k)) for k in path), leaf)
                for path, leaf in jax.tree_util.tree_leaves_with_path(self.params)
            ]
            return {
                name: np.asarray(jax.device_get(leaf.ravel()[:64]))
                for name, leaf in named if not name.endswith(tuple(still))
            }

        def prepare_learning(self):
            self.params_before = self.sample_params()
            self.step_marks = []  # (host time, peak bytes, compiles so far) per step
            super().prepare_learning()

        def post_backward_callback(self):
            super().post_backward_callback()
            jax.block_until_ready(self.params)
            stats = jax.devices()[0].memory_stats() or {}
            self.step_marks.append(
                (time.monotonic(), stats.get("peak_bytes_in_use"), _compile_events(self.watcher))
            )


def _abstract(tree):
    """Shapes, dtypes and shardings of ``tree``: enough to lower a step again
    whatever became of the buffers (a train step donates its inputs)."""
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree
    )


def _logged_steps(config, steps: int):
    """The jsonl tracker's row for each optimizer step: there must be
    ``steps`` of them, every loss finite."""
    import numpy as np

    rows = []
    logging_dir = config.train.logging_dir
    for name in sorted(os.listdir(logging_dir)):
        if name.endswith(".jsonl"):
            with open(os.path.join(logging_dir, name)) as f:
                rows += [json.loads(line) for line in f if line.strip()]
    rows = [row for row in rows if "losses/total_loss" in row]
    if len(rows) != steps:
        raise AssertionError(f"tracker logged {len(rows)} steps, wanted {steps}")
    for i, row in enumerate(rows):
        losses = {k: v for k, v in row.items() if k.startswith("losses/")}
        if not all(np.isfinite(v) for v in losses.values()):
            raise AssertionError(f"step {i + 1}: non-finite loss {losses}")
    return rows


def _compile_events(watcher) -> int:
    """Every XLA compile of the process so far, as the watcher's ledger has it."""
    return sum(
        entry["event_compiles_warmup"] + entry["event_compiles_steady"]
        for entry in watcher.ledger().values()
    )


def phase_ppo(sizes: Sizes, out_dir: str, serving: bool = False, phase: Optional[str] = None):
    """``trlx_tpu.train()`` for ``sizes.steps`` optimizer steps, then the
    assertions of the issue: finite losses at every step, parameters changed,
    a committed checkpoint, the flash kernel in the compiled train step (on
    TPU), no compile after the first step — and, with ``serving``, that the
    engine really served the rollouts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trlx_tpu
    from trlx_tpu.analysis.rt.watcher import CompileWatcher
    from trlx_tpu.parallel import mesh as mesh_lib
    from trlx_tpu.resilience import find_latest_committed
    from trlx_tpu.utils.loading import get_trainer

    phase = phase or ("ppo_serving" if serving else "ppo")
    on_tpu = jax.default_backend() == "tpu"
    shutil.rmtree(out_dir, ignore_errors=True)  # this phase's own output, from an earlier run
    _register_observed_trainer()
    config = ppo_config(sizes, out_dir, serving=serving)

    t_start = time.monotonic()
    with CompileWatcher() as watcher:
        get_trainer(config.train.trainer).watcher = watcher
        trainer = trlx_tpu.train(
            reward_fn=reward_fn, prompts=make_prompts(sizes, 2 * sizes.batch), config=config
        )
        compiles_total = _compile_events(watcher)

    # -- the run itself
    c = trainer.model_config
    say(phase, f"model: vocab={c.vocab_size} d={c.hidden_size} layers={c.num_layers} "
               f"heads={c.num_heads} positions={c.max_position_embeddings} "
               f"compute={jnp.dtype(c.compute_dtype).name} attention={c.attention_impl}; "
               f"mesh={dict(trainer.mesh.shape)}")
    if trainer.iter_count != sizes.steps:
        raise AssertionError(f"took {trainer.iter_count} optimizer steps, wanted {sizes.steps}")
    marks = [(t_start, None, 0)] + trainer.step_marks
    for i, row in enumerate(_logged_steps(config, sizes.steps)):
        say(phase, f"step {i + 1}/{sizes.steps}: " + " ".join(
            f"{k.split('/')[1]}={v:.4g}" for k, v in sorted(row.items()) if k.startswith("losses/"))
            + f" reward_mean={row.get('rollout_scores/mean', float('nan')):.4g}"
            + f" info_wall_s_since_last_step={marks[i + 1][0] - marks[i][0]:.2f}"
            + f" info_peak_bytes_in_use={marks[i + 1][1]}"
            + f" compiles_since_last_step={marks[i + 1][2] - marks[i][2]}")
    (B, P, R), step = next(iter(trainer._train_steps.items()))
    say(phase, f"train step shapes: batch={B} prompt={P} response={R} "
               f"({len(trainer._train_steps)} compiled shape(s))")
    # the trainer re-appends eos to a response that never sampled one
    want = (sizes.batch, sizes.prompt_len, sizes.new_tokens + 1)
    if (B, P, R) != want or len(trainer._train_steps) != 1:
        raise AssertionError(f"train step shapes {list(trainer._train_steps)}, wanted {want}")

    after = trainer.sample_params()
    still = [name for name, before in trainer.params_before.items() if np.array_equal(before, after[name])]
    say(phase, f"parameters changed in {len(after) - len(still)}/{len(after)} sampled leaves")
    if still:
        raise AssertionError(f"parameter leaves that did not move: {still}")

    committed = find_latest_committed(config.train.checkpoint_dir)
    say(phase, f"checkpoint committed: {committed}")
    if committed is None:
        raise AssertionError("no committed checkpoint")

    up_to_first = trainer.step_marks[0][2]
    after_first = compiles_total - up_to_first
    say(phase, f"compiles: {up_to_first} up to the first optimizer step, "
               f"{after_first} after it (CompileWatcher)")
    if after_first:
        raise AssertionError(f"{after_first} compile(s) after the first optimizer step")

    # -- which path ran: read it off the compiled programs
    batch = next(iter(trainer.store.create_loader(sizes.batch)))
    with trainer.mesh:
        text = step.lower(
            _abstract(trainer.params), _abstract(trainer.opt_state),
            mesh_lib.put_batch(trainer.mesh, batch),
        ).compile().as_text()
    kernel_in_step = "tpu_custom_call" in text
    say(phase, f"tpu_custom_call in the compiled train step: {kernel_in_step}")
    if on_tpu and not kernel_in_step:
        raise AssertionError("the compiled train step holds no Pallas kernel: flash fell back")

    if serving:
        _check_serving(phase, trainer, on_tpu)
    elif trainer._serving_client is not None:
        raise AssertionError("serving engine present in the one-shot phase")
    return trainer


def _check_serving(phase: str, trainer, on_tpu: bool) -> None:
    import jax.numpy as jnp

    from trlx_tpu.ops.paged_attention import resolve_paged_impl
    from trlx_tpu.utils.metrics import gauges

    if trainer._serving_client is None:
        raise AssertionError("train.serving.enabled was not honoured: no serving client")
    engine = trainer._serving_engine
    finished = gauges.get("serving/finished_requests")
    in_use = gauges.get("serving/blocks_in_use")
    impl = resolve_paged_impl(engine.trunk.config.paged_attention_impl)
    say(phase, f"engine served: finished_requests={finished:.0f} "
               f"delivered_tokens={gauges.get('serving/delivered_tokens'):.0f} "
               f"blocks_in_use_at_exit={in_use:.0f}; resolved paged impl: {impl} "
               f"(slots={engine.num_slots} block_size={engine.block_size} "
               f"blocks={engine.num_blocks})")
    if not finished > 0 or in_use != 0:
        raise AssertionError(f"finished_requests={finished}, blocks_in_use={in_use}")
    # the donated pools must still be the engine's own after the last round
    pool = engine.cache["k"][0]
    if pool.is_deleted() or not bool(jnp.isfinite(pool.astype(jnp.float32)).all()):
        raise AssertionError("the engine's KV pool is deleted or non-finite after the run")
    per_slot = jnp.zeros((engine.num_slots,), jnp.int32)
    text = engine._decode_step.lower(
        _abstract(trainer.params["transformer"]), per_slot, engine.cache, engine._rng, per_slot
    ).compile().as_text()
    kernel_in_step = "tpu_custom_call" in text
    say(phase, f"tpu_custom_call in the compiled decode step: {kernel_in_step}")
    if on_tpu and (impl == "pallas") != kernel_in_step:
        raise AssertionError(f"paged impl {impl} but tpu_custom_call={kernel_in_step}")


# --------------------------------------------------------------- sharded


def _bytes_per_device(trees) -> Dict[int, int]:
    import jax

    held: Dict[int, int] = {}
    for leaf in jax.tree.leaves(trees):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + shard.data.nbytes
    return held


def _expected_bytes_per_device(trees) -> Tuple[int, int]:
    """(bytes each device should hold, bytes of leaves no rule shards) from
    the leaves' own shardings: a leaf's shard is its shape over the mesh axes
    its spec names."""
    import jax
    import numpy as np

    per_device = replicated = 0
    for leaf in jax.tree.leaves(trees):
        shard_shape = leaf.sharding.shard_shape(leaf.shape)
        nbytes = int(np.prod(shard_shape)) * leaf.dtype.itemsize
        per_device += nbytes
        if tuple(shard_shape) == tuple(leaf.shape):
            replicated += nbytes
    return per_device, replicated


def build_trainer(config, devices=None):
    """A trainer as ``trlx_tpu.train()`` builds it — on a mesh over ``devices``
    when given. The mesh config has no device subset, so the one-device twin
    is steered here, in the check, not through a new option of the program."""
    from unittest import mock

    from trlx_tpu.parallel import mesh as mesh_lib
    from trlx_tpu.utils.loading import get_trainer

    cls = get_trainer(config.train.trainer)
    if devices is None:
        return cls(config=config, reward_fn=reward_fn)
    build = mesh_lib.mesh_from_config
    with mock.patch.object(
        mesh_lib, "mesh_from_config", lambda mesh_config: build(mesh_config, devices=devices)
    ):
        return cls(config=config, reward_fn=reward_fn)


def phase_sharded(sizes: Sizes, out_dir: str) -> None:
    """PPO on a ``data=1, fsdp=<all devices>`` mesh through
    ``trlx_tpu.train()``, then the sharded learner against a one-device twin
    from the same seed: the scoring forward of one fixed batch, and the first
    optimizer step's loss and gradient norm."""
    import jax
    import numpy as np

    import trlx_tpu
    from trlx_tpu.data.ppo_types import PPORLBatch
    from trlx_tpu.parallel import mesh as mesh_lib

    phase = "sharded"
    shutil.rmtree(out_dir, ignore_errors=True)  # this phase's own output, from an earlier run
    chips = jax.device_count()
    sizes = dataclasses.replace(sizes, steps=3)

    # -- the user's path: three steps on the sharded mesh
    config = ppo_config(sizes, os.path.join(out_dir, "train"), fsdp=chips, trainer="PPOTrainer")
    trainer = trlx_tpu.train(
        reward_fn=reward_fn, prompts=make_prompts(sizes, 2 * sizes.batch), config=config
    )
    for i, row in enumerate(_logged_steps(config, sizes.steps)):
        say(phase, f"train() step {i + 1}/{sizes.steps} on mesh {dict(trainer.mesh.shape)}: "
                   f"total_loss={row['losses/total_loss']:.4g}")

    # -- the state is really spread
    state = {"params": trainer.params, "opt_state": trainer.opt_state}
    held = _bytes_per_device(state)
    expected, replicated = _expected_bytes_per_device(state)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    say(phase, f"params + optimizer state: {total} bytes in all; by the sharding rules each "
               f"device holds {expected} ({replicated} of them in leaves no rule shards)")
    for device_id in sorted(held):
        say(phase, f"  device {device_id}: {held[device_id]} bytes")
    if len(held) != chips or any(h != expected for h in held.values()):
        raise AssertionError(f"per-device bytes {held}, expected {expected} on each of {chips}")
    # what is not replicated must split evenly: a learner that has only met
    # one chip would leave device 0 holding everything
    if not expected - replicated <= (total - replicated) / chips * 1.01:
        raise AssertionError("sharded leaves do not split evenly over the mesh")
    del trainer, state

    # -- sharded against one device, same seed
    # (self-healing on: its guarded step is the one that reports a gradient norm)
    sharded, single = (
        build_trainer(
            ppo_config(sizes, os.path.join(out_dir, name), fsdp=fsdp, trainer="PPOTrainer",
                       self_healing=True),
            devices,
        )
        for name, fsdp, devices in (("sharded", chips, None), ("single", 1, jax.devices()[:1]))
    )
    say(phase, f"twins: mesh {dict(sharded.mesh.shape)} on {sharded.mesh.devices.size} devices, "
               f"mesh {dict(single.mesh.shape)} on {[d.id for d in single.mesh.devices.flat]}")

    rng = np.random.default_rng(SEED)
    B, P, R = sizes.batch, sizes.prompt_len, sizes.new_tokens
    vocab = sharded.tokenizer.vocab_size
    seq = rng.integers(3, vocab, (B, P + R)).astype(np.int32)
    mask = np.ones((B, P + R), np.int32)
    mask[:, :P] = np.arange(P)[None, :] >= rng.integers(0, P // 2, (B, 1))  # left padding

    def score(t):
        dbatch = mesh_lib.put_batch(t.mesh, {"seq": seq, "mask": mask})
        with t.mesh:
            out = t._get_score_fn(B, P, R)(
                t.params, t._ref_scoring_params(), t.frozen_branch_params,
                dbatch["seq"], dbatch["mask"],
            )
        return [np.asarray(jax.device_get(x), np.float32) for x in out]

    scored = {"sharded": score(sharded), "single": score(single)}
    for name, a, b in zip(("logprobs", "values", "ref_logprobs"), scored["sharded"], scored["single"]):
        _check(phase, f"scoring forward {name} [B={B} P={P} R={R}]",
               float(np.abs(a - b).max()), SHARDED_ATOL)

    logprobs, values, _ = scored["single"]
    batch = PPORLBatch(
        query_tensors=seq[:, :P], response_tensors=seq[:, P:],
        logprobs=logprobs, values=values,
        rewards=rng.normal(size=(B, R)).astype(np.float32),
        attention_mask=mask[:, :P], response_mask=mask[:, P:],
        policy_version=np.zeros((B,), np.int32),
    )
    for t in (sharded, single):
        t.num_mb = 1  # prepare_learning's, which would collect rollouts too
    stats = {"sharded": sharded.train_step(batch), "single": single.train_step(batch)}
    # not the policy loss: the batch's logprobs are this policy's own, so it is
    # zero but for rounding, and its relative error means nothing
    for key in ("losses/total_loss", "losses/value_loss", "health/grad_norm"):
        a, b = stats["sharded"][key], stats["single"][key]
        say(phase, f"first optimizer step {key}: sharded={a:.6g} single={b:.6g} "
                   f"(relative bound {SHARDED_RTOL})")
        if not (np.isfinite(a) and abs(a - b) <= SHARDED_RTOL * abs(b)):
            raise AssertionError(f"{key}: sharded {a} vs single {b}")
    if stats["sharded"]["health/update_applied"] != 1.0:
        raise AssertionError("the sharded step's update was not applied")


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = parser.parse_args(argv)
    out_dir = os.path.join(REPO_ROOT, "chip_smoke_out")  # git-ignored

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: jax found {device.platform!r} ({device.device_kind}), not a TPU; "
              "nothing was run", file=sys.stderr)
        return 1

    from trlx_tpu.utils.compilation_cache import configure_compilation_cache

    cache_dir = configure_compilation_cache()  # before the first compile
    info = phase_device(cache_dir)
    if info["count"] != args.chips:
        raise AssertionError(f"--chips {args.chips} but jax has {info['count']} devices")
    if args.chips == 1:
        phase_kernels(FULL)
        phase_ppo(FULL, os.path.join(out_dir, "ppo"))
        phase_ppo(FULL, os.path.join(out_dir, "ppo_serving"), serving=True)
        phase_ppo(MOE, os.path.join(out_dir, "ppo_moe"), phase="ppo_moe")
    else:
        phase_sharded(FULL, os.path.join(out_dir, "sharded"))
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
