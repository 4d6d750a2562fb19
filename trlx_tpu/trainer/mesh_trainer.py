"""MeshRLTrainer — the single shared trainer engine over a TPU mesh.

This is the TPU-native replacement for BOTH reference backends (SURVEY.md §7): the
Accelerate engine (`/root/reference/trlx/trainer/accelerate_base_trainer.py:40-682`)
and the NeMo/Megatron one. One SPMD program over a ``data × fsdp × model`` mesh covers
DP / ZeRO / TP / SP via PartitionSpecs, so there is exactly one code path.

Responsibilities (reference line refs in method docstrings): model+optimizer setup
with layer freezing, jitted gradient-accumulation train step, the jitted generation
engine with shape bucketing, stop-sequence decode, distributed evaluate, the main
``learn()`` loop with periodic eval/checkpoint/save-best, checkpoint save/load, and
tracker logging with the reference's stat names.
"""

import json
import os
from abc import abstractmethod
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.transformer import TransformerConfig
from trlx_tpu.obs import Observability, batch_token_count, compile_log, op_scopes
from trlx_tpu.ops.attention import decode_cache_lane_fill, decode_cache_read_share
from trlx_tpu.ops.generation import generate as generate_op
from trlx_tpu.ops.generation import LENGTH_BUCKETS, generate_seq2seq, left_pad_batch, pad_to_bucket
from trlx_tpu.parallel import mesh as mesh_lib
from trlx_tpu.pipeline.tokenization import load_tokenizer
from trlx_tpu.resilience import Resilience, chaos_poison_batch, find_latest_committed
from trlx_tpu.trainer import BaseRLTrainer, register_trainer
from trlx_tpu.utils import (
    Clock,
    filter_non_scalars,
    get_git_tag,
    get_optimizer_class,
    get_scheduler_class,
    set_seed,
    significant,
)
from trlx_tpu.utils import logging
from trlx_tpu.utils.compilation_cache import configure_compilation_cache
from trlx_tpu.utils.metrics import gauges
from trlx_tpu.utils.trackers import make_tracker

logger = logging.get_logger(__name__)


def pack_scores(scores) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fixed-shape encoding of reward_fn output for cross-host broadcast:
    (header [dense, width], padded [B, width] f32, lens [B] i32). Handles both
    per-sample scalars and dense per-token reward arrays (ragged, padded)."""
    dense = len(scores) > 0 and np.ndim(scores[0]) > 0
    if dense:
        lens = np.asarray([len(s) for s in scores], np.int32)
        width = max(1, int(lens.max()))
        padded = np.zeros((len(scores), width), np.float32)
        for i, s in enumerate(scores):
            padded[i, : len(s)] = np.asarray(s, np.float32)
    else:
        lens = np.zeros((len(scores),), np.int32)
        width = 1
        padded = np.asarray(jax.device_get(list(scores)), np.float32).reshape(-1, 1)
    return np.asarray([int(dense), width], np.int32), padded, lens


def unpack_scores(dense: bool, padded: np.ndarray, lens: np.ndarray):
    """Inverse of :func:`pack_scores`."""
    if dense:
        return [padded[i, : lens[i]] for i in range(padded.shape[0])]
    return padded[:, 0].tolist()


@register_trainer
class MeshRLTrainer(BaseRLTrainer):
    """Shared engine; algorithm trainers subclass and provide
    ``setup_model / create_train_dataloader / train_step / prepare_learning``."""

    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        # distributed init MUST precede any backend-initializing jax call
        # (PRNGKey creation below queries devices)
        mesh_lib.initialize_distributed()
        # persistent XLA compile cache: 20-40s first-compiles restore in ms on
        # subsequent runs with identical shapes. MUST come before the process's
        # first compile — jax latches cache-enablement at that point, and even
        # the PRNGKey below compiles a module
        configure_compilation_cache(config=config)
        # always on, and before the first compile too, so that the log holds
        # the model's init as well: its callbacks fire per compile, never per step
        compile_log.install()
        self.np_rng = set_seed(config.train.seed)
        # identical on EVERY process: rng is a replicated jit input to generate,
        # and jax requires replicated inputs to be equal across hosts
        self.rng = jax.random.PRNGKey(config.train.seed)
        self.mesh = mesh_lib.mesh_from_config(config.mesh)
        self.tokenizer = load_tokenizer(config.tokenizer)

        self.compute_dtype = jnp.dtype(config.mesh.compute_dtype)
        self.param_dtype = jnp.dtype(config.mesh.param_dtype)

        self.setup_model()
        self.setup_optimizer()

        self.iter_count = 0
        self.nth_evaluation = 0
        self.best_reward = -float("inf")
        self.clock = Clock()
        self.generate_kwargs = dict(getattr(config.method, "gen_kwargs", {}) or {})
        self.generate_experience_kwargs = getattr(config.method, "gen_experience_kwargs", None)
        self._compiled_generate = {}
        self._rollout_params = None  # cached low-precision copy (rollout_param_dtype)
        self._cast_rollout_params = None  # its jitted cast fn (built once)

        run_name = config.train.run_name
        if run_name is None:
            tag, branch = get_git_tag()
            config.train.run_name = run_name = (
                f"{config.model.model_path.split('/')[-1]}"
                f"/{jax.device_count()}chips:{branch}"
            ).replace("/", "_")
        self.tracker = make_tracker(config.train, config.to_dict())
        # observability layer (span tracing / MFU / memory gauges / watchdog);
        # a disabled config makes every obs call a near-no-op
        obs_logging_dir = config.train.logging_dir or os.path.join(
            config.train.checkpoint_dir, "logs"
        )
        self.obs = Observability(config.train.observability, obs_logging_dir)
        # resilience subsystem (async atomic checkpointing / preemption
        # handling / auto-resume / reward retries); with the default disabled
        # config every hook is a no-op and reward_fn is wrapped only with the
        # free chaos check
        self.resilience = Resilience(
            config.train.resilience, multiprocess=jax.process_count() > 1
        )
        self.reward_fn = self.resilience.wrap_reward_fn(self.reward_fn)
        # self-healing health guard (skip -> rollback -> halt escalation
        # ladder; docs/resilience.md). None when disabled — the compiled train
        # step and the learn loop are then byte-identical to an unconfigured
        # run. Must exist before any train step is built: make_grad_accum_step
        # compiles the on-device skip guard only when a guard is present.
        self.health = None
        sh_config = config.train.self_healing
        if sh_config.enabled:
            from trlx_tpu.resilience.health import TrainingHealthGuard

            self.health = TrainingHealthGuard(
                sh_config,
                diagnostics_dir=sh_config.diagnostics_dir
                or os.path.join(config.train.checkpoint_dir, "diagnostics"),
            )
        self.self_healing_summary = None

    # ------------------------------------------------------------- model setup

    @abstractmethod
    def setup_model(self):
        """Set self.module, self.params (sharded train_state pytree incl. heads),
        self.model_config, self.model_type."""
        ...

    def pipeline_overrides(self) -> Dict[str, Any]:
        """Model overrides enabling pipeline parallelism when ``mesh.pipe > 1``
        (stacked layer layout + GPipe schedule, trlx_tpu/parallel/pipeline.py).
        Validates the config combinations PP cannot serve: stacked layers have no
        per-layer param paths, so partial layer freezing and the hydra/value
        branches (which capture a mid-stack activation) are unavailable — PPO
        falls back to the full reference copy it already uses at
        ``num_layers_unfrozen=-1`` (the NeMo PP reference does the same,
        modeling_nemo_ppo.py:228-244)."""
        mc = self.config.mesh
        if mc.pipe <= 1:
            return {}
        if self.config.model.model_arch_type == "seq2seq":
            raise ValueError("pipeline parallelism (mesh.pipe > 1) is causal-LM only")
        if self.config.model.num_layers_unfrozen >= 0:
            raise ValueError(
                "mesh.pipe > 1 requires num_layers_unfrozen=-1: pipelined models "
                "keep block params stacked and cannot freeze or branch at a layer "
                "boundary (PPO then uses a full reference copy automatically)"
            )
        if getattr(self.config.method, "num_value_layers_unfrozen", 0):
            raise ValueError("mesh.pipe > 1 requires num_value_layers_unfrozen=0")
        overrides: Dict[str, Any] = {
            "pipeline_stages": mc.pipe,
            "pipeline_microbatches": mc.pipeline_microbatches,
        }
        if mc.sequence_shard:
            logger.warning(
                "mesh.sequence_shard is disabled under pipeline parallelism: "
                "the pipelined stack applies no sequence-sharding constraints"
            )
            overrides["sequence_sharding"] = False
        return overrides

    def restore_mesh(self, overrides: Dict[str, Any]):
        """Mesh to hand ``load_pretrained`` for direct-to-device sharded restore
        of native checkpoints — or None when the model will use the stacked
        layout, whose host-side [L, ...] restack (``maybe_stack_loaded``) needs
        host arrays (np.asarray on non-addressable shards would throw on pods)."""
        if overrides.get("scan_layers") or overrides.get("pipeline_stages", 1) > 1:
            return None
        return self.mesh

    def maybe_stack_loaded(self, trunk_params, num_layers: int, stacked: Optional[bool] = None):
        """Convert HF-loaded per-layer params to the stacked layout when the
        built model uses it (``mesh.pipe > 1`` or ``scan_layers``)."""
        if stacked is None:
            stacked = getattr(self.model_config, "stacked", False)
        if stacked and trunk_params is not None:
            from trlx_tpu.parallel.pipeline import stack_layer_params

            return stack_layer_params(trunk_params, num_layers)
        return trunk_params

    def trainable_path_predicate(self, path: str) -> bool:
        """Which params receive gradients (parity: ``freeze_bottom_causal_layers``,
        reference utils/modeling.py:22-45): with num_layers_unfrozen = N > 0, only
        the top N transformer layers and all heads train; -1 trains everything."""
        if self.config.model.peft_config:
            # peft mode: only adapters (LoRA / prefix K-V / prompt embeddings)
            # and heads receive gradients
            if any(a in path for a in ("lora_", "prefix_k", "prefix_v", "prompt_embeddings")):
                return True
            return "transformer" not in path and "t5" not in path
        n_unfrozen = self.config.model.num_layers_unfrozen
        if n_unfrozen < 0:
            return True
        if "transformer" not in path:
            return True  # heads always train
        if "layers_scan" in path:
            # stacked blocks have no per-layer paths; partial freezing cannot be
            # honored. Reachable only when pipeline_stages was forced through
            # model_overrides (mesh.pipe > 1 validates this earlier).
            raise ValueError(
                "num_layers_unfrozen >= 0 cannot be applied to a stacked "
                "(pipeline_stages > 1) model; set num_layers_unfrozen=-1"
            )
        if "layers_" in path:
            layer = int(path.split("layers_")[1].split("/")[0])
            return layer >= self.model_config.num_layers - n_unfrozen
        # embeddings / final norm / lm_head of the trunk
        return False

    def _trainable_labels(self, params) -> Any:
        def build(tree, prefix=""):
            if isinstance(tree, dict):
                return {k: build(v, f"{prefix}/{k}" if prefix else str(k)) for k, v in tree.items()}
            return "train" if self.trainable_path_predicate(prefix) else "freeze"

        return build(params)

    def _learner_overlap_active(self) -> bool:
        """Whether the overlapped-collective FSDP step (``train.learner_overlap``,
        ``trlx_tpu/parallel/fsdp.py``) replaces the GSPMD grad-accum step.

        Config-level gate (``self.health`` does not exist yet during
        ``setup_optimizer``): requires a pure data/fsdp mesh — the shard_map
        body computes the full model locally, so TP (``model > 1``) and PP
        (``pipe > 1``) fall back — and no self-healing guard (the on-device
        skip guard is built into the GSPMD step only). Falls back with a
        warning, never raises: off-path runs stay byte-identical.
        """
        cfg = getattr(self.config.train, "learner_overlap", None)
        if cfg is None or not cfg.enabled:
            return False
        from trlx_tpu.parallel.fsdp import can_overlap

        if not can_overlap(self.mesh):
            logger.warning(
                "train.learner_overlap requires a pure data/fsdp mesh "
                f"(model=1, pipe=1), got {dict(self.mesh.shape)}: falling back "
                "to the GSPMD train step"
            )
            return False
        if self.config.train.self_healing.enabled:
            logger.warning(
                "train.learner_overlap is incompatible with the self-healing "
                "health guard (on-device skip lives in the GSPMD step): "
                "falling back to the GSPMD train step"
            )
            return False
        return True

    def setup_optimizer(self):
        """optax optimizer + schedule from the registries (parity:
        accelerate_base_trainer.py:173-201), masked by the freeze predicate, with
        optimizer state sharded like the params (ZeRO analogue)."""
        opt_config = self.config.optimizer
        kwargs = dict(opt_config.kwargs)
        lr = kwargs.pop("lr", 1e-5)
        sched_kwargs = dict(self.config.scheduler.kwargs)
        sched_lr = sched_kwargs.pop("learning_rate", lr)
        self.lr_schedule = get_scheduler_class(self.config.scheduler.name)(
            learning_rate=sched_lr, **sched_kwargs
        )
        max_grad_norm = kwargs.pop("max_grad_norm", None)
        overlap = self._learner_overlap_active()
        opt_name = opt_config.name
        if overlap and self.config.train.learner_overlap.int8_opt_state:
            # ZeRO + int8: blockwise-quantized Adam moments over each device's
            # LOCAL shard (ops/quantized_adam.py) — the block layout must be
            # shard-local, so this option only exists under the overlap step
            if str(opt_name).lower() in ("adam", "adamw", "adamw_8bit_bnb"):
                opt_name = "adamw_8bit_bnb"
            else:
                logger.warning(
                    f"learner_overlap.int8_opt_state ignored: optimizer "
                    f"{opt_name!r} is not adam-family"
                )
        tx = get_optimizer_class(opt_name)(learning_rate=self.lr_schedule, **kwargs)
        # Under the overlapped step, global-norm clipping cannot be an optax
        # link: the transform would see only this device's gradient SHARD.
        # The step computes the shard-aware global norm itself.
        self._overlap_max_grad_norm = max_grad_norm if overlap else None
        if max_grad_norm and not overlap:
            tx = optax.chain(optax.clip_by_global_norm(max_grad_norm), tx)
        labels = self._trainable_labels(self.params)
        self.tx = optax.multi_transform({"train": tx, "freeze": optax.set_to_zero()}, labels)
        if overlap:
            # ZeRO-sharded init: tx.init runs INSIDE shard_map on each
            # device's parameter shard, so the moments are born shard-local —
            # required for the int8 option (quantization blocks must tile the
            # local shard) and never materializes full-size state anywhere
            from trlx_tpu.parallel import fsdp as fsdp_lib

            self._overlap_specs = fsdp_lib.make_overlap_specs(
                self.params, self.tx, self.mesh
            )
            init = fsdp_lib.make_sharded_opt_init(self.tx, self._overlap_specs, self.mesh)
            with self.mesh:
                self.opt_state = init(self.params)
            return
        # Explicit state shardings: moment leaves take their param's layout by
        # key path, scalars replicate. Leaving this to GSPMD propagation
        # REPLICATES the moments (zeros_like outputs carry no input-derived
        # sharding) — for a full-finetune 7B that is 54G of Adam state per
        # device, measured by the v5e compiler (scripts/scale_proof.py). The
        # explicit specs also fix the old scalar-on-device-0 restore hazard.
        from trlx_tpu.parallel.sharding import make_state_shardings

        state_shardings = make_state_shardings(
            jax.eval_shape(self.tx.init, self.params), self.mesh
        )
        with self.mesh:
            self.opt_state = jax.jit(self.tx.init, out_shardings=state_shardings)(self.params)

    # -------------------------------------------------------------- train step

    def make_grad_accum_step(
        self, loss_fn: Callable, num_mb: int, donate: bool = True, name: str = "train_step"
    ):
        """Build the jitted optimizer step: scan over ``num_mb`` microbatches
        accumulating grads (replaces torch grad-accum no_sync windows,
        accelerate_base_trainer.py:502-516), then one optax update.

        ``loss_fn(params, microbatch) -> (loss, stats_dict)``. ``name`` is the
        compiled program's: the profiler's module events read ``jit_<name>``.

        With the self-healing health guard active (``train.self_healing``),
        the step takes one extra *traced* scalar — the grad-norm cap — and
        discards the computed update on device when the loss or global grad
        norm is non-finite or the norm exceeds the cap: the input buffers are
        donated, so by the time the host could inspect the stats the old
        params are already gone — the skip decision has to live inside the
        XLA program (the ``optax.apply_if_finite`` pattern). The cap is a
        traced argument precisely so the guard's rolling threshold never
        triggers a retrace. Without a guard the exact original program is
        compiled — off-config runs stay bit-identical.

        With ``train.learner_overlap`` active the step is instead built by
        :func:`trlx_tpu.parallel.fsdp.make_overlapped_grad_accum_step` —
        explicit shard_map collectives (per-leaf allgather forward,
        reduce-scatter backward), a gradient-SHARD accumulation carry, and a
        shard-local optimizer update over the ZeRO state from
        ``setup_optimizer``. The overlap-off program below is untouched.
        """
        if self._learner_overlap_active():
            from trlx_tpu.parallel import fsdp as fsdp_lib

            lov = self.config.train.learner_overlap
            logger.info(
                "learner_overlap: overlapped FSDP step active "
                f"(fsdp={self.mesh.shape['fsdp']}, num_microbatches={num_mb}, "
                f"int8_opt_state={lov.int8_opt_state}, remat={lov.remat}, "
                f"max_grad_norm={self._overlap_max_grad_norm})"
            )
            return fsdp_lib.make_overlapped_grad_accum_step(
                loss_fn,
                self.tx,
                self._overlap_specs,
                self.mesh,
                num_mb,
                max_grad_norm=self._overlap_max_grad_norm,
                lr_schedule=self.lr_schedule,
                donate=donate,
                name=name,
            )

        def compute_update(params, opt_state, batch):
            mbs = jax.tree.map(lambda x: x.reshape((num_mb, x.shape[0] // num_mb) + x.shape[1:]), batch)

            def body(grads_acc, mb):
                (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                with jax.named_scope("accumulate"):  # apart from the backward it follows
                    grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                return grads_acc, (loss, stats)

            zero_grads = jax.tree.map(jnp.zeros_like, params)
            with jax.named_scope("loss"):  # forward and backward
                grads, (losses, stats) = jax.lax.scan(body, zero_grads, mbs)
            with jax.named_scope("optimizer"):
                grads = jax.tree.map(lambda g: g / num_mb, grads)
                updates, new_opt_state = self.tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            mean_stats = jax.tree.map(lambda x: jnp.mean(x, axis=0), stats)
            mean_stats["learning_rate_group_0"] = self.lr_schedule(
                _opt_step_count(opt_state)
            )
            return new_params, new_opt_state, mean_stats, losses, grads

        def step(params, opt_state, batch):
            new_params, new_opt_state, mean_stats, _, _ = compute_update(params, opt_state, batch)
            return new_params, new_opt_state, mean_stats

        guard = self.health
        if guard is None:
            step.__name__ = name
            return jax.jit(step, donate_argnums=(0, 1) if donate else ())

        def guarded_step(params, opt_state, batch, grad_norm_cap):
            new_params, new_opt_state, mean_stats, losses, grads = compute_update(
                params, opt_state, batch
            )
            grad_norm = optax.global_norm(grads)
            loss_mean = jnp.mean(losses)
            ok = (
                jnp.isfinite(loss_mean)
                & jnp.isfinite(grad_norm)
                & (grad_norm <= grad_norm_cap)
            )
            keep = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
            new_params = jax.tree.map(keep, new_params, params)
            new_opt_state = jax.tree.map(keep, new_opt_state, opt_state)
            mean_stats["health/grad_norm"] = grad_norm
            mean_stats["health/update_applied"] = ok.astype(jnp.float32)
            return new_params, new_opt_state, mean_stats

        guarded_step.__name__ = name
        jitted = jax.jit(guarded_step, donate_argnums=(0, 1) if donate else ())

        def run(params, opt_state, batch):
            args = (params, opt_state, batch, jnp.float32(guard.grad_norm_cap()))
            op_scopes.note(name, jitted, args, mesh=self.mesh)  # the program itself, which ``run`` hides
            return jitted(*args)

        return run

    # -------------------------------------------------------------- generation

    @abstractmethod
    def gen_step_fn(self):
        """Return step_fn(params, ids, mask, positions, cache)->(logits,hidden,cache)
        and init_cache_fn(batch, total_len) for the generation engine."""
        ...

    def gen_logits_processor(self, **kwargs):
        """Optional decode-time logits processor (ILQL advantage shaping)."""
        return None

    def pop_gen_processor_kwargs(self, gen_kwargs: Dict[str, Any]) -> Dict[str, Any]:
        """Remove and return gen_kwargs consumed by the logits processor rather
        than the generation engine (e.g. ILQL's ``beta``); they become part of
        the compile key so eval sweeps over them recompile per value."""
        return {}

    def generation_params(self):
        """Params used by generate(): the masters, or (train.rollout_param_dtype)
        a cached low-precision copy — decode streams every weight per token, so
        f32 masters double rollout HBM traffic. The copy is invalidated after
        each optimizer step and re-cast lazily (one cast per experience phase)."""
        dtype_name = self.config.train.rollout_param_dtype
        if dtype_name is None:
            return self.params
        if self._rollout_params is None:
            if self._cast_rollout_params is None:
                dtype = jnp.dtype(dtype_name)

                def cast(x):
                    return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x

                def cast_rollout_params(p):
                    return jax.tree.map(cast, p)

                # built once: a fresh jit wrapper per re-cast would re-trace the
                # full param tree every optimizer step
                self._cast_rollout_params = jax.jit(cast_rollout_params)
            with self.mesh:
                self._rollout_params = self._cast_rollout_params(self.params)
        return self._rollout_params

    def generate(
        self,
        prompts_ids: List[np.ndarray],
        eval_mode: bool = False,
        params: Optional[Any] = None,
        **kwargs,
    ):
        """Generate continuations for a list of ragged prompt id arrays.

        Host side: bucket-pad prompts (left) to limit recompiles; device side: one
        compiled generate per (B, P, gen-kwargs) key. Parity:
        accelerate_base_trainer.py:256-283 (generate vs generate_eval kwargs).

        ``params`` overrides the sampling parameters (the async rollout engine
        passes a published snapshot so the producer keeps a stable behavior
        policy while the live ``self.params`` are being donated/updated);
        default is :meth:`generation_params` (the masters or their cached
        low-precision rollout copy).
        """
        gen_kwargs = dict(self.generate_kwargs)
        if not eval_mode and self.generate_experience_kwargs:
            gen_kwargs = dict(self.generate_experience_kwargs)
        gen_kwargs.update(kwargs)
        gen_kwargs.setdefault("eos_token_id", self.tokenizer.eos_token_id)
        gen_kwargs.setdefault("pad_token_id", self.tokenizer.pad_token_id)
        max_new = int(gen_kwargs.pop("max_new_tokens", 16))
        proc_kwargs = self.pop_gen_processor_kwargs(gen_kwargs)

        max_len = max(len(p) for p in prompts_ids)
        P = pad_to_bucket(max_len, LENGTH_BUCKETS)
        ids, mask = left_pad_batch(prompts_ids, gen_kwargs["pad_token_id"], P)

        is_seq2seq = getattr(self, "is_seq2seq", False)
        key = (
            ids.shape, max_new, is_seq2seq,
            tuple(sorted(gen_kwargs.items())), tuple(sorted(proc_kwargs.items())),
        )
        if key not in self._compiled_generate:
            if is_seq2seq:
                fns = self.seq2seq_gen_fns()
                fn = partial(
                    generate_seq2seq,
                    fns["encode"], fns["cross_kv"], fns["decode"], fns["init_cache"],
                    max_new_tokens=max_new,
                    decoder_start_token_id=self.decoder_start_token_id,
                    logits_processor=self.gen_logits_processor(**proc_kwargs),
                    **gen_kwargs,
                )

                def program(params, i, m, r):
                    return fn(params=params, input_ids=i, attention_mask=m, rng=r)
            else:
                step_fn, init_cache_fn = self.gen_step_fn()
                fn = partial(
                    generate_op,
                    step_fn,
                    init_cache_fn=init_cache_fn,
                    max_new_tokens=max_new,
                    logits_processor=self.gen_logits_processor(**proc_kwargs),
                    **gen_kwargs,
                )

                def program(params, i, m, r):
                    return fn(params, input_ids=i, attention_mask=m, rng=r)

            # one name for every trainer's generate program: the profiler's
            # module events read ``jit_generate``
            program.__name__ = "generate"
            # outputs replicated: every host must address the full result
            # (host-side decode/reward runs identically on all processes)
            self._compiled_generate[key] = jax.jit(
                program, out_shardings=mesh_lib.replicated(self.mesh)
            )
        self.rng, sub = jax.random.split(self.rng)
        batch = mesh_lib.put_batch(self.mesh, {"ids": ids, "mask": mask})
        gen_params = params if params is not None else self.generation_params()
        # the span covers dispatch + the device_get sync: decode is async until
        # the host fetch, so timing only the dispatch would undercount wildly
        with self.obs.span("generate"):
            with self.mesh, compile_log.attributed("generate"):
                args = (gen_params, batch["ids"], batch["mask"], sub)
                op_scopes.note("generate", self._compiled_generate[key], args, mesh=self.mesh)
                out = self._compiled_generate[key](*args)
            sequences = np.asarray(jax.device_get(out["sequences"]))
            response_mask = np.asarray(jax.device_get(out["response_mask"]))
        model_config = getattr(self, "model_config", None)
        if isinstance(model_config, TransformerConfig) and model_config.attention_layers:
            # the loop ran until the longest row ended; its first token came from the prefill
            steps = int(response_mask.sum(axis=1).max()) - 1
            c, rows = model_config, ids.shape[0]
            with self.mesh:
                decode = (c.attention_impl, c.biased_attention, c.num_heads, c.cache_layout(rows, P + max_new), rows)
                gauges.set("rollout/cache_read_share", decode_cache_read_share(*decode, max_new, steps))
                gauges.set("rollout/cache_lane_fill", decode_cache_lane_fill(*decode))
        # seq2seq sequences are [decoder_start] + response: pad_len for decode() is 1
        return sequences, response_mask, 1 if is_seq2seq else P

    def decode(
        self,
        prompts: List[np.ndarray],
        samples: np.ndarray,
        prompt_pad_len: int,
        append_eos: bool = False,
        response_masks: Optional[np.ndarray] = None,
    ) -> Tuple[List[str], List[str], List[str], List[np.ndarray]]:
        """Decode generated sequences into (str_samples, str_prompts, str_outputs,
        trimmed_output_ids), trimming at the first stop sequence and (optionally)
        re-appending eos (parity: accelerate_base_trainer.py:203-255).

        Trimming is token-level on the rollout hot path: response lengths come from
        the generation ``response_mask`` and stop sequences are found by token-
        subsequence scan (native ``find_stop_positions``), so output ids are sliced
        from the sampled tokens without re-tokenization. A string-level check
        remains only as a net for stop sequences that cross token boundaries."""
        from trlx_tpu.native import find_stop_positions

        B = len(prompts)
        resp_all = np.ascontiguousarray(samples[:, prompt_pad_len:], np.int32)
        eos = self.tokenizer.eos_token_id
        pad = self.tokenizer.pad_token_id
        if response_masks is not None:
            lens = np.asarray(response_masks).sum(axis=1).astype(np.int64)
        else:
            valid = resp_all != pad
            lens = np.where(
                valid.any(axis=1), resp_all.shape[1] - np.argmax(valid[:, ::-1], axis=1), 0
            ).astype(np.int64)
        # response_mask counts the eos token itself; output ids exclude it
        if eos is not None and B > 0:
            last = resp_all[np.arange(B), np.maximum(lens - 1, 0)]
            lens = lens - ((lens > 0) & (last == eos)).astype(np.int64)
        token_stopped = np.zeros(B, bool)
        if self.stop_sequences:
            if not hasattr(self, "_stop_token_ids"):
                self._stop_token_ids = [
                    self.tokenizer(s, add_special_tokens=False).input_ids
                    for s in self.stop_sequences
                ]
            stop_pos = find_stop_positions(resp_all, self._stop_token_ids)
            token_stopped = stop_pos < lens
            lens = np.minimum(lens, stop_pos)

        str_samples, str_prompts, str_outputs, out_ids = [], [], [], []
        for i, prompt in enumerate(prompts):
            str_prompt = self.tokenizer.decode(prompt, skip_special_tokens=True)
            resp = resp_all[i, : lens[i]]
            if token_stopped[i]:
                # parity with the reference's str_output[:ix].rstrip(): drop the
                # whitespace run preceding the stop sequence (token-level)
                while len(resp) and self.tokenizer.decode(resp[-1:]).strip() == "":
                    resp = resp[:-1]
            str_output = self.tokenizer.decode(resp, skip_special_tokens=True)
            if token_stopped[i]:
                str_output = str_output.rstrip()
            for stop in self.stop_sequences:
                stop_ix = str_output.find(stop)
                if stop_ix >= 0:  # crossed a token boundary; rare slow path
                    str_output = str_output[:stop_ix].rstrip()
                    resp = np.asarray(
                        self.tokenizer(str_output, add_special_tokens=False).input_ids, np.int32
                    )
            trimmed = list(resp)
            if append_eos and eos is not None:
                trimmed.append(eos)
            if len(trimmed) == 0:  # never emit empty responses (breaks PPO shapes)
                trimmed = [eos or 0]
            str_samples.append(str_prompt + str_output)
            str_prompts.append(str_prompt)
            str_outputs.append(str_output)
            out_ids.append(np.asarray(trimmed, np.int32))
        return str_samples, str_prompts, str_outputs, out_ids

    # -------------------------------------------------------------- evaluation

    @property
    def reward_on_process_zero(self) -> bool:
        """Resolved ``train.reward_on_process_zero``: None (default) means auto —
        on exactly when this is a multi-process run (a served reward model must
        not be hit once per host, and a nondeterministic server would silently
        desync the hosts' rollouts)."""
        flag = self.config.train.reward_on_process_zero
        if flag is None:
            return jax.process_count() > 1
        return bool(flag)

    def call_reward_fn(self, **kwargs):
        """Invoke reward_fn; with :attr:`reward_on_process_zero` only process 0
        calls it and the scores are broadcast to every host.

        Every process must enter this function at the same point in the program
        (the broadcasts are collectives)."""
        if not self.reward_on_process_zero or jax.process_count() == 1:
            return self.reward_fn(**kwargs)
        scores = self.reward_fn(**kwargs) if jax.process_index() == 0 else None
        return self.broadcast_scores(scores, len(kwargs["samples"]))

    def broadcast_scores(self, scores, batch_size: int):
        """Broadcast process-0 scores to every host. MAIN THREAD ONLY: the
        broadcasts are collectives and must execute in identical program order
        on every process — the overlap rollout path keeps reward_fn on a worker
        thread but drains its futures through here on the main thread."""
        from jax.experimental import multihost_utils

        if jax.process_index() == 0:
            header, padded, lens = pack_scores(scores)
        else:
            header = np.zeros((2,), np.int32)
        header = np.asarray(multihost_utils.broadcast_one_to_all(header))
        dense, width = bool(header[0]), int(header[1])
        if jax.process_index() != 0:
            padded = np.zeros((batch_size, width), np.float32)
            lens = np.zeros((batch_size,), np.int32)
        padded = np.asarray(multihost_utils.broadcast_one_to_all(padded))
        lens = np.asarray(multihost_utils.broadcast_one_to_all(lens))
        return unpack_scores(dense, padded, lens)

    def evaluate(self) -> Dict[str, Any]:
        """Generate on eval prompts, score with reward_fn/metric_fn, log a sample
        table (parity: accelerate_base_trainer.py:339-500, incl. gen-kwarg sweeps
        via list-valued gen_kwargs)."""
        logger.info("Evaluating model")
        stats: Dict[str, Any] = {}
        sweep_keys = [k for k, v in self.generate_kwargs.items() if isinstance(v, list)]
        sweeps = [{}]
        if sweep_keys:
            sweeps = []
            base = {k: v for k, v in self.generate_kwargs.items() if k not in sweep_keys}
            from itertools import product

            for combo in product(*[self.generate_kwargs[k] for k in sweep_keys]):
                sweeps.append({**base, **dict(zip(sweep_keys, combo))})

        for sweep_kwargs in sweeps:
            suffix = "".join(f"@{k}={v}" for k, v in sweep_kwargs.items() if k in sweep_keys)
            # decode per batch with that batch's own prompt pad length: batches may
            # bucket to different prompt lengths, so a shared pad_len would slice
            # later batches' responses at the wrong offset
            str_samples, str_prompts, str_outputs, meta = [], [], [], {}
            for batch in self.eval_pipeline.create_loader(self.config.train.batch_size):
                prompts = batch["input_ids"]
                samples, resp_mask, pad_len = self.generate(prompts, eval_mode=True, **sweep_kwargs)
                s, p, o, _ = self.decode(prompts, samples, pad_len, response_masks=resp_mask)
                str_samples.extend(s)
                str_prompts.extend(p)
                str_outputs.extend(o)
                for k, v in batch.items():
                    if k != "input_ids":
                        meta.setdefault(k, []).extend(v)

            columns = ["prompt", "output"]
            columns_data = [str_prompts, str_outputs]
            if self.reward_fn is not None:
                rewards = self.call_reward_fn(
                    samples=str_samples, prompts=str_prompts, outputs=str_outputs,
                    tokenizer=self.tokenizer, **meta,
                )
                rewards = [float(np.sum(r)) if np.ndim(r) > 0 else float(r) for r in rewards]
                columns.append("reward")
                columns_data.append(rewards)
                stats[f"reward/mean{suffix}"] = float(np.mean(rewards))
                stats[f"reward/std{suffix}"] = float(np.std(rewards))
            if self.metric_fn is not None:
                metrics = self.metric_fn(
                    samples=str_samples, prompts=str_prompts, outputs=str_outputs, **meta
                )
                for k, xs in metrics.items():
                    stats[f"metrics/{k}{suffix}"] = float(np.mean(xs))
                    if np.ndim(xs) > 0 and len(xs) == len(str_samples):
                        columns.append(k)
                        columns_data.append(list(map(float, xs)))
            rows = list(zip(*columns_data))
            if jax.process_index() == 0:
                self.tracker.log_table(f"samples{suffix}", columns, [list(r) for r in rows], self.iter_count)
                for row in rows[:4]:
                    logger.info(" | ".join(str(c)[:72] for c in row))
        self.nth_evaluation += 1
        return stats

    # -------------------------------------------------------------- main loop

    @abstractmethod
    def create_train_dataloader(self):
        ...

    @abstractmethod
    def train_step(self, batch) -> Dict[str, float]:
        """One optimizer step on a host batch; returns flat stats."""
        ...

    # ----------------------------------------------------- staged learn batches
    # The microbatch-interleaved learn seam for stream-overlapped PPO
    # (docs/serving.md "Stream-overlapped PPO"): during the streaming window
    # the experience producer collates upcoming first-epoch learner batches
    # and ``device_put``s them while decode still owns the wall-clock, then
    # the train loop consumes the pre-staged device copies instead of
    # re-transferring. Purely a transfer optimization — the staged host batch
    # must match the loader's batch exactly or the whole stage is discarded,
    # so the optimizer sees identical data either way.

    def _clear_staged_learn(self) -> None:
        self._staged_learn: List[Tuple[Any, Any]] = []

    def _stage_learn_batch(self, host_batch, device_batch) -> None:
        """Record a (host, device) learn-batch pair staged ahead of the loop."""
        if not hasattr(self, "_staged_learn"):
            self._clear_staged_learn()
        self._staged_learn.append((host_batch, device_batch))

    @staticmethod
    def _host_batches_equal(a, b) -> bool:
        flat_a, tree_a = jax.tree.flatten(a)
        flat_b, tree_b = jax.tree.flatten(b)
        if tree_a != tree_b:
            return False
        return all(np.array_equal(x, y) for x, y in zip(flat_a, flat_b))

    def _pop_staged_learn(self, batch):
        """Device copy staged for ``batch``, or None to fall back to a fresh
        transfer. Staged batches are predictions of the loader's output in
        order; the first mismatch (quarantine drop, truncation, reshuffle)
        invalidates the remainder — correctness never depends on staging."""
        staged = getattr(self, "_staged_learn", None)
        if not staged:
            return None
        host, dev = staged[0]
        if self._host_batches_equal(host, batch):
            staged.pop(0)
            return dev
        self._clear_staged_learn()
        return None

    def prepare_learning(self):
        pass

    def post_epoch_callback(self, epoch: int):
        pass

    def post_backward_callback(self):
        pass

    def on_learn_end(self):
        """Teardown hook guaranteed to run when :meth:`learn` exits (normal
        return, early stop, or exception) — PPO uses it to drain and join the
        async rollout producer so no thread outlives training."""
        pass

    def learn(self):
        """Main training loop (parity: accelerate_base_trainer.py:518-652)."""
        try:
            return self._learn_loop()
        finally:
            if self.health is not None:
                # the run summary half of "visible in gauges and the run
                # summary" — stashed on the trainer so callers/tests see it
                self.self_healing_summary = self.health.report()
                logger.info(f"self-healing summary: {self.self_healing_summary}")
            self.on_learn_end()
            # after the engine drain: the writer flush below may be the
            # emergency checkpoint, and the producer must not race it
            self.resilience.close()
            # after on_learn_end: producer teardown spans still get recorded
            self.obs.close()

    def _warn_recompiled(self, settled: int) -> int:
        """One warning for the compiles recorded since ``settled``, if there
        are any; returns the count now."""
        total = compile_log.log.total
        if total == settled:
            return settled
        new = compile_log.log.compiles()[-(total - settled):]
        entries = sorted({entry or compile_log.UNATTRIBUTED for _, _, entry in new})
        logger.warning(
            f"step {self.iter_count}: {total - settled} XLA compile(s) after the first full "
            f"iteration ({sum(s for _, s, _ in new):.2f} s; entries: {', '.join(entries)}) — "
            "a shape, dtype or static argument changed"
        )
        return total

    def _spanned_batches(self, loader):
        """The loader's batches, each fetch (shuffle, collate) under a ``data``
        span — the host work between two ``learn`` spans that is not logging."""
        batches = iter(loader)
        while True:
            with self.obs.span("data"):
                try:
                    batch = next(batches)
                except StopIteration:
                    return
            yield batch

    def _maybe_resume(self, train_config):
        """Restore from an explicit resume path (missing → hard error, never a
        silent fresh start) or, under resilience auto-resume, from the newest
        *committed* checkpoint in checkpoint_dir. Runs BEFORE prepare_learning
        so the first rollouts already use the restored params, RNG streams,
        and prompt-stream position."""
        path = train_config.resume_from_checkpoint
        if path:
            if not os.path.exists(path):
                raise FileNotFoundError(
                    f"train.resume_from_checkpoint={path!r} does not exist; "
                    "refusing to silently train from scratch"
                )
            self.load(path)
            return
        if self.resilience.auto_resume:
            latest = find_latest_committed(train_config.checkpoint_dir)
            if latest is not None:
                logger.info(f"Auto-resume: restoring newest committed checkpoint {latest}")
                self.load(latest)

    def _learn_loop(self):
        train_config = self.config.train
        self.iter_count = 0
        self._maybe_resume(train_config)
        self.prepare_learning()
        self.obs.configure_model(self.params, getattr(self, "model_config", None))
        self.obs.beat("learner")

        with self.obs.span("evaluate"):
            results = self.evaluate() if getattr(self, "eval_pipeline", None) else {}
        self.tracker.log(results, self.iter_count)
        if self.iter_count >= train_config.total_steps:
            # resumed at (or past) the end of training: nothing left to do
            self._report_sweep_result(results)
            return results

        profiling = False
        # compiles recorded when the first full iteration ended: every shape is
        # compiled by then, so a later one is the operator's "which step recompiled"
        compiles_settled = None
        try:
            for epoch in range(train_config.epochs):
                for batch in self._spanned_batches(self.create_train_dataloader()):
                    if train_config.profile_dir:
                        if self.iter_count == train_config.profile_start_step and not profiling:
                            jax.profiler.start_trace(train_config.profile_dir)
                            profiling = True
                        elif self.iter_count >= train_config.profile_end_step and profiling:
                            self._stop_profile()
                            profiling = False
                    # chaos site "nan-loss": poison the batch to non-finite
                    # (free when unarmed) — the health guard must catch it
                    batch = chaos_poison_batch(batch)
                    self.clock.tick()  # reset: measure train_step alone
                    # drop the rollout param copy BEFORE the step: fwd+bwd+update is
                    # the peak-memory window and the copy is stale after it anyway
                    self._rollout_params = None
                    with self.obs.span("learn"):
                        stats = self.train_step(batch)
                    stats["time/forward_backward"] = self.clock.tick()
                    self.iter_count += 1
                    self.obs.beat("learner")
                    self.post_backward_callback()
                    if compiles_settled is not None:
                        compiles_settled = self._warn_recompiled(compiles_settled)

                    if self.health is not None:
                        action = self.health.observe(stats, self.iter_count)
                        if action == "rollback":
                            # may raise TrainingHealthError when the budget is
                            # exhausted (fail closed, diagnostics bundle path
                            # in the message)
                            self._handle_health_rollback()
                            # the rest of this epoch's batches came from the
                            # anomalous policy — re-collect experience instead
                            # (post_epoch_callback refills the store)
                            break

                    if self.resilience.should_stop(self.iter_count):
                        return self._preempt_exit(stats)

                    if (
                        train_config.checkpoint_interval
                        and self.iter_count % train_config.checkpoint_interval == 0
                    ):
                        with self.obs.span("checkpoint"):
                            self._save_checkpoint(
                                os.path.join(train_config.checkpoint_dir, self._checkpoint_name())
                            )
                            self.save_pretrained(os.path.join(train_config.checkpoint_dir, "hf_model"))

                    if (
                        train_config.eval_interval
                        and self.iter_count % train_config.eval_interval == 0
                    ) or self.iter_count >= train_config.total_steps:
                        with self.obs.span("evaluate"):
                            results = self.evaluate() if getattr(self, "eval_pipeline", None) else {}
                        self.obs.beat("learner")  # a long eval is not a stall
                        stats.update(results)
                        if train_config.save_best and "reward/mean" in results:
                            # under SPMD every process computes the same global reward,
                            # replacing the reference's MAX all-reduce guard (:616-638)
                            if results["reward/mean"] > self.best_reward:
                                self.best_reward = results["reward/mean"]
                                self._save_checkpoint(
                                    os.path.join(train_config.checkpoint_dir, "best_checkpoint")
                                )
                        if self._sweep_tick(results):
                            # ASHA early stop: exit cleanly (no signals: a killed
                            # jax process may not release its chip at once)
                            logger.info("Sweep scheduler requested early stop")
                            self._report_sweep_result(results)
                            return results

                    with self.obs.span("log"):
                        if self.obs.enabled:
                            tokens, samples, seq_len = batch_token_count(batch)
                            stats.update(self.obs.step_stats(tokens, samples, seq_len))
                        stats = {k: significant(v) if isinstance(v, float) else v for k, v in stats.items()}
                        self.tracker.log(stats, self.iter_count)
                    if self.iter_count % 10 == 0 or self.iter_count == 1:
                        brief = {k: v for k, v in stats.items() if "loss" in k or "reward" in k}
                        logger.info(f"step {self.iter_count}/{train_config.total_steps} {brief}")

                    if self.iter_count >= train_config.total_steps:
                        # padded like the interval checkpoints, so the dir's
                        # lexicographic order is chronological (resume relies on it)
                        self._save_checkpoint(
                            os.path.join(train_config.checkpoint_dir, self._checkpoint_name())
                        )
                        self._report_sweep_result(results)
                        return results
                self.post_epoch_callback(epoch)
                if compiles_settled is None:
                    compiles_settled = compile_log.log.total
                else:
                    compiles_settled = self._warn_recompiled(compiles_settled)
        finally:
            # the profiler window must close however the loop exits (total_steps
            # return, sweep early stop, or an exception mid-window) — otherwise
            # jax.profiler.stop_trace() is never called and the trace is lost
            if profiling:
                self._stop_profile()
        self._report_sweep_result(results)
        return results

    def _stop_profile(self):
        """Close the ``train.profile_dir`` session and put the programs' tables
        of their own instructions beside the trace (``op_scopes.json``: a device
        event's instruction name -> scope; docs/observability.md)."""
        jax.profiler.stop_trace()
        path = os.path.join(self.config.train.profile_dir, "op_scopes.json")
        try:
            rows = op_scopes.write(path)
            logger.info(f"wrote {path}: {rows} instructions by program")
        except Exception as e:  # the trace is what the operator asked for; the key to it is extra
            logger.warning(f"op_scopes.json not written: {e!r}")

    def _sweep_tick(self, results) -> bool:
        """Under a sweep: report intermediate metrics (consumed by the ASHA
        scheduler in trlx_tpu/sweep.py) and poll the stop file. Returns True if
        the scheduler asked this trial to stop."""
        if not os.environ.get("TRLX_SWEEP"):
            return False
        if jax.process_index() == 0:
            print(
                "SWEEP_METRIC "
                + json.dumps({"step": self.iter_count, **filter_non_scalars(results or {})}),
                flush=True,
            )
        # The stop decision must be COLLECTIVE: rank 0 reads the file and the
        # result is broadcast, so every rank returns from learn() together (a
        # per-rank filesystem poll could race the file's creation and leave the
        # mesh with a missing participant)
        stop_file = os.environ.get("TRLX_SWEEP_STOP_FILE")
        stop = bool(stop_file and os.path.exists(stop_file)) if jax.process_index() == 0 else False
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            stop = bool(multihost_utils.broadcast_one_to_all(jnp.asarray(stop)))
        return stop

    def _report_sweep_result(self, results):
        """Final-metrics line consumed by the sweep runner (trlx_tpu/sweep.py)."""
        if os.environ.get("TRLX_SWEEP") and jax.process_index() == 0:
            print("SWEEP_RESULT " + json.dumps(filter_non_scalars(results or {})), flush=True)

    # ------------------------------------------------------------- checkpoints

    def _checkpoint_name(self, it: Optional[int] = None) -> str:
        """``checkpoint_<step>`` zero-padded to total_steps' width, so the
        checkpoint dir's lexicographic order equals chronological order (the
        resume scan additionally parses legacy unpadded names numerically)."""
        it = self.iter_count if it is None else it
        return f"checkpoint_{it:0{len(str(self.config.train.total_steps))}d}"

    def _state_dict(self) -> Dict[str, Any]:
        """Full JSON-serializable trainer state for ``state.json``: counters,
        both RNG streams (jax sampling key + host numpy generator), and
        algorithm extras (PPO's prompt-stream position) — everything needed to
        continue the exact sample sequence after a restart."""
        from trlx_tpu.resilience.resume import pack_np_rng, pack_rng_key

        return {
            "iter_count": self.iter_count,
            "best_reward": self.best_reward,
            "nth_evaluation": self.nth_evaluation,
            "rng_key": pack_rng_key(self.rng),
            "np_rng_state": pack_np_rng(self.np_rng),
            **self._extra_state(),
        }

    def _extra_state(self) -> Dict[str, Any]:
        """Algorithm-specific additions to state.json (override in subclasses)."""
        return {}

    def _restore_extra_state(self, state: Dict[str, Any]):
        """Inverse of :meth:`_extra_state` (state.json dict, already loaded)."""
        pass

    def _save_checkpoint(self, directory: str, block: bool = False):
        """Route one checkpoint through the resilience async writer when
        available (host snapshot now, serialize + atomic commit on the writer
        thread; only waits if a *prior* write is still in flight) or the
        synchronous :meth:`save` otherwise. ``block=True`` is the emergency-
        checkpoint path: the commit must land inside the grace window."""
        writer = self.resilience.writer
        if writer is None:
            self.save(directory)
            return
        # host snapshot before returning to the loop: the next train step
        # donates the device buffers, so the writer must never touch them
        trees = {"params": jax.device_get(self.params)}
        if self.config.train.save_optimizer:
            trees["opt_state"] = jax.device_get(self.opt_state)
        writer.save(os.path.abspath(directory), trees, self._state_dict(), block=block)

    def _preempt_exit(self, results):
        """Preemption path: blocking emergency checkpoint inside the grace
        window, then a clean return (``learn()``'s finally drains the rollout
        engine, flushes the writer, and closes the trackers)."""
        handler = self.resilience.preemption
        grace = handler.grace_remaining_s
        logger.warning(
            f"Preempted ({handler.reason}); writing emergency checkpoint at "
            f"step {self.iter_count} ({grace:.0f}s of grace remaining)"
        )
        path = os.path.join(self.config.train.checkpoint_dir, self._checkpoint_name())
        with self.obs.span("checkpoint"):
            self._save_checkpoint(path, block=True)
        remaining = handler.grace_remaining_s
        if remaining is not None and remaining < 0:
            logger.warning(
                f"Emergency checkpoint exceeded the grace window by {-remaining:.0f}s "
                "— raise resilience.grace_period_s or shrink checkpoint_interval"
            )
        self._report_sweep_result(results)
        return results

    def _handle_health_rollback(self):
        """Escalation-ladder step 2/3: the health guard saw ``rollback_after``
        consecutive anomalies. Restore the newest committed checkpoint if the
        rollback budget allows, else halt (raises :class:`TrainingHealthError`
        with a diagnostics bundle path — fail closed, never spin forever)."""
        if not self.health.rollback_budget_left():
            self.health.halt(
                self.iter_count,
                f"rollback budget exhausted ({self.health.config.max_rollbacks}) "
                f"with anomalies still occurring",
            )
        restored = self._health_rollback()
        self.health.on_rollback(self.iter_count, restored)

    def _health_rollback(self) -> bool:
        """Restore the newest committed checkpoint (exact-resume semantics:
        iter_count, RNG streams, prompt-stream position). Returns False when
        no committed checkpoint exists yet — the guard still burns a unit of
        rollback budget so a run that anomalizes before its first checkpoint
        cannot loop forever."""
        target = None
        writer = self.resilience.writer
        if writer is not None:
            # an in-flight async commit may be the freshest good state; wait
            # for it (this also re-raises any writer error now, not later)
            writer.wait()
            target = writer.last_committed
        if target is None:
            target = find_latest_committed(self.config.train.checkpoint_dir)
        if target is None:
            logger.warning(
                f"health rollback requested but no committed checkpoint exists "
                f"in {self.config.train.checkpoint_dir} — continuing with "
                f"current (possibly damaged) state"
            )
            return False
        self.load(target)
        self._post_rollback_restore()
        return True

    def _post_rollback_restore(self):
        """Re-anchor run state that :meth:`load` cannot rebuild by itself
        after a *mid-run* restore (vs. startup resume). Subclasses override:
        PPO rebuilds its prompt stream and republishes the restored params to
        the async producer."""
        pass

    def save(self, directory: str):
        """Sharded checkpoint (params, opt_state, state.json) via orbax (parity:
        accelerator.save_state, accelerate_base_trainer.py:309-317). state.json
        is written atomically (tmp file + rename) and the ``_COMMITTED``
        sentinel lands last, marking the directory complete — :meth:`load`
        warns when it is missing and auto-resume skips such torn dirs."""
        import orbax.checkpoint as ocp

        from trlx_tpu.resilience.checkpoint import STATE_FILE, mark_committed, write_json_atomic

        path = os.path.abspath(directory)
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(os.path.join(path, "params"), self.params, force=True)
        if self.config.train.save_optimizer:
            ckptr.save(os.path.join(path, "opt_state"), self.opt_state, force=True)
        ckptr.wait_until_finished()
        if jax.process_index() == 0:
            write_json_atomic(os.path.join(path, STATE_FILE), self._state_dict())
            mark_committed(path)
        logger.info(f"Saved checkpoint to {path}")

    def load(self, directory: str):
        """Restore a checkpoint saved by :meth:`save` (parity:
        accelerate_base_trainer.py:318-333)."""
        import orbax.checkpoint as ocp

        from trlx_tpu.resilience.checkpoint import is_committed

        path = os.path.abspath(directory)
        if not is_committed(path):
            logger.warning(
                f"Checkpoint {path} has no _COMMITTED sentinel — it may be torn "
                "(interrupted write) or predate atomic saves; restoring anyway "
                "since it was requested explicitly"
            )
        ckptr = ocp.StandardCheckpointer()

        def restore_like(sub, template):
            """Restore + re-place every leaf on its template sharding: orbax can
            hand back single-device arrays for scalar leaves (observed: a resumed
            adam `count` landed on device 0 while params spanned the mesh, and
            the next train_step died with 'incompatible devices')."""
            restored = ckptr.restore(sub, template)
            return jax.tree.map(
                lambda r, t: (
                    jax.device_put(r, t.sharding)
                    if isinstance(t, jax.Array) and r.sharding != t.sharding
                    else r
                ),
                restored, template,
            )

        self.params = restore_like(os.path.join(path, "params"), self.params)
        self._rollout_params = None
        opt_path = os.path.join(path, "opt_state")
        if os.path.exists(opt_path) and self.config.train.save_optimizer:
            self.opt_state = restore_like(opt_path, self.opt_state)
        state_path = os.path.join(path, "state.json")
        if os.path.exists(state_path):
            from trlx_tpu.resilience.resume import restore_np_rng, unpack_rng_key

            with open(state_path) as f:
                state = json.load(f)
            self.iter_count = state.get("iter_count", 0)
            self.best_reward = state.get("best_reward", -float("inf"))
            self.nth_evaluation = state.get("nth_evaluation", self.nth_evaluation)
            if state.get("rng_key") is not None:
                self.rng = unpack_rng_key(state["rng_key"], self.rng)
            if state.get("np_rng_state") is not None:
                restore_np_rng(self.np_rng, state["np_rng_state"])
            self._restore_extra_state(state)
        logger.info(f"Restored checkpoint from {path} (iter {self.iter_count})")

    def save_pretrained(self, directory: str):
        """Export the trunk in HF format + heads as msgpack (parity:
        accelerate_base_trainer.py:284-307; heads-only extras mirror the peft
        state-dict surgery in modeling_base.py:347-353)."""
        from flax.serialization import to_bytes

        from trlx_tpu.models.hf_loading import save_pretrained_hf

        params = jax.device_get(self.params)
        trunk_key = "transformer" if "transformer" in params else ("t5" if "t5" in params else None)
        trunk = params[trunk_key] if trunk_key else params
        if isinstance(trunk, dict) and "layers_scan" in trunk:
            # HF layout is per-layer: unstack the pipeline layout before export
            from trlx_tpu.parallel.pipeline import unstack_layer_params

            trunk = unstack_layer_params(trunk, self.model_config.num_layers)
        if getattr(self.model_config, "lora_r", 0):
            from trlx_tpu.models.transformer import merge_lora_params

            trunk = merge_lora_params(trunk, self.model_config)
        os.makedirs(directory, exist_ok=True)
        if jax.process_index() == 0:
            try:
                save_pretrained_hf(directory, self.model_type, trunk, self.model_config)
            except Exception as e:
                logger.warning(f"HF export unavailable ({e}); saving native params only")
            heads = {k: v for k, v in params.items() if k != trunk_key}
            if heads:
                with open(os.path.join(directory, "heads.msgpack"), "wb") as f:
                    f.write(to_bytes(heads))
            if self.config.model.peft_config:
                from trlx_tpu.models.hf_loading import save_adapters

                save_adapters(directory, params)


def _opt_step_count(opt_state) -> jnp.ndarray:
    """Best-effort extraction of the optax step count for LR logging."""
    leaves = jax.tree.leaves(opt_state)
    for leaf in leaves:
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.integer) and leaf.ndim == 0:
            return leaf
    return jnp.array(0)
