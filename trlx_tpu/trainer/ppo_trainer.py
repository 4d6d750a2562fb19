"""PPO trainer (parity: `/root/reference/trlx/trainer/accelerate_ppo_trainer.py:35-553`):
rollout store management, hydra-vs-full reference model, KL controllers, the
``make_experience`` pipeline (generate → reward → logprob/value/ref passes → KL
penalty → rollout store), and the PPO loss driver.

TPU-first shape: rollout generation and the scoring forwards are jitted fixed-shape
SPMD programs; the reference's rank-0 ``broadcast``/``scatter`` of reward scores
(:325-338) disappears because reward_fn runs on the single controller and scores are
placed onto the mesh with the batch.
"""

import os
import time
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from trlx_tpu.analysis.rt import contracts as rt_contracts
from trlx_tpu.analysis.rt import seeds as rt_seeds
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.ppo_types import PPORLBatch, PPORLElement
from trlx_tpu.methods.ppo import PPOConfig
from trlx_tpu.models.hf_loading import load_pretrained
from trlx_tpu.models.policy import (
    CausalLMWithValueHead,
    branch_param_subtree,
    head_of,
)
from trlx_tpu.models.transformer import TransformerLM, loop_counters, moe_counters
from trlx_tpu.obs import compile_log, op_scopes, span
from trlx_tpu.obs.flight import flight
from trlx_tpu.ops.generation import LENGTH_BUCKETS, left_pad_batch, pad_to_bucket
from trlx_tpu.parallel import mesh as mesh_lib
from trlx_tpu.parallel.sharding import make_param_shardings
from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage
from trlx_tpu.resilience.quarantine import chaos_corrupt_elements
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.mesh_trainer import MeshRLTrainer
from trlx_tpu.utils import infinite_loader, logging
from trlx_tpu.utils.metrics import gauges
from trlx_tpu.utils.modeling import (
    RunningMoments,
    flatten_dict,
    logprobs_of_labels,
    response_logprobs,
)

logger = logging.get_logger(__name__)

#: Max distinct response-length buckets the streaming path may compile per
#: (B, P) score-fn family — the recompile bound docs/serving.md documents.
#: Sourced from the declared ``stream_score_ladder`` shape contract
#: (trlx_tpu/analysis/rt/contracts.py) so the runtime guard, the SH001
#: sanction list, and the CompileWatcher probe all share one number.
_STREAM_MAX_R_BUCKETS = rt_contracts.get("stream_score_ladder").max_shapes


@jax.jit
def copy_params(tree):
    """A donate-free device copy of a parameter tree (its own program name,
    ``jit_copy_params``, in the profiler's module events)."""
    return jax.tree.map(lambda x: x.copy(), tree)


def overlap_r_buckets(max_new: int) -> List[int]:
    """The quantized response-length ladder for streaming microbuckets:
    ≤ :data:`_STREAM_MAX_R_BUCKETS` pow2 shapes covering up to
    ``max_new + 1`` (decode may re-append eos)."""
    top = max(1, max_new + 1)
    # ceil(top / d) for d in 8,4,2,1 — dedup after pow2 padding keeps the
    # ladder at <= 4 entries with the full shape always present
    return sorted({pad_to_bucket(max(1, -(-top // d)), LENGTH_BUCKETS) for d in (8, 4, 2, 1)})


def quantize_stream_response(r: int, ladder: List[int]) -> int:
    """Snap a raw completion length onto the streaming ladder — the ONLY
    sanctioned path from a data-dependent ``len()`` to the jitted score fn's
    R dimension (declared in the ``stream_score_ladder`` shape contract).

    ``TRLX_RT_SEED_REGRESSION=shape_churn`` makes this return the raw length
    — the unbucketed-shape defect the compile gate must catch (ci.sh proves
    the gate fails closed; see trlx_tpu/analysis/rt/seeds.py)."""
    if rt_seeds.shape_churn():
        return r
    for cand in ladder:
        if r <= cand:
            return cand
    return pad_to_bucket(r, LENGTH_BUCKETS)  # defensive; the ladder covers max_new+1


def check_stream_bucket_family(families, B: int, P: int, R: int, limit: int = _STREAM_MAX_R_BUCKETS):
    """Record R under the (B, P) family and assert the family stays bounded.

    Varied completion lengths must quantize onto a fixed small ladder of
    padded shapes (``_overlap_r_buckets``); a shape escaping the ladder means
    unbounded jit recompiles, which this turns into a loud failure instead of
    a silent compile storm."""
    fam = families.setdefault((B, P), set())
    fam.add(R)
    if len(fam) > limit:
        raise AssertionError(
            f"streaming score-fn bucket family (B={B}, P={P}) grew to "
            f"{sorted(fam)}; the response-length quantizer must keep "
            f"<= {limit} shapes per family"
        )


@register_trainer
class PPOTrainer(MeshRLTrainer):
    #: the compiled train step's name (module events read ``jit_<name>``) and
    #: the entry its compiles are attributed to
    train_step_name = "ppo_train_step"

    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        if not isinstance(config.method, PPOConfig):
            raise ValueError("PPOTrainer requires method=PPOConfig")
        self.method: PPOConfig = config.method

        self.store = PPORolloutStorage(self.tokenizer.pad_token_id)
        self.kl_ctl = self.method.kl_controller()
        self.running_moments = RunningMoments()
        self.mean_kl = 0.0
        self.rollout_stats: Dict[str, float] = {}
        self._score_fns = {}
        # (B, P) -> set of R shapes compiled through the streaming path; the
        # quantizer in _overlap_r_buckets must keep each family bounded
        self._score_fn_families = {}
        self._train_steps = {}

        # async rollout engine state (trlx_tpu/rollout; resolved in
        # prepare_learning — None means the synchronous path). Under
        # train.self_healing this is a ProducerSupervisor wrapping engine
        # generations; it exposes the same surface
        self._engine = None
        self._async_cfg = None
        self._policy_version = 0

        # generation-island runtime (trlx_tpu/serving/island; resolved in
        # _start_async_engine when train.islands is enabled). None keeps the
        # trainer byte-identical to the monolithic publish path.
        self._island = None

        # prompt-stream position (trlx_tpu/resilience): draws from the
        # infinite prompt iterator, checkpointed and replayed on resume so a
        # restarted run continues the exact prompt sequence
        self._prompt_batches_drawn = 0
        self._resume_prompt_batches = 0
        self._prompt_pipeline = None

        # continuous-batching serving engine (trlx_tpu/serving; resolved in
        # prepare_learning). None = the one-shot generate path. When set,
        # _generate_chunks routes generation through the GenerationClient;
        # decode/reward/scoring/quarantine downstream are identical.
        self._serving_client = None
        self._serving_engine = None
        self._serving_autoscaler = None
        self._serving_max_new = 0
        self._serving_min_new = 0
        self._serving_param_ref = None

        # experience quarantine (trlx_tpu/resilience/quarantine): screens
        # every assembled PPORLElement when self-healing is on; None = the
        # historical trust-everything behavior
        self._quarantine = None
        sh_config = config.train.self_healing
        if sh_config.enabled:
            from trlx_tpu.resilience.quarantine import ExperienceQuarantine

            self._quarantine = ExperienceQuarantine(
                sh_config.quarantine_dir
                or os.path.join(config.train.checkpoint_dir, "quarantine")
            )

        if config.train.rollout_logging_dir is not None:
            self.log_rollouts = True
            self.setup_rollout_logging(config)
        else:
            self.log_rollouts = False

    # ------------------------------------------------------------------ model

    def setup_model(self):
        """Build policy+value model; reference model is either the hydra frozen
        top-branch (num_layers_unfrozen > 0) or a full frozen param copy
        (parity: get_arch + ref_model setup, accelerate_ppo_trainer.py:65-108).
        ``model_arch_type == "seq2seq"`` selects the T5 path (parity:
        modeling_ppo.py:1242-1350)."""
        self.is_seq2seq = self.config.model.model_arch_type == "seq2seq"
        # validates mesh.pipe combinations (incl. rejecting seq2seq) regardless
        # of which arch branch runs below
        pp_overrides = self.pipeline_overrides()
        overrides = dict(self.config.model.model_overrides or {})
        overrides.setdefault("param_dtype", self.param_dtype)
        overrides.setdefault("compute_dtype", self.compute_dtype)
        if self.is_seq2seq:
            self._setup_seq2seq_model(overrides)
            return
        # per-scale remat override for the overlapped learner (docs/parallelism.md
        # "Learner overlap & FSDP"): learner_overlap.remat, when set, beats
        # mesh.remat but still yields to explicit model_overrides
        lov = getattr(self.config.train, "learner_overlap", None)
        if lov is not None and lov.enabled and lov.remat is not None:
            overrides.setdefault("remat", lov.remat)
        overrides.setdefault("remat", self.config.mesh.remat)
        overrides.setdefault("sequence_sharding", self.config.mesh.sequence_shard)
        from trlx_tpu.models.hf_loading import merge_loaded_params, peft_overrides

        overrides.update(peft_overrides(self.config.model.peft_config))
        overrides.update(pp_overrides)
        self.model_config, trunk_params, self.model_type = load_pretrained(
            self.config.model.model_path, overrides, mesh=self.restore_mesh(overrides)
        )
        trunk_params = self.maybe_stack_loaded(trunk_params, self.model_config.num_layers)
        self.module = CausalLMWithValueHead(
            self.model_config,
            num_value_layers=getattr(self.config.method, "num_value_layers_unfrozen", 0),
        )
        self.trunk_module = TransformerLM(self.model_config)

        params = self.module.init(
            jax.random.PRNGKey(self.config.train.seed),
            jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1, 2), jnp.int32),
        )["params"]
        if trunk_params is not None:
            params = dict(params)
            params["transformer"] = merge_loaded_params(params["transformer"], trunk_params)
        n_value_layers = getattr(self.config.method, "num_value_layers_unfrozen", 0)
        if n_value_layers > 0:
            from trlx_tpu.models.policy import init_value_branch_from_trunk

            params = init_value_branch_from_trunk(params, self.model_config, n_value_layers)

        shardings = make_param_shardings(params, self.mesh)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x, self.param_dtype), s), params, shardings
        )

        # The reference copies must NOT alias self.params: the train step donates
        # its input buffers (real buffer reuse on TPU), so aliased frozen params
        # would be deleted after the first optimizer step.
        def device_copy(tree):
            with self.mesh:
                return copy_params(tree)

        n_unfrozen = self.config.model.num_layers_unfrozen
        if n_unfrozen > self.model_config.num_layers:
            raise ValueError(
                f"num_layers_unfrozen={n_unfrozen} exceeds num_layers={self.model_config.num_layers}"
            )
        self.peft_base_ref = bool(self.config.model.peft_config)
        if self.config.model.offload_ref and (self.peft_base_ref or n_unfrozen > 0):
            # only the FULL-copy reference lives in HBM at size worth offloading;
            # hydra/peft refs are already the cheap option — say so instead of
            # silently ignoring the flag
            logger.warning(
                "offload_ref ignored: the reference is a hydra branch / disabled-"
                "adapter view (num_layers_unfrozen > 0 or peft), not a full copy"
            )
        if self.peft_base_ref:
            # peft mode: the trunk is frozen and only adapters train, so the KL
            # reference is the SAME params applied through a module with the
            # adapters structurally disabled (flax ignores the extra adapter
            # entries) — the reference's disable_adapter() forward_hydra path
            # (modeling_ppo.py:410-453) with zero extra memory.
            self.base_trunk_module = TransformerLM(
                self.model_config.replace(lora_r=0, peft_type="none", num_virtual_tokens=0)
            )
            self.branch_start = None
            self.frozen_branch_params = None
            self.ref_params = None
        elif n_unfrozen > 0:
            self.branch_start = self.model_config.num_layers - n_unfrozen
            branch = branch_param_subtree(self.params["transformer"], self.branch_start, self.model_config)
            self.frozen_branch_params = device_copy(branch)
            self.ref_params = None
        else:
            self.branch_start = None
            self.frozen_branch_params = None
            if self.config.model.offload_ref:
                self._setup_ref_offload(self.params["transformer"], shardings["transformer"])
                self.ref_params = None
            else:
                self.ref_params = device_copy(self.params["transformer"])

    def _setup_seq2seq_model(self, overrides):
        from trlx_tpu.models.hf_loading import load_pretrained_seq2seq, t5_peft_overrides
        from trlx_tpu.models.policy import Seq2SeqLMWithValueHead

        peft = t5_peft_overrides(self.config.model.peft_config)
        if peft:
            overrides = {**(overrides or {}), **peft}

        self.model_config, t5_params = load_pretrained_seq2seq(
            self.config.model.model_path, overrides, mesh=self.mesh
        )
        self.model_type = "t5"
        self.peft_base_ref = bool(peft)
        self.decoder_start_token_id = self.model_config.decoder_start_token_id
        self.module = Seq2SeqLMWithValueHead(self.model_config)
        params = self.module.init(
            jax.random.PRNGKey(self.config.train.seed),
            jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32),
            jnp.zeros((1, 2), jnp.int32),
        )["params"]
        if t5_params is not None:
            params = dict(params)
            params["t5"] = t5_params
        shardings = make_param_shardings(params, self.mesh)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x, self.param_dtype), s), params, shardings
        )

        # seq2seq reference model: with num_layers_unfrozen > 0, a frozen copy of
        # just the top-N decoder blocks (+ final LN + head) — the reference's
        # T5Branch shape (modeling_ppo.py:1483-1593); otherwise a full frozen copy
        def device_copy(tree):
            with self.mesh:
                return copy_params(tree)

        n_unfrozen = self.config.model.num_layers_unfrozen
        if n_unfrozen > self.model_config.num_decoder_layers:
            raise ValueError(
                f"num_layers_unfrozen={n_unfrozen} exceeds "
                f"num_decoder_layers={self.model_config.num_decoder_layers}"
            )
        if self.config.model.offload_ref and (
            self.peft_base_ref or 0 < n_unfrozen < self.model_config.num_decoder_layers
        ):
            logger.warning(
                "offload_ref ignored: the seq2seq reference is a decoder branch /"
                " disabled-adapter view, not a full copy"
            )
        if self.peft_base_ref:
            # adapters-only training: the KL reference is the SAME t5 params
            # applied through a module with LoRA structurally disabled (mirrors
            # the causal peft path / reference disable_adapter() forward_hydra)
            from trlx_tpu.models.t5 import T5LM

            self.base_t5_module = T5LM(self.model_config.replace(lora_r=0))
            self.branch_start = None
            self.frozen_branch_params = None
            self.ref_params = None
        elif 0 < n_unfrozen < self.model_config.num_decoder_layers:
            from trlx_tpu.models.policy import t5_branch_param_subtree

            self.branch_start = self.model_config.num_decoder_layers - n_unfrozen
            branch = t5_branch_param_subtree(self.params["t5"], self.branch_start, self.model_config)
            self.frozen_branch_params = device_copy(branch)
            self.ref_params = None
        else:
            # n_unfrozen in (-1, 0, num_decoder_layers): full frozen copy. The
            # all-layers-unfrozen case cannot use the branch — the branch reuses
            # the live model's decoder-block-0 relative bias, which would then
            # be training.
            self.branch_start = None
            self.frozen_branch_params = None
            if self.config.model.offload_ref:
                self._setup_ref_offload(self.params["t5"], shardings["t5"])
                self.ref_params = None
            else:
                self.ref_params = device_copy(self.params["t5"])

    def _setup_ref_offload(self, tree, shardings):
        """Keep the full frozen KL-reference in HOST memory (ModelConfig.offload_ref):
        pinned-host placement where the backend supports memory kinds (TPU), host
        numpy otherwise (single-host only — multi-host uses the pinned path). The
        ref streams onto the device per rollout-scoring phase and is dropped for
        the update phase, where HBM actually peaks — the reference's NeMo
        CPU-pinned policy/ref swap (modeling_nemo_ppo.py:228-312)."""
        self._ref_shardings = shardings
        self._ref_dev = None
        try:
            host_sh = jax.tree.map(lambda s: s.with_memory_kind("pinned_host"), shardings)
            self._ref_host = jax.device_put(tree, host_sh)
            jax.block_until_ready(self._ref_host)
            self._ref_host_kind = "pinned_host"
        except Exception as e:
            # the numpy fallback gathers the whole tree to one host, which is
            # only correct (and only possible — np.asarray of a non-addressable
            # sharded jax.Array raises) in a single-process run; on multi-host
            # a pinned_host failure is a real configuration error, not
            # something to paper over
            if jax.process_count() > 1:
                raise
            logger.info(f"offload_ref: pinned_host placement unavailable ({type(e).__name__}: {e}); "
                        "falling back to host numpy copies")
            self._ref_host = jax.tree.map(lambda x: np.asarray(x), tree)
            self._ref_host_kind = "numpy"
        logger.info(f"offload_ref: frozen reference held in {self._ref_host_kind} host memory")

    def _ref_scoring_params(self):
        """Device view of the ref params for the scoring forward; materialized
        once per rollout phase (released by :meth:`_release_ref`)."""
        if getattr(self, "_ref_host", None) is None:
            return self.ref_params
        if self._ref_dev is None:
            with self.mesh:
                self._ref_dev = jax.device_put(self._ref_host, self._ref_shardings)
        return self._ref_dev

    def _pin_ref(self):
        """Pin the device ref view for a whole streaming window: materialize it
        once up front and make :meth:`_release_ref` a no-op until
        :meth:`_unpin_ref`. Without the pin, any release inside the window
        would force per-bucket host→device re-uploads of the full reference
        tree — exactly the transfer the streaming path exists to hide."""
        self._ref_pinned = True
        if getattr(self, "_ref_host", None) is not None:
            self._ref_scoring_params()

    def _unpin_ref(self):
        """End of the streaming window (stream drain): allow release again."""
        self._ref_pinned = False

    def _release_ref(self):
        """Free the device ref copy after make_experience (no-op unless
        offloaded; deferred while a streaming window holds the pin)."""
        if getattr(self, "_ref_pinned", False):
            return
        self._ref_dev = None

    def trainable_path_predicate(self, path: str) -> bool:
        if getattr(self, "is_seq2seq", False):
            if self.config.model.peft_config:
                # adapters + heads only — the generic predicate already treats
                # the t5 trunk like the causal transformer trunk
                return super().trainable_path_predicate(path)
            n_unfrozen = self.config.model.num_layers_unfrozen
            if n_unfrozen < 0 or "t5" not in path:
                return True
            # freeze encoder + bottom decoder blocks; top-N decoder blocks + heads train
            if "decoder_blocks_" in path:
                layer = int(path.split("decoder_blocks_")[1].split("/")[0])
                return layer >= self.model_config.num_decoder_layers - n_unfrozen
            return "decoder_ln" in path
        return super().trainable_path_predicate(path)

    # ------------------------------------------------------------- generation

    def seq2seq_gen_fns(self):
        module = self.module

        return {
            "encode": lambda params, ids, mask: module.apply(
                {"params": params}, ids, mask, method=module.encode
            ),
            "cross_kv": lambda params, enc: module.apply(
                {"params": params}, enc, method=module.precompute_cross_kv
            ),
            "decode": lambda params, tok, enc, enc_mask, dec_mask, pos, cache, ckv: module.apply(
                {"params": params}, tok, enc, enc_mask, dec_mask, pos, cache, ckv,
                method=module.decode_step,
            ),
            "init_cache": lambda params, b, n: self._t5_module().init_cache(b, n),
        }

    def _t5_module(self):
        from trlx_tpu.models.t5 import T5LM

        return T5LM(self.model_config)

    def gen_step_fn(self):
        trunk = self.trunk_module

        def step(params, ids, mask, positions, cache):
            logits, hidden, _, cache = trunk.apply(
                {"params": params["transformer"]}, ids, mask, positions, cache
            )
            return logits, hidden, cache

        init_cache = lambda b, s: trunk.init_cache(b, s)
        return step, init_cache

    # ------------------------------------------------------------- experience

    def add_prompt_pipeline(self, pipeline):
        """Attach the prompt pipeline for rollouts (parity: :245-249). The loader
        batches ``decode_batch_size`` prompts (generation is bandwidth-bound and
        wants the widest batch that fits); reward/scoring still run per
        ``chunk_size`` sub-chunk."""
        batch = self.method.decode_batch_size or self.method.chunk_size
        # kept so a health-guard rollback can rebuild the stream from scratch
        # and replay draws to the restored position (an iterator can't rewind)
        self._prompt_pipeline = pipeline
        loader = pipeline.create_loader(batch, shuffle=True, seed=self.config.train.seed)
        stream = infinite_loader(loader)
        lookahead = self.config.train.async_rollouts.length_bucket_lookahead
        if lookahead > 1:
            from trlx_tpu.rollout.engine import length_bucketed

            stream = length_bucketed(stream, lookahead)
        self.prompt_iterator = stream

    def setup_rollout_logging(self, config):
        import os
        import uuid

        self.run_id = f"run-{uuid.uuid4()}"
        self.rollout_logging_dir = os.path.join(config.train.rollout_logging_dir, self.run_id)
        # the base dir may not exist yet and a crashed run may have left the
        # run dir behind: both are fine, never assert/mkdir-race here
        os.makedirs(self.rollout_logging_dir, exist_ok=True)
        with open(os.path.join(self.rollout_logging_dir, "config.json"), "w") as f:
            import json

            f.write(json.dumps(config.to_dict(), indent=2))

    def _get_score_fn(self, B: int, P: int, R: int, bounded_family: bool = False):
        """Jitted scoring pass: policy logprobs+values and reference logprobs over
        the response window (parity: :414-446). One compile per (B, P, R).

        ``bounded_family`` marks a streaming-microbucket caller: R is then
        asserted to stay within the ≤4-shape quantized ladder per (B, P)
        family, so varied completion lengths cannot trigger unbounded
        recompiles."""
        if bounded_family:
            check_stream_bucket_family(self._score_fn_families, B, P, R)
        key = (B, P, R)
        if key in self._score_fns:
            return self._score_fns[key]
        # rows the vocabulary head is taken over / rows the forward runs; a
        # seq2seq decoder's positions are the response already
        gauges.set("score/head_rows_share", 1.0 if self.is_seq2seq else R / (P + R))

        if self.is_seq2seq:
            module, t5 = self.module, self._t5_module()
            start_tok = self.decoder_start_token_id
            branch_start = self.branch_start
            peft_base_ref = self.peft_base_ref
            base_t5 = getattr(self, "base_t5_module", None)

            def ppo_score(params, ref_params, frozen_branch, q_ids, q_mask, r_ids, r_mask):
                Bs = q_ids.shape[0]
                dec_in = jnp.concatenate(
                    [jnp.full((Bs, 1), start_tok, jnp.int32), r_ids[:, :-1]], axis=1
                )
                dec_mask = jnp.concatenate(
                    [jnp.ones((Bs, 1), jnp.int32), r_mask[:, :-1]], axis=1
                )
                if peft_base_ref:
                    # same (frozen) t5 params, adapters structurally disabled
                    logits, values, _ = module.apply(
                        {"params": params}, q_ids, q_mask, dec_in, dec_mask
                    )
                    ref_logits, _, _ = base_t5.apply(
                        {"params": params["t5"]}, q_ids, q_mask, dec_in, dec_mask
                    )
                elif branch_start is not None:
                    logits, values, enc, branch_hidden, pos_bias = module.apply(
                        {"params": params}, q_ids, q_mask, dec_in, dec_mask, branch_start,
                        method=module.forward_with_branch,
                    )
                    ref_logits = t5.apply(
                        {"params": frozen_branch}, branch_hidden, enc, q_mask, dec_mask,
                        pos_bias, branch_start, method=t5.forward_branch,
                    )
                else:
                    logits, values, _ = module.apply({"params": params}, q_ids, q_mask, dec_in, dec_mask)
                    ref_logits, _, _ = t5.apply({"params": ref_params}, q_ids, q_mask, dec_in, dec_mask)
                with jax.named_scope("logprobs"):
                    logprobs = logprobs_of_labels(logits, r_ids)
                    ref_logprobs = logprobs_of_labels(ref_logits, r_ids)
                return logprobs, values.astype(jnp.float32), ref_logprobs

            self._score_fns[key] = jax.jit(
                ppo_score, out_shardings=mesh_lib.replicated(self.mesh)
            )
            return self._score_fns[key]

        module, trunk = self.module, self.trunk_module
        branch_start = self.branch_start
        peft_base_ref = self.peft_base_ref
        base_trunk = getattr(self, "base_trunk_module", None)

        start = P - 1  # the row whose successor is the first response token

        def ppo_score(params, ref_params, frozen_branch, seq, mask):
            with jax.named_scope("policy_forward"):
                hidden, values, branch_hidden, _ = module.apply(
                    {"params": params}, seq, mask, branch_layer=branch_start, with_head=False
                )
            with jax.named_scope("logprobs"):
                logprobs = response_logprobs(hidden, head_of(module, params), seq, start, R)
            with jax.named_scope("reference_forward"):
                if peft_base_ref:
                    # same (frozen) trunk params, adapters structurally disabled
                    ref_head = head_of(base_trunk, params["transformer"])
                    _, ref_hidden, _, _ = base_trunk.apply(
                        {"params": params["transformer"]}, seq, mask, with_head=False
                    )
                elif branch_start is not None:
                    ref_head = head_of(trunk, frozen_branch)
                    ref_hidden = module.apply(
                        {"params": {"transformer": frozen_branch}},
                        branch_hidden, mask, None, branch_start, with_head=False,
                        method=module.forward_branch,
                    )
                else:
                    ref_head = head_of(trunk, ref_params)
                    _, ref_hidden, _, _ = trunk.apply({"params": ref_params}, seq, mask, with_head=False)
            with jax.named_scope("logprobs"):
                ref_logprobs = response_logprobs(ref_hidden, ref_head, seq, start, R)
            return logprobs, values[:, start : start + R].astype(jnp.float32), ref_logprobs

        self._score_fns[key] = jax.jit(
            ppo_score, out_shardings=mesh_lib.replicated(self.mesh)
        )
        return self._score_fns[key]

    # ------------------------------------------------------------- serving

    def _resolve_serving(self):
        """Build the continuous-batching GenerationClient when
        ``train.serving.enabled`` and the run shape supports it; otherwise
        log why and keep the one-shot generate path (``_serving_client``
        stays None). Called once from prepare_learning."""
        cfg = self.config.train.serving
        if not cfg.enabled or self._serving_client is not None:
            return

        def fallback(reason):
            logger.warning(f"train.serving disabled for this run: {reason}")

        if self.is_seq2seq:
            return fallback("seq2seq generation is not paged")
        if self.model_config.stacked:
            return fallback("stacked/pipelined layouts keep the contiguous cache")
        if self.model_config.peft_type in ("prompt", "prefix"):
            return fallback("prompt/prefix peft puts virtual rows in the cache")
        if self.mesh is not None and self.mesh.size > 1:
            return fallback("multi-device mesh (the paged step is single-device)")
        if self.gen_logits_processor() is not None:
            return fallback("decode-time logits processor in use")

        from trlx_tpu.models.transformer import TransformerLM
        from trlx_tpu.serving import (
            GenerationClient,
            ServingEngine,
            ServingResiliencePolicy,
            ServingSupervisor,
        )

        gen_kwargs = dict(self.generate_experience_kwargs or self.generate_kwargs)
        gen_kwargs.setdefault("eos_token_id", self.tokenizer.eos_token_id)
        gen_kwargs.setdefault("pad_token_id", self.tokenizer.pad_token_id)
        self._serving_max_new = int(gen_kwargs.pop("max_new_tokens", 16))
        self._serving_min_new = int(gen_kwargs.pop("min_new_tokens", 0))
        eos = gen_kwargs.pop("eos_token_id")
        pad = gen_kwargs.pop("pad_token_id")
        sample_keys = ("temperature", "top_k", "top_p", "do_sample", "top_k_impl")
        unknown = set(gen_kwargs) - set(sample_keys)
        if unknown:
            return fallback(f"unsupported gen_kwargs for the serving engine: {sorted(unknown)}")

        trunk_config = self.model_config.replace(
            kv_cache_quant=(
                self.model_config.kv_cache_quant
                if cfg.kv_cache_quant is None else bool(cfg.kv_cache_quant)
            ),
            paged_attention_impl=cfg.attention_impl,
        )
        num_slots = cfg.num_slots or (
            self.method.decode_batch_size or self.method.chunk_size
        )
        # prompts are admitted unpadded, so capacity only needs the real
        # prompt lengths (<= seq_length) plus the decode budget
        max_seq_len = self.config.train.seq_length + self._serving_max_new
        svr = self.config.train.serving_resilience
        policy = None
        if svr.enabled:
            policy = ServingResiliencePolicy(
                request_ttl_s=svr.request_ttl_s,
                max_pending_age_s=svr.max_pending_age_s,
                max_pending=svr.max_pending,
                high_watermark=svr.high_watermark,
                low_watermark=svr.low_watermark,
                preemption=svr.preemption,
            )
        svt = self.config.train.serving_tenancy
        # one registry across engine generations: tenant contracts (and the
        # aging policy) survive supervised restarts by construction
        tenants = svt.build_registry() if svt.enabled else None

        def build_engine(replica_seat=0):
            # each fleet seat samples from its own rng stream (seed offset by
            # the seat); seat 0 keeps the single-engine seed so a one-replica
            # fleet is byte-identical to the bare engine
            return ServingEngine(
                TransformerLM(trunk_config),
                None,  # snapshot installed per rollout phase in _serving_generate
                num_slots=num_slots,
                max_seq_len=max_seq_len,
                block_size=cfg.block_size,
                num_blocks=cfg.num_blocks,
                eos_token_id=eos,
                pad_token_id=pad,
                gen_kwargs=gen_kwargs,
                min_new_tokens=self._serving_min_new,
                prefix_caching=cfg.prefix_caching,
                seed=self.config.train.seed + 17 + replica_seat,
                policy=policy,
                spec_k=cfg.spec_k,
                spec_ngram=cfg.spec_ngram,
                prefill_chunk=cfg.prefill_chunk,
                tenants=tenants,
                state_sharding=mesh_lib.replicated(self.mesh),
            )

        svf = self.config.train.serving_fleet
        if svf.enabled:
            # fleet mode: N supervised replicas behind the prefix-affinity
            # router (docs/serving.md "Fleet serving"); replicas are always
            # supervisor-wrapped — re-route on replica death rides the
            # supervisor's export/adopt replay seam
            from trlx_tpu.fleet import FleetAutoscaler, fleet_factory

            diag = svr.diagnostics_dir or os.path.join(
                self.config.train.checkpoint_dir, "diagnostics"
            )
            self._serving_engine = fleet_factory(
                build_engine,
                svf,
                max_restarts=svr.max_restarts,
                backoff_base_s=svr.restart_backoff_base_s,
                backoff_max_s=svr.restart_backoff_max_s,
                wedge_timeout_s=svr.wedge_timeout_s,
                diagnostics_dir=diag,
            )
            if svf.autoscale:
                self._serving_autoscaler = FleetAutoscaler(
                    self._serving_engine,
                    min_replicas=svf.min_replicas,
                    max_replicas=svf.max_replicas,
                    scale_up_pending_per_slot=svf.scale_up_pending_per_slot,
                    scale_down_occupancy=svf.scale_down_occupancy,
                    breach_rounds=svf.breach_rounds,
                    cooldown_rounds=svf.cooldown_rounds,
                )
        elif svr.enabled:
            # supervised: crashes/wedges rebuild the engine (same factory
            # args) and replay every accepted request — docs/serving.md
            diag = svr.diagnostics_dir or os.path.join(
                self.config.train.checkpoint_dir, "diagnostics"
            )
            self._serving_engine = ServingSupervisor(
                build_engine,
                max_restarts=svr.max_restarts,
                backoff_base_s=svr.restart_backoff_base_s,
                backoff_max_s=svr.restart_backoff_max_s,
                wedge_timeout_s=svr.wedge_timeout_s,
                diagnostics_dir=diag,
            )
        else:
            self._serving_engine = build_engine()
        self._serving_client = GenerationClient(self._serving_engine)
        logger.info(
            f"serving engine enabled: slots={num_slots}, "
            f"block_size={cfg.block_size}, blocks={self._serving_engine.num_blocks}, "
            f"int8_kv={trunk_config.kv_cache_quant}, impl={cfg.attention_impl}, "
            f"resilience={'on' if svr.enabled else 'off'}, "
            f"tenancy={'on' if svt.enabled else 'off'}, "
            f"fleet={svf.num_replicas if svf.enabled else 'off'}"
        )

    def _serving_generate(self, prompts, params=None):
        """Continuous-batched replacement for ``self.generate`` in the rollout
        producer: same ``(sequences, response_mask, pad_len)`` contract. The
        engine flushes its prefix cache whenever the parameter snapshot
        object changes (each publish / rollout-copy recast is a new tree)."""
        gen_params = params if params is not None else self.generation_params()
        tparams = gen_params["transformer"]
        if self._island is None and tparams is not self._serving_param_ref:
            # islands mode skips this install: the engine self-swaps to the
            # newest committed broadcast at its own round boundaries, and the
            # producer's snapshot stays the behavior-scoring policy (the
            # ≤1-version drift is absorbed by the clipped-IS correction)
            self._serving_engine.set_params(tparams)
            self._serving_param_ref = tparams
        with self.obs.span("generate"):
            out = self._serving_client.generate_batch(prompts, self._serving_max_new)
        if self._serving_autoscaler is not None:
            self._serving_autoscaler.observe()
        return out

    # --------------------------------------------------- stream-overlapped PPO

    def _overlap_r_buckets(self) -> List[int]:
        """The quantized response-length ladder for this run's ``max_new``
        (module-level :func:`overlap_r_buckets` carries the construction)."""
        return overlap_r_buckets(self._serving_max_new)

    def _make_experience_streamed(
        self, num_rollouts, iter_count, ppo_rl_elements, accumulated_kl, all_scores_log
    ):
        """Streaming experience pipeline (``train.serving.stream_overlap``;
        docs/serving.md "Stream-overlapped PPO").

        As each sequence finishes in the engine, its reward_fn call is
        dispatched from a bounded worker pool; completed-and-scored sequences
        are batched — in engine completion order, which is deterministic under
        greedy decode — into fixed-shape microbuckets for the jitted score fn;
        and first-epoch learner microbatches are collated and ``device_put``
        while the tail of the batch is still decoding. The scoring dispatch is
        double-buffered: bucket k's results are harvested only when bucket
        k+1 is about to dispatch (or at drain), so the next bucket's
        host→device transfer overlaps the in-flight device compute.

        Rollout contents (query/response tensors, store order) are identical
        to the serial serving path; score normalization runs per microbucket
        instead of per chunk, so running-moment grouping legitimately differs.
        ``TRLX_OVERLAP_SEED_REGRESSION=serialize`` forces serial in-memory
        consumption (block on every reward before the next decode round) —
        the seeded regression the overlap-fraction CI gate must catch."""
        import copy
        import random as pyrandom
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor

        from trlx_tpu.obs.overlap import OverlapWindow
        from trlx_tpu.pipeline.ppo_pipeline import ppo_collate_fn
        from trlx_tpu.resilience.chaos import chaos
        from trlx_tpu.rollout.reorder import ReorderBuffer

        cfg = self.config.train.serving
        serialize = os.environ.get("TRLX_OVERLAP_SEED_REGRESSION", "") == "serialize"
        mb = int(cfg.overlap_microbucket or self.method.chunk_size)
        pad_id = self.tokenizer.pad_token_id
        r_ladder = self._overlap_r_buckets()
        # the reward worker threads must not share the main thread's HF fast
        # tokenizer (not re-entrant — same reasoning as overlap_reward_scoring)
        if not hasattr(self, "_reward_tokenizer"):
            self._reward_tokenizer = copy.deepcopy(self.tokenizer)

        window = OverlapWindow()
        reorder = ReorderBuffer()
        # one clock for flight events: the engine scheduler's (so the reward /
        # store_wait tail lines up with the engine-side phase decomposition)
        flight_clock = self._serving_client.engine.scheduler.clock
        pending = deque()  # (gidx, future, prompt, out_ids, uid) in completion order
        ready = deque()  # reward resolved, waiting for a full microbucket
        inflight = [None]  # one dispatched-but-unharvested scoring bucket
        dropped = [False]  # quarantine broke the 1:1 index map → stop staging
        cur = {"P": 0}  # current prompt batch's shared prompt bucket
        stage = {"perm": None, "next": 0}

        def stream_reward(kw):
            # chaos site "producer-wedge" in the streamed path: this reward
            # RPC stalls briefly (a stuck scorer the bounded pool rides out —
            # exactly-once accounting must hold regardless)
            if chaos.should_fail("producer-wedge"):
                logger.warning("chaos: streamed reward wedged at site 'producer-wedge'")
                time.sleep(0.2)
            t0 = time.perf_counter()
            with span("reward"):
                out = self.reward_fn(**kw)
            window.note_work(t0, time.perf_counter())
            return out

        def r_bucket(r):
            return quantize_stream_response(r, r_ladder)

        def dispatch(items):
            # harvest bucket k-1 first: its device compute had a full bucket's
            # worth of decode/reward time to finish, so the get is cheap, and
            # the put_batch below then overlaps whatever is still in flight
            harvest()
            t0 = time.perf_counter()
            n_real = len(items)
            raw = [it[3] for it in items]
            dense = np.ndim(raw[0]) > 0
            if dense:
                dense_scores = [np.asarray(s, np.float32) for s in raw]
                scores = np.asarray([s.sum() for s in dense_scores], np.float32)
            else:
                dense_scores = None
                scores = np.asarray(jax.device_get(raw), np.float32).reshape(-1)
            all_scores_log.extend(scores.tolist())
            # normalization runs per microbucket in completion order — the
            # documented stats difference vs the serial per-chunk grouping
            self.running_moments.update(scores)
            if self.method.cliprange_reward:
                scores = np.clip(
                    scores, -self.method.cliprange_reward, self.method.cliprange_reward
                )
            if self.method.scale_reward == "running":
                scores = scores / max(self.running_moments.std, 1e-8)
            elif self.method.scale_reward == "ref":
                scores = scores / max(self.method.ref_std or 1.0, 1e-8)

            padded = list(items) + [items[-1]] * (mb - n_real)
            prompts_b = [it[1] for it in padded]
            outs_b = [it[2] for it in padded]
            R = r_bucket(max(len(o) for o in outs_b))
            q_ids, q_mask = left_pad_batch(prompts_b, pad_id, cur["P"])
            r_ids = np.full((mb, R), pad_id, np.int32)
            r_mask = np.zeros((mb, R), np.int32)
            for j, o in enumerate(outs_b):
                r_ids[j, : len(o)] = o
                r_mask[j, : len(o)] = 1
            score_fn = self._get_score_fn(mb, cur["P"], R, bounded_family=True)
            # unlike the serial span, no device_get here: the forward is left
            # in flight (async dispatch) and harvested at the next bucket
            # boundary — that asynchrony IS the decode/score overlap
            with span("score"):
                seq = np.concatenate([q_ids, r_ids], axis=1)
                smask = np.concatenate([q_mask, r_mask], axis=1)
                dbatch = mesh_lib.put_batch(self.mesh, {"seq": seq, "mask": smask})
                with self.mesh, compile_log.attributed("ppo_score"):
                    args = (self.params, self._ref_scoring_params(), self.frozen_branch_params,
                            dbatch["seq"], dbatch["mask"])
                    op_scopes.note("ppo_score", score_fn, args, mesh=self.mesh)
                    logprobs, values, ref_logprobs = score_fn(*args)
            window.note_work(t0, time.perf_counter())
            inflight[0] = (items, scores, dense_scores, r_mask, logprobs, values, ref_logprobs)
            if serialize:
                harvest()

        def harvest():
            if inflight[0] is None:
                return
            items, scores, dense_scores, rm_b, lp, v, rlp = inflight[0]
            inflight[0] = None
            t0 = time.perf_counter()
            n_real = len(items)
            lp = np.asarray(jax.device_get(lp))[:n_real]
            v = np.asarray(jax.device_get(v))[:n_real]
            rlp = np.asarray(jax.device_get(rlp))[:n_real]
            rm = rm_b[:n_real]
            # per-token KL penalty & reward assembly — the same k3 math as
            # _score_and_store, per microbucket
            log_ratio = (lp - rlp) * rm
            kl_per_token = np.exp(log_ratio) - 1.0 - log_ratio
            accumulated_kl.append(kl_per_token.sum(axis=1).mean())
            kl_coef = self.kl_ctl.value
            t_store = flight_clock() if flight.enabled else 0.0
            new_elements = []
            for j in range(n_real):
                _, prompt, out, _, uid = items[j]
                if flight.enabled:
                    # the scored element lands in the rollout store here — the
                    # flight's store_wait tail closes
                    flight.record(uid, "store", t=t_store)
                l = int(rm[j].sum())
                rewards = -kl_coef * log_ratio[j, :l]
                if dense_scores is not None:
                    ds = dense_scores[j]
                    rewards[: min(l, len(ds))] += ds[: min(l, len(ds))]
                else:
                    rewards[l - 1] += scores[j]
                new_elements.append(
                    PPORLElement(
                        query_tensor=np.asarray(prompt, np.int32),
                        response_tensor=np.asarray(out, np.int32),
                        logprobs=lp[j, :l],
                        values=v[j, :l],
                        rewards=rewards.astype(np.float32),
                    )
                )
            # same trust boundary as _score_and_store; chaos replaces by
            # position, so new_elements[j] still corresponds to items[j]
            new_elements = chaos_corrupt_elements(new_elements)
            kept = new_elements
            if self._quarantine is not None:
                kept = self._quarantine.filter(
                    new_elements, context=f"iter={self.iter_count}"
                )
            kept_ids = {id(e) for e in kept}
            for j, elem in enumerate(new_elements):
                gidx = items[j][0]
                if id(elem) in kept_ids:
                    reorder.add(gidx, elem)
                else:
                    dropped[0] = True
                    reorder.add(gidx, None)  # tombstone: never stall the cursor
            ppo_rl_elements.extend(reorder.pop_ready())
            maybe_stage_learn()
            window.note_work(t0, time.perf_counter())

        def maybe_stage_learn():
            if not cfg.overlap_learn_stage or dropped[0]:
                return
            bs = self.config.train.batch_size
            if stage["perm"] is None:
                # replicate NumpyLoader's first-epoch permutation for the
                # loader create_train_dataloader will build over the store
                # (seed + iter_count, epoch 0); a mismatch at consume time is
                # detected by content and falls back to a fresh transfer
                idxs = list(range(num_rollouts))
                pyrandom.Random(self.config.train.seed + iter_count).shuffle(idxs)
                stage["perm"] = idxs
            avail = min(len(ppo_rl_elements), num_rollouts)
            while True:
                start = stage["next"] * bs
                if start + bs > num_rollouts:
                    break
                chunk = stage["perm"][start : start + bs]
                if any(ix >= avail for ix in chunk):
                    break
                t0 = time.perf_counter()
                with span("learn_stage"):
                    host = ppo_collate_fn(pad_id, [ppo_rl_elements[ix] for ix in chunk])
                    dev = mesh_lib.put_batch(self.mesh, host)
                self._stage_learn_batch(host, dev)
                window.note_work(t0, time.perf_counter())
                stage["next"] += 1

        def pump(block=False):
            # move FIFO-completed rewards to ready: bucket composition follows
            # engine completion order (deterministic), never worker timing
            while pending:
                gidx, fut, prompt, out, uid = pending[0]
                if not (block or fut.done()):
                    break
                pending.popleft()
                result = fut.result()[0]
                if flight.enabled:
                    flight.record(uid, "reward_done", t=flight_clock())
                ready.append((gidx, prompt, out, result, uid))
            while len(ready) >= mb:
                dispatch([ready.popleft() for _ in range(mb)])

        gen_params = self.generation_params()
        tparams = gen_params["transformer"]
        if tparams is not self._serving_param_ref:
            self._serving_engine.set_params(tparams)
            self._serving_param_ref = tparams

        self._pin_ref()
        self._clear_staged_learn()
        generated = 0
        try:
            with ThreadPoolExecutor(
                max_workers=max(1, int(cfg.overlap_reward_workers)),
                thread_name_prefix="overlap-reward",
            ) as pool:
                while generated < num_rollouts:
                    batch = next(self.prompt_iterator)
                    self._prompt_batches_drawn += 1
                    prompts = batch["input_ids"]
                    metadata = {k: v for k, v in batch.items() if k != "input_ids"}
                    base = generated
                    generated += len(prompts)
                    cur["P"] = pad_to_bucket(
                        max((len(p) for p in prompts), default=1), LENGTH_BUCKETS
                    )

                    def on_finish(i, req, _base=base, _prompts=prompts, _meta=metadata):
                        gidx = _base + i
                        prompt = np.asarray(_prompts[i], np.int32)
                        gen = np.asarray(req.generated, np.int32)
                        row = np.concatenate([prompt, gen])[None, :]
                        rmask = np.ones((1, len(gen)), np.int32)
                        str_samples, str_prompts, str_outputs, out_ids = self.decode(
                            [prompt], row, len(prompt), append_eos=True,
                            response_masks=rmask,
                        )
                        kw = dict(
                            samples=str_samples, prompts=str_prompts,
                            outputs=str_outputs, tokenizer=self._reward_tokenizer,
                            **{k: [v[i]] for k, v in _meta.items()},
                        )
                        fut = pool.submit(stream_reward, kw)
                        if flight.enabled:
                            flight.record(
                                req.uid, "reward_dispatch", t=flight_clock()
                            )
                        pending.append((gidx, fut, prompt, out_ids[0], req.uid))
                        if serialize:
                            fut.result()  # seeded regression: serial consumption
                        pump()

                    with self.obs.span("decode"):
                        self._serving_client.stream_batch(
                            prompts, self._serving_max_new, on_finish,
                            on_step=window.note_decode,
                        )
                    # drain before the next batch can change the prompt bucket
                    pump(block=True)
                    if ready:
                        dispatch([ready.popleft() for _ in range(len(ready))])
                    harvest()
        finally:
            self._unpin_ref()
        eng = self._serving_engine
        eng.note_overlap(window.decode_busy_s, window.overlapped_s)
        eng.export_gauges()
        if self._serving_autoscaler is not None:
            self._serving_autoscaler.observe()

    # ------------------------------------------------------------- experience

    def _generate_chunks(self, tokenizer, params=None):
        """One device generation at decode_batch_size, split into chunk_size
        sub-chunks for reward_fn / the scoring forward. ``params`` overrides
        the sampling params (async producer passes a published snapshot)."""
        batch = next(self.prompt_iterator)
        self._prompt_batches_drawn += 1
        prompts = batch["input_ids"]
        metadata = {k: v for k, v in batch.items() if k != "input_ids"}
        if self._serving_client is not None:
            samples, resp_mask, pad_len = self._serving_generate(prompts, params=params)
        else:
            samples, resp_mask, pad_len = self.generate(prompts, eval_mode=False, params=params)
        str_samples, str_prompts, str_outputs, out_ids = self.decode(
            prompts, samples, pad_len, append_eos=True, response_masks=resp_mask
        )
        cs = self.method.chunk_size
        subs = []
        for i in range(0, len(prompts), cs):
            sl = slice(i, i + cs)
            reward_kwargs = dict(
                samples=str_samples[sl], prompts=str_prompts[sl],
                outputs=str_outputs[sl], tokenizer=tokenizer,
                **{k: v[sl] for k, v in metadata.items()},
            )
            subs.append(((prompts[sl], out_ids[sl]), reward_kwargs))
        return subs

    def make_experience(self, num_rollouts: int = 1024, iter_count: int = 0):
        """Roll out prompts → generations → rewards → KL-penalized per-token reward
        assembly → rollout store (parity: :251-524; see SURVEY.md §3.2).

        With ``method.overlap_reward_scoring``, reward_fn for chunk i runs on a
        worker thread while chunk i+1 generates on the device — double-buffering
        that hides a served reward model's RPC round-trip (the reference runs its
        Triton reward scoring serially on rank 0, :303-317)."""
        # the parent of generate / reward / score: its self time is the host work
        # between them (tokenizer decode, element assembly, push_to_store)
        with span("experience"):
            logger.info(f"Collecting {num_rollouts} rollouts")
            ppo_rl_elements: List[PPORLElement] = []
            accumulated_kl = []
            all_scores_log = []
            self.clock.tick()

            overlap = self.method.overlap_reward_scoring
            stream = (
                self._serving_client is not None
                and self.config.train.serving.stream_overlap
                and jax.process_count() == 1
            )
            if self.config.train.serving.stream_overlap and self._serving_client is not None and not stream:
                logger.warning(
                    "serving.stream_overlap is single-process only: "
                    "running the serial serving consumption path"
                )
            if stream:
                # stream-overlapped PPO: reward/score/learn-stage while the tail
                # of the batch is still decoding (docs/serving.md)
                self._make_experience_streamed(
                    num_rollouts, iter_count, ppo_rl_elements, accumulated_kl, all_scores_log
                )
            elif overlap:
                import copy
                from collections import deque
                from concurrent.futures import ThreadPoolExecutor

                # Multihost + reward_on_process_zero composes with overlap: only
                # process 0's reward_fn runs on the worker thread
                # (pure RPC/python, no collectives); the broadcast — a collective —
                # happens at future-drain time on the MAIN thread, which reaches
                # each drain in the same program order on every host.
                broadcasting = self.reward_on_process_zero and jax.process_count() > 1
                score_locally = not broadcasting or jax.process_index() == 0
                if broadcasting:
                    logger.info(
                        "overlap_reward_scoring active with reward_on_process_zero: "
                        "process-0 worker-thread scoring + main-thread broadcast"
                    )

                # reward_fn runs on a worker thread while the main thread keeps using
                # self.tokenizer in decode(); HF fast tokenizers are not re-entrant
                # ("Already borrowed"), so the worker gets its own copy
                if not hasattr(self, "_reward_tokenizer"):
                    self._reward_tokenizer = copy.deepcopy(self.tokenizer)
                generated = 0  # count at generation time: len(ppo_rl_elements) lags
                with ThreadPoolExecutor(max_workers=1) as pool:
                    pending = deque()
                    while generated < num_rollouts or pending:
                        if generated < num_rollouts:
                            new = [
                                (chunk, pool.submit(self._spanned_reward_fn, **kw) if score_locally else None)
                                for chunk, kw in self._generate_chunks(self._reward_tokenizer)
                            ]
                            generated += sum(len(chunk[0]) for chunk, _ in new)
                        else:
                            new = []
                        # drain the previous generation's scores while this one's
                        # reward futures run behind the next device generation
                        while pending:
                            pchunk, pfut = pending.popleft()
                            scores = pfut.result() if pfut is not None else None
                            if broadcasting:
                                scores = self.broadcast_scores(scores, len(pchunk[0]))
                            self._score_and_store(
                                pchunk, scores, ppo_rl_elements, accumulated_kl, all_scores_log
                            )
                        pending.extend(new)
            else:
                while len(ppo_rl_elements) < num_rollouts:
                    for chunk, reward_kwargs in self._generate_chunks(self.tokenizer):
                        with span("reward"):
                            scores = self.call_reward_fn(**reward_kwargs)
                        self._score_and_store(chunk, scores, ppo_rl_elements, accumulated_kl, all_scores_log)

            self.mean_kl = float(np.mean(accumulated_kl))
            rollout_time = self.clock.tick()
            self.rollout_stats = {
                "rollout_scores/mean": float(np.mean(all_scores_log)),
                "rollout_scores/std": float(np.std(all_scores_log)),
                "rollout_scores/running_mean": float(self.running_moments.mean),
                "rollout_scores/running_std": float(self.running_moments.std),
                "policy/sqrt_kl": float(np.sqrt(max(self.mean_kl, 0.0))),
                "kl_ctl_value": float(self.kl_ctl.value),
                "time/rollout_time": rollout_time,
            }
            if self.log_rollouts:
                self.store.export_history(location=self.rollout_logging_dir, tokenizer=self.tokenizer)
            self.push_to_store(ppo_rl_elements[:num_rollouts])
            # offloaded ref: drop the device copy before the update phase (where
            # grads + optimizer state peak HBM); no-op otherwise
            self._release_ref()

    def _spanned_reward_fn(self, **kwargs):
        """reward_fn under a ``reward`` span (overlap path runs it on a worker
        thread — the span keeps the RPC round-trip visible on that thread's
        timeline)."""
        with span("reward"):
            return self.reward_fn(**kwargs)

    def _length_caps(self):
        """The longest prompt and response the configuration allows: ``trlx.train``
        truncates every prompt to ``max_prompt_length``, and a response is the
        experience generation's ``max_new_tokens`` and the re-appended eos."""
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        return self.config.max_prompt_length, int(gen_kwargs.get("max_new_tokens", 16)) + 1

    def _score_and_store(
        self, chunk, scores, ppo_rl_elements, accumulated_kl, all_scores_log, params=None
    ):
        """Normalize scores, run the jitted logprob/value/ref scoring forward, and
        assemble KL-penalized PPORLElements (parity: :364-502).

        ``params`` overrides the policy used for the behavior logprob/value
        scoring pass — the async producer passes the same published snapshot it
        sampled with, so stored logprobs are the true behavior policy's even
        while the learner mutates ``self.params``."""
        policy_params = self.params if params is None else params
        prompts, out_ids = chunk
        dense = np.ndim(scores[0]) > 0
        if dense:
            dense_scores = [np.asarray(s, np.float32) for s in scores]
            scores = np.asarray([s.sum() for s in dense_scores], np.float32)
        else:
            dense_scores = None
            scores = np.asarray(jax.device_get(scores), np.float32).reshape(-1)

        all_scores_log.extend(scores.tolist())
        # clip + normalize scores (parity: :364-381)
        scores_mean, scores_std = self.running_moments.update(scores)
        if self.method.cliprange_reward:
            scores = np.clip(scores, -self.method.cliprange_reward, self.method.cliprange_reward)
        if self.method.scale_reward == "running":
            scores = scores / max(self.running_moments.std, 1e-8)
        elif self.method.scale_reward == "ref":
            scores = scores / max(self.method.ref_std or 1.0, 1e-8)

        # fixed-shape scoring forward: each axis pads to its rung of the ladder,
        # and no further than the longest length the configuration allows
        longest_p = max(len(p) for p in prompts)
        longest_r = max(len(o) for o in out_ids)
        p_cap, r_cap = self._length_caps()
        P = pad_to_bucket(longest_p, LENGTH_BUCKETS, cap=p_cap)
        R = pad_to_bucket(longest_r, LENGTH_BUCKETS, cap=r_cap)
        gauges.set("score/padded_positions_share", 1.0 - (longest_p + longest_r) / (P + R))
        q_ids, q_mask = left_pad_batch(prompts, self.tokenizer.pad_token_id, P)
        r_ids = np.full((len(out_ids), R), self.tokenizer.pad_token_id, np.int32)
        r_mask = np.zeros((len(out_ids), R), np.int32)
        for i, o in enumerate(out_ids):
            r_ids[i, : len(o)] = o
            r_mask[i, : len(o)] = 1
        score_fn = self._get_score_fn(q_ids.shape[0], P, R)
        # the span includes the device_get: the scoring forward is async until
        # the host fetch (same reasoning as the generate span)
        with span("score"):
            if self.is_seq2seq:
                dbatch = mesh_lib.put_batch(
                    self.mesh, {"q": q_ids, "qm": q_mask, "r": r_ids, "rm": r_mask}
                )
                with self.mesh, compile_log.attributed("ppo_score"):
                    args = (policy_params, self._ref_scoring_params(), self.frozen_branch_params,
                            dbatch["q"], dbatch["qm"], dbatch["r"], dbatch["rm"])
                    op_scopes.note("ppo_score", score_fn, args, mesh=self.mesh)
                    logprobs, values, ref_logprobs = score_fn(*args)
            else:
                seq = np.concatenate([q_ids, r_ids], axis=1)
                mask = np.concatenate([q_mask, r_mask], axis=1)
                dbatch = mesh_lib.put_batch(self.mesh, {"seq": seq, "mask": mask})
                with self.mesh, compile_log.attributed("ppo_score"):
                    args = (policy_params, self._ref_scoring_params(), self.frozen_branch_params,
                            dbatch["seq"], dbatch["mask"])
                    op_scopes.note("ppo_score", score_fn, args, mesh=self.mesh)
                    logprobs, values, ref_logprobs = score_fn(*args)
            logprobs = np.asarray(jax.device_get(logprobs))
            values = np.asarray(jax.device_get(values))
            ref_logprobs = np.asarray(jax.device_get(ref_logprobs))

        # per-token KL penalty & reward assembly (parity: :457-492)
        log_ratio = (logprobs - ref_logprobs) * r_mask
        kl_per_token = np.exp(log_ratio) - 1.0 - log_ratio  # k3 estimator (:461)
        # controller sees the per-SEQUENCE kl sum (reference :460 kl.sum(1).mean());
        # the shipped AdaptiveKL targets (e.g. 6.0) are calibrated to that scale
        mean_kl = kl_per_token.sum(axis=1).mean()
        accumulated_kl.append(mean_kl)

        kl_coef = self.kl_ctl.value
        new_elements = []
        for i in range(len(prompts)):
            l = int(r_mask[i].sum())
            rewards = -kl_coef * log_ratio[i, :l]
            if dense:
                ds = dense_scores[i]
                rewards[: min(l, len(ds))] += ds[: min(l, len(ds))]
            else:
                rewards[l - 1] += scores[i]
            new_elements.append(
                PPORLElement(
                    query_tensor=np.asarray(prompts[i], np.int32),
                    response_tensor=r_ids[i, :l],
                    logprobs=logprobs[i, :l],
                    values=values[i, :l],
                    rewards=rewards.astype(np.float32),
                )
            )
        # experience crosses a trust boundary here: this is the single choke
        # point both the sync path (make_experience) and the async producer
        # assemble elements through, so the quarantine screen covers both.
        # chaos site "bad-element" fabricates an offender first (free unarmed)
        new_elements = chaos_corrupt_elements(new_elements)
        if self._quarantine is not None:
            new_elements = self._quarantine.filter(
                new_elements, context=f"iter={self.iter_count}"
            )
        ppo_rl_elements.extend(new_elements)


    # ---------------------------------------------------------- async rollouts

    def _resolve_async_config(self):
        """The effective ``train.async_rollouts`` block, or None for the
        synchronous path. ``max_staleness=0`` means fully on-policy — exactly
        the synchronous semantics, so we run that code path rather than an
        async engine that must block on every publish."""
        cfg = getattr(self.config.train, "async_rollouts", None)
        if cfg is None or not cfg.enabled:
            return None
        if cfg.max_staleness <= 0:
            logger.warning(
                "async_rollouts.max_staleness=0 requests fully on-policy data: "
                "running the synchronous rollout path"
            )
            return None
        if jax.process_count() > 1:
            logger.warning(
                "async_rollouts is single-process only (cross-host reward "
                "broadcast ordering is undefined off the main thread): "
                "running the synchronous rollout path"
            )
            return None
        return cfg

    def _start_async_engine(self):
        from trlx_tpu.rollout import (
            AsyncRolloutEngine,
            ExperienceQueue,
            ParameterPublisher,
            StalenessAccountant,
        )

        cfg = self._async_cfg

        def device_copy(tree):
            # donate-free snapshot: the train step donates self.params' buffers,
            # so the producer must read an independent copy (same pattern as the
            # frozen KL reference in setup_model)
            with self.mesh:
                return copy_params(tree)

        icfg = getattr(self.config.train, "islands", None)
        if icfg is not None and icfg.enabled and self._serving_engine is None:
            logger.warning(
                "train.islands requires train.serving (the generation island "
                "IS the continuous-batching engine): running the monolithic "
                "publish path"
            )
            icfg = None
        if icfg is not None and icfg.enabled:
            from trlx_tpu.parallel.mesh import carve_islands
            from trlx_tpu.rollout import ChunkedParameterPublisher
            from trlx_tpu.serving import GenerationIsland

            placement = carve_islands(icfg.gen_devices)
            # published trees are full trainer params (transformer + heads);
            # the serving engine runs only the transformer trunk
            self._island = GenerationIsland(
                self._serving_engine, param_selector=lambda tree: tree["transformer"]
            )
            publisher = ChunkedParameterPublisher(
                copy_fn=device_copy,
                chunk_layers=icfg.chunk_layers,
                chunk_pause_s=icfg.chunk_pause_s,
                round_gate=self._island.round_gate,
            )
            self._island.bind_publisher(publisher)
            logger.info(
                f"generation island carved: gen={len(placement.gen)} device(s), "
                f"learn={len(placement.learn)} device(s), "
                f"shared={placement.shared}, chunk_layers={icfg.chunk_layers}"
            )
        else:
            publisher = ParameterPublisher(copy_fn=device_copy)
        self._policy_version = publisher.publish(self.params)
        capacity = cfg.queue_capacity or 4 * self.method.num_rollouts
        queue = ExperienceQueue(capacity, cfg.high_watermark, cfg.low_watermark)
        accountant = StalenessAccountant(cfg.max_staleness)
        sh_config = self.config.train.self_healing
        supervised = sh_config.enabled

        def make_engine():
            # generations share queue/publisher/accountant; under supervision
            # a dead generation must not close the queue its successor feeds
            return AsyncRolloutEngine(
                self._produce_rollout_chunk,
                publisher,
                queue,
                accountant,
                close_queue_on_death=not supervised,
            )

        if supervised:
            from trlx_tpu.rollout import ProducerSupervisor

            self._engine = ProducerSupervisor(
                make_engine,
                max_restarts=sh_config.max_producer_restarts,
                backoff_base_s=sh_config.restart_backoff_base_s,
                backoff_max_s=sh_config.restart_backoff_max_s,
                wedge_timeout_s=sh_config.wedge_timeout_s,
                diagnostics_dir=sh_config.diagnostics_dir
                or os.path.join(self.config.train.checkpoint_dir, "diagnostics"),
            )
        else:
            self._engine = make_engine()
        self._engine.start()
        if self._island is not None:
            # windows open after the seed publish, so the first broadcast's
            # compile/copy cost never pollutes the idle-bubble fractions
            self._island.open_window()
        logger.info(
            f"async rollout engine started{' (supervised)' if supervised else ''}: "
            f"queue_capacity={capacity} "
            f"(high={queue.high_watermark}, low={queue.low_watermark}), "
            f"max_staleness={cfg.max_staleness}, "
            f"publish_interval={cfg.publish_interval}"
        )

    def _produce_rollout_chunk(self, params, version):
        """PRODUCER THREAD: one decode-batch of generate → reward → score, with
        the published snapshot as both sampling and behavior-scoring policy.
        Runs concurrently with the learner's train steps; shares no mutable
        state with them except the float stats below (atomic swaps under the
        GIL) — evaluate(), which does share the tokenizer/RNG/generation
        caches, pauses the engine around itself."""
        elements: List[PPORLElement] = []
        kls: List[float] = []
        scores_log: List[float] = []
        t0 = time.monotonic()
        for chunk, reward_kwargs in self._generate_chunks(self.tokenizer, params=params):
            with span("reward"):
                scores = self.reward_fn(**reward_kwargs)
            self._score_and_store(chunk, scores, elements, kls, scores_log, params=params)
        if kls:
            self.mean_kl = float(np.mean(kls))
        self.rollout_stats = {
            "rollout_scores/mean": float(np.mean(scores_log)),
            "rollout_scores/std": float(np.std(scores_log)),
            "rollout_scores/running_mean": float(self.running_moments.mean),
            "rollout_scores/running_std": float(self.running_moments.std),
            "policy/sqrt_kl": float(np.sqrt(max(self.mean_kl, 0.0))),
            "kl_ctl_value": float(self.kl_ctl.value),
            "time/rollout_chunk_time": time.monotonic() - t0,
            "rollout/producer_version": float(version),
        }
        if self._island is not None and self._serving_client is not None:
            # behavior policy as actually served (the island may have swapped
            # mid-batch; drift vs. `version` is what clipped-IS absorbs)
            self.rollout_stats["rollout/served_version"] = float(
                self._serving_client.policy_version
            )
        return elements

    def _refill_store_async(self):
        """Pull ``num_rollouts`` staleness-admitted elements from the engine
        into the rollout store (the async analogue of make_experience)."""
        n = self.method.num_rollouts
        t0 = time.monotonic()
        with span("queue_wait"):
            elements = self._engine.collect(
                n, self._policy_version, timeout=self._async_cfg.collect_timeout_s
            )
        gauges.set("rollout/collect_wait_s", time.monotonic() - t0)
        if self.log_rollouts:
            self.store.export_history(location=self.rollout_logging_dir, tokenizer=self.tokenizer)
        self.push_to_store(elements[:n])

    # ------------------------------------------------------------- train loop

    def _extra_state(self):
        return {"prompt_batches_drawn": self._prompt_batches_drawn}

    def _restore_extra_state(self, state):
        self._resume_prompt_batches = int(state.get("prompt_batches_drawn", 0))

    def _fast_forward_prompt_stream(self):
        """Replay the restored number of prompt-batch draws. Exact replay (not
        modulo the loader length) because ``NumpyLoader`` reshuffles per epoch
        from ``seed + epoch`` — position N is only reproducible by drawing N
        times from the same freshly-built iterator."""
        n = self._resume_prompt_batches
        self._resume_prompt_batches = 0
        if n <= 0 or getattr(self, "prompt_iterator", None) is None:
            return
        for _ in range(n):
            next(self.prompt_iterator)
        self._prompt_batches_drawn = n
        logger.info(f"Auto-resume: fast-forwarded the prompt stream by {n} batches")

    def prepare_learning(self):
        bs = self.config.train.batch_size
        self.num_mb = max(1, bs // (self.config.train.minibatch_size or bs))
        self._fast_forward_prompt_stream()
        self._resolve_serving()
        self._async_cfg = self._resolve_async_config()
        icfg = getattr(self.config.train, "islands", None)
        if icfg is not None and icfg.enabled and self._async_cfg is None:
            logger.warning(
                "train.islands requires train.async_rollouts (the bounded "
                "experience queue is the island seam): islands disabled"
            )
        if self._async_cfg is not None:
            self._start_async_engine()
            self._refill_store_async()
        else:
            self.make_experience(self.method.num_rollouts, self.iter_count)

    def create_train_dataloader(self):
        """ppo_epochs passes over the current rollout store per outer epoch."""
        loader = self.store.create_loader(
            self.config.train.batch_size, shuffle=True, seed=self.config.train.seed + self.iter_count
        )
        for _ in range(self.method.ppo_epochs):
            yield from loader

    def _get_train_step(self, B: int, P: int, R: int):
        key = (B, P, R)
        if key in self._train_steps:
            return self._train_steps[key]
        gauges.set("learn/head_rows_share", 1.0 if self.is_seq2seq else R / (P + R))  # as in _get_score_fn
        module, method = self.module, self.method

        # staleness-aware IS correction (async engine only): the mode is fixed
        # for the trainer's lifetime, so it needs no compile-key entry. With it
        # OFF this traces the identical program as before — the bitwise-equal
        # guarantee of the synchronous / max_staleness=0 path.
        use_is = self._engine is not None and bool(self._async_cfg.staleness_correction)
        is_clip = float(self._async_cfg.is_ratio_clip) if use_is else None

        def loss_extra(mb: PPORLBatch):
            if use_is and mb.staleness is not None:
                return dict(staleness=mb.staleness, is_ratio_clip=is_clip)
            return {}

        if self.is_seq2seq:
            start_tok = self.decoder_start_token_id

            def loss_fn_s2s(params, mb: PPORLBatch):
                Bs = mb.response_tensors.shape[0]
                dec_in = jnp.concatenate(
                    [jnp.full((Bs, 1), start_tok, jnp.int32), mb.response_tensors[:, :-1]], axis=1
                )
                dec_mask = jnp.concatenate(
                    [jnp.ones((Bs, 1), jnp.int32), mb.response_mask[:, :-1]], axis=1
                )
                logits, values_pred, _ = module.apply(
                    {"params": params}, mb.query_tensors, mb.attention_mask, dec_in, dec_mask
                )
                with jax.named_scope("logprobs"):
                    logprobs = logprobs_of_labels(logits, mb.response_tensors)
                values_pred = values_pred.astype(jnp.float32)
                advantages, returns = method.get_advantages_and_returns(
                    mb.values, mb.rewards, mb.response_mask
                )
                loss, stats = method.loss(
                    logprobs, values_pred, mb.logprobs, mb.values, advantages, returns,
                    mb.response_mask, **loss_extra(mb),
                )
                return loss, flatten_dict(stats)

            self._train_steps[key] = self.make_grad_accum_step(
                loss_fn_s2s, self.num_mb, name=self.train_step_name
            )
            return self._train_steps[key]

        counts_experts = self.model_config.num_experts > 0
        counts_loops = self.model_config.loop_steps > 1
        sown_collections = [
            name for name, counted in (("moe_stats", counts_experts), ("loop_stats", counts_loops)) if counted
        ]

        def loss_fn(params, mb: PPORLBatch):
            seq = jnp.concatenate([mb.query_tensors, mb.response_tensors], axis=1)
            mask = jnp.concatenate([mb.attention_mask, mb.response_mask], axis=1)
            if sown_collections:  # the expert layers' loads, the loop's counters: out beside the forward's answers
                (hidden, values_pred, _, _), sown = module.apply(
                    {"params": params}, seq, mask, with_head=False, mutable=sown_collections
                )
            else:
                hidden, values_pred, _, _ = module.apply({"params": params}, seq, mask, with_head=False)
            start = mb.query_tensors.shape[1] - 1
            Rr = mb.response_tensors.shape[1]
            with jax.named_scope("logprobs"):  # the head over the response window, as in the scorer
                logprobs = response_logprobs(hidden, head_of(module, params), seq, start, Rr)
            values_pred = values_pred[:, start : start + Rr].astype(jnp.float32)
            advantages, returns = method.get_advantages_and_returns(
                mb.values, mb.rewards, mb.response_mask
            )
            loss, stats = method.loss(
                logprobs, values_pred, mb.logprobs, mb.values, advantages, returns,
                mb.response_mask, **loss_extra(mb),
            )
            stats = flatten_dict(stats)
            if counts_experts:
                stats.update(moe_counters(sown["moe_stats"]))
                stats["moe/assignments"] = jnp.float32(
                    seq.size * self.model_config.experts_per_token
                    * sum(map(self.model_config.is_expert_layer, range(self.model_config.num_layers)))
                )
            if counts_loops:
                stats.update(loop_counters(sown["loop_stats"]))
            return loss, stats

        self._train_steps[key] = self.make_grad_accum_step(
            loss_fn, self.num_mb, name=self.train_step_name
        )
        return self._train_steps[key]

    def train_step(self, batch: PPORLBatch) -> Dict[str, float]:
        if self._engine is not None:
            # staleness is learner-relative and must be stamped NOW (the
            # learner kept publishing while this collated batch waited), not
            # at collate time
            stale = np.maximum(
                0, self._policy_version - np.asarray(batch.policy_version, np.int64)
            ).astype(np.int32)
            gauges.set("rollout/batch_staleness_mean", float(stale.mean()))
            gauges.set("rollout/batch_staleness_max", float(stale.max()))
            if self._async_cfg.staleness_correction:
                batch = batch.replace(staleness=stale)
        # stream-overlap learn seam: consume the device copy staged during the
        # decode window when it matches this batch exactly; fresh transfer
        # otherwise (identical data either way)
        with span("learn.put"):  # host -> device input
            dbatch = self._pop_staged_learn(batch)
            if dbatch is None:
                dbatch = mesh_lib.put_batch(self.mesh, batch)
        step = self._get_train_step(
            batch.query_tensors.shape[0], batch.query_tensors.shape[1], batch.response_tensors.shape[1]
        )
        t_learn0 = time.monotonic()
        with span("learn.step"), self.mesh, compile_log.attributed(self.train_step_name):  # dispatch
            # (under the health guard ``step`` is a plain wrapper, which notes the jitted step it calls)
            op_scopes.note(self.train_step_name, step, (self.params, self.opt_state, dbatch), mesh=self.mesh)
            self.params, self.opt_state, stats = step(self.params, self.opt_state, dbatch)
        with span("learn.sync"):  # the host waiting for the device
            out = {k: float(v) for k, v in jax.device_get(stats).items()}
        for name, value in out.items():
            # a microbatch's counters: the experts' summed over the expert layers, the loop's per pass
            if name.startswith(("moe/", "loop/")):
                gauges.set(name, value)
        if self._island is not None:
            # device_get above synced the step; the interval is real compute
            self._island.note_learn(t_learn0, time.monotonic())
            self._island.export_gauges()
        out.update(self.rollout_stats)
        if self._engine is not None:
            out.update(gauges.snapshot("rollout/"))
        if self._serving_client is not None:
            out.update(gauges.snapshot("serving/"))
            out.update(gauges.snapshot("fleet/"))
        return out

    def post_backward_callback(self):
        """KL controller update per optimizer step (parity: :227-231); under the
        async engine, also publish a fresh parameter snapshot so the producer's
        next chunk samples from the newest policy."""
        self.kl_ctl.update(self.mean_kl, n_steps=self.config.train.batch_size)
        if self._engine is not None and (
            self.iter_count % max(1, self._async_cfg.publish_interval) == 0
        ):
            t_pub0 = time.monotonic()
            self._policy_version = self._engine.publisher.publish(self.params)
            gauges.set("rollout/learner_version", float(self._policy_version))
            if self._island is not None:
                # the broadcast runs on the learner island's thread — it is
                # learner busy time, even though the chunks hide under decode
                self._island.note_learn(t_pub0, time.monotonic())

    def post_epoch_callback(self, epoch: int):
        """Discard stale rollouts and collect fresh experience (parity: :219-225).
        Async: the producer has been filling the queue during the optimizer
        epochs, so this usually just drains already-generated experience."""
        self.store.clear_history()
        if self._engine is not None:
            self._refill_store_async()
        else:
            self.make_experience(self.method.num_rollouts, self.iter_count)

    def _post_rollback_restore(self):
        """Mid-run health rollback: re-anchor the PPO-specific run state that
        :meth:`load` alone cannot rebuild. The prompt iterator cannot rewind,
        so it is rebuilt from the retained pipeline and the restored draw
        count is replayed (the same exact-resume mechanics as a process
        restart); the async producer is resynced by publishing the restored
        params so its next chunk samples from the good policy, not the
        anomalous one; experience already collected from the bad policy is
        dropped (post_epoch_callback refills the store after the epoch
        breaks)."""
        def reanchor():
            if self._prompt_pipeline is not None:
                self.add_prompt_pipeline(self._prompt_pipeline)
                self._prompt_batches_drawn = 0
                self._fast_forward_prompt_stream()
            if self._engine is not None:
                self._policy_version = self._engine.publisher.publish(self.params)
                gauges.set("rollout/learner_version", float(self._policy_version))

        if self._engine is not None and self._engine.running:
            # the producer draws from prompt_iterator between produce
            # iterations — swap it only while production is paused
            with self._engine.paused():
                reanchor()
        else:
            reanchor()
        self.store.clear_history()

    def evaluate(self):
        """Eval shares the tokenizer, RNG, and compiled-generate caches with the
        rollout producer: pause the engine for the duration."""
        if self._engine is not None and self._engine.running:
            with self._engine.paused():
                return super().evaluate()
        return super().evaluate()

    def on_learn_end(self):
        """Drain and join the rollout producer (no dangling threads, whatever
        path exited learn()). Producer errors found here are logged, not
        raised: this runs in learn()'s finally and must not mask the original
        exception; a producer death during training already surfaces through
        collect()."""
        engine, self._engine = self._engine, None
        island, self._island = self._island, None
        if engine is None:
            return
        try:
            stats = engine.stop(timeout=self._async_cfg.drain_timeout_s)
            logger.info(f"async rollout engine stopped: {stats}")
        except Exception as e:
            logger.warning(f"async rollout engine teardown: {type(e).__name__}: {e}")
        finally:
            if island is not None:
                # final numbers before the prefix-aware gauge clear
                logger.info(f"generation island closed: {island.summary()}")
                island.close()
