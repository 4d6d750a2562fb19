"""ILQL trainer (parity: `/root/reference/trlx/trainer/accelerate_ilql_trainer.py`):
offline experience building (returns standardization, last-action reward, action/state
index bookkeeping), the ILQL loss driver, periodic Polyak target-Q sync, and the
advantage-shaped generation used at evaluation.
"""

from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.ilql_types import ILQLBatch
from trlx_tpu.methods.ilql import ILQLConfig, batched_index_select
from trlx_tpu.models.hf_loading import load_pretrained
from trlx_tpu.models.heads import sync_target_q_heads as _sync_heads
from trlx_tpu.models.policy import CausalLMWithILQLHeads
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.ops.generation import LENGTH_BUCKETS, pad_to_bucket
from trlx_tpu.parallel import mesh as mesh_lib
from trlx_tpu.parallel.sharding import make_param_shardings
from trlx_tpu.pipeline.offline_pipeline import ILQLRolloutStorage, tokenize_dialogue
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.mesh_trainer import MeshRLTrainer
from trlx_tpu.utils import logging
from trlx_tpu.utils.modeling import flatten_dict

logger = logging.get_logger(__name__)

BUCKETS = (4,) + LENGTH_BUCKETS  # an offline sample may be a token or two


def make_experience(samples, rewards, tokenizer=None, max_length: int = 2048,
                    verbose: bool = True) -> ILQLRolloutStorage:
    """Tokenize dialogues and compute ILQL index bookkeeping (parity:
    accelerate_ilql_trainer.py:30-100): per-sample ``actions_ixs`` = positions whose
    *next* token is an output token; ``states_ixs`` = actions + terminal; rewards are
    standardized returns placed on the last action."""
    if verbose:
        logger.info("Collecting rollouts")
    if tokenizer is not None:
        samples = [tokenize_dialogue(s, tokenizer, max_length) for s in samples]

    all_input_ids, all_actions_ixs, all_states_ixs, all_dones = [], [], [], []
    for sample in samples:
        length = 0
        input_ids = np.asarray([t for msg in sample for t in msg.tokens], np.int32)
        all_input_ids.append(input_ids)
        actions_ixs = []
        for dm in sample:
            if dm.is_output:
                actions_ixs.append(np.arange(length - 1, length + len(dm.tokens) - 1))
            length += len(dm.tokens)
        states_ixs = np.concatenate([*actions_ixs, [length - 1]])
        all_dones.append(np.asarray([1] * (len(states_ixs) - 1) + [0], np.int32))
        all_actions_ixs.append(np.concatenate(actions_ixs).astype(np.int32))
        all_states_ixs.append(states_ixs.astype(np.int32))

    returns = np.asarray(rewards, np.float64)
    returns = returns - returns.mean()
    std = returns.std()
    if not np.isnan(std) and std > 0:
        returns = returns / (std + np.finfo(returns.dtype).eps)
    rewards_per_token = [np.zeros(len(x), np.float32) for x in all_actions_ixs]
    for rs, ret in zip(rewards_per_token, returns):
        rs[-1] = ret

    attention_mask = [np.ones(len(x), np.int32) for x in all_input_ids]
    return ILQLRolloutStorage(
        all_input_ids, attention_mask, rewards_per_token, all_states_ixs, all_actions_ixs, all_dones
    )


@register_trainer
class ILQLTrainer(MeshRLTrainer):
    def __init__(self, config: TRLConfig, logit_mask=None, **kwargs):
        super().__init__(config, **kwargs)
        if not isinstance(config.method, ILQLConfig):
            raise ValueError("ILQLTrainer requires method=ILQLConfig")
        self.method: ILQLConfig = config.method
        # `beta` shapes decode logits; it is not a generation-engine kwarg. A
        # list (reference ilql_hh gen_kwargs beta=[1, 4]) stays in generate_kwargs
        # so evaluate() sweeps it; pop_gen_processor_kwargs routes the per-call
        # value to the logits processor. Default/rollout beta = first entry.
        beta = self.generate_kwargs.get("beta", 1.0)
        if isinstance(beta, (list, tuple)):
            # normalize to list: evaluate()'s sweep detection matches lists only
            self.generate_kwargs["beta"] = list(beta)
            self.ilql_beta = float(beta[0])
        else:
            self.ilql_beta = float(self.generate_kwargs.pop("beta", 1.0))
        # optional [V, V] next-token transition mask (parity: reference trainers'
        # logit_mask kwarg used by randomwalks; masks invalid successor tokens)
        self.logit_mask = None if logit_mask is None else np.asarray(logit_mask, bool)
        self._train_steps = {}
        self._sync_fn = None

    def setup_model(self):
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
        overrides.setdefault("remat", self.config.mesh.remat)
        overrides.setdefault("sequence_sharding", self.config.mesh.sequence_shard)
        from trlx_tpu.models.hf_loading import merge_loaded_params, peft_overrides

        overrides.update(peft_overrides(self.config.model.peft_config))
        overrides.update(pp_overrides)
        self.model_config, trunk_params, self.model_type = load_pretrained(
            self.config.model.model_path, overrides, mesh=self.restore_mesh(overrides)
        )
        trunk_params = self.maybe_stack_loaded(trunk_params, self.model_config.num_layers)
        self.module = CausalLMWithILQLHeads(self.model_config, two_qs=self.config.method.two_qs)
        self.trunk_module = TransformerLM(self.model_config)

        params = self.module.init(
            jax.random.PRNGKey(self.config.train.seed),
            jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1, 2), jnp.int32),
        )["params"]
        if trunk_params is not None:
            params = dict(params)
            params["transformer"] = merge_loaded_params(params["transformer"], trunk_params)
        # start target heads equal to online heads (parity: ILQLHeads init sync)
        params["ilql_heads"] = _sync_heads(dict(params["ilql_heads"]), alpha=1.0)
        shardings = make_param_shardings(params, self.mesh)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x, self.param_dtype), s), params, shardings
        )

    def _setup_seq2seq_model(self, overrides):
        from trlx_tpu.models.hf_loading import (
            load_pretrained_seq2seq,
            merge_loaded_params,
            t5_peft_overrides,
        )
        from trlx_tpu.models.policy import Seq2SeqLMWithILQLHeads

        overrides = {**(overrides or {}), **t5_peft_overrides(self.config.model.peft_config)}
        self.model_config, t5_params = load_pretrained_seq2seq(
            self.config.model.model_path, overrides, mesh=self.mesh
        )
        self.model_type = "t5"
        self.decoder_start_token_id = self.model_config.decoder_start_token_id
        self.module = Seq2SeqLMWithILQLHeads(self.model_config, two_qs=self.config.method.two_qs)
        params = self.module.init(
            jax.random.PRNGKey(self.config.train.seed),
            jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32),
            jnp.zeros((1, 3), jnp.int32),
        )["params"]
        if t5_params is not None:
            params = dict(params)
            params["t5"] = merge_loaded_params(params["t5"], t5_params)
        params["ilql_heads"] = _sync_heads(dict(params["ilql_heads"]), alpha=1.0)
        shardings = make_param_shardings(params, self.mesh)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x, self.param_dtype), s), params, shardings
        )

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
            "init_cache": lambda params, b, n: self._t5().init_cache(b, n),
        }

    def _t5(self):
        from trlx_tpu.models.t5 import T5LM

        return T5LM(self.model_config)

    def trainable_path_predicate(self, path: str) -> bool:
        if "target_q_heads" in path:
            return False  # target heads update only via Polyak sync
        return super().trainable_path_predicate(path)

    # ------------------------------------------------------------- generation

    def gen_step_fn(self):
        trunk = self.trunk_module

        def step(params, ids, mask, positions, cache):
            logits, hidden, _, cache = trunk.apply(
                {"params": params["transformer"]}, ids, mask, positions, cache
            )
            return logits, hidden, cache

        return step, lambda b, s: trunk.init_cache(b, s)

    def pop_gen_processor_kwargs(self, gen_kwargs):
        if "beta" in gen_kwargs:
            val = gen_kwargs.pop("beta")
            # un-swept list (e.g. rollout path): use its first entry
            beta = float(val[0]) if isinstance(val, (list, tuple)) else float(val)
            return {"beta": beta}
        return {}

    def gen_logits_processor(self, beta=None):
        """Perturb decode logits by beta*(minQ - V) from the target heads
        (parity: modeling_ilql.py:325-412)."""
        module = self.module
        beta = self.ilql_beta if beta is None else beta
        logit_mask = None if self.logit_mask is None else jnp.asarray(self.logit_mask)

        def processor(params, hidden, logits, prev_tok):
            qs, target_qs, vs = module.apply(
                {"params": {"ilql_heads": params["ilql_heads"]}},
                hidden[:, None, :],
                method=module.heads_only,
            )
            q = target_qs[0]
            for tq in target_qs[1:]:
                q = jnp.minimum(q, tq)
            adv = q[:, 0, :] - vs[:, 0, :]
            shaped = logits + beta * adv
            if logit_mask is not None:
                # parity: reference masks logits by the previous token's allowed
                # successors (modeling_ilql.py generate: logits[~mask[last]] = -inf)
                allowed = logit_mask[prev_tok]  # [B, V] bool
                shaped = jnp.where(allowed, shaped, -1e10)
            return shaped

        return processor

    # ------------------------------------------------------------- experience

    def make_experience(self, samples, rewards, max_length: int = 2048):
        if getattr(self, "is_seq2seq", False):
            self.store = make_experience_seq2seq(samples, rewards, self.tokenizer, max_length)
        else:
            self.store = make_experience(samples, rewards, self.tokenizer, max_length)

    # ------------------------------------------------------------- train loop

    def prepare_learning(self):
        bs = self.config.train.batch_size
        self.num_mb = max(1, bs // (self.config.train.minibatch_size or bs))

    def create_train_dataloader(self):
        return self.store.create_loader(
            self.config.train.batch_size, shuffle=True, seed=self.config.train.seed
        )

    def _get_train_step(self, B: int, T: int, A: int):
        key = (B, T, A)
        if key in self._train_steps:
            return self._train_steps[key]
        module, method = self.module, self.method

        def loss_fn(params, mb: ILQLBatch):
            logits, qs, target_qs, vs, _ = module.apply(
                {"params": params}, mb.input_ids, mb.attention_mask, None,
                mb.actions_ixs, mb.states_ixs,
            )
            action_logits = batched_index_select(logits, mb.actions_ixs)
            loss, stats = method.loss((action_logits, (qs, target_qs, vs)), mb)
            return loss, flatten_dict(stats)

        self._train_steps[key] = self.make_grad_accum_step(loss_fn, self.num_mb, name="ilql_train_step")
        return self._train_steps[key]

    def _get_train_step_s2s(self, B: int, T: int, D: int):
        key = ("s2s", B, T, D)
        if key in self._train_steps:
            return self._train_steps[key]
        module, method = self.module, self.method

        def loss_fn(params, mb):
            logits, qs, target_qs, vs = module.apply(
                {"params": params}, mb.input_ids, mb.attention_mask,
                mb.decoder_input_ids, None, mb.actions_ixs, mb.states_ixs,
            )
            action_logits = batched_index_select(logits, mb.actions_ixs)
            loss, stats = method.loss((action_logits, (qs, target_qs, vs)), mb)
            return loss, flatten_dict(stats)

        self._train_steps[key] = self.make_grad_accum_step(loss_fn, self.num_mb, name="ilql_train_step")
        return self._train_steps[key]

    def train_step(self, batch: ILQLBatch) -> Dict[str, float]:
        if getattr(self, "is_seq2seq", False):
            return self._train_step_s2s(batch)
        B, T = batch.input_ids.shape
        A = batch.actions_ixs.shape[1]
        Tb, Ab = pad_to_bucket(T, BUCKETS), pad_to_bucket(A, BUCKETS)
        pad2 = lambda x, n, v=0: np.pad(np.asarray(x), ((0, 0), (0, n - x.shape[1])), constant_values=v)
        padded = ILQLBatch(
            input_ids=pad2(batch.input_ids, Tb, self.tokenizer.pad_token_id),
            attention_mask=pad2(batch.attention_mask, Tb),
            rewards=pad2(batch.rewards, Ab, 0.0),
            states_ixs=pad2(batch.states_ixs, Ab + 1),
            actions_ixs=pad2(batch.actions_ixs, Ab),
            dones=pad2(batch.dones, Ab + 1),
        )
        dbatch = mesh_lib.put_batch(self.mesh, padded)
        step = self._get_train_step(B, Tb, Ab)
        with self.mesh:
            self.params, self.opt_state, stats = step(self.params, self.opt_state, dbatch)
        return {k: float(v) for k, v in jax.device_get(stats).items()}

    def _train_step_s2s(self, batch) -> Dict[str, float]:
        from trlx_tpu.data.ilql_types import ILQLSeq2SeqBatch

        B, T = batch.input_ids.shape
        D = batch.decoder_input_ids.shape[1]
        A = batch.actions_ixs.shape[1]
        Tb = pad_to_bucket(T, BUCKETS)
        # the loss takes actions = decoder_input_ids[:, 1:], so D must equal A+1
        Ab = pad_to_bucket(max(A, D - 1), BUCKETS)
        Db = Ab + 1
        pad2 = lambda x, n, v=0: np.pad(np.asarray(x), ((0, 0), (0, n - x.shape[1])), constant_values=v)
        padded = ILQLSeq2SeqBatch(
            input_ids=pad2(batch.input_ids, Tb, self.tokenizer.pad_token_id),
            attention_mask=pad2(batch.attention_mask, Tb),
            decoder_input_ids=pad2(batch.decoder_input_ids, Db, self.tokenizer.pad_token_id),
            rewards=pad2(batch.rewards, Ab, 0.0),
            states_ixs=pad2(batch.states_ixs, Ab + 1),
            actions_ixs=pad2(batch.actions_ixs, Ab),
            dones=pad2(batch.dones, Ab + 1),
        )
        dbatch = mesh_lib.put_batch(self.mesh, padded)
        step = self._get_train_step_s2s(B, Tb, Db)
        with self.mesh:
            self.params, self.opt_state, stats = step(self.params, self.opt_state, dbatch)
        return {k: float(v) for k, v in jax.device_get(stats).items()}

    def post_backward_callback(self):
        """Polyak-sync target Q heads every ``steps_for_target_q_sync`` steps
        (parity: accelerate_ilql_trainer.py:138-140)."""
        if self.iter_count % self.method.steps_for_target_q_sync == 0:
            if self._sync_fn is None:
                alpha = self.method.alpha

                def sync(params):
                    new = dict(params)
                    new["ilql_heads"] = _sync_heads(dict(params["ilql_heads"]), alpha)
                    return new

                self._sync_fn = jax.jit(sync, donate_argnums=0)
            with self.mesh:
                self.params = self._sync_fn(self.params)


def make_experience_seq2seq(samples, rewards, tokenizer=None, max_length: int = 2048, verbose: bool = True):
    """Seq2seq ILQL experience (parity: accelerate_ilql_trainer.py:178-243):
    encoder input = prompt tokens, decoder = output tokens; actions over the decoder
    sequence; standardized returns on the last action."""
    from trlx_tpu.pipeline.offline_pipeline import ILQLSeq2SeqRolloutStorage

    if verbose:
        logger.info("Collecting rollouts (seq2seq)")
    if tokenizer is not None:
        samples = [tokenize_dialogue(s, tokenizer, max_length) for s in samples]

    all_input_ids, all_output_ids, all_actions_ixs, all_states_ixs, all_dones = [], [], [], [], []
    for sample in samples:
        prompt_msgs = [m for m in sample if not m.is_output]
        output_msgs = [m for m in sample if m.is_output]
        all_input_ids.append(
            np.asarray([t for m in prompt_msgs for t in m.tokens], np.int32)
        )
        out = np.asarray([t for m in output_msgs for t in m.tokens], np.int32)
        all_output_ids.append(out)
        length = len(out)
        actions_ixs = np.arange(0, max(length - 1, 1))
        states_ixs = np.concatenate([actions_ixs, [max(length - 1, 1)]])
        all_dones.append(np.asarray([1] * (len(states_ixs) - 1) + [0], np.int32))
        all_actions_ixs.append(actions_ixs.astype(np.int32))
        all_states_ixs.append(states_ixs.astype(np.int32))

    returns = np.asarray(rewards, np.float64)
    returns = returns - returns.mean()
    std = returns.std()
    if not np.isnan(std) and std > 0:
        returns = returns / (std + np.finfo(returns.dtype).eps)
    rewards_per_token = [np.zeros(len(x), np.float32) for x in all_actions_ixs]
    for rs, ret in zip(rewards_per_token, returns):
        rs[-1] = ret

    attention_mask = [np.ones(len(x), np.int32) for x in all_input_ids]
    return ILQLSeq2SeqRolloutStorage(
        all_input_ids, attention_mask, all_output_ids, rewards_per_token,
        all_states_ixs, all_actions_ixs, all_dones,
    )
