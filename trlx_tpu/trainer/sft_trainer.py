"""SFT trainer (parity: `/root/reference/trlx/trainer/accelerate_sft_trainer.py:29-97`):
supervised fine-tuning on strings or (prompt, output) dialogues with prompt-masked CE.
"""

from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.methods.sft import SFTConfig
from trlx_tpu.models.hf_loading import load_pretrained
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.ops.generation import LENGTH_BUCKETS, pad_to_bucket
from trlx_tpu.parallel import mesh as mesh_lib
from trlx_tpu.parallel.sharding import make_param_shardings
from trlx_tpu.pipeline.offline_pipeline import DialogStore, tokenize_dialogue
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.mesh_trainer import MeshRLTrainer
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def _resolve_pad_id(tokenizer):
    """pad_token_id with an eos fallback (causal-style tokenizers reused for T5
    experiments often carry pad_token_id=None); None only if both are unset."""
    pad = tokenizer.pad_token_id
    if pad is None:
        pad = getattr(tokenizer, "eos_token_id", None)
    return pad


class Seq2SeqSFTStore:
    """(encoder prompt ids, decoder target ids) pairs; right-padded at collate.
    The reference has no seq2seq SFT at all — its SFT trainer is causal-only —
    but the T5 PPO recipe needs a supervised warm-start stage, so this closes
    the gap the same way DialogStore does for causal dialogues."""

    IGNORE_INDEX = DialogStore.IGNORE_INDEX

    def __init__(self, pairs, tokenizer):
        self.pairs = pairs  # list of (enc_ids, dec_ids) int arrays
        self.tokenizer = tokenizer
        # resolve the pad id up front: causal-style tokenizers reused for T5
        # experiments often have pad_token_id=None, which would otherwise
        # surface as an opaque np.full TypeError at collate time
        self.pad_id = _resolve_pad_id(tokenizer)
        if self.pad_id is None:
            raise ValueError(
                "Seq2SeqSFTStore requires a tokenizer with pad_token_id (or "
                "eos_token_id as a fallback); both are None on "
                f"{type(tokenizer).__name__}"
            )

    def __len__(self):
        return len(self.pairs)

    def __getitem__(self, ix):
        return self.pairs[ix]

    def create_loader(self, batch_size: int, shuffle: bool = True, drop_last: bool = True,
                      seed: int = 0):
        from trlx_tpu.pipeline.offline_pipeline import NumpyLoader

        pad = self.pad_id

        def collate(items):
            enc_w = max(len(e) for e, _ in items)
            dec_w = max(len(d) for _, d in items)
            B = len(items)
            out = {
                "input_ids": np.full((B, enc_w), pad, np.int32),
                "attention_mask": np.zeros((B, enc_w), np.int32),
                "labels": np.full((B, dec_w), self.IGNORE_INDEX, np.int32),
            }
            for i, (e, d) in enumerate(items):
                out["input_ids"][i, : len(e)] = e
                out["attention_mask"][i, : len(e)] = 1
                out["labels"][i, : len(d)] = d
            return out

        return NumpyLoader(self, batch_size, collate, shuffle=shuffle,
                           drop_last=drop_last, seed=seed)


@register_trainer
class SFTTrainer(MeshRLTrainer):
    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        self.method: SFTConfig = config.method
        self._train_steps = {}

    def setup_model(self):
        self.is_seq2seq = self.config.model.model_arch_type == "seq2seq"
        if self.is_seq2seq:
            return self._setup_seq2seq_model()
        overrides = dict(self.config.model.model_overrides or {})
        overrides.setdefault("param_dtype", self.param_dtype)
        overrides.setdefault("compute_dtype", self.compute_dtype)
        overrides.setdefault("remat", self.config.mesh.remat)
        overrides.setdefault("sequence_sharding", self.config.mesh.sequence_shard)
        from trlx_tpu.models.hf_loading import init_params, merge_loaded_params, peft_overrides

        overrides.update(peft_overrides(self.config.model.peft_config))
        overrides.update(self.pipeline_overrides())
        self.model_config, trunk_params, self.model_type = load_pretrained(
            self.config.model.model_path, overrides, mesh=self.restore_mesh(overrides)
        )
        trunk_params = self.maybe_stack_loaded(trunk_params, self.model_config.num_layers)
        self.trunk_module = TransformerLM(self.model_config)
        init_tree = init_params(self.model_config, self.trunk_module, self.config.train.seed)
        if trunk_params is not None:
            init_tree = merge_loaded_params(init_tree, trunk_params)
        params = {"transformer": init_tree}
        shardings = make_param_shardings(params, self.mesh)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x, self.param_dtype), s), params, shardings
        )

    def _setup_seq2seq_model(self):
        from trlx_tpu.models.hf_loading import (
            load_pretrained_seq2seq,
            merge_loaded_params,
            t5_peft_overrides,
        )
        from trlx_tpu.models.t5 import T5LM

        self.pipeline_overrides()  # validates mesh.pipe (raises: PP is causal-only)
        overrides = dict(self.config.model.model_overrides or {})
        overrides.setdefault("param_dtype", self.param_dtype)
        overrides.setdefault("compute_dtype", self.compute_dtype)
        overrides.update(t5_peft_overrides(self.config.model.peft_config))
        self.model_config, t5_params = load_pretrained_seq2seq(
            self.config.model.model_path, overrides, mesh=self.mesh
        )
        self.model_type = "t5"
        self.decoder_start_token_id = self.model_config.decoder_start_token_id
        self.module = T5LM(self.model_config)
        params_t5 = self.module.init(
            jax.random.PRNGKey(self.config.train.seed),
            jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32),
            jnp.zeros((1, 2), jnp.int32),
        )["params"]
        if t5_params is not None:
            params_t5 = merge_loaded_params(params_t5, t5_params)
        params = {"t5": params_t5}
        shardings = make_param_shardings(params, self.mesh)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x, self.param_dtype), s), params, shardings
        )

    def seq2seq_gen_fns(self):
        module = self.module

        return {
            "encode": lambda params, ids, mask: module.apply(
                {"params": params["t5"]}, ids, mask, method=module.encode
            ),
            "cross_kv": lambda params, enc: module.apply(
                {"params": params["t5"]}, enc, method=module.precompute_cross_kv
            ),
            "decode": lambda params, tok, enc, enc_mask, dec_mask, pos, cache, ckv: module.apply(
                {"params": params["t5"]}, tok, enc, enc_mask, dec_mask, pos, cache, ckv,
                method=module.decode,
            ),
            "init_cache": lambda params, b, n: self.module.init_cache(b, n),
        }

    def gen_step_fn(self):
        trunk = self.trunk_module

        def step(params, ids, mask, positions, cache):
            logits, hidden, _, cache = trunk.apply(
                {"params": params["transformer"]}, ids, mask, positions, cache
            )
            return logits, hidden, cache

        return step, lambda b, s: trunk.init_cache(b, s)

    def make_experience(self, samples: List, seq_length: int):
        """Tokenize dialogues into the DialogStore (parity: sft_trainer :60-70);
        seq2seq: (prompt segments..., final output) -> encoder/decoder pair."""
        dialogs = [tokenize_dialogue(s, self.tokenizer, seq_length) for s in samples]
        if self.is_seq2seq:
            pairs = []
            for msgs in dialogs:
                enc = [t for m in msgs if not m.is_output for t in m.tokens]
                dec = [t for m in msgs if m.is_output for t in m.tokens]
                if not enc or not dec:
                    continue  # degenerate after truncation
                pairs.append((np.asarray(enc, np.int32), np.asarray(dec, np.int32)))
            self.store = Seq2SeqSFTStore(pairs, self.tokenizer)
            return
        self.store = DialogStore(dialogs, self.tokenizer)

    def create_train_dataloader(self):
        return self.store.create_loader(
            self.config.train.batch_size, shuffle=True, seed=self.config.train.seed
        )

    def prepare_learning(self):
        bs = self.config.train.batch_size
        self.num_mb = max(1, bs // (self.config.train.minibatch_size or bs))

    def _get_s2s_train_step(self, B: int, Te: int, Td: int):
        key = ("s2s", B, Te, Td)
        if key in self._train_steps:
            return self._train_steps[key]
        module = self.module
        start_id = self.decoder_start_token_id
        ignore = Seq2SeqSFTStore.IGNORE_INDEX

        def loss_fn(params, mb):
            labels = mb["labels"]
            valid = (labels != ignore).astype(jnp.int32)
            safe = jnp.where(valid.astype(bool), labels, 0)
            # teacher forcing: decoder reads [start, y_0..y_{T-2}], predicts y_t
            dec_in = jnp.concatenate(
                [jnp.full((labels.shape[0], 1), start_id, jnp.int32), safe[:, :-1]], axis=1
            )
            dec_mask = jnp.concatenate(
                [jnp.ones((labels.shape[0], 1), jnp.int32), valid[:, :-1]], axis=1
            )
            logits, _, _ = module.apply(
                {"params": params["t5"]}, mb["input_ids"], mb["attention_mask"],
                dec_in, dec_mask,
            )
            logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logprobs, safe[..., None], axis=-1)[..., 0]
            mask = valid.astype(jnp.float32)
            loss = jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
            from trlx_tpu.utils.modeling import flatten_dict

            return loss, flatten_dict(dict(losses=dict(loss=loss)))

        self._train_steps[key] = self.make_grad_accum_step(loss_fn, self.num_mb, name="sft_train_step")
        return self._train_steps[key]

    def _get_train_step(self, B: int, T: int):
        key = (B, T)
        if key in self._train_steps:
            return self._train_steps[key]
        trunk, method = self.trunk_module, self.method

        def loss_fn(params, mb):
            logits, _, _, _ = trunk.apply(
                {"params": params["transformer"]}, mb["input_ids"], mb["attention_mask"]
            )
            loss_mask = (mb["labels"] != DialogStore.IGNORE_INDEX).astype(jnp.float32)
            labels = jnp.where(mb["labels"] == DialogStore.IGNORE_INDEX, 0, mb["labels"])
            loss, stats = method.loss(logits, labels, loss_mask * mb["attention_mask"])
            from trlx_tpu.utils.modeling import flatten_dict

            return loss, flatten_dict(stats)

        self._train_steps[key] = self.make_grad_accum_step(loss_fn, self.num_mb, name="sft_train_step")
        return self._train_steps[key]

    def train_step(self, batch) -> Dict[str, float]:
        if self.is_seq2seq:
            return self._train_step_s2s(batch)
        B, T = batch["input_ids"].shape
        Tb = pad_to_bucket(T, LENGTH_BUCKETS)
        # pad rows to a num_mb multiple (fully-masked rows contribute zero loss)
        Bp = ((B + self.num_mb - 1) // self.num_mb) * self.num_mb
        pad = ((0, Bp - B), (0, Tb - T))
        padded = {
            "input_ids": np.pad(batch["input_ids"], pad, constant_values=self.tokenizer.pad_token_id),
            "attention_mask": np.pad(batch["attention_mask"], pad),
            "labels": np.pad(batch["labels"], pad, constant_values=DialogStore.IGNORE_INDEX),
        }
        B = Bp
        dbatch = mesh_lib.put_batch(self.mesh, padded)
        step = self._get_train_step(B, Tb)
        with self.mesh:
            self.params, self.opt_state, stats = step(self.params, self.opt_state, dbatch)
        return {k: float(v) for k, v in jax.device_get(stats).items()}

    def _train_step_s2s(self, batch) -> Dict[str, float]:
        B, Te = batch["input_ids"].shape
        Td = batch["labels"].shape[1]
        Teb, Tdb = pad_to_bucket(Te, LENGTH_BUCKETS), pad_to_bucket(Td, LENGTH_BUCKETS)
        Bp = ((B + self.num_mb - 1) // self.num_mb) * self.num_mb
        padded = {
            "input_ids": np.pad(
                batch["input_ids"], ((0, Bp - B), (0, Teb - Te)),
                constant_values=_resolve_pad_id(self.tokenizer),
            ),
            "attention_mask": np.pad(batch["attention_mask"], ((0, Bp - B), (0, Teb - Te))),
            "labels": np.pad(
                batch["labels"], ((0, Bp - B), (0, Tdb - Td)),
                constant_values=Seq2SeqSFTStore.IGNORE_INDEX,
            ),
        }
        dbatch = mesh_lib.put_batch(self.mesh, padded)
        step = self._get_s2s_train_step(Bp, Teb, Tdb)
        with self.mesh:
            self.params, self.opt_state, stats = step(self.params, self.opt_state, dbatch)
        return {k: float(v) for k, v in jax.device_get(stats).items()}
