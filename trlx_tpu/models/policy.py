"""Policy/value/ref model composition for PPO and ILQL.

Parity targets (all in `/root/reference/trlx/models/`):
- ``AutoModelForCausalLMWithValueHead`` (modeling_ppo.py:266-382): trunk + value head.
- ``AutoModelForCausalLMWithHydraValueHead`` (modeling_ppo.py:385-453): adds a frozen
  top-branch reference model run from the branch-point hidden state. In JAX this needs
  NO per-architecture branch classes: the frozen branch is the same ``TransformerLM``
  module applied with a *separate frozen param subtree* via ``method="forward_from"``.
- ``AutoModelForCausalLMWithILQLHeads`` (modeling_ilql.py:262-442): trunk + ILQL heads
  evaluated at gathered state/action positions.
"""

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import unfreeze

from trlx_tpu.methods.ilql import batched_index_select
from trlx_tpu.models.heads import ILQLHeads, ValueHead
from trlx_tpu.models.transformer import KVCache, TransformerConfig, TransformerLM


class CausalLMWithValueHead(nn.Module):
    """Trunk LM + scalar value head. ``branch_layer`` (when set in a call) returns the
    activation entering that layer, for the hydra reference branch. A call with
    ``with_head=False`` returns the post-norm hidden states where the logits
    would stand and takes no vocabulary head: the caller applies :meth:`head`
    to the rows it reads (``utils.modeling.response_logprobs``).

    ``num_value_layers`` > 0 gives the value function its own trainable *branch* of
    top layers fed from the trunk activation ``num_value_layers`` from the top
    (parity: ``make_value_branch``, modeling_ppo.py:255-263)."""

    config: TransformerConfig
    num_value_layers: int = 0

    def setup(self):
        from trlx_tpu.models.transformer import _LOOP_REFUSALS, Block, _norm_module

        if self.num_value_layers > 0 and self.config.loop_steps > 1:
            raise ValueError(_LOOP_REFUSALS["branch"])
        if self.num_value_layers > self.config.num_layers:
            raise ValueError(
                f"num_value_layers_unfrozen={self.num_value_layers} exceeds "
                f"num_layers={self.config.num_layers}"
            )
        self.transformer = TransformerLM(self.config)
        self.v_head = ValueHead(self.config)
        if self.num_value_layers > 0:
            start = self.config.num_layers - self.num_value_layers  # copies of the trunk's top layers
            self.value_blocks = [
                Block(self.config, expert_layer=self.config.is_expert_layer(start + i))
                for i in range(self.num_value_layers)
            ]
            self.value_ln = _norm_module(self.config)

    def _value_branch(self, hidden, attention_mask, positions):
        from trlx_tpu.models.transformer import make_attn_bias

        B, T, _ = hidden.shape
        default_positions, mask_bias = make_attn_bias(self.config, attention_mask, B, T)
        if positions is None:
            positions = default_positions
        x = hidden
        for blk in self.value_blocks:
            x, _ = blk(x, mask_bias, positions, None, attention_mask)
        return self.v_head(self.value_ln(x))

    def __call__(
        self,
        input_ids: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
        positions: Optional[jnp.ndarray] = None,
        cache: Optional[KVCache] = None,
        branch_layer: Optional[int] = None,
        with_head: bool = True,
    ):
        if self.num_value_layers > 0:
            if cache is not None:
                # the trained value fn is value_ln(value_blocks(...)); v_head on the
                # trunk hidden would silently return meaningless numbers
                raise NotImplementedError(
                    "value-branch models do not support cached decode value reads; "
                    "use lm_only for generation"
                )
            value_start = self.config.num_layers - self.num_value_layers
            capture = sorted({value_start, *(() if branch_layer is None else (branch_layer,))})
            logits, hidden, captures, new_cache = self.transformer(
                input_ids, attention_mask, positions, cache, tuple(capture), with_head
            )
            values = self._value_branch(captures[value_start], attention_mask, positions)
            branch_hidden = None if branch_layer is None else captures[branch_layer]
            return (logits if with_head else hidden), values, branch_hidden, new_cache
        logits, hidden, branch_hidden, new_cache = self.transformer(
            input_ids, attention_mask, positions, cache, branch_layer, with_head
        )
        values = self.v_head(hidden)
        return (logits if with_head else hidden), values, branch_hidden, new_cache

    def head(self, rows: jnp.ndarray) -> jnp.ndarray:
        """The trunk's vocabulary head over post-norm rows (``TransformerLM.head``)."""
        return self.transformer.head(rows)

    def lm_only(
        self,
        input_ids: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
        positions: Optional[jnp.ndarray] = None,
        cache: Optional[KVCache] = None,
    ):
        """Forward without the value head (generation decode steps)."""
        logits, _, _, new_cache = self.transformer(input_ids, attention_mask, positions, cache)
        return logits, new_cache

    def forward_branch(
        self,
        hidden: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray],
        positions: Optional[jnp.ndarray],
        start_layer: int,
        with_head: bool = True,
    ):
        """Frozen-branch forward (hydra): run layers[start_layer:] + head from a
        cached activation (``with_head=False``: the post-norm hidden states
        instead of the logits). Call with the frozen param subtree."""
        return self.transformer.forward_from(hidden, attention_mask, positions, start_layer, with_head)

    def init_cache(self, batch_size: int, max_length: int) -> KVCache:
        return self.transformer_init_cache(batch_size, max_length)

    def transformer_init_cache(self, batch_size: int, max_length: int) -> KVCache:
        # plain helper (not a module method) — cache needs no params
        return TransformerLM(self.config).init_cache(batch_size, max_length)


def head_of(module, params: Dict[str, Any]):
    """The vocabulary head of ``module`` (a ``TransformerLM`` or a
    ``CausalLMWithValueHead``) with ``params``, as ``response_logprobs`` takes
    it: rows [..., d] -> logits [..., V]. Of ``params`` it reads the tied
    embedding or ``lm_head`` alone, so a hydra branch's subtree will do."""
    return lambda rows: module.apply({"params": params}, rows, method=module.head)


class CausalLMWithILQLHeads(nn.Module):
    """Trunk LM + ILQL {V, Q, target-Q} heads (parity: modeling_ilql.py:262-442)."""

    config: TransformerConfig
    two_qs: bool = True

    def setup(self):
        self.transformer = TransformerLM(self.config)
        self.ilql_heads = ILQLHeads(self.config, two_qs=self.two_qs)

    def __call__(
        self,
        input_ids: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
        positions: Optional[jnp.ndarray] = None,
        actions_ixs: Optional[jnp.ndarray] = None,
        states_ixs: Optional[jnp.ndarray] = None,
        cache: Optional[KVCache] = None,
    ):
        logits, hidden, _, new_cache = self.transformer(
            input_ids, attention_mask, positions, cache
        )
        if states_ixs is not None:
            states_hs = batched_index_select(hidden, states_ixs)
            actions_hs = batched_index_select(hidden, actions_ixs)
        else:
            states_hs = actions_hs = hidden
        qs, target_qs, vs = self.ilql_heads(states_hs, actions_hs)
        return logits, qs, target_qs, vs, new_cache

    def heads_only(self, hidden: jnp.ndarray):
        """Apply the ILQL heads to trunk hidden states [B, T, H] (used by the
        advantage-shaped decode, parity: modeling_ilql.py:325-412)."""
        return self.ilql_heads(hidden, hidden)


def init_value_branch_from_trunk(
    params: Dict[str, Any], config: TransformerConfig, num_value_layers: int
) -> Dict[str, Any]:
    """Copy the (pretrained) top-N trunk layers + final norm into the value-branch
    params (parity with the reference's ModelBranch deepcopy of pretrained blocks,
    modeling_ppo.py:523-533) so the value function starts from trunk features, not
    random init. Leaves are host copies to avoid any buffer aliasing with the
    (donated) trunk params."""
    import numpy as np

    copy_leaf = lambda x: np.array(jax.device_get(x))
    p = dict(params)
    start = config.num_layers - num_value_layers
    for i in range(num_value_layers):
        p[f"value_blocks_{i}"] = jax.tree.map(copy_leaf, params["transformer"][f"layers_{start + i}"])
    if config.final_norm and "ln_f" in params["transformer"]:
        p["value_ln"] = jax.tree.map(copy_leaf, params["transformer"]["ln_f"])
    return p


def branch_param_subtree(trunk_params: Dict[str, Any], start_layer: int, config: TransformerConfig) -> Dict[str, Any]:
    """Extract the frozen reference-branch params: top layers + final norm + output
    head (+ tied embedding). This is the JAX analogue of the reference's
    ``deepcopy`` of unfrozen blocks into ``frozen_head`` (modeling_ppo.py:385-410)."""
    if config.loop_steps > 1:
        from trlx_tpu.models.transformer import _LOOP_REFUSALS

        raise ValueError(_LOOP_REFUSALS["branch"])
    t = unfreeze(trunk_params) if hasattr(trunk_params, "unfreeze") else dict(trunk_params)
    sub: Dict[str, Any] = {}
    for i in range(start_layer, config.num_layers):
        key = f"layers_{i}"
        if key in t:
            sub[key] = jax.tree.map(lambda x: x, t[key])
    if config.final_norm and "ln_f" in t:
        sub["ln_f"] = jax.tree.map(lambda x: x, t["ln_f"])
    if config.tie_word_embeddings:
        sub["embed_tokens"] = jax.tree.map(lambda x: x, t["embed_tokens"])
    elif "lm_head" in t:
        sub["lm_head"] = jax.tree.map(lambda x: x, t["lm_head"])
    return sub


def apply_hydra_branch(
    module: CausalLMWithValueHead,
    branch_params: Dict[str, Any],
    branch_hidden: jnp.ndarray,
    attention_mask: Optional[jnp.ndarray],
    start_layer: int,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Reference logits from the frozen branch (parity: ``forward_hydra``)."""
    return module.apply(
        {"params": {"transformer": branch_params}},
        branch_hidden,
        attention_mask,
        positions,
        start_layer,
        method=module.forward_branch,
    )


def t5_branch_param_subtree(t5_params: Dict[str, Any], start_layer: int, config) -> Dict[str, Any]:
    """Frozen decoder-top branch params: decoder blocks [start_layer:], the final
    decoder LN, and the output head (tied embedding or lm_head). The analogue of
    :func:`branch_param_subtree` for the seq2seq hydra reference (reference
    ``T5Branch``, modeling_ppo.py:1483-1593) — ~num_layers_unfrozen decoder
    blocks of extra memory instead of a full frozen T5 copy."""
    t = dict(t5_params)
    sub: Dict[str, Any] = {}
    for i in range(start_layer, config.num_decoder_layers):
        key = f"decoder_blocks_{i}"
        if key in t:
            sub[key] = jax.tree.map(lambda x: x, t[key])
    sub["decoder_ln"] = jax.tree.map(lambda x: x, t["decoder_ln"])
    if config.tie_word_embeddings:
        sub["shared"] = jax.tree.map(lambda x: x, t["shared"])
    elif "lm_head" in t:
        sub["lm_head"] = jax.tree.map(lambda x: x, t["lm_head"])
    return sub


class Seq2SeqLMWithValueHead(nn.Module):
    """T5-style seq2seq LM + scalar value head over decoder hidden states
    (parity: ``AutoModelForSeq2SeqLMWithValueHead``, modeling_ppo.py:1242-1350)."""

    config: "object"  # trlx_tpu.models.t5.T5Config

    def setup(self):
        from trlx_tpu.models.t5 import T5LM
        from trlx_tpu.models.heads import MLPHead

        self.t5 = T5LM(self.config)
        self.v_head_mlp = MLPHead(_t5_head_cfg(self.config), out_dim=1)

    def __call__(self, input_ids, attention_mask, decoder_input_ids, decoder_attention_mask=None):
        logits, hidden, enc = self.t5(input_ids, attention_mask, decoder_input_ids, decoder_attention_mask)
        values = self.v_head_mlp(hidden)[..., 0]
        return logits, values, enc

    def forward_with_branch(
        self, input_ids, attention_mask, decoder_input_ids, decoder_attention_mask, branch_layer
    ):
        """(logits, values, enc, branch_hidden, position_bias) — the scoring
        forward used with the decoder-top hydra reference branch."""
        logits, hidden, enc, branch_hidden, position_bias = self.t5.forward_with_branch(
            input_ids, attention_mask, decoder_input_ids, decoder_attention_mask, branch_layer
        )
        values = self.v_head_mlp(hidden)[..., 0]
        return logits, values, enc, branch_hidden, position_bias

    def encode(self, input_ids, attention_mask):
        return self.t5.encode(input_ids, attention_mask)

    def precompute_cross_kv(self, enc_states):
        return self.t5.precompute_cross_kv(enc_states)

    def decode_step(self, decoder_input_ids, enc_states, encoder_attention_mask,
                    decoder_attention_mask, positions, cache, cross_kvs):
        logits, hidden, new_cache = self.t5.decode(
            decoder_input_ids, enc_states, encoder_attention_mask,
            decoder_attention_mask, positions, cache, cross_kvs,
        )
        return logits, hidden, new_cache


def _t5_head_cfg(t5_config):
    """Adapter so MLPHead (which reads hidden_size etc.) works on T5Config."""
    from trlx_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=t5_config.vocab_size, hidden_size=t5_config.d_model,
        param_dtype=t5_config.param_dtype, compute_dtype=t5_config.compute_dtype,
    )


class Seq2SeqLMWithILQLHeads(nn.Module):
    """T5 + ILQL {V, Q, target-Q} heads over decoder hidden states
    (parity: ``AutoModelForSeq2SeqLMWithILQLHeads``, modeling_ilql.py:481-666)."""

    config: "object"  # trlx_tpu.models.t5.T5Config
    two_qs: bool = True

    def setup(self):
        from trlx_tpu.models.t5 import T5LM

        self.t5 = T5LM(self.config)
        self.ilql_heads = ILQLHeads(_t5_head_cfg(self.config), two_qs=self.two_qs)

    def __call__(
        self,
        input_ids,
        attention_mask,
        decoder_input_ids,
        decoder_attention_mask=None,
        actions_ixs=None,
        states_ixs=None,
    ):
        logits, hidden, _ = self.t5(
            input_ids, attention_mask, decoder_input_ids, decoder_attention_mask
        )
        if states_ixs is not None:
            states_hs = batched_index_select(hidden, states_ixs)
            actions_hs = batched_index_select(hidden, actions_ixs)
        else:
            states_hs = actions_hs = hidden
        qs, target_qs, vs = self.ilql_heads(states_hs, actions_hs)
        return logits, qs, target_qs, vs

    def heads_only(self, hidden):
        return self.ilql_heads(hidden, hidden)

    def encode(self, input_ids, attention_mask):
        return self.t5.encode(input_ids, attention_mask)

    def precompute_cross_kv(self, enc_states):
        return self.t5.precompute_cross_kv(enc_states)

    def decode_step(self, decoder_input_ids, enc_states, encoder_attention_mask,
                    decoder_attention_mask, positions, cache, cross_kvs):
        return self.t5.decode(
            decoder_input_ids, enc_states, encoder_attention_mask,
            decoder_attention_mask, positions, cache, cross_kvs,
        )
