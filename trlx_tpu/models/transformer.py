"""Generic causal decoder LM in Flax covering the reference's model families.

The reference wraps HF torch models and re-implements a *frozen branch forward* per
architecture (`/root/reference/trlx/models/modeling_ppo.py:502-1637`: GPT/OPT/Bloom/
Llama/GPTBigCode branches). Here a single configurable module covers gpt2, gpt-neox/
pythia, gpt-j, opt, and llama: positional scheme (learned/rotary, neox- or gptj-style),
norm type (LN/RMS), activation (gelu/gelu_new/relu/silu), GLU mlp, parallel residual,
biases, GQA, and tied embeddings are all config switches. The same block stack is
reusable as the hydra frozen branch by calling ``forward_from`` on the top-N layers
with a separate (frozen) param subtree — no per-architecture branch code.

TPU-first details: all matmuls run in ``compute_dtype`` (bf16) against fp32 master
params; attention uses an additive mask built from fixed shapes (no dynamic shapes);
the KV cache is an explicit functional pytree updated with ``dynamic_update_slice`` so
generation jits to a single XLA while-loop; activations can be sequence-sharded via
``with_sharding_constraint`` hooks (Megatron-SP analogue).
"""

import contextlib
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from flax import linen as nn

from trlx_tpu.ops import kv_cache
from trlx_tpu.ops.attention import attend, decode_cache_fold, flash_placement
from trlx_tpu.parallel.mesh import BATCH_AXES, MODEL_AXIS, PIPE_AXIS
from trlx_tpu.parallel.sharding import (
    ambient_mesh,
    constrain_gathered,
    constrain_seq,
)

# a layer's buffers (ops/kv_cache.py) under their keys, each a list of L arrays (per-layer carries ->
# in-place decode writes) or one stacked [L, ...] array when config.stacked, plus "index": i32[]; with
# layer_kinds, keys and values over the attention layers alone and "conv" over the convolution layers
KVCache = Dict[str, Any]


def _concrete_zero(x) -> bool:
    """True iff ``x`` is a concrete (non-traced) scalar equal to 0."""
    try:
        return int(x) == 0
    except Exception:  # jax TracerError and friends
        return False


@dataclass(frozen=True)
class TransformerConfig:
    """Architecture hyperparameters; presets for each family in
    :mod:`trlx_tpu.models.presets`."""

    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA; None = num_heads
    head_dim: Optional[int] = None  # None = hidden_size // num_heads
    intermediate_size: Optional[int] = None  # None = 4*hidden
    max_position_embeddings: int = 1024

    pos_embedding: str = "learned"  # "learned" | "rotary" | "alibi" | "none"
    rope_style: str = "neox"  # "neox" (rotate-half) | "gptj" (interleaved)
    rotary_pct: float = 1.0
    rope_theta: float = 10000.0
    pos_offset: int = 0  # OPT uses a +2 offset into its learned table

    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-5
    activation: str = "gelu_new"  # "gelu_new" | "gelu" | "relu" | "silu"
    glu: bool = False  # SwiGLU-style gated mlp (llama)
    parallel_residual: bool = False  # gptj / neox style
    shared_parallel_ln: bool = False  # gptj: one LN feeds both attn and mlp
    attn_bias: bool = True
    mlp_bias: bool = True
    embed_ln: bool = False  # LayerNorm on embeddings (bloom word_embeddings_layernorm)
    head_bias: bool = False  # gptj's lm_head carries a bias
    tie_word_embeddings: bool = True
    final_norm: bool = True

    initializer_range: float = 0.02
    # Scale the residual-out projections (o_proj/down_proj) by 1/sqrt(2*L):
    # each residual stream sums 2L projection outputs, so flat-std init grows
    # the stream variance linearly with depth — the depth-48 first-step loss
    # spikes a gpt2-xl-shaped run showed (3.3 -> 7-13 under clip+warmup) while depth-24
    # trained cleanly. HF GPT-2 applies exactly this scaling in _init_weights
    # ("Scale initializations of select weights... by 1/sqrt(2*n_layer)"), and
    # the reference inherits it through from_pretrained/from_config
    # (/root/reference/trlx/models/modeling_base.py:124-161); random-init runs
    # here need it explicitly. Off reproduces the flat 0.02 behavior.
    depth_scaled_init: bool = True
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: str = "none"  # "none" | "full" | "per_layer" | "nothing_saveable" | "dots_saveable"
    attention_impl: str = "xla"  # "xla" | "flash" (Pallas) | "ring" (sequence-parallel)
    # int8 KV cache (per-row symmetric quantization over the head dim): at wide
    # decode batches the KV cache dominates decode HBM traffic, so halving its
    # footprint raises the decode bandwidth roofline ~2x (the reference has no
    # analogue; its CUDA decode reads fp16 KV). Scales stored f32 per (b,h,slot).
    kv_cache_quant: bool = False
    # Paged-KV decode (serving engine): implementation for the block-table
    # gather attention — "auto" | "pallas" | "xla" (see ops/paged_attention.py;
    # auto = fused kernel on a single-device TPU, XLA gather elsewhere).
    paged_attention_impl: str = "auto"
    # Pipeline parallelism (the reference's Apex pipeline engine analogue,
    # modeling_nemo_ppo.py:713-731). > 1 stores block params STACKED ([L, ...]
    # under "layers_scan", sharded over the mesh "pipe" axis) and runs cache-free
    # forwards as a GPipe microbatch schedule over ppermute; cached decode runs a
    # sequential layer scan (layer shards streamed — the NeMo analogue toggles PP
    # scheduling off for inference too, modeling_nemo_ppo.py:838-870).
    pipeline_stages: int = 1
    pipeline_microbatches: int = 4
    # Stacked-layer layout WITHOUT pipelining: params [L, ...] under
    # "layers_scan", forwards run lax.scan over layers. Compile time becomes
    # O(1) in depth (an unrolled 32-layer llama body is traced/compiled 32x;
    # the scanned body once) at the cost of per-layer freeze paths and hydra
    # branches (same restrictions as pipeline_stages > 1).
    scan_layers: bool = False

    # Latent attention ("mla"): queries of num_heads x (qk_nope_head_dim +
    # qk_rope_head_dim); one kv_lora_rank-wide latent and one shared rotary key
    # per token, keys and values (v_head_dim wide) expanded per head from the
    # latent. The cache holds the latent and the rotary key, [B, S, rank] +
    # [B, S, rope] a layer, and decode attends over it in the absorbed form.
    attention_kind: str = "mha"  # "mha" | "mla"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Sparse experts (ops/moe.py): num_experts > 0 gives every layer from
    # first_dense_layers on a router over num_experts (sigmoid scores, the
    # experts_per_token largest of score + selection bias chosen, weights
    # normalised and scaled) beside num_shared_experts always-on experts of the
    # same width. experts_held / expert_offset say which routed experts this
    # process holds (None: all): one chip's share of a layer divided over
    # several; an assignment to an expert held elsewhere adds nothing here.
    num_experts: int = 0
    experts_per_token: int = 0
    experts_held: Optional[int] = None
    expert_offset: int = 0
    num_shared_experts: int = 0
    moe_intermediate_size: int = 0
    first_dense_layers: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # Looped layers: the whole stack is applied loop_steps times over the same
    # parameters, the final norm at the end of every pass and its output fed to
    # the next; the cache holds keys and values of every (pass, layer), entry
    # pass * num_layers + layer, read only by that pass of later tokens.
    # sandwich_norms puts a norm after each sub-layer as well as before it
    # (ln_1_post, ln_2_post). exit_gate holds the leaf of a per-pass exit gate
    # (hidden -> 1, with a bias); at early_exit_threshold >= 1 no token leaves
    # early, every pass runs and the gate is read for the loop/ counters alone.
    loop_steps: int = 1
    sandwich_norms: bool = False
    exit_gate: bool = False
    early_exit_threshold: float = 1.0
    # A mixer chosen layer by layer: layer_kinds names each layer "attention" or
    # "conv" (empty: every layer attends; kinds past num_layers are dropped, so a
    # depth cut keeps the leading layers). A "conv" layer mixes tokens by a gated
    # short convolution (ShortConv): a causal depthwise filter of conv_taps taps
    # along the sequence between two gates. Its cache entry is the row's last
    # conv_taps - 1 gated inputs, [B, conv_taps - 1, hidden] whatever the length,
    # beside the attention layers' keys and values (ops/kv_cache.py).
    layer_kinds: Tuple[str, ...] = ()
    conv_taps: int = 3
    # RMSNorm over each query and key head's dimensions, a scale of its own for
    # q and for k, before rotary (q_norm, k_norm)
    qk_norm: bool = False
    # what the router adds to the sum of the chosen scores before dividing by it
    router_norm_eps: float = 0.0
    # "sigmoid" (a score an expert, chosen by score + selection bias) or "softmax" (Qwen3-MoE's rule:
    # a softmax over all experts, no bias leaf)
    router_scoring: str = "sigmoid"
    # Learned sparse attention (ops/sparse_attention.py, DeepSeek's lightning indexer): index_topk > 0
    # gives every attention layer an indexer of index_heads query heads of index_head_dim over one key
    # head (LayerNorm on the key), rotary on the first index_rope_dim of their dimensions, per-head
    # weights from the hidden state; each query attends over the index_topk earlier keys it scores
    # highest. The cache holds the indexer's key beside keys and values. The indexer is held frozen:
    # its outputs take no gradient and its leaves no update (the trainers' freeze predicate).
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_rope_dim: int = 0

    def __post_init__(self):
        # a list out of a json or yml file; a depth cut by num_layers alone keeps the leading layers' kinds
        kinds = tuple(self.layer_kinds)[: self.num_layers]
        object.__setattr__(self, "layer_kinds", kinds)
        if set(kinds) - {"attention", "conv"} or (kinds and len(kinds) != self.num_layers):
            raise ValueError(
                f"layer_kinds {kinds} does not name each of {self.num_layers} layers 'attention' or 'conv'")

    @property
    def stacked(self) -> bool:
        """Whether block params use the stacked [num_layers, ...] layout."""
        return self.pipeline_stages > 1 or self.scan_layers

    def is_expert_layer(self, index: int) -> bool:
        return self.num_experts > 0 and index >= self.first_dense_layers

    def is_conv_layer(self, index: int) -> bool:
        return bool(self.layer_kinds) and self.layer_kinds[index] == "conv"

    @property
    def conv_layers(self) -> int:
        return self.layer_kinds.count("conv")

    @property
    def attention_layers(self) -> int:
        return self.num_layers - self.conv_layers

    @property
    def sparse_attention(self) -> bool:
        return self.index_topk > 0

    @property
    def held_experts(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def cache_entries(self) -> int:
        """Attention caches a forward writes: one for every (pass, attention layer)."""
        return self.loop_steps * self.attention_layers
    # Megatron-SP analogue: shard the residual stream's sequence dim over the
    # `model` axis between blocks (reference sequence_parallel cfg,
    # modeling_nemo_ppo.py:160-164). Applied on cache-free forwards.
    sequence_sharding: bool = False

    # Native peft equivalents (reference uses the peft library —
    # modeling_base.py:162-240). LoRA: r=0 disables. peft_type "prefix" adds
    # per-layer learned K/V prefixes; "prompt" prepends learned virtual-token
    # embeddings. A module built with peft_type="none"/lora_r=0 simply ignores
    # adapter params present in the tree — that IS the disable_adapter path.
    lora_r: int = 0
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("q_proj", "v_proj")
    peft_type: str = "none"  # "none" | "prefix" | "prompt" (lora via lora_r)
    num_virtual_tokens: int = 0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dim_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def biased_attention(self) -> bool:
        """Whether scores carry a bias of their own (alibi) or keys learned rows in front
        (prefix tuning): what no attention kernel takes (``ops.attention.attend``)."""
        return self.pos_embedding == "alibi" or self.peft_type == "prefix"

    def cache_layout(self, batch_size: int, max_length: int, dtype=None) -> Dict[str, Tuple]:
        """One attention layer of the contiguous cache for ``max_length`` tokens a row
        (``ops/kv_cache.py``); a convolution layer's is :meth:`conv_state_layout`."""
        dtype = dtype or self.compute_dtype
        if self.peft_type == "prompt":
            max_length += self.num_virtual_tokens  # virtual rows live in the cache too
        if self.attention_kind == "mla":
            return kv_cache.latent_cache_layout(batch_size, max_length, self.kv_lora_rank, self.qk_rope_head_dim, dtype)
        if self.sparse_attention:
            return kv_cache.indexed_cache_layout(
                batch_size, max_length, self.kv_heads, self.dim_per_head, self.index_head_dim, dtype)
        shape = (batch_size, self.kv_heads, max_length, self.dim_per_head)
        # kv heads beside the rows where single-token steps take the decode kernel and fewer than 128 rows decode
        fold = 1 if self.kv_cache_quant else decode_cache_fold(
            self.attention_impl, self.biased_attention, batch_size, self.num_heads, self.kv_heads)
        return kv_cache.kv_cache_layout(shape, dtype, self.kv_cache_quant, fold)

    def conv_state_layout(self, batch_size: int, dtype=None) -> Dict[str, Tuple]:
        """One convolution layer of the contiguous cache: the gated inputs the next token's filter reads."""
        return kv_cache.conv_state_layout(batch_size, self.conv_taps, self.hidden_size, dtype or self.compute_dtype)

    def residual_init_std(self) -> float:
        """Init std for projections writing into the residual stream
        (o_proj/down_proj): ``initializer_range / sqrt(2*num_layers)`` under
        ``depth_scaled_init`` (see the field's comment), flat otherwise."""
        if self.depth_scaled_init:
            return self.initializer_range / math.sqrt(2 * self.num_layers)
        return self.initializer_range

    def replace(self, **kw) -> "TransformerConfig":
        return replace(self, **kw)


def remat_policy(name: str, saved: Tuple[str, ...] = ()):
    """Rematerialization policy by config name (shared by the listed-layer stack
    and the pipelined stage scan). ``per_layer`` = save only the block-boundary
    residuals (an ``nn.remat`` with no policy), the scale-appropriate middle
    ground between ``nothing_saveable`` (recompute everything, xl-class) and
    ``dots_saveable`` (keep matmul outputs, small models) — guidance per model
    scale in docs/parallelism.md "Learner overlap & FSDP".

    ``saved``: ``checkpoint_name``s that ``full``, ``per_layer`` and
    ``dots_saveable`` keep besides; ``nothing_saveable`` keeps none.
    ``TransformerLM`` passes the packed selection of a model with an indexer
    (``ops/sparse_attention.py``: ``SELECTION_NAME``, 1 bit a (query, key) pair,
    9.2 MB a layer and row at 8,448 x 8,704), so the backward does not select
    again; for every other model the tuple is empty and the policy as it was."""
    names = jax.checkpoint_policies.save_only_these_names(*saved) if saved else None
    dots = jax.checkpoint_policies.dots_saveable
    return {
        "full": names,
        "per_layer": names,
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_saveable": jax.checkpoint_policies.save_from_both_policies(dots, names) if saved else dots,
    }[name]


def _saved_names(config: TransformerConfig) -> Tuple[str, ...]:
    """The ``checkpoint_name``s a layer's remat keeps for this model: the packed
    selection where it has an indexer, else none."""
    if not config.sparse_attention:
        return ()
    from trlx_tpu.ops.sparse_attention import SELECTION_NAME

    return (SELECTION_NAME,)


def _act(name: str):
    return {
        "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu": lambda x: jax.nn.gelu(x, approximate=False),
        "relu": jax.nn.relu,
        "silu": jax.nn.silu,
    }[name]


def _norm_module(config: TransformerConfig, name: Optional[str] = None):
    kw = dict(epsilon=config.norm_eps, dtype=config.compute_dtype, param_dtype=config.param_dtype)
    if name is not None:
        kw["name"] = name
    if config.norm == "rmsnorm":
        return nn.RMSNorm(**kw)
    return nn.LayerNorm(**kw)


def make_causal_bias(attention_mask: Optional[jnp.ndarray], B: int, T: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(positions, additive causal+padding mask bias) for a cache-free forward."""
    if attention_mask is not None:
        positions = jnp.clip(jnp.cumsum(attention_mask, axis=1) - 1, 0, None).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    causal = jnp.tril(jnp.ones((T, T), dtype=bool))[None, None, :, :]
    if attention_mask is not None:
        causal = jnp.logical_and(causal, attention_mask[:, None, None, :].astype(bool))
    return positions, jnp.where(causal, 0.0, -1e9).astype(jnp.float32)


def make_attn_bias(
    config: TransformerConfig, attention_mask: Optional[jnp.ndarray], B: int, T: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(positions, additive mask bias incl. ALiBi when configured) for a
    cache-free forward. Use this, not make_causal_bias, wherever the config is
    at hand — it folds the positional bias in so new call sites cannot miss it."""
    positions, bias = make_causal_bias(attention_mask, B, T)
    if config.pos_embedding == "alibi":
        bias = bias + alibi_bias(config, attention_mask, B, T)
    return positions, bias


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (same algorithm as HF ``build_alibi_tensor``)."""
    closest = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = base ** np.arange(1, closest + 1)
    if closest != num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        num_rem = min(closest, num_heads - closest)
        extra = extra_base ** np.arange(1, 1 + 2 * num_rem, 2)
        slopes = np.concatenate([slopes, extra])
    return jnp.asarray(slopes, jnp.float32)


def alibi_bias(config: TransformerConfig, attention_mask: Optional[jnp.ndarray], B: int, S: int) -> jnp.ndarray:
    """[B, H, 1, S] additive ALiBi bias over key slots.

    Matches HF Bloom: bias = slope * key_position, where key position counts
    valid tokens (softmax-shift-invariant vs the relative form, since the
    -slope*q_pos term is constant per query row)."""
    if attention_mask is not None:
        m = attention_mask.astype(jnp.float32)
        key_pos = (jnp.clip(jnp.cumsum(m, axis=1) - 1, 0, None) * m)
    else:
        key_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.float32)[None, :], (B, S))
    slopes = alibi_slopes(config.num_heads)
    return slopes[None, :, None, None] * key_pos[:, None, None, :]


def make_rotary(config: TransformerConfig, positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables [B, T, rot_dim/2] for the given positions."""
    rot_dim = int(config.dim_per_head * config.rotary_pct)
    rot_dim -= rot_dim % 2
    return rotary_tables(rot_dim, config.rope_theta, positions)


def rotary_tables(rot_dim: int, theta: float, positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin [B, T, rot_dim/2] of ``rot_dim`` rotary dimensions."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    freqs = positions[..., None].astype(jnp.float32) * inv_freq  # [B,T,rot/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, style: str) -> jnp.ndarray:
    """Rotate queries/keys. x: [B, T, H, D]; cos/sin [B, T, rot/2]."""
    rot_dim = cos.shape[-1] * 2
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    if style == "gptj":
        # interleaved pairs (x0,x1),(x2,x3),...
        x1 = x_rot[..., 0::2]
        x2 = x_rot[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    else:
        # neox rotate-half: first half paired with second half
        half = rot_dim // 2
        x1 = x_rot[..., :half]
        x2 = x_rot[..., half:]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        rotated = jnp.concatenate([r1, r2], axis=-1)
    return jnp.concatenate([rotated, x_pass], axis=-1).astype(x.dtype)


class LoraDense(nn.Module):
    """Dense with the same param layout as nn.Dense (``kernel``/``bias``) plus
    optional low-rank adapters ``lora_a``/``lora_b`` (y += x A B * alpha/r).
    ``lora_a`` is normal-initialized, ``lora_b`` zeros, so the adapter starts as a
    no-op — the LoRA convention."""

    features: int
    use_bias: bool
    dtype: Any
    param_dtype: Any
    kernel_init: Any
    r: int = 0
    alpha: float = 16.0

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init, (in_features, self.features), self.param_dtype)
        y = x.astype(self.dtype) @ kernel.astype(self.dtype)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        if self.r > 0:
            a = self.param(
                "lora_a", nn.initializers.normal(1.0 / self.r), (in_features, self.r), self.param_dtype
            )
            b = self.param("lora_b", nn.initializers.zeros, (self.r, self.features), self.param_dtype)
            y = y + (x.astype(self.dtype) @ a.astype(self.dtype)) @ b.astype(self.dtype) * (
                self.alpha / self.r
            )
        return y


def merge_lora_params(params: Dict[str, Any], config: "TransformerConfig") -> Dict[str, Any]:
    """Fold adapters into base kernels (W += A B * alpha/r) and drop lora leaves —
    used when exporting to HF format (parity: peft ``merge_and_unload``)."""
    import numpy as np

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        if "kernel" in tree and "lora_a" in tree:
            scale = config.lora_alpha / config.lora_r
            out["kernel"] = np.asarray(tree["kernel"]) + np.asarray(tree["lora_a"]) @ np.asarray(
                tree["lora_b"]
            ) * scale
            for k, v in tree.items():
                if k not in ("kernel", "lora_a", "lora_b"):
                    out[k] = walk(v)
            return out
        return {k: walk(v) for k, v in tree.items()}

    return walk(params)


class Attention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        mask_bias: jnp.ndarray,
        positions: jnp.ndarray,
        cache: Optional[Dict[str, jnp.ndarray]] = None,
        kv_valid: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
        """x: [B,T,Hid]; mask_bias additive [B,1,T,S]; cache holds this layer's k/v
        [B,Hkv,S,D] plus the global write index. ``kv_valid`` [B,T] marks a
        multi-token forward whose keys are its own tokens — cache-free (training /
        scoring) or generation prefill (cache written from slot 0). Which path the
        attention takes is ``ops.attention.attend``'s to decide."""
        c = self.config
        B, T, _ = x.shape
        dense = lambda feats, name, bias, std=c.initializer_range: LoraDense(
            feats, use_bias=bias, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            kernel_init=nn.initializers.normal(std), name=name,
            r=c.lora_r if name in c.lora_targets else 0, alpha=c.lora_alpha,
        )
        res_std = c.residual_init_std()
        q = dense(c.num_heads * c.dim_per_head, "q_proj", c.attn_bias)(x)
        k = dense(c.kv_heads * c.dim_per_head, "k_proj", c.attn_bias)(x)
        v = dense(c.kv_heads * c.dim_per_head, "v_proj", c.attn_bias)(x)
        q = q.reshape(B, T, c.num_heads, c.dim_per_head)
        k = k.reshape(B, T, c.kv_heads, c.dim_per_head)
        v = v.reshape(B, T, c.kv_heads, c.dim_per_head)
        if c.qk_norm:
            head_norm = lambda name: nn.RMSNorm(
                epsilon=c.norm_eps, dtype=c.compute_dtype, param_dtype=c.param_dtype, name=name)
            q, k = head_norm("q_norm")(q), head_norm("k_norm")(k)

        if c.pos_embedding == "rotary":
            cos, sin = make_rotary(c, positions)
            q = apply_rotary(q, cos, sin, c.rope_style)
            k = apply_rotary(k, cos, sin, c.rope_style)

        if c.sparse_attention:
            out, new_cache = self._selected(q, k, v, x, positions, mask_bias, cache, kv_valid)
            return dense(c.hidden_size, "o_proj", c.attn_bias, res_std)(out), new_cache

        if cache is not None and kv_cache.is_paged(cache):
            # Paged step (serving engine) against the block-pool cache. T == 1
            # is the steady-state decode: the new row lands at position
            # context_lens (its block is always exclusively owned — the
            # allocator never leaves a live write frontier inside a shared
            # prefix block), then attention runs over context_lens+1 tokens
            # gathered through the block table. T > 1 is the speculative-
            # verify / chunked-prefill append: token j lands at context_lens+j
            # and query j attends causally over context_lens+j+1 tokens.
            # Causality is structural — only written slots are valid — so no
            # mask_bias is consumed; alibi (a position-dependent score bias)
            # and prefix tuning (scale-less prepended rows) don't fit that
            # contract and the serving engine refuses such configs.
            if c.pos_embedding == "alibi" or c.peft_type == "prefix":
                raise ValueError(
                    "paged decode does not support alibi or prefix tuning"
                )
            from trlx_tpu.ops.paged_attention import (
                paged_decode_attention, paged_verify_attention,
                write_paged_kv, write_paged_kv_multi,
            )

            if T == 1:
                new_cache = write_paged_kv(cache, k[:, 0], v[:, 0])
                out = paged_decode_attention(
                    q[:, 0], new_cache["k"], new_cache["v"],
                    cache["block_tables"], cache["context_lens"] + 1,
                    k_scale=new_cache.get("k_scale"), v_scale=new_cache.get("v_scale"),
                    scale=1.0 / math.sqrt(c.dim_per_head),
                    impl=c.paged_attention_impl,
                )
            else:
                new_cache = write_paged_kv_multi(cache, k, v)
                out = paged_verify_attention(
                    q, new_cache["k"], new_cache["v"],
                    cache["block_tables"], cache["context_lens"],
                    k_scale=new_cache.get("k_scale"), v_scale=new_cache.get("v_scale"),
                    scale=1.0 / math.sqrt(c.dim_per_head),
                    impl=c.paged_attention_impl,
                )
            out = out.reshape(B, T, c.num_heads * c.dim_per_head).astype(c.compute_dtype)
            out = dense(c.hidden_size, "o_proj", c.attn_bias, res_std)(out)
            return out, new_cache

        new_cache = index = None
        if cache is not None:
            index = cache["index"]
            new_cache = kv_cache.write_kv_cache(cache, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), index)
        # prefix tuning: learned per-layer K/V (never cached — they are static),
        # joined by attend in front of whatever it attends over
        prefix = None
        if c.peft_type == "prefix" and c.num_virtual_tokens > 0:
            prefix = tuple(
                self.param(
                    name, nn.initializers.normal(c.initializer_range),
                    (c.num_virtual_tokens, c.kv_heads, c.dim_per_head), c.param_dtype,
                )
                for name in ("prefix_k", "prefix_v")
            )
        out = attend(
            q, k, v, new_cache, mask_bias, kv_valid, index,
            1.0 / math.sqrt(c.dim_per_head), c.attention_impl, c.biased_attention, prefix,
        )
        out = dense(c.hidden_size, "o_proj", c.attn_bias, res_std)(out)
        return out, new_cache

    def _selected(self, q, k, v, x, positions, mask_bias, cache, kv_valid):
        """Learned sparse attention (``config.index_topk``): the indexer, then
        attention over the keys it selects. Multi-token forwards whose keys are
        their own tokens take the selection and attention kernels (XLA under
        ``attention_impl="xla"``); a single-token step over the cache scores
        every written slot and gathers the selected ones. Sows the pairs kept and
        the causal pairs into ``dsa_stats`` where a caller asks for them."""
        from trlx_tpu.ops import sparse_attention as dsa

        c = self.config
        B, T = x.shape[:2]
        if cache is not None and kv_cache.is_paged(cache):
            raise ValueError(_DSA_REFUSALS["paged"])
        qi, ki, w = Indexer(c, name="indexer")(x, positions)
        scale = 1.0 / math.sqrt(c.dim_per_head)
        new_cache = None
        if cache is not None:
            new_cache = kv_cache.write_indexed_cache(cache, k, v, ki, cache["index"])
            if T == 1:
                allowed = mask_bias[:, 0, 0, :] > -1.0  # the additive mask: 0 where a slot may be read
                return dsa.decode_selected(q, qi, w, new_cache, allowed, topk=c.index_topk, scale=scale), new_cache
            if kv_valid is None:
                raise ValueError("learned sparse attention takes a cached forward of several tokens only as the "
                                 "prefill from slot 0 with its attention mask")
        valid = jnp.ones((B, T), jnp.int32) if kv_valid is None else kv_valid.astype(jnp.int32)
        out, mask = dsa.attend_selected(
            q, k, v, qi, ki, w, valid, topk=c.index_topk, scale=scale, kernels=c.attention_impl == "flash")
        if self.is_mutable_collection("dsa_stats"):
            kept, causal, off = dsa.pair_counts(mask, valid, c.index_topk)
            self.sow("dsa_stats", "kept_pairs", kept)
            self.sow("dsa_stats", "causal_pairs", causal)
            self.sow("dsa_stats", "queries_off", off)
        return out, new_cache


class Indexer(nn.Module):
    """DeepSeek-V3.2's lightning indexer over the attention's input ``x``:
    ``qi = rope(x W_q)`` as ``index_heads`` heads of ``index_head_dim``, ``ki =
    rope(LayerNorm(x W_k))`` one key head, ``w = (x W_w) / sqrt(heads *
    head_dim)``; rotary (rotate-half, ``rope_theta``) on the first
    ``index_rope_dim`` dimensions of each. Held frozen: every output is
    stop-gradient, so the PPO loss gives the leaves nothing (top-k is discrete;
    the published indexer's own objective is no part of PPO)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        B, T, _ = x.shape
        H, D = c.index_heads, c.index_head_dim
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            kernel_init=nn.initializers.normal(c.initializer_range), name=name)
        with jax.named_scope("dsa.index"):
            qi = dense(H * D, "wq")(x).reshape(B, T, H, D)
            ki = nn.LayerNorm(epsilon=c.norm_eps, dtype=c.compute_dtype, param_dtype=c.param_dtype,
                              name="k_norm")(dense(D, "wk")(x))
            cos, sin = rotary_tables(c.index_rope_dim, c.rope_theta, positions)
            qi = apply_rotary(qi, cos, sin, "neox")
            ki = apply_rotary(ki[:, :, None], cos, sin, "neox")[:, :, 0]
            w = dense(H, "weights_proj")(x).astype(jnp.float32) * (1.0 / math.sqrt(H * D))
        return jax.lax.stop_gradient(qi), jax.lax.stop_gradient(ki), jax.lax.stop_gradient(w)


class _Kernel(nn.Module):
    """A bare ``kernel`` parameter under the module's name, for a projection
    that is applied in more than one form."""

    shape: Tuple[int, ...]
    std: float
    param_dtype: Any

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        return self.param("kernel", nn.initializers.normal(self.std), self.shape, self.param_dtype)


_MLA_REFUSALS = {
    "paged": "the paged cache's blocks are kv_heads x head_dim rows; latent attention caches one "
             "kv_lora_rank + qk_rope_head_dim row a token, which the paged kernels cannot read",
    "kv_cache_quant": "kv_cache_quant scales int8 rows per head; a latent row has no heads",
    "stacked": "scan_layers / pipeline_stages > 1 stack one cache array [L, B, Hkv, S, D]; latent "
               "attention caches [B, S, kv_lora_rank] + [B, S, qk_rope_head_dim] a layer",
}


_DSA_REFUSALS = {
    "paged": "the paged block pool holds keys and values by token and no indexer key beside them; learned sparse "
             "attention (index_topk) scores every written slot's indexer key a step, which serving/allocator.py does "
             "not lay out: the serving engine does not run this model yet",
    "stacked": "scan_layers / pipeline_stages > 1 scan one cache array [L, B, Hkv, S, D]; learned sparse attention "
               "(index_topk) caches slot-major keys and values and an indexer key a layer",
    "kv_cache_quant": "kv_cache_quant scales int8 rows per head of a heads-first cache; learned sparse attention "
                      "(index_topk) gathers slot-major rows and has not been held against a reference in int8",
    "ring": "attention_impl='ring' splits the sequence over chips, and the selection (index_topk) ranks each query's "
            "keys over the whole sequence, which nothing exchanges yet",
    "sequence_sharding": "sequence_sharding splits the residual stream's sequence over the model axis, and the "
                         "selection (index_topk) ranks each query's keys over the whole sequence",
    "peft": "prompt and prefix tuning prepend rows to what attention reads, which the indexer (index_topk) has no "
            "keys for",
    "mla": "learned sparse attention (index_topk) is built on per-head grouped-query attention, not on latent "
           "attention",
}


_LOOP_REFUSALS = {
    "stacked": "scan_layers / pipeline_stages > 1 scan one stack of layers over one cache array [L, ...] once; "
               "looped layers (loop_steps > 1) walk the stack loop_steps times over a cache entry for every "
               "(pass, layer), which the stage scan does not carry",
    "kv_cache_quant": "kv_cache_quant has not been held against a reference through looped layers (loop_steps > 1), "
                      "whose later passes read what the earlier ones rounded",
    "paged": "the paged block pool holds num_layers pools a token; looped layers (loop_steps > 1) need one for "
             "every (pass, layer), which the allocator and the paged kernels' callers do not lay out",
    "branch": "a looped model (loop_steps > 1) has no frozen trunk under unfrozen top layers: the top layers feed "
              "the bottom ones of the next pass, so there is no layer from which a hydra or value branch could "
              "start; use a full reference copy (num_layers_unfrozen=-1) and num_value_layers_unfrozen=0",
    "early_exit": "early_exit_threshold < 1 lets the rows of one fixed-shape batch leave at different passes "
                  "(and leaves the later passes' cache entries of a token unwritten), which this forward does "
                  "not do: every pass runs for every token",
    "final_norm": "looped layers (loop_steps > 1) feed each pass the final norm's output; final_norm=False has none",
}


_CONV_REFUSALS = {
    "paged": "the paged block pool holds keys and values by token; a convolution layer (layer_kinds) holds a "
             "fixed-size state for each row, which needs a slot of its own in serving/allocator.py and a snapshot "
             "for preemption: the serving engine does not run this model yet",
    "stacked": "scan_layers / pipeline_stages > 1 run one scanned Block over stacked parameters and one cache array "
               "[L, ...], and the layers of this model are not alike: layer_kinds makes some of them convolutions",
    "kv_cache_quant": "kv_cache_quant has not been held against a reference beside a convolution's float state "
                      "(layer_kinds)",
    "ring": "attention_impl='ring' splits the sequence over chips, and a convolution layer (layer_kinds) reads its "
            "left neighbours across the split, which nothing exchanges yet",
    "sequence_sharding": "sequence_sharding splits the residual stream's sequence over the model axis, and a "
                         "convolution layer (layer_kinds) reads its left neighbours across the split",
    "peft": "prompt and prefix tuning prepend rows to what attention reads; a convolution layer (layer_kinds) "
            "has no such rows",
}


class ShortConv(nn.Module):
    """Gated short convolution (a ``"conv"`` layer of ``layer_kinds``).

    ``[b, c, x] = z W_in`` in that order; ``u = b * x``, zero at padded
    positions (``valid``: prompts are left-padded, and a convolution, unlike
    masked attention, would otherwise carry the pad rows' values into the first
    real tokens); ``v_t = sum_j w_j * u_{t - (taps - 1) + j}``, a causal
    depthwise filter of ``conv_taps`` taps along the sequence (``w`` is
    ``[hidden, taps]``, one filter a channel); ``y = (c * v) W_out``. Plain XLA:
    the shifted multiply-adds fuse. With ``cache`` (this layer's state,
    ``ops/kv_cache.py``) the filter reads the row's last ``taps - 1`` inputs
    from it, whether the forward is the prefill (the state is zeros) or a decode
    step, and the state after the forward is returned."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, cache=None, valid=None):
        c = self.config
        T, d, taps = x.shape[1], c.hidden_size, c.conv_taps
        dense = lambda feats, name, std=c.initializer_range: LoraDense(
            feats, use_bias=False, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            kernel_init=nn.initializers.normal(std), name=name,
            r=c.lora_r if name in c.lora_targets else 0, alpha=c.lora_alpha,
        )
        with jax.named_scope("conv"):
            b, gate, xx = jnp.split(dense(3 * d, "in_proj")(x), 3, axis=-1)
            u = b * xx
            if valid is not None:
                u = u * valid[..., None].astype(u.dtype)
            w = _Kernel((d, taps), c.initializer_range, c.param_dtype, name="conv")().astype(c.compute_dtype)
            new_cache = None
            if cache is None:
                seen = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))  # nothing before the first token
            else:
                seen, new_cache = kv_cache.roll_conv_state(cache, u)
            v = sum(w[:, j] * seen[:, j : j + T] for j in range(taps))
            return dense(d, "out_proj", c.residual_init_std())(gate * v), new_cache


class LatentAttention(nn.Module):
    """Multi-head latent attention (``attention_kind="mla"``).

    ``q = x W_q`` as heads of ``nope + rope``; ``[c_raw, k_rope] = x W_kva``,
    ``c = RMSNorm(c_raw)``, ``k_rope`` one rotary head shared by all; keys and
    values are ``c W_kvb`` per head, values ``v_head_dim`` wide. Multi-token
    forwards (cache-free, and prefill from slot 0) expand k and v and go
    through the flash kernels at key width ``nope + rope`` and value width
    ``v_head_dim``. A forward over the cache (decode) holds only ``c`` and the
    rotated ``k_rope`` per token and attends in the absorbed form: ``q_nope``
    is carried into the latent through W_kvb's key part, scores are taken
    against ``c`` and ``k_rope`` directly, the probabilities weigh ``c``, and
    W_kvb's value part brings the result back out — every head over one
    ``rank + rope``-wide key and one ``rank``-wide value."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask_bias, positions, cache=None, kv_valid=None):
        c = self.config
        if c.peft_type == "prefix":
            raise ValueError("prefix tuning prepends per-head keys and values; latent attention has none")
        if cache is not None and kv_cache.is_paged(cache):
            raise ValueError(_MLA_REFUSALS["paged"])
        B, T, _ = x.shape
        H, nope, rope, vdim, rank = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank
        dense = lambda feats, name, std=c.initializer_range: LoraDense(
            feats, use_bias=c.attn_bias, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            kernel_init=nn.initializers.normal(std), name=name,
            r=c.lora_r if name in c.lora_targets else 0, alpha=c.lora_alpha,
        )
        scale = 1.0 / math.sqrt(nope + rope)
        with jax.named_scope("mla"):
            q = dense(H * (nope + rope), "q_proj")(x).reshape(B, T, H, nope + rope)
            kva = dense(rank + rope, "kv_a_proj")(x)
            latent = nn.RMSNorm(
                epsilon=c.norm_eps, dtype=c.compute_dtype, param_dtype=c.param_dtype, name="kv_a_norm"
            )(kva[..., :rank])
            cos, sin = rotary_tables(rope, c.rope_theta, positions)
            q_nope = q[..., :nope]
            q_rope = apply_rotary(q[..., nope:], cos, sin, c.rope_style)
            k_rope = apply_rotary(kva[..., None, rank:], cos, sin, c.rope_style)  # [B, T, 1, rope]
            w_kvb = _Kernel((rank, H * (nope + vdim)), c.initializer_range, c.param_dtype, name="kv_b_proj")()
            w_kvb = w_kvb.astype(c.compute_dtype).reshape(rank, H, nope + vdim)

            new_cache = None
            if cache is not None:
                new_cache = kv_cache.write_latent_cache(cache, latent, k_rope, cache["index"])
            # per-head keys and values of this forward's own tokens: cache-free, or a prefill the flash kernel takes
            expanded = cache is None or flash_placement(c.attention_impl, c.biased_attention, B, T, kv_valid, H, H)[0]
            if expanded:
                kv = jnp.einsum("btl,lhm->bthm", latent, w_kvb)
                k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (B, T, H, rope))], axis=-1)
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                v = kv[..., nope:]
        if expanded:
            # outside the "mla" scope: the kernels keep the module's name, %attn.N
            out = attend(q, k, v, None, mask_bias, kv_valid, None, scale, c.attention_impl, c.biased_attention, None)
        else:
            with jax.named_scope("mla"):  # absorbed, over the cache
                ck, kr = new_cache["c"].astype(c.compute_dtype), new_cache["k_rope"].astype(c.compute_dtype)
                q_latent = jnp.einsum("bthn,lhn->bthl", q_nope, w_kvb[..., :nope])
                scores = jnp.einsum("bthl,bsl->bhts", q_latent, ck) + jnp.einsum("bthr,bsr->bhts", q_rope, kr)
                probs = jax.nn.softmax(scores.astype(jnp.float32) * scale + mask_bias, axis=-1)
                out_latent = jnp.einsum("bhts,bsl->bthl", probs.astype(c.compute_dtype), ck)
                out = jnp.einsum("bthl,lhv->bthv", out_latent, w_kvb[..., nope:])
        with jax.named_scope("mla"):
            out = out.astype(c.compute_dtype).reshape(B, T, H * vdim)
            out = dense(c.hidden_size, "o_proj", c.residual_init_std())(out)
        return out, new_cache


class MLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c = self.config
        dense = lambda feats, name, std=c.initializer_range: LoraDense(
            feats, use_bias=c.mlp_bias, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            kernel_init=nn.initializers.normal(std), name=name,
            r=c.lora_r if name in c.lora_targets else 0, alpha=c.lora_alpha,
        )
        act = _act(c.activation)
        if c.glu:
            h = act(dense(c.ffn_dim, "gate_proj")(x)) * dense(c.ffn_dim, "up_proj")(x)
        else:
            h = act(dense(c.ffn_dim, "up_proj")(x))
        return dense(c.hidden_size, "down_proj", c.residual_init_std())(h)


class _Router(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self):
        c = self.config
        kernel = self.param(
            "kernel", nn.initializers.normal(c.initializer_range), (c.hidden_size, c.num_experts), c.param_dtype)
        if c.router_scoring == "softmax":
            return kernel, None
        # the selection bias (published ``e_score_correction_bias``): it chooses and does not weigh
        bias = self.param("bias", nn.initializers.zeros, (c.num_experts,), c.param_dtype)
        return kernel, bias


class _Experts(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self):
        c = self.config
        E, d, f = c.held_experts, c.hidden_size, c.moe_intermediate_size
        init = lambda std: nn.initializers.normal(std)
        return (
            self.param("gate", init(c.initializer_range), (E, d, f), c.param_dtype),
            self.param("up", init(c.initializer_range), (E, d, f), c.param_dtype),
            self.param("down", init(c.residual_init_std()), (E, f, d), c.param_dtype),
        )


class SparseMLP(nn.Module):
    """Routed experts (``ops/moe.py``: route, sort by expert, grouped products
    over the experts held, combine — dropless) beside the shared experts. The
    load of each held expert is sown into the ``moe_stats`` collection, which
    costs one small reduction a layer and nothing where no caller asks for it."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        from trlx_tpu.ops import moe

        c = self.config
        B, T, d = x.shape
        kernel, bias = _Router(c, name="router")()
        gate, up, down = _Experts(c, name="experts")()
        flat = x.reshape(B * T, d)

        def experts(flat, real=None):
            chosen, weights = moe.route(
                flat, kernel, bias, top_k=c.experts_per_token, norm_topk=c.norm_topk_prob,
                scale=c.routed_scaling_factor, norm_eps=c.router_norm_eps, scoring=c.router_scoring)
            if real is not None:  # padding goes to no expert: no rows, no load
                chosen = jnp.where(real[:, None], chosen, -1)
            return moe.expert_ffn(
                flat, chosen, weights, gate, up, down, expert_offset=c.expert_offset, act=_act(c.activation))

        chunks = moe.token_chunks(B * T)
        if chunks == 1:
            routed, load = experts(flat)
        else:  # a long prefill: passes of TOKEN_CHUNK tokens, one dropless buffer at a time, the last padded
            pad = chunks * moe.TOKEN_CHUNK - B * T
            passes = jnp.pad(flat, ((0, pad), (0, 0))).reshape(chunks, moe.TOKEN_CHUNK, d)
            real = (jnp.arange(chunks * moe.TOKEN_CHUNK) < B * T).reshape(chunks, -1) if pad else None
            routed, load = jax.lax.map(lambda a: experts(*a), (passes, real))
            routed, load = routed.reshape(-1, d)[: B * T], load.sum(0)
        self.sow("moe_stats", "load", load)
        out = routed.reshape(B, T, d)
        if c.num_shared_experts:
            with jax.named_scope("moe.shared"):
                shared = c.replace(intermediate_size=c.num_shared_experts * c.moe_intermediate_size)
                out = out + MLP(shared, name="shared")(x)
        return out


def moe_counters(moe_stats) -> Dict[str, jnp.ndarray]:
    """The ``moe_stats`` collection of one forward as counters, summed over the
    expert layers: assignments that fell to held experts, the largest and the
    mean load of a held expert (float32 scalars)."""
    loads = [x.astype(jnp.float32) for x in jax.tree.leaves(moe_stats)]
    return {
        "moe/assignments_held": sum(x.sum() for x in loads),
        "moe/load_max": sum(x.max() for x in loads),
        "moe/load_mean": sum(x.mean() for x in loads),
    }


def dsa_counters(dsa_stats) -> Dict[str, jnp.ndarray]:
    """The ``dsa_stats`` collection of one forward as counters, summed over the
    attention layers: the (query, key) pairs the selection kept, the causal
    pairs there were, and the queries that kept other than ``min(topk,
    allowed)`` keys, over real query rows (float32 scalars)."""
    # sow keeps a tuple under each name: a leaf's path ends (..., name, index in the tuple)
    out = {f"dsa/{name}": jnp.float32(0.0) for name in ("kept_pairs", "causal_pairs", "queries_off")}
    for path, value in jax.tree_util.tree_leaves_with_path(dsa_stats):
        out[f"dsa/{path[-2].key}"] = out[f"dsa/{path[-2].key}"] + value
    return out


def loop_counters(loop_stats) -> Dict[str, jnp.ndarray]:
    """The ``loop_stats`` collection of one forward as counters (float32
    scalars): ``loop/hidden_delta_<t>`` for every pass after the first and,
    with an exit gate, ``loop/exit_pass_expected``."""
    # sow keeps a tuple under each name: a leaf's path ends (..., name, index in the tuple)
    return {f"loop/{path[-2].key}": value for path, value in jax.tree_util.tree_leaves_with_path(loop_stats)}


class Block(nn.Module):
    config: TransformerConfig
    expert_layer: bool = False  # this layer's FFN is the sparse one (config.is_expert_layer)
    conv_layer: bool = False  # this layer's mixer is the short convolution (config.is_conv_layer)

    @nn.compact
    def __call__(self, x, mask_bias, positions, cache=None, kv_valid=None):
        c = self.config
        attention = LatentAttention if c.attention_kind == "mla" else Attention
        mlp = SparseMLP if self.expert_layer else MLP
        if self.conv_layer:
            # a cached forward of more than one token whose mask did not come along (``kv_valid`` marks the
            # forwards whose keys are their own tokens) would convolve over pad rows unmasked
            if cache is not None and kv_valid is None and x.shape[1] > 1:
                raise ValueError("a convolution layer takes a cached forward of several tokens only as the "
                                 "prefill from slot 0 with its attention mask")
            mix = lambda h: ShortConv(c, name="conv")(h, cache, kv_valid)
        else:
            mix = lambda h: attention(c, name="attn")(h, mask_bias, positions, cache, kv_valid)
        if c.parallel_residual:
            if c.sandwich_norms:
                raise ValueError("sandwich_norms norm each sub-layer's output on its own; parallel_residual sums them")
            h1 = _norm_module(c, "ln_1")(x)
            h2 = h1 if c.shared_parallel_ln else _norm_module(c, "ln_2")(x)
            attn_out, new_cache = mix(h1)
            mlp_out = mlp(c, name="mlp")(h2)
            out = x + attn_out + mlp_out
            if c.sequence_sharding and cache is None:
                out = constrain_seq(out)
            return out, new_cache
        attn_out, new_cache = mix(_norm_module(c, "ln_1")(x))
        if c.sandwich_norms:
            attn_out = _norm_module(c, "ln_1_post")(attn_out)
        x = x + attn_out
        mlp_out = mlp(c, name="mlp")(_norm_module(c, "ln_2")(x))
        if c.sandwich_norms:
            mlp_out = _norm_module(c, "ln_2_post")(mlp_out)
        x = x + mlp_out
        # per-layer Megatron-SP residual constraint lives HERE (not in the caller's
        # layer loop) so every path — listed loop, nn.scan stack, value branch,
        # forward_from — gets it identically
        if c.sequence_sharding and cache is None:
            x = constrain_seq(x)
        return x, new_cache


class TransformerLM(nn.Module):
    """Decoder-only LM. ``__call__`` returns (logits, final_hidden, branch_hidden,
    cache); ``forward_from`` re-runs the top layers from a branch activation (hydra)."""

    config: TransformerConfig

    def setup(self):
        c = self.config
        self.embed_tokens = nn.Embed(
            c.vocab_size, c.hidden_size, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            embedding_init=nn.initializers.normal(c.initializer_range),
        )
        if c.embed_ln:
            self.embed_layernorm = _norm_module(c)
        if c.peft_type == "prompt" and c.num_virtual_tokens > 0:
            # prompt tuning: learned virtual-token embeddings prepended to the
            # input (parity: peft PROMPT_TUNING, modeling_base.py:162-240)
            self.prompt_embeddings = self.param(
                "prompt_embeddings", nn.initializers.normal(c.initializer_range),
                (c.num_virtual_tokens, c.hidden_size), c.param_dtype,
            )
        if c.pos_embedding == "learned":
            self.embed_positions = nn.Embed(
                c.max_position_embeddings + c.pos_offset, c.hidden_size,
                dtype=c.compute_dtype, param_dtype=c.param_dtype,
                embedding_init=nn.initializers.normal(c.initializer_range),
            )
        block = Block
        if c.remat != "none":
            block = nn.remat(Block, policy=remat_policy(c.remat, _saved_names(c)))
        if c.stacked and c.num_experts > 0:
            raise ValueError(
                "scan_layers / pipeline_stages > 1 run one scanned Block over stacked parameters, "
                "and the layers of this model are not alike: the first "
                f"{c.first_dense_layers} have a dense FFN, the rest routed experts"
            )
        if c.attention_kind == "mla":
            for refused, why in (("stacked", c.stacked), ("kv_cache_quant", c.kv_cache_quant)):
                if why:
                    raise ValueError(_MLA_REFUSALS[refused])
        if c.conv_layers:
            for refused, why in (
                ("stacked", c.stacked), ("kv_cache_quant", c.kv_cache_quant), ("ring", c.attention_impl == "ring"),
                ("sequence_sharding", c.sequence_sharding), ("peft", c.peft_type != "none"),
            ):
                if why:
                    raise ValueError(_CONV_REFUSALS[refused])
        if c.sparse_attention:
            for refused, why in (
                ("stacked", c.stacked), ("kv_cache_quant", c.kv_cache_quant), ("ring", c.attention_impl == "ring"),
                ("sequence_sharding", c.sequence_sharding), ("peft", c.peft_type != "none"),
                ("mla", c.attention_kind == "mla"),
            ):
                if why:
                    raise ValueError(_DSA_REFUSALS[refused])
        if c.early_exit_threshold < 1:
            raise ValueError(_LOOP_REFUSALS["early_exit"])
        if c.loop_steps > 1:
            for refused, why in (
                ("stacked", c.stacked), ("kv_cache_quant", c.kv_cache_quant), ("final_norm", not c.final_norm),
            ):
                if why:
                    raise ValueError(_LOOP_REFUSALS[refused])
        if c.exit_gate:
            self.exit_gate = nn.Dense(
                1, use_bias=True, dtype=c.compute_dtype, param_dtype=c.param_dtype,
                kernel_init=nn.initializers.normal(c.initializer_range),
            )
        if c.stacked:
            if c.pipeline_stages > 1:
                if c.num_layers % c.pipeline_stages != 0:
                    raise ValueError(
                        f"num_layers={c.num_layers} not divisible by "
                        f"pipeline_stages={c.pipeline_stages}"
                    )
                if c.attention_impl == "ring":
                    raise ValueError(
                        "pipeline_stages > 1 cannot nest ring attention's shard_map; "
                        "use attention_impl='xla' or 'flash'"
                    )
                if c.sequence_sharding:
                    raise ValueError(
                        "pipeline_stages > 1 does not apply sequence-sharding "
                        "constraints inside the pipelined stack; set "
                        "sequence_sharding=False (the trainer does this automatically "
                        "when mesh.pipe > 1)"
                    )
            # stacked layout: one scanned Block whose params carry a leading
            # [num_layers] dim (sharded over "pipe" by the partition rules)
            self.layers_scan = nn.scan(
                block,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast, 0, nn.broadcast),
                out_axes=0,
                length=c.num_layers,
            )(c, name="layers_scan")
            self.layers = ()
        else:
            self.layers = [
                block(c, expert_layer=c.is_expert_layer(i), conv_layer=c.is_conv_layer(i)) for i in range(c.num_layers)
            ]
        if c.final_norm:
            self.ln_f = _norm_module(c)
        if not c.tie_word_embeddings:
            self.lm_head = nn.Dense(
                c.vocab_size, use_bias=c.head_bias, dtype=c.compute_dtype, param_dtype=c.param_dtype,
                kernel_init=nn.initializers.normal(c.initializer_range),
            )

    def head(self, x: jnp.ndarray) -> jnp.ndarray:
        """The vocabulary head over post-norm rows [..., d] -> logits [..., V]
        in the compute dtype. A caller that reads a window of the positions
        takes hidden states from a forward with ``with_head=False`` and applies
        this to the window's rows (``utils.modeling.response_logprobs``)."""
        if self.config.tie_word_embeddings:
            emb = self.embed_tokens.embedding.astype(self.config.compute_dtype)
            return x @ emb.T
        return self.lm_head(x)

    def _final(self, x: jnp.ndarray, with_head: bool = True) -> Tuple[Optional[jnp.ndarray], jnp.ndarray]:
        """(logits, or None without the head; post-norm hidden)."""
        if self.config.final_norm:
            x = self.ln_f(x)
        return (self.head(x) if with_head else None), x

    def embed(self, input_ids: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        x = self.embed_tokens(input_ids)
        if self.config.pos_embedding == "learned":
            x = x + self.embed_positions(positions + self.config.pos_offset)
        if self.config.embed_ln:
            x = self.embed_layernorm(x)
        return x

    def __call__(
        self,
        input_ids: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
        positions: Optional[jnp.ndarray] = None,
        cache: Optional[KVCache] = None,
        branch_layer: Optional[int] = None,
        with_head: bool = True,
    ):
        """input_ids [B,T]; attention_mask [B,T] (1=real token). With ``cache``,
        T may be 1 (decode step) and the mask must cover the cache length [B,S].
        Returns (logits [B,T,V], hidden [B,T,Hid] post-norm, branch_hidden or None,
        new cache or None). ``branch_layer`` = index of the first *unfrozen* layer;
        its input activation is returned for the hydra reference branch.
        ``with_head=False`` leaves the vocabulary head out (logits is None): the
        caller applies :meth:`head` to the rows it reads."""
        c = self.config
        if branch_layer is not None and c.loop_steps > 1:
            raise NotImplementedError(_LOOP_REFUSALS["branch"])
        B, T = input_ids.shape
        nv = c.num_virtual_tokens if c.peft_type == "prompt" else 0
        # prompt tuning prepends nv virtual rows internally; the external
        # contract (T-length outputs, T/S-length masks) is preserved by
        # extending masks here and slicing logits/hidden before returning.
        # Virtual rows occupy slots/positions 0..nv-1; real positions shift +nv.
        nv_rows = 0  # virtual rows present in this forward's activations
        if cache is not None:
            if c.attention_kind == "mla":
                S = cache["c"][0].shape[1]  # per-layer [B,S,rank]
            elif c.sparse_attention:
                S = cache["ki"][0].shape[1]  # per-layer [B,S,Di]
            elif not c.attention_layers:
                S = attention_mask.shape[1]  # no layer holds slots: the mask alone says how many there are
            else:
                ck = cache["k"]
                # list layout: per-layer [B,H,S,D]; stacked layout: [L,B,H,S,D]
                S = ck[0].shape[2] if isinstance(ck, (list, tuple)) else ck.shape[3]
            idx = cache["index"]
            # a concrete-zero index marks prefill-from-zero (any T, including 1);
            # a traced index is a decode step inside the generation while_loop
            prompt_prefill = nv > 0 and _concrete_zero(idx)
            if nv > 0 and not (prompt_prefill or T == 1):
                raise ValueError(
                    "prompt-tuning cached forwards support only prefill-from-zero "
                    "or single-token decode steps"
                )
            ext_mask = attention_mask
            if nv and attention_mask is not None:
                ext_mask = jnp.concatenate(
                    [jnp.ones((B, nv), attention_mask.dtype), attention_mask], axis=1
                )
            if positions is None:
                # auto-derived decode positions come from the cache index, which
                # already counts the nv virtual slots — shift only at prefill
                base = idx + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
                int_positions = base + nv if prompt_prefill else base
            else:
                int_positions = positions + nv if nv else positions
            nv_rows = nv if prompt_prefill else 0
            T_eff = T + nv_rows
            # Causal structure over cache *slots*: slots are written in temporal
            # order, so slot index ordering == temporal ordering even with left
            # padding (where position values repeat under the pad mask).
            kv_slot = jnp.arange(S)[None, None, None, :]
            q_slot = (idx + jnp.arange(T_eff, dtype=jnp.int32))[None, None, :, None]
            causal = kv_slot <= q_slot
            if ext_mask is not None:
                causal = jnp.logical_and(causal, ext_mask[:, None, None, :].astype(bool))
            mask_bias = jnp.where(causal, 0.0, -1e9).astype(jnp.float32)
            if c.pos_embedding == "alibi":
                mask_bias = mask_bias + alibi_bias(c, ext_mask, B, S)
            x = self.embed(input_ids, int_positions)
            layer_positions = int_positions
            if nv_rows:
                virt_pos = jnp.broadcast_to(jnp.arange(nv, dtype=jnp.int32)[None, :], (B, nv))
                layer_positions = jnp.concatenate([virt_pos, int_positions], axis=1)
                pe = jnp.broadcast_to(
                    self.prompt_embeddings.astype(x.dtype)[None], (B, nv, c.hidden_size)
                )
                x = jnp.concatenate([pe, x], axis=1)
            if T_eff > 1 and ext_mask is not None and _concrete_zero(idx):
                # generation prefill: the cache is written from slot 0, so the
                # flash path may attend over the prefix k/v alone. The
                # concrete-zero check must happen HERE, outside the remat
                # wrapper around the blocks: nn.remat turns every cache leaf —
                # including a Python-int index — into a tracer, so a check
                # inside Attention can never see the concrete 0 and would
                # silently disable flash prefill whenever remat is on.
                kv_valid = ext_mask[:, :T_eff]
            else:
                kv_valid = None
        else:
            mask_in = attention_mask
            if nv:
                nv_rows = nv
                if mask_in is None:
                    mask_in = jnp.ones((B, T), jnp.int32)
                ext_mask = jnp.concatenate([jnp.ones((B, nv), mask_in.dtype), mask_in], axis=1)
                default_positions, mask_bias = make_attn_bias(c, ext_mask, B, T + nv)
                int_positions = default_positions[:, nv:] if positions is None else positions + nv
                layer_positions = jnp.concatenate([default_positions[:, :nv], int_positions], axis=1)
                pe = jnp.broadcast_to(
                    self.prompt_embeddings.astype(c.compute_dtype)[None], (B, nv, c.hidden_size)
                )
                x = jnp.concatenate([pe, self.embed(input_ids, int_positions)], axis=1)
                kv_valid = ext_mask
            else:
                default_positions, mask_bias = make_attn_bias(c, attention_mask, B, T)
                if positions is None:
                    positions = default_positions
                x = self.embed(input_ids, positions)
                layer_positions = positions
                kv_valid = attention_mask
        # branch_layer: int -> return that single activation; tuple -> dict of them
        capture_set = ()
        if branch_layer is not None:
            capture_set = branch_layer if isinstance(branch_layer, tuple) else (branch_layer,)
        seq_shard = c.sequence_sharding and cache is None
        if seq_shard:
            x = constrain_seq(x)
        captures = {}
        if c.stacked:
            if capture_set:
                raise NotImplementedError(
                    "stacked/pipelined models do not support hydra branch capture "
                    "(per-layer activations are internal to the stage scan); use a "
                    "separate reference model (num_layers_unfrozen=-1) and "
                    "num_value_layers_unfrozen=0"
                )
            x, stacked_kv = self._apply_stacked(x, mask_bias, layer_positions, cache, kv_valid)
        else:
            # the loop/ counters are computed where a caller asks for the collection, and at init (the gate's leaf)
            counting = (c.loop_steps > 1 or c.exit_gate) and (
                self.is_initializing() or self.is_mutable_collection("loop_stats"))
            new_layer_caches, states = [], []
            # a layer's rank among the layers of its own kind
            rank = [sum(c.is_conv_layer(j) == c.is_conv_layer(i) for j in range(i)) for i in range(c.num_layers)]
            for step in range(c.loop_steps):
                # one pass of the stack; a model that does not loop traces what it traced before the loop was here
                with jax.named_scope("loop.pass") if c.loop_steps > 1 else contextlib.nullcontext():
                    for i, layer in enumerate(self.layers):
                        if i in capture_set:
                            captures[i] = x
                        layer_cache = None
                        if cache is not None:
                            # what this pass wrote, read by this pass alone; a layer's buffers stand in the lists
                            # of its own kind (keys and values, or a convolution's state) at its rank among them
                            conv = c.is_conv_layer(i)
                            entry = step * (c.conv_layers if conv else c.attention_layers) + rank[i]
                            layout = c.conv_state_layout(B) if conv else c.cache_layout(B, 1)
                            layer_cache = {key: cache[key][entry] for key in layout}
                            layer_cache["index"] = cache["index"]
                        x, new_lc = layer(x, mask_bias, layer_positions, layer_cache, kv_valid)
                        if cache is not None:
                            new_layer_caches.append(new_lc)
                    if step < c.loop_steps - 1:  # the last pass's norm is _final's, below
                        x = self.ln_f(x)
                        if counting:
                            states.append(x[:, nv_rows:])
            stacked_kv = None
            if cache is not None:
                # keep the per-entry list layout (no jnp.stack: restacking would
                # copy the full cache every decode step)
                stacked_kv = {}
                for lc in new_layer_caches:  # in the order of (pass, layer): each list in its own kind's order
                    for key, value in lc.items():
                        stacked_kv.setdefault(key, []).append(value)
        if seq_shard:
            # gather the sequence dim before heads (Megatron's
            # gather_from_sequence_parallel_region analogue)
            x = constrain_gathered(x)
        logits, hidden = self._final(x, with_head)
        if nv_rows:  # drop virtual rows: external output shape is [B, T, ...]
            logits = None if logits is None else logits[:, nv_rows:]
            hidden = hidden[:, nv_rows:]
        if not c.stacked and counting:
            real = attention_mask if cache is None and attention_mask is not None else jnp.ones((B, T), jnp.int32)
            self._sow_loop_stats(states + [hidden], real.astype(jnp.float32))
        new_cache = None
        if cache is not None:
            if c.stacked:
                # re-pin the written cache's layout: the decode while_loop's
                # carry sharding follows the BODY output, and unpinned it
                # reverts to GSPMD's choice (replicated over pipe — see
                # _constrain_cache_leaf)
                stacked_kv = {
                    k: self._constrain_cache_leaf(v, stacked=True)
                    for k, v in stacked_kv.items()
                }
            new_cache = {**stacked_kv, "index": cache["index"] + T + nv_rows}
        if branch_layer is not None and not isinstance(branch_layer, tuple):
            branch_out = captures.get(branch_layer)
        else:
            branch_out = captures if isinstance(branch_layer, tuple) else None
        return logits, hidden, branch_out, new_cache

    def _sow_loop_stats(self, states, real) -> None:
        """The ``loop_stats`` collection of one forward (:func:`loop_counters`),
        means over the real tokens ``real`` [B, T] marks, from the normed state
        after each pass: how far each further pass still moves the state, and
        the pass the gate's exit distribution expects to leave at
        (``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the rest at the last)."""
        states = [h.astype(jnp.float32) for h in states]
        mean = lambda v: (v * real).sum() / jnp.maximum(real.sum(), 1.0)
        norm = lambda h: jnp.sqrt((h * h).sum(-1))
        for t in range(1, len(states)):
            moved = norm(states[t] - states[t - 1]) / jnp.maximum(norm(states[t - 1]), 1e-30)
            self.sow("loop_stats", f"hidden_delta_{t + 1}", mean(moved))
        if self.config.exit_gate:
            stay, expected = 1.0, 0.0
            for t, h in enumerate(states):
                leave = jax.nn.sigmoid(self.exit_gate(h).astype(jnp.float32)[..., 0])
                expected = expected + (t + 1) * (stay if t == len(states) - 1 else leave * stay)
                stay = stay * (1.0 - leave)
            self.sow("loop_stats", "exit_pass_expected", mean(expected))

    def _apply_stacked(self, x, mask_bias, positions, cache, kv_valid):
        """Run the stacked block stack (``pipeline_stages > 1`` or ``scan_layers`` layout).

        Cached decode → sequential ``nn.scan`` over the stacked params (each
        layer's shard is streamed to where it's needed; the NeMo reference
        likewise drops pipeline scheduling for inference,
        modeling_nemo_ppo.py:838-870). Cache-free forwards → the GPipe
        microbatch schedule over the mesh's ``pipe`` axis when one is active.
        Returns (x, stacked_kv or None)."""
        c = self.config
        if cache is not None:
            scan_cache = {key: cache[key] for key in cache if key != "index"}
            scan_cache["index"] = jnp.broadcast_to(cache["index"], (c.num_layers,))
            x, ys = self.layers_scan(x, mask_bias, positions, scan_cache, kv_valid)
            return x, ys
        if not self.is_initializing():
            mesh = ambient_mesh()
            if mesh is not None and mesh.shape.get(PIPE_AXIS, 1) > 1:
                from trlx_tpu.parallel.pipeline import pipeline_apply

                stack = self.variables["params"]["layers_scan"]
                x = pipeline_apply(c, stack, x, mask_bias, positions, kv_valid, mesh)
                return x, None
        x, _ = self.layers_scan(x, mask_bias, positions, None, kv_valid)
        return x, None

    def forward_from(
        self,
        hidden: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray],
        positions: Optional[jnp.ndarray],
        start_layer: int,
        with_head: bool = True,
    ):
        """Run layers[start_layer:] + final norm + lm head from a branch activation.
        This is the hydra frozen-branch forward (reference ``forward_hydra``,
        modeling_ppo.py:410-453) — called with the frozen param subtree via
        ``apply({"params": frozen}, ..., method="forward_from")``. Returns the
        logits, or with ``with_head=False`` the post-norm hidden states."""
        if self.config.stacked:
            raise NotImplementedError(
                "hydra branch forwards need per-layer params; stacked models "
                "use a separate reference model (num_layers_unfrozen=-1)"
            )
        if self.config.loop_steps > 1:
            raise NotImplementedError(_LOOP_REFUSALS["branch"])
        B, T, _ = hidden.shape
        default_positions, mask_bias = make_attn_bias(self.config, attention_mask, B, T)
        if positions is None:
            positions = default_positions
        x = hidden
        for layer in self.layers[start_layer:]:
            x, _ = layer(x, mask_bias, positions, None, attention_mask)
        logits, hidden = self._final(x, with_head)
        return logits if with_head else hidden

    def _constrain_cache_leaf(self, x: jnp.ndarray, stacked: bool) -> jnp.ndarray:
        """Pin the KV-cache layout over the mesh. Stacked decode ([L, B, H, ...]
        leaves) runs a sequential layer scan on EVERY device, so the layer dim
        must stay local — decode under pipeline layouts is pure data
        parallelism over `pipe`: batch shards over (pipe, data, fsdp), kv heads
        over `model`. Left to GSPMD propagation the cache came back REPLICATED
        over pipe (17.5G/device at 7B decode batch 128), and sharding the LAYER
        dim over pipe instead makes the scan all-gather the whole cache (both
        measured by the v5e compiler, scripts/scale_proof.py). No-op outside a
        mesh context; non-divisible dims are dropped."""
        mesh = ambient_mesh()
        if mesh is None:
            return x
        from trlx_tpu.parallel.sharding import _clip_spec
        from jax.sharding import NamedSharding, PartitionSpec

        batch_entry = ((PIPE_AXIS,) + BATCH_AXES) if stacked else BATCH_AXES
        entries = ([None] if stacked else []) + [batch_entry, MODEL_AXIS]
        entries += [None] * (x.ndim - len(entries))
        spec = _clip_spec(PartitionSpec(*entries), x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def init_cache(self, batch_size: int, max_length: int, dtype=None) -> KVCache:
        c = self.config
        per_layer = c.cache_layout(batch_size, max_length, dtype)
        entry_bytes = kv_cache.bytes_per_token(per_layer, batch_size)  # one layer's, for the gauges below
        if c.attention_kind == "mla":
            from trlx_tpu.utils.metrics import gauges

            gauges.set("mla/cache_bytes_per_token", c.num_layers * entry_bytes)
        if c.sparse_attention:
            from trlx_tpu.utils.metrics import gauges

            gauges.set("dsa/cache_bytes_per_token", c.cache_entries * entry_bytes)
        if c.loop_steps > 1:
            from trlx_tpu.utils.metrics import gauges

            gauges.set("loop/passes", c.loop_steps)
            gauges.set("loop/cache_bytes_per_token", c.cache_entries * entry_bytes)
        if c.conv_layers:
            from trlx_tpu.utils.metrics import gauges

            state = c.conv_state_layout(batch_size, dtype)
            gauges.set("hybrid/attention_layers", c.attention_layers)
            gauges.set("hybrid/conv_layers", c.conv_layers)
            gauges.set("hybrid/cache_bytes_per_token", c.cache_entries * entry_bytes)
            gauges.set("hybrid/state_bytes_per_row",
                       c.loop_steps * c.conv_layers * kv_cache.state_bytes_per_row(state))
        if c.stacked:
            # nn.scan layout needs one [L, ...] array per k/v
            out = {
                key: self._constrain_cache_leaf(
                    jnp.zeros((c.num_layers,) + shp, dt), stacked=True
                )
                for key, (shp, dt) in per_layer.items()
            }
            out["index"] = jnp.array(0, jnp.int32)
            return out
        # Per-layer list layout: the decode while_loop then carries each layer's
        # buffer as its own carry leaf, so the per-step dynamic_update_slice is a
        # true in-place single-token write. A single stacked [L, ...] array forces
        # XLA to slice out every layer and re-stack the WHOLE cache each step —
        # profiled at 3.6ms of a 4.65ms gpt2-124M decode step on one v5e chip
        # (~15x the HBM bound for this model). Looped layers hold an entry for
        # every (pass, layer) in the same list, entry pass * num_layers + layer. Where
        # layer_kinds makes some layers convolutions, the keys' and values' lists hold the
        # attention layers alone and "conv" the states of the convolution layers.
        out = {
            key: [jnp.zeros(shp, dt) for _ in range(c.cache_entries)]
            for key, (shp, dt) in per_layer.items()
        }
        if c.conv_layers:
            (shp, dt), = state.values()
            out["conv"] = [jnp.zeros(shp, dt) for _ in range(c.loop_steps * c.conv_layers)]
        out["index"] = jnp.array(0, jnp.int32)
        return out

    def init_paged_cache(
        self, num_blocks: int, block_size: int, max_blocks_per_seq: int,
        batch_size: int, dtype=None,
    ) -> KVCache:
        """Block-pool cache for the serving engine (see ops/paged_attention.py):
        per-layer k/v pools ``[num_blocks, Hkv, block_size, D]`` (int8 + f32
        row scales under ``kv_cache_quant``) plus shared ``block_tables``
        ``[B, max_blocks_per_seq]`` and ``context_lens`` ``[B]``. Block 0 is
        the allocator's reserved null block; fresh tables point at it."""
        from trlx_tpu.ops.paged_attention import paged_pool_layout

        c = self.config
        if c.attention_kind == "mla":
            raise ValueError(_MLA_REFUSALS["paged"])
        if c.loop_steps > 1:
            raise ValueError(_LOOP_REFUSALS["paged"])
        if c.conv_layers:
            raise ValueError(_CONV_REFUSALS["paged"])
        if c.sparse_attention:
            raise ValueError(_DSA_REFUSALS["paged"])
        layout = paged_pool_layout(
            num_blocks, block_size, c.kv_heads, c.dim_per_head,
            dtype or c.compute_dtype, c.kv_cache_quant,
        )
        if c.stacked:
            # nn.scan layout: one stacked [L, ...] pool per k/v leaf, walked by
            # paged_verify's layer scan (paged_decode keeps the per-layer list
            # restriction — see its docstring)
            out = {
                key: jnp.zeros((c.num_layers,) + shp, dt)
                for key, (shp, dt) in layout.items()
            }
        else:
            out = {
                key: [jnp.zeros(shp, dt) for _ in range(c.num_layers)]
                for key, (shp, dt) in layout.items()
            }
        out["block_tables"] = jnp.zeros((batch_size, max_blocks_per_seq), jnp.int32)
        out["context_lens"] = jnp.zeros((batch_size,), jnp.int32)
        return out

    def paged_decode(self, input_ids: jnp.ndarray, cache: KVCache):
        """One decode step against the paged block-pool cache: ``input_ids``
        [B, 1], ``cache`` from :meth:`init_paged_cache` (pools possibly
        populated by the serving engine's prefill scatter). Returns
        (logits [B, 1, V], hidden [B, 1, Hid], new cache with
        ``context_lens`` advanced by 1). Idle slots (context_lens == 0 with a
        null block table row) still produce finite output — the engine
        discards it."""
        c = self.config
        if c.loop_steps > 1:
            raise ValueError(_LOOP_REFUSALS["paged"])
        if c.conv_layers:
            raise ValueError(_CONV_REFUSALS["paged"])
        if c.sparse_attention:
            raise ValueError(_DSA_REFUSALS["paged"])
        if c.stacked:
            raise NotImplementedError("paged decode: per-layer list layout only")
        if c.peft_type in ("prompt", "prefix"):
            raise NotImplementedError("paged decode does not support peft prompt/prefix")
        B, T = input_ids.shape
        if T != 1:
            raise ValueError(
                "paged_decode is a single-token step; use paged_verify for "
                "multi-token appends"
            )
        lens = cache["context_lens"]
        positions = lens[:, None].astype(jnp.int32)  # incoming token's position
        x = self.embed(input_ids, positions)
        pool_keys = [k for k in cache if k not in ("block_tables", "context_lens")]
        new_layer_caches = []
        for i, layer in enumerate(self.layers):
            layer_cache = {key: cache[key][i] for key in pool_keys}
            layer_cache["block_tables"] = cache["block_tables"]
            layer_cache["context_lens"] = lens
            x, new_lc = layer(x, None, positions, layer_cache, None)
            new_layer_caches.append(new_lc)
        logits, hidden = self._final(x)
        new_cache = {
            key: [lc[key] for lc in new_layer_caches] for key in pool_keys
        }
        new_cache["block_tables"] = cache["block_tables"]
        new_cache["context_lens"] = lens + 1
        return logits, hidden, new_cache

    def paged_verify(self, input_ids: jnp.ndarray, cache: KVCache):
        """Multi-token paged step (speculative verify / chunked prefill):
        ``input_ids`` [B, Q]; token j is written through the block table at
        position ``context_lens + j`` and attends causally over every earlier
        position plus itself. Returns (logits [B, Q, V], hidden [B, Q, Hid],
        new cache with ``context_lens`` UNCHANGED) — the caller decides how
        far the frontier actually advances (speculative accept count, chunk
        length); KV rows written past the accepted frontier stay invisible to
        the attention mask and are rewritten before they can ever become
        valid, which is what makes rollback free. Supports both the per-layer
        list layout and the stacked ``scan_layers`` layout (pools ``[L, ...]``,
        walked by the layer scan with the table/lens broadcast across L)."""
        c = self.config
        if c.loop_steps > 1:
            raise ValueError(_LOOP_REFUSALS["paged"])
        if c.conv_layers:
            raise ValueError(_CONV_REFUSALS["paged"])
        if c.sparse_attention:
            raise ValueError(_DSA_REFUSALS["paged"])
        if c.peft_type in ("prompt", "prefix"):
            raise NotImplementedError("paged verify does not support peft prompt/prefix")
        B, Q = input_ids.shape
        lens = cache["context_lens"]
        positions = lens[:, None].astype(jnp.int32) + jnp.arange(Q, dtype=jnp.int32)[None, :]
        x = self.embed(input_ids, positions)
        pool_keys = [k for k in cache if k not in ("block_tables", "context_lens")]
        if c.stacked:
            scan_cache = {key: cache[key] for key in pool_keys}
            scan_cache["block_tables"] = jnp.broadcast_to(
                cache["block_tables"], (c.num_layers,) + cache["block_tables"].shape
            )
            scan_cache["context_lens"] = jnp.broadcast_to(
                lens, (c.num_layers,) + lens.shape
            )
            x, ys = self.layers_scan(x, None, positions, scan_cache, None)
            new_cache = {key: ys[key] for key in pool_keys}
        else:
            new_layer_caches = []
            for i, layer in enumerate(self.layers):
                layer_cache = {key: cache[key][i] for key in pool_keys}
                layer_cache["block_tables"] = cache["block_tables"]
                layer_cache["context_lens"] = lens
                x, new_lc = layer(x, None, positions, layer_cache, None)
                new_layer_caches.append(new_lc)
            new_cache = {
                key: [lc[key] for lc in new_layer_caches] for key in pool_keys
            }
        new_cache["block_tables"] = cache["block_tables"]
        new_cache["context_lens"] = lens
        logits, hidden = self._final(x)
        return logits, hidden, new_cache
