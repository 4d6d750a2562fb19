"""T5-style encoder-decoder LM in Flax (capability parity with the reference's
seq2seq path: `AutoModelForSeq2SeqLMWithValueHead`/`T5Branch`,
`/root/reference/trlx/models/modeling_ppo.py:1242-1593`, and the ILQL seq2seq heads,
`modeling_ilql.py:481-666`).

Architecture: T5 — RMS-style layernorm (no mean subtraction, no bias), relative
position bias in the first self-attention layer of each stack (shared by the rest),
ReLU or gated-GeLU FFN, no biases, tied embeddings with ``d_model**-0.5`` decoder
output scaling (HF `tie_word_embeddings`). Decoder supports a functional KV cache for
jitted incremental decoding; cross-attention K/V are precomputed once at prefill.
"""

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple


import jax
import jax.numpy as jnp
from flax import linen as nn

from trlx_tpu.ops import kv_cache


@dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    d_ff: int = 2048
    num_layers: int = 6  # encoder layers
    num_decoder_layers: int = 6
    num_heads: int = 8
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "relu"  # "relu" | "gated-gelu"
    tie_word_embeddings: bool = True
    initializer_factor: float = 1.0
    decoder_start_token_id: int = 0
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # LoRA adapters (parity: reference peft support is architecture-agnostic,
    # modeling_base.py:162-240 — T5 must not be excluded). Target names are the
    # T5 projection modules: q/k/v/o (attention) and wi/wi_0/wi_1/wo (FFN) —
    # HF peft's default for T5 is ("q", "v").
    lora_r: int = 0
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("q", "v")
    # int8 decoder self-attention KV cache (see TransformerConfig.kv_cache_quant)
    kv_cache_quant: bool = False

    @property
    def is_gated(self) -> bool:
        return self.feed_forward_proj.startswith("gated")

    def replace(self, **kw) -> "T5Config":
        return replace(self, **kw)


def from_hf_t5_config(hf_config, overrides: Optional[Dict[str, Any]] = None) -> T5Config:
    config = T5Config(
        vocab_size=hf_config.vocab_size, d_model=hf_config.d_model, d_kv=hf_config.d_kv,
        d_ff=hf_config.d_ff, num_layers=hf_config.num_layers,
        num_decoder_layers=hf_config.num_decoder_layers, num_heads=hf_config.num_heads,
        relative_attention_num_buckets=hf_config.relative_attention_num_buckets,
        relative_attention_max_distance=getattr(hf_config, "relative_attention_max_distance", 128),
        layer_norm_epsilon=hf_config.layer_norm_epsilon,
        feed_forward_proj="gated-gelu" if "gated" in hf_config.feed_forward_proj else "relu",
        tie_word_embeddings=hf_config.tie_word_embeddings,
        decoder_start_token_id=hf_config.decoder_start_token_id or 0,
    )
    if overrides:
        config = config.replace(**overrides)
    return config


def relative_position_bucket(relative_position, bidirectional: bool, num_buckets: int, max_distance: int):
    """T5 relative position bucketing (same math as HF)."""
    ret = 0
    n = -relative_position
    if bidirectional:
        num_buckets //= 2
        ret += (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_if_large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact + 1e-6)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    val_if_large = jnp.minimum(val_if_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_if_large)


class T5LayerNorm(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x):
        c = self.config
        scale = self.param("scale", nn.initializers.ones, (c.d_model,), c.param_dtype)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + c.layer_norm_epsilon)
        return (x * scale).astype(c.compute_dtype)


class T5Attention(nn.Module):
    config: T5Config
    has_relative_bias: bool = False
    bidirectional: bool = True

    def setup(self):
        from trlx_tpu.models.transformer import LoraDense

        c = self.config
        inner = c.num_heads * c.d_kv
        # same param layout as nn.Dense; low-rank adapters engage per target name
        dense = lambda feats, name: LoraDense(
            feats, use_bias=False, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            kernel_init=nn.initializers.normal(c.initializer_factor * (c.d_model**-0.5)),
            r=c.lora_r if name in c.lora_targets else 0, alpha=c.lora_alpha,
        )
        self.q = dense(inner, "q")
        self.k = dense(inner, "k")
        self.v = dense(inner, "v")
        self.o = dense(c.d_model, "o")
        if self.has_relative_bias:
            self.relative_attention_bias = nn.Embed(
                c.relative_attention_num_buckets, c.num_heads,
                dtype=c.compute_dtype, param_dtype=c.param_dtype,
                embedding_init=nn.initializers.normal(c.initializer_factor * (c.d_model**-0.5)),
            )

    def compute_bias(self, q_pos: jnp.ndarray, k_pos: jnp.ndarray) -> jnp.ndarray:
        """[1, H, Tq, Tk] position bias."""
        c = self.config
        rel = k_pos[None, :] - q_pos[:, None]
        buckets = relative_position_bucket(
            rel, self.bidirectional, c.relative_attention_num_buckets,
            c.relative_attention_max_distance,
        )
        values = self.relative_attention_bias(buckets)  # [Tq, Tk, H]
        return values.transpose(2, 0, 1)[None]

    def __call__(
        self,
        x: jnp.ndarray,
        kv: Optional[jnp.ndarray] = None,
        mask_bias: Optional[jnp.ndarray] = None,
        position_bias: Optional[jnp.ndarray] = None,
        cache: Optional[Dict[str, jnp.ndarray]] = None,
        kv_static: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    ):
        """x [B,T,D]; kv = encoder states for cross-attn; cache = self-attn KV cache;
        kv_static = precomputed cross-attn (k, v). T5 does NOT scale scores by
        1/sqrt(d) (folded into init)."""
        c = self.config
        B, T, _ = x.shape
        q = self.q(x).reshape(B, T, c.num_heads, c.d_kv)
        # kh/vh [B, H, S, D] — the cache layout (contiguous per-(b,h) along S,
        # see TransformerLM.Attention: avoids a full-cache transposed copy per
        # decode step)
        if kv_static is not None:
            kh, vh = kv_static  # already [B, H, S, D] (cross_kv)
            new_cache = None
        else:
            src = x if kv is None else kv
            S = src.shape[1]
            k = self.k(src).reshape(B, S, c.num_heads, c.d_kv)
            v = self.v(src).reshape(B, S, c.num_heads, c.d_kv)
            kh = k.transpose(0, 2, 1, 3)
            vh = v.transpose(0, 2, 1, 3)
            if cache is not None:
                new_cache = kv_cache.write_kv_cache(cache, kh, vh, cache["index"])
                kh, vh = kv_cache.read_kv_cache(new_cache, c.compute_dtype)
            else:
                new_cache = None
        scores = jnp.einsum("bthd,bhsd->bhts", q, kh).astype(jnp.float32)
        if position_bias is not None:
            scores = scores + position_bias.astype(jnp.float32)
        if mask_bias is not None:
            scores = scores + mask_bias
        probs = jax.nn.softmax(scores, axis=-1).astype(c.compute_dtype)
        out = jnp.einsum("bhts,bhsd->bthd", probs, vh).reshape(B, T, c.num_heads * c.d_kv)
        return self.o(out), new_cache


class T5FFN(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x):
        from trlx_tpu.models.transformer import LoraDense

        c = self.config
        dense = lambda feats, name: LoraDense(
            feats, use_bias=False, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            kernel_init=nn.initializers.normal(c.initializer_factor * (c.d_model**-0.5)), name=name,
            r=c.lora_r if name in c.lora_targets else 0, alpha=c.lora_alpha,
        )
        if c.is_gated:
            h = jax.nn.gelu(dense(c.d_ff, "wi_0")(x), approximate=True) * dense(c.d_ff, "wi_1")(x)
        else:
            h = jax.nn.relu(dense(c.d_ff, "wi")(x))
        return dense(c.d_model, "wo")(h)


class T5EncoderBlock(nn.Module):
    config: T5Config
    has_relative_bias: bool = False

    def setup(self):
        self.ln_1 = T5LayerNorm(self.config)
        self.attn = T5Attention(self.config, self.has_relative_bias, bidirectional=True)
        self.ln_2 = T5LayerNorm(self.config)
        self.mlp = T5FFN(self.config)

    def __call__(self, x, mask_bias, position_bias):
        a, _ = self.attn(self.ln_1(x), None, mask_bias, position_bias)
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return x


class T5DecoderBlock(nn.Module):
    config: T5Config
    has_relative_bias: bool = False

    def setup(self):
        self.ln_1 = T5LayerNorm(self.config)
        self.self_attn = T5Attention(self.config, self.has_relative_bias, bidirectional=False)
        self.ln_cross = T5LayerNorm(self.config)
        self.cross_attn = T5Attention(self.config, False, bidirectional=True)
        self.ln_2 = T5LayerNorm(self.config)
        self.mlp = T5FFN(self.config)

    def __call__(self, x, self_mask_bias, position_bias, enc_states, cross_mask_bias, cache=None, cross_kv=None):
        a, new_cache = self.self_attn(self.ln_1(x), None, self_mask_bias, position_bias, cache)
        x = x + a
        kv_arg = None if cross_kv is not None else enc_states
        ca, _ = self.cross_attn(self.ln_cross(x), kv_arg, cross_mask_bias, None, None, cross_kv)
        x = x + ca
        x = x + self.mlp(self.ln_2(x))
        return x, new_cache

    def cross_kv(self, enc_states):
        """Precompute cross-attention K/V from encoder states (prefill).
        Returned in the [B, H, S, D] attention layout."""
        c = self.config
        B, S, _ = enc_states.shape
        k = self.cross_attn.k(enc_states).reshape(B, S, c.num_heads, c.d_kv)
        v = self.cross_attn.v(enc_states).reshape(B, S, c.num_heads, c.d_kv)
        return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


class T5LM(nn.Module):
    """Encoder-decoder LM; methods: encode / decode / __call__ (full seq2seq fwd)."""

    config: T5Config

    def setup(self):
        c = self.config
        self.shared = nn.Embed(
            c.vocab_size, c.d_model, dtype=c.compute_dtype, param_dtype=c.param_dtype,
            embedding_init=nn.initializers.normal(c.initializer_factor),
        )
        self.encoder_blocks = [
            T5EncoderBlock(c, has_relative_bias=(i == 0)) for i in range(c.num_layers)
        ]
        self.encoder_ln = T5LayerNorm(c)
        self.decoder_blocks = [
            T5DecoderBlock(c, has_relative_bias=(i == 0)) for i in range(c.num_decoder_layers)
        ]
        self.decoder_ln = T5LayerNorm(c)
        if not c.tie_word_embeddings:
            self.lm_head = nn.Dense(
                c.vocab_size, use_bias=False, dtype=c.compute_dtype, param_dtype=c.param_dtype,
                kernel_init=nn.initializers.normal(c.initializer_factor),
            )

    def encode(self, input_ids: jnp.ndarray, attention_mask: Optional[jnp.ndarray] = None):
        B, S = input_ids.shape
        x = self.shared(input_ids)
        mask_bias = None
        if attention_mask is not None:
            mask_bias = jnp.where(attention_mask[:, None, None, :].astype(bool), 0.0, -1e9).astype(jnp.float32)
        pos = jnp.arange(S)
        position_bias = self.encoder_blocks[0].attn.compute_bias(pos, pos)
        for block in self.encoder_blocks:
            x = block(x, mask_bias, position_bias)
        return self.encoder_ln(x)

    def _decoder_stack(
        self, x, self_mask_bias, position_bias, enc_states, cross_mask_bias, cache, cross_kvs,
        branch_layer=None,
    ):
        new_caches = []
        branch_hidden = None
        for i, block in enumerate(self.decoder_blocks):
            if branch_layer is not None and i == branch_layer:
                branch_hidden = x
            layer_cache = None
            if cache is not None:
                layer_cache = {key: cache[key][i] for key in cache if key != "index"}
                layer_cache["index"] = cache["index"]
            ckv = None if cross_kvs is None else (cross_kvs[0][i], cross_kvs[1][i])
            x, new_lc = block(x, self_mask_bias, position_bias, enc_states, cross_mask_bias, layer_cache, ckv)
            if cache is not None:
                new_caches.append(new_lc)
        hidden = self.decoder_ln(x)
        new_cache = None
        if cache is not None:
            # per-layer list layout (see TransformerLM.init_cache): restacking
            # would copy the whole cache every decode step
            new_cache = {
                key: [lc[key] for lc in new_caches] for key in new_caches[0]
            }
            new_cache["index"] = cache["index"] + x.shape[1]
        return hidden, new_cache, branch_hidden

    def _head(self, hidden):
        c = self.config
        if c.tie_word_embeddings:
            hidden = hidden * (c.d_model**-0.5)
            return hidden @ self.shared.embedding.astype(c.compute_dtype).T
        return self.lm_head(hidden)

    def _self_bias_nocache(self, T, decoder_attention_mask):
        """Cache-free causal self-attention bias [*,1,T,T]."""
        causal = jnp.tril(jnp.ones((T, T), dtype=bool))[None, None]
        if decoder_attention_mask is not None:
            causal = jnp.logical_and(causal, decoder_attention_mask[:, None, None, :].astype(bool))
        return jnp.where(causal, 0.0, -1e9).astype(jnp.float32)

    def _cross_bias(self, encoder_attention_mask):
        if encoder_attention_mask is None:
            return None
        return jnp.where(
            encoder_attention_mask[:, None, None, :].astype(bool), 0.0, -1e9
        ).astype(jnp.float32)

    def decode(
        self,
        decoder_input_ids: jnp.ndarray,
        enc_states: jnp.ndarray,
        encoder_attention_mask: Optional[jnp.ndarray] = None,
        decoder_attention_mask: Optional[jnp.ndarray] = None,
        positions: Optional[jnp.ndarray] = None,
        cache: Optional[Dict[str, Any]] = None,
        cross_kvs: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    ):
        """Returns (logits, hidden, new_cache). With ``cache``, T may be 1 and
        ``positions`` gives absolute decoder positions for the relative bias."""
        B, T = decoder_input_ids.shape
        x = self.shared(decoder_input_ids)

        if cache is not None:
            S = cache["k"][0].shape[2]  # per-layer [B,H,S,D]
            idx = cache["index"]
            if positions is None:
                positions = idx + jnp.arange(T, dtype=jnp.int32)
            else:
                positions = positions.reshape(-1)[:T] if positions.ndim > 1 else positions
            kv_slot = jnp.arange(S)[None, None, None, :]
            q_slot = (idx + jnp.arange(T, dtype=jnp.int32))[None, None, :, None]
            causal = kv_slot <= q_slot
            if decoder_attention_mask is not None:
                causal = jnp.logical_and(causal, decoder_attention_mask[:, None, None, :].astype(bool))
            self_mask_bias = jnp.where(causal, 0.0, -1e9).astype(jnp.float32)
            k_pos = jnp.arange(S)
            position_bias = self.decoder_blocks[0].self_attn.compute_bias(positions, k_pos)
        else:
            self_mask_bias = self._self_bias_nocache(T, decoder_attention_mask)
            pos = jnp.arange(T)
            position_bias = self.decoder_blocks[0].self_attn.compute_bias(pos, pos)

        cross_mask_bias = self._cross_bias(encoder_attention_mask)

        hidden, new_cache, _ = self._decoder_stack(
            x, self_mask_bias, position_bias, enc_states, cross_mask_bias, cache, cross_kvs
        )
        return self._head(hidden), hidden, new_cache

    def __call__(
        self,
        input_ids: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
        decoder_input_ids: Optional[jnp.ndarray] = None,
        decoder_attention_mask: Optional[jnp.ndarray] = None,
    ):
        """Full seq2seq forward: (logits, decoder_hidden, encoder_states)."""
        enc = self.encode(input_ids, attention_mask)
        logits, hidden, _ = self.decode(
            decoder_input_ids, enc, attention_mask, decoder_attention_mask
        )
        return logits, hidden, enc

    def forward_with_branch(
        self,
        input_ids: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray],
        decoder_input_ids: jnp.ndarray,
        decoder_attention_mask: Optional[jnp.ndarray],
        branch_layer: int,
    ):
        """Full forward that also captures the hydra branch point: returns
        (logits, decoder_hidden, encoder_states, branch_hidden, position_bias).
        ``branch_hidden`` is the input activation of decoder block
        ``branch_layer``; ``position_bias`` is the (frozen-by-construction)
        relative bias the branch re-uses."""
        enc = self.encode(input_ids, attention_mask)
        B, T = decoder_input_ids.shape
        x = self.shared(decoder_input_ids)
        self_mask_bias = self._self_bias_nocache(T, decoder_attention_mask)
        pos = jnp.arange(T)
        position_bias = self.decoder_blocks[0].self_attn.compute_bias(pos, pos)
        cross_mask_bias = self._cross_bias(attention_mask)
        hidden, _, branch_hidden = self._decoder_stack(
            x, self_mask_bias, position_bias, enc, cross_mask_bias, None, None,
            branch_layer=branch_layer,
        )
        return self._head(hidden), hidden, enc, branch_hidden, position_bias

    def forward_branch(
        self,
        branch_hidden: jnp.ndarray,
        enc_states: jnp.ndarray,
        encoder_attention_mask: Optional[jnp.ndarray],
        decoder_attention_mask: Optional[jnp.ndarray],
        position_bias: jnp.ndarray,
        start_layer: int,
    ):
        """Frozen decoder-top branch: run decoder blocks [start_layer:] + final LN
        + head from a captured branch activation (the reference's ``T5Branch``,
        modeling_ppo.py:1483-1593 — a decoder-top reference model instead of a
        full frozen T5 copy). Apply with the frozen param subtree from
        :func:`trlx_tpu.models.policy.t5_branch_param_subtree`; encoder states
        and position_bias come from the live model, whose encoder / bottom
        decoder blocks are frozen by the train mask, so they equal the reference
        model's."""
        B, T, _ = branch_hidden.shape
        self_mask_bias = self._self_bias_nocache(T, decoder_attention_mask)
        cross_mask_bias = self._cross_bias(encoder_attention_mask)
        x = branch_hidden
        for block in self.decoder_blocks[start_layer:]:
            x, _ = block(x, self_mask_bias, position_bias, enc_states, cross_mask_bias, None, None)
        return self._head(self.decoder_ln(x))

    def precompute_cross_kv(self, enc_states):
        # per-layer lists (not stacked arrays): slicing layer i from a stacked
        # [L, ...] array inside the decode loop copies the whole thing per step
        ks, vs = [], []
        for block in self.decoder_blocks:
            k, v = block.cross_kv(enc_states)
            ks.append(k)
            vs.append(v)
        return ks, vs

    def init_cache(self, batch_size: int, max_length: int, dtype=None) -> Dict[str, Any]:
        c = self.config
        dtype = dtype or c.compute_dtype
        # per-layer list layout: in-place single-token writes in the decode loop
        # (a stacked [L, ...] array forces full-cache slice/restack copies per step)
        shape = (batch_size, c.num_heads, max_length, c.d_kv)
        per_layer = kv_cache.kv_cache_layout(shape, dtype, c.kv_cache_quant)
        out = {
            key: [jnp.zeros(shp, dt) for _ in range(c.num_decoder_layers)]
            for key, (shp, dt) in per_layer.items()
        }
        out["index"] = jnp.array(0, jnp.int32)
        return out
