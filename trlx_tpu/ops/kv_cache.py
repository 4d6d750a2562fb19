"""What one layer of the contiguous (one-shot generator's) cache holds, and how it is written and read.

A layer's cache is a plain dict of arrays — the decode ``while_loop`` carries it, so its pytree
structure is part of the compiled program — in one of five formats:

- per-head rows: ``k``, ``v`` ``[B, Hkv, S, D]`` in the compute dtype, contiguous along S per (b, h) so
  that the decode matvec streams them (``[B, S, Hkv, D]`` cost a transposed copy of every layer a step).
  Where fewer than 128 rows decode, ``fold`` kv heads stand beside each row (:func:`fold_heads`):
  ``[B * fold, Hkv / fold, S, D]``, so that the decode kernel, which takes the leading dimension on the
  128 lanes, reads (row, kv head) pairs where it read padding. The shapes alone say so
  (``cache["k"].shape[0] // B``); who chooses the fold is ``ops/attention.py::decode_cache_fold``;
- per-head int8 rows (``kv_cache_quant``): ``k``, ``v`` int8 plus ``k_scale``, ``v_scale``
  ``[B, Hkv, S, 1]`` float32, one symmetric scale a row (the paged pool quantizes its rows the same way);
- latent: ``c`` ``[B, S, rank]``, the normed latent, and ``k_rope`` ``[B, S, rope]``, the rotated shared key;
- indexed (learned sparse attention, ``ops/sparse_attention.py``): ``k``, ``v`` ``[B, S, Hkv, D]`` slot-major,
  so that the keys and values of a selected slot are one contiguous row to gather, and ``ki`` ``[B, S, Di]``,
  the indexer's key of every slot, which a decode step reads whole;
- a short convolution's state: ``conv`` ``[B, taps - 1, d]``, the row's last gated inputs, the ones the next
  token's filter reads beside its own — a fixed size whatever the length, so nothing of it is "per token".

A layout is the same dict with ``(shape, dtype)`` in place of each array; how many layers there are and
whether they are stacked is the model's. The serving engine's paged cache is laid out in
``ops/paged_attention.py`` and only recognised here (:func:`is_paged`).
"""

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Layout = Dict[str, Tuple[Tuple[int, ...], jnp.dtype]]


def kv_cache_layout(shape: Tuple[int, ...], dtype, quant: bool, fold: int = 1) -> Layout:
    """Per-head buffers of one layer, ``shape`` ``[B, Hkv, S, D]``: int8 values + one f32
    scale per row when ``quant``, else ``fold`` kv heads beside each row."""
    if quant:
        assert fold == 1, "int8 rows keep the einsum path, which reads them unfolded"
        return {
            "k": (shape, jnp.int8), "v": (shape, jnp.int8),
            "k_scale": (shape[:-1] + (1,), jnp.float32),
            "v_scale": (shape[:-1] + (1,), jnp.float32),
        }
    B, kv_heads, S, D = shape
    assert kv_heads % fold == 0, (kv_heads, fold)
    shape = (B * fold, kv_heads // fold, S, D)
    return {"k": (shape, dtype), "v": (shape, dtype)}


def fold_heads(x: jnp.ndarray, fold: int) -> jnp.ndarray:
    """``[B, Hkv, T, D]`` -> ``[B * fold, Hkv / fold, T, D]``: row ``b * fold + i`` holds row ``b``'s kv
    head ``hg * fold + i`` at head position ``hg`` — rows-major, so a shard of the batch axis keeps whole
    rows, and a shard of the heads a run of neighbouring kv heads."""
    if fold == 1:  # graftcheck: noqa[JX004] — a Python int read off the shapes
        return x
    B, kv_heads, T, D = x.shape
    return x.reshape(B, kv_heads // fold, fold, T, D).transpose(0, 2, 1, 3, 4).reshape(B * fold, kv_heads // fold, T, D)


def unfold_heads(x: jnp.ndarray, fold: int) -> jnp.ndarray:
    """:func:`fold_heads` undone: ``[B * fold, Hkv / fold, T, D]`` -> ``[B, Hkv, T, D]``."""
    if fold == 1:  # graftcheck: noqa[JX004] — a Python int read off the shapes
        return x
    rows, held, T, D = x.shape
    return x.reshape(rows // fold, fold, held, T, D).transpose(0, 2, 1, 3, 4).reshape(rows // fold, held * fold, T, D)


def latent_cache_layout(batch_size: int, max_length: int, rank: int, rope_dim: int, dtype) -> Layout:
    """A latent-attention layer's buffers: the normed latent and the rotated shared key of every token."""
    return {
        "c": ((batch_size, max_length, rank), dtype),
        "k_rope": ((batch_size, max_length, rope_dim), dtype),
    }


def indexed_cache_layout(batch_size: int, max_length: int, kv_heads: int, head_dim: int, index_dim: int,
                         dtype) -> Layout:
    """A learned-sparse-attention layer's buffers: slot-major keys and values and the indexer's key."""
    kv = ((batch_size, max_length, kv_heads, head_dim), dtype)
    return {"k": kv, "v": kv, "ki": ((batch_size, max_length, index_dim), dtype)}


def conv_state_layout(batch_size: int, taps: int, width: int, dtype) -> Layout:
    """A short-convolution layer's buffer: the last ``taps - 1`` gated inputs of every row."""
    return {"conv": ((batch_size, taps - 1, width), dtype)}


def has_row_scales(layer) -> bool:
    """Whether a layer's cache (or layout) holds int8 rows with a scale each."""
    return "k_scale" in layer


def is_latent(layer) -> bool:
    """Whether a layer's cache (or layout) holds latents, not per-head keys and values."""
    return "c" in layer


def is_indexed(layer) -> bool:
    """Whether a layer's cache (or layout) holds slot-major keys and values beside an indexer's keys."""
    return "ki" in layer


def is_paged(layer) -> bool:
    """Whether a layer's cache is the serving engine's block pool, read through block tables."""
    return "block_tables" in layer


def bytes_per_token(layout: Layout, batch_size: int) -> int:
    """Bytes one token's slot takes in one layer of ``layout`` for ``batch_size`` rows, every buffer
    counted (a folded layer holds ``batch_size * fold`` rows of ``Hkv / fold`` heads: the same bytes)."""
    slot_axis = 1 if is_latent(layout) or is_indexed(layout) else 2
    return sum(
        math.prod(shape) // (batch_size * shape[slot_axis]) * jnp.dtype(dtype).itemsize
        for shape, dtype in layout.values()
    )


def state_bytes_per_row(layout: Layout) -> int:
    """Bytes one row's state takes in one convolution layer of ``layout``."""
    (shape, dtype), = layout.values()
    return math.prod(shape[1:]) * jnp.dtype(dtype).itemsize


def quantize_kv_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-row int8 quantization over the trailing (head) dim:
    x [..., D] -> (int8 values [..., D], f32 scales [..., 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def write_kv_cache(cache: Dict[str, jnp.ndarray], kT: jnp.ndarray, vT: jnp.ndarray, idx):
    """Append [B,H,T,D] rows at slot ``idx``; quantizes when the cache carries
    scale planes (kv_cache_quant layout), folds them where the cache is folded. Shared by the
    causal and T5 decoders — the quant scheme must stay identical between them."""
    at = (0, 0, idx, 0)
    if has_row_scales(cache):  # graftcheck: noqa[JX004] — a key of the dict, which is the carry's structure
        kq, ks = quantize_kv_rows(kT)
        vq, vs = quantize_kv_rows(vT)
        return {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq, at),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq, at),
            "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], ks, at),
            "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], vs, at),
        }
    # the prefill's fold rides the transpose its caller makes to [B,H,T,D]; a decode step's is one token a row
    fold = cache["k"].shape[0] // kT.shape[0]
    return {
        "k": jax.lax.dynamic_update_slice(cache["k"], fold_heads(kT.astype(cache["k"].dtype), fold), at),
        "v": jax.lax.dynamic_update_slice(cache["v"], fold_heads(vT.astype(cache["v"].dtype), fold), at),
    }


def read_kv_cache(cache: Dict[str, jnp.ndarray], compute_dtype, batch_size: Optional[int] = None):
    """(kh, vh) ``[B, Hkv, S, D]`` to attend over; int8 caches dequantize on read — XLA fuses the
    convert+scale into the score einsum's operand stream, so HBM moves int8. A folded cache is
    unfolded to ``batch_size`` rows (None: it holds what it shows), a copy of it: the einsum paths'
    price, which the decode kernel does not pay."""
    if has_row_scales(cache):  # graftcheck: noqa[JX004] — a key of the dict, which is the carry's structure
        # multiply int8 values by the f32 scale at full precision, THEN cast:
        # casting the scale to bf16 first would truncate it to 8 mantissa bits
        # and stack avoidable error on top of the int8 quantization
        return (
            (cache["k"].astype(jnp.float32) * cache["k_scale"]).astype(compute_dtype),
            (cache["v"].astype(jnp.float32) * cache["v_scale"]).astype(compute_dtype),
        )
    fold = 1 if batch_size is None else cache["k"].shape[0] // batch_size
    return unfold_heads(cache["k"], fold), unfold_heads(cache["v"], fold)


def write_latent_cache(cache: Dict[str, jnp.ndarray], latent: jnp.ndarray, k_rope: jnp.ndarray, idx):
    """Append ``latent`` [B,T,rank] and ``k_rope`` [B,T,1,rope], the one rotated
    head all share, at slot ``idx``."""
    at = (0, idx, 0)
    return {
        "c": jax.lax.dynamic_update_slice(cache["c"], latent.astype(cache["c"].dtype), at),
        "k_rope": jax.lax.dynamic_update_slice(cache["k_rope"], k_rope[:, :, 0].astype(cache["k_rope"].dtype), at),
    }


def write_indexed_cache(cache: Dict[str, jnp.ndarray], k: jnp.ndarray, v: jnp.ndarray, ki: jnp.ndarray, idx):
    """Append ``k``, ``v`` [B,T,Hkv,D] and the indexer's ``ki`` [B,T,Di] at slot ``idx``, as the projections
    leave them."""
    return {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0)),
        "ki": jax.lax.dynamic_update_slice(cache["ki"], ki.astype(cache["ki"].dtype), (0, idx, 0)),
    }


def roll_conv_state(cache: Dict[str, jnp.ndarray], u: jnp.ndarray):
    """``u`` [B, T, d], the gated inputs of T new tokens (zero at padded positions), behind the state:
    (the ``taps - 1 + T`` inputs the tokens' filters read, in the order of time; the state after them,
    the last ``taps - 1`` of those). One rule for the prefill (the state is zeros, the prompt left-padded,
    so its last positions are real) and for a decode step (T = 1: the state rolls by one)."""
    state = cache["conv"]
    seen = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    return seen, {"conv": seen[:, seen.shape[1] - state.shape[1]:].astype(state.dtype)}
