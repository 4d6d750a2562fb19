"""Paged-KV decode attention: gather K/V through a block table.

The serving engine (``trlx_tpu/serving``) stores the KV cache as a pool of
fixed-size token blocks instead of one contiguous ``[B, Hkv, S, D]`` buffer
per sequence. Each decode slot addresses its tokens through a per-sequence
block table, so

- finished sequences release their blocks immediately (continuous batching
  never pays for the longest straggler's padding),
- shared prompt prefixes map to the *same* physical blocks (ref-counted by
  the allocator), and
- the attention for one step reads exactly ``context_len`` tokens per slot,
  not the padded maximum.

Two implementations with one contract:

- :func:`paged_attention_xla` — gather + masked softmax in plain XLA. The
  reference path: runs everywhere (CPU tests, deviceless AOT audit, SPMD
  meshes where a Mosaic kernel cannot be auto-partitioned).
- :func:`paged_attention_pallas` — a fused Pallas kernel that walks the block
  table via scalar prefetch (the table is read in BlockSpec index maps, so
  each grid step DMAs only its own block) and dequantizes int8 blocks
  in-register: the per-row scales fold into the scores (k) and the softmax
  probabilities (v), leaving the HBM stream a pure int8 load — the same
  algebra the dense decode path uses (models/transformer.py), so the two
  paths agree numerically.

Layouts (per layer):

- ``k_pool`` / ``v_pool``: ``[num_blocks, Hkv, block_size, D]`` in the cache
  dtype, or int8 under quantization — head-major, so one head of one block
  is a whole ``[block_size, D]`` tile: the block shape the chip's compiler
  accepts for the kernel at any ``block_size`` and dtype,
- ``k_scale`` / ``v_scale``: ``[num_blocks, Hkv, block_size]`` f32 per-row
  scales (quantized layout only; scheme: :func:`quantize_kv_rows`),
- ``block_tables``: ``[B, max_blocks]`` int32 physical block ids,
- ``context_lens``: ``[B]`` int32 — valid tokens per slot INCLUDING the token
  written this step (so it is always >= 1 for any slot that ran the step;
  idle slots recycle the reserved null block and their output is discarded
  by the scheduler, but it must still be finite).

Block 0 is reserved by the allocator as the null block: unused block-table
entries point at it, keeping every gather in range without masking tricks.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.analysis.ir.entrypoints import EntryArtifacts, register_entrypoint
from trlx_tpu.ops.kv_cache import has_row_scales, quantize_kv_rows

NEG_INF = -1e30  # kernel-internal mask value (f32 exact, like ops/attention.py)


def _gather_blocks(pool: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Each slot's blocks laid end to end: ``[NB, Hkv, BS, ...]`` through
    ``[B, MB]`` tables -> ``[B, Hkv, MB*BS, ...]``. Tables are always in range
    (unused entries point at the null block 0)."""
    g = jnp.moveaxis(jnp.take(pool, block_tables, axis=0), 2, 1)  # [B, Hkv, MB, BS, ...]
    return g.reshape(g.shape[:2] + (g.shape[2] * g.shape[3],) + g.shape[4:])


def paged_attention_xla(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Reference path for one decode token per slot: q ``[B, H, D]``,
    ``context_lens`` INCLUDING this step's token; returns ``[B, H, D]`` in
    ``q.dtype``. The ``Q == 1`` case of :func:`paged_verify_attention_xla`."""
    return paged_verify_attention_xla(
        q[:, None], k_pool, v_pool, block_tables, context_lens - 1, **kwargs
    )[:, 0]


def paged_verify_attention_xla(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Reference path: gather each slot's blocks, mask, softmax in f32.
    Scales (when given) fold into scores/probs exactly as the Pallas kernel
    and the dense int8 decode path do.

    q ``[B, Q, H, D]`` — Q tokens appended per slot in one step (decode,
    speculative verify, chunked prefill), token ``j`` sitting at position
    ``context_lens + j`` (``context_lens`` here = tokens present BEFORE this
    step's append, unlike the decode entry which gets the post-write count).
    Query ``j`` attends causally: positions ``< context_lens + j + 1``.
    Returns ``[B, Q, H, D]``.

    Q folds into the grouped-head row axis (query head h maps to kv head
    ``h // rep``) — masked scores sit at :data:`NEG_INF`, whose softmax
    probability underflows to exact 0, so stale/garbage KV past a slot's
    frontier contributes exactly nothing.
    """
    B, Q, H, D = q.shape
    Hkv = k_pool.shape[1]
    S = block_tables.shape[1] * k_pool.shape[2]
    rep = H // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    kh = _gather_blocks(k_pool, block_tables)  # [B, Hkv, S, D]
    vh = _gather_blocks(v_pool, block_tables)
    # [B, Q, H, D] -> [B, Hkv, Q*rep, D]; row r <-> (q_idx = r // rep, rep = r % rep)
    qg = (
        q.reshape(B, Q, Hkv, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Hkv, Q * rep, D)
    )

    scores = jnp.einsum(
        "bkrd,bksd->bkrs", qg, kh, preferred_element_type=jnp.float32
    ) * scale
    if k_scale is not None:
        scores = scores * _gather_blocks(k_scale, block_tables)[:, :, None, :]
    q_idx = jnp.arange(Q * rep, dtype=jnp.int32) // rep  # [Q*rep]
    valid = (
        jnp.arange(S, dtype=jnp.int32)[None, None, :]
        < context_lens[:, None, None] + q_idx[None, :, None] + 1
    )  # [B, Q*rep, S]
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * _gather_blocks(v_scale, block_tables)[:, :, None, :]
    out = jnp.einsum(
        "bkrs,bksd->bkrd", probs, vh.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    out = (
        out.reshape(B, Hkv, Q, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Q, H, D)
    )
    return out.astype(q.dtype)


def _paged_kernel(
    tables_ref,  # scalar prefetch: [B, MB] int32
    lens_ref,  # scalar prefetch: [B] int32 (tokens present BEFORE the append)
    q_ref,  # [rows, D], rows = Q*rep
    k_ref,  # [BS, D]: one head of one physical block
    v_ref,
    ks_ref,  # [Hkv, BS] f32 or None (bound via partial when quantized)
    vs_ref,
    o_ref,  # [rows, D]
    m_scratch,  # [rows, 1] f32
    l_scratch,  # [rows, 1] f32
    acc_scratch,  # [rows, D] f32
    *,
    block_size: int,
    num_blocks_per_seq: int,
    scale: float,
    rep: int,
):
    """One (slot, kv head) walks its block table along the innermost grid
    axis with the flash-attention running max/sum. The Q query positions fold
    into the row axis (row r is query ``r // rep``, query-head ``r % rep``), so
    only the mask limit is per-row: query j sees tokens ``< lens + j + 1``.
    Single-token decode is the ``Q == 1`` case.
    """
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q = q_ref[...].astype(jnp.float32)  # [rows, D]
    k = k_ref[...].astype(jnp.float32)  # [BS, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [rows, BS]
    if ks_ref is not None:
        s = s * ks_ref[pl.ds(h, 1), :]

    rows = q.shape[0]
    token_idx = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_size), 1
    )
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0) // rep
    s = jnp.where(token_idx < lens_ref[b] + q_idx + 1, s, NEG_INF)

    m_prev = m_scratch[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # fully-masked blocks keep m == NEG_INF; exp(s - m) would be exp(0) there
    p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)  # [rows, BS]
    alpha = jnp.exp(m_prev - m_new)
    l_scratch[...] = alpha * l_scratch[...] + jnp.sum(p, axis=1, keepdims=True)
    if vs_ref is not None:
        p = p * vs_ref[pl.ds(h, 1), :]
    v = v_ref[...].astype(jnp.float32)  # [BS, D]
    acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scratch[...] = m_new

    @pl.when(j == num_blocks_per_seq - 1)
    def _finalize():
        l = l_scratch[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # lens >= 1, but never NaN anyway
        o_ref[...] = (acc_scratch[...] / safe_l).astype(o_ref.dtype)


def _drop_scale_refs(kernel):
    """Adapter for the unquantized layout: same kernel, no scale operands."""

    @functools.wraps(kernel)
    def wrapped(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, *scratch):
        return kernel(
            tables_ref, lens_ref, q_ref, k_ref, v_ref, None, None, o_ref, *scratch
        )

    return wrapped


def paged_verify_attention_pallas(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused kernel: grid ``(B, Hkv, max_blocks)``, block table scalar-prefetched
    so each step's BlockSpec index map selects the physical block to DMA —
    the gather never materializes ``[B, S, Hkv, D]`` in HBM, and int8 blocks
    dequantize in-register via score/prob scale folding. q ``[B, Q, H, D]``;
    ``context_lens`` = tokens present BEFORE the append.

    Every block's two minor dimensions equal the operand's own (``[BS, D]`` of
    the head-major pool, ``[Hkv, BS]`` of a scale pool, ``[rows, D]`` of the
    grouped queries): the one block shape the TPU lowering takes at any
    ``block_size``, head count and dtype.
    """
    B, Q, H, D = q.shape
    NB, Hkv, BS, _ = k_pool.shape
    MB = block_tables.shape[1]
    rep = H // Hkv
    rows = Q * rep
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    quant = k_scale is not None

    qg = (
        q.reshape(B, Q, Hkv, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Hkv, rows, D)
    )
    kernel = functools.partial(
        _paged_kernel,
        block_size=BS, num_blocks_per_seq=MB, scale=scale, rep=rep,
    )
    if not quant:
        kernel = _drop_scale_refs(kernel)

    # index maps receive (*grid, *scalar_prefetch_refs)
    q_spec = pl.BlockSpec((None, None, rows, D), lambda b, h, j, t, n: (b, h, 0, 0))
    kv_spec = pl.BlockSpec(
        (None, None, BS, D), lambda b, h, j, t, n: (t[b, j], h, 0, 0)
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [qg, k_pool, v_pool]
    if quant:
        # all heads' scales of the block; the kernel picks row h
        sc_spec = pl.BlockSpec((None, Hkv, BS), lambda b, h, j, t, n: (t[b, j], 0, 0))
        in_specs += [sc_spec, sc_spec]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, MB),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32), *inputs)
    return (
        out.reshape(B, Hkv, Q, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Q, H, D)
    )


def paged_attention_pallas(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Single-token decode through the fused kernel: q ``[B, H, D]``,
    ``context_lens`` INCLUDING this step's token — the ``Q == 1`` verify."""
    return paged_verify_attention_pallas(
        q[:, None], k_pool, v_pool, block_tables, context_lens - 1, **kwargs
    )[:, 0]


def resolve_paged_impl(impl: str = "auto") -> str:
    """``impl`` in {"auto", "pallas", "xla"} -> the path that will run.

    "auto" picks the kernel on a single-device TPU backend and the XLA
    gather path everywhere else — a Mosaic kernel cannot be auto-partitioned
    by XLA SPMD, and on CPU interpret mode would only emulate it (the XLA
    path IS the CPU-native implementation; the kernel still runs under
    ``interpret=True`` in tests to prove parity).
    """
    if impl == "auto":
        single_tpu = jax.default_backend() == "tpu" and jax.device_count() == 1
        return "pallas" if single_tpu else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown paged attention impl {impl!r}")
    return impl


def paged_verify_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    *,
    impl: str = "auto",
    **kwargs,
) -> jnp.ndarray:
    """Multi-token dispatch on :func:`resolve_paged_impl`. q is
    ``[B, Q, H, D]``; ``context_lens`` counts tokens present BEFORE the
    Q-token append; ``kwargs`` are the scale pools and the softmax scale."""
    if resolve_paged_impl(impl) == "pallas":
        return paged_verify_attention_pallas(
            q, k_pool, v_pool, block_tables, context_lens,
            interpret=jax.default_backend() == "cpu", **kwargs,
        )
    return paged_verify_attention_xla(
        q, k_pool, v_pool, block_tables, context_lens, **kwargs
    )


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Single-token dispatch: q ``[B, H, D]``, ``context_lens`` INCLUDING this
    step's token — the ``Q == 1`` case of :func:`paged_verify_attention`."""
    return paged_verify_attention(
        q[:, None], k_pool, v_pool, block_tables, context_lens - 1, **kwargs
    )[:, 0]


def paged_slots(
    block_tables: jnp.ndarray, pos: jnp.ndarray, num_blocks: int, block_size: int
):
    """Where token positions ``pos`` ``[B, Q]`` live in the pools, through
    ``block_tables`` ``[B, MB]``: ``(block, offset)``, each ``[B, Q]``. A
    position outside the table's reach (``< 0`` or ``>= MB * block_size``)
    gets the out-of-range block ``num_blocks``, which
    :func:`scatter_paged_rows` drops."""
    reach = block_tables.shape[1] * block_size
    pos_c = jnp.clip(pos, 0, reach - 1)
    block = jnp.take_along_axis(block_tables, pos_c // block_size, axis=1)
    return jnp.where((pos >= 0) & (pos < reach), block, num_blocks), pos_c % block_size


def scatter_paged_rows(
    pool: jnp.ndarray, block: jnp.ndarray, offset: jnp.ndarray, rows: jnp.ndarray
) -> jnp.ndarray:
    """Store ``rows`` ``[..., Hkv, *tail]`` at ``pool[block, :, offset]`` for
    index arrays ``block``/``offset`` ``[...]`` (from :func:`paged_slots`) over
    a head-major pool ``[NB, Hkv, BS, *tail]``."""
    return pool.at[block, :, offset].set(rows.astype(pool.dtype), mode="drop")


def write_paged_kv_multi(
    cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray
) -> dict:
    """Write Q tokens' K/V per slot into the block pool: ``k_new``/``v_new``
    ``[B, Q, Hkv, D]``, token ``j`` landing at position ``context_lens + j``
    through the slot's block table.

    ``cache`` is one layer's paged cache: pools plus the shared
    ``block_tables`` / ``context_lens`` (lens here = tokens already present,
    i.e. the write position of the first incoming token). Quantizes when the
    layer carries scale pools (same per-row scheme as the contiguous cache:
    ``quantize_kv_rows`` per ``[Hkv, D]`` row, so the speculative path stays
    bit-identical to non-speculative greedy decode).

    Distinct live slots always write distinct physical slots (the allocator
    never lets a write frontier sit in a shared block); idle slots all write
    the reserved null block 0, whose contents are never read as valid.
    Positions past the table's reach (``>= max_blocks * block_size``) are
    dropped outright and positions whose table entry is the padding 0 land in
    the null block — the engine only ever *validates* positions it reserved
    real blocks for, and any position is rewritten before the attention mask
    can expose it, so overflow writes are harmless garbage.
    """
    NB, Hkv, BS, D = cache["k"].shape
    B, Q = k_new.shape[:2]
    lens = cache["context_lens"]
    pos = lens[:, None] + jnp.arange(Q, dtype=lens.dtype)[None, :]  # [B, Q]
    block, offset = paged_slots(cache["block_tables"], pos, NB, BS)

    out = dict(cache)
    new = {"k": k_new, "v": v_new}
    if has_row_scales(cache):
        for key, rows in (("k", k_new), ("v", v_new)):
            quantized, row_scale = quantize_kv_rows(rows.reshape(B * Q, Hkv, D))
            new[key] = quantized.reshape(B, Q, Hkv, D)
            new[key + "_scale"] = row_scale[..., 0].reshape(B, Q, Hkv)
    for key, rows in new.items():
        out[key] = scatter_paged_rows(cache[key], block, offset, rows)
    return out


def write_paged_kv(cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray) -> dict:
    """Write one token's K/V per slot (``[B, Hkv, D]``) at ``context_lens``:
    the ``Q == 1`` case of :func:`write_paged_kv_multi`."""
    return write_paged_kv_multi(cache, k_new[:, None], v_new[:, None])


def paged_pool_layout(
    num_blocks: int, block_size: int, kv_heads: int, dim_per_head: int,
    dtype, quant: bool,
) -> dict:
    """Per-layer pool buffers as ``{key: (shape, dtype)}`` (mirror of the
    contiguous ``ops.kv_cache.kv_cache_layout``)."""
    shape = (num_blocks, kv_heads, block_size, dim_per_head)
    if quant:
        return {
            "k": (shape, jnp.int8), "v": (shape, jnp.int8),
            "k_scale": (shape[:-1], jnp.float32),
            "v_scale": (shape[:-1], jnp.float32),
        }
    return {"k": (shape, dtype), "v": (shape, dtype)}


# -- AOT audit surface (graftcheck-ir) ----------------------------------------


@register_entrypoint("paged_decode_step", specs=("small",))
def build_paged_decode_step(spec: str, mesh) -> EntryArtifacts:
    """The serving engine's steady-state decode step as graftcheck-ir audits
    it: one token per slot through ``TransformerLM.paged_decode`` (paged-KV
    write + paged attention per layer) followed by the pinned sampling
    pipeline (:data:`trlx_tpu.ops.sampling.AUDIT_GEN_KWARGS`) — the jitted
    callable :class:`trlx_tpu.serving.engine.ServingEngine` runs every step.

    Audited with the XLA gather path (the deviceless CPU lowering cannot
    build a Mosaic artifact, and under the multi-device audit mesh the
    dispatch picks XLA anyway), int8-KV layout — the bandwidth-bound
    configuration the engine targets.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.ops.sampling import AUDIT_GEN_KWARGS, sample_token
    from trlx_tpu.parallel.mesh import BATCH_AXES
    from trlx_tpu.parallel.sharding import make_param_shardings

    dims = dict(hidden=64, layers=2, heads=4, vocab=256, B=8,
                num_blocks=24, block_size=8, max_blocks=4)
    model_config = PRESETS["gpt2"].replace(
        vocab_size=dims["vocab"], hidden_size=dims["hidden"],
        num_layers=dims["layers"], num_heads=dims["heads"],
        intermediate_size=4 * dims["hidden"], max_position_embeddings=1024,
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
        kv_cache_quant=True,
    )
    trunk = TransformerLM(model_config)

    params_shape = jax.eval_shape(
        lambda: trunk.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32)
        )
    )["params"]
    abs_params = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, make_param_shardings(params_shape, mesh),
    )

    B = dims["B"]
    NB, BS, MB = dims["num_blocks"], dims["block_size"], dims["max_blocks"]
    kvh, dph = model_config.kv_heads, model_config.dim_per_head
    repl = NamedSharding(mesh, PartitionSpec())
    bsh = NamedSharding(mesh, PartitionSpec(BATCH_AXES))
    layout = paged_pool_layout(NB, BS, kvh, dph, model_config.compute_dtype, True)
    abs_cache = {
        key: [jax.ShapeDtypeStruct(shp, dt, sharding=repl)
              for _ in range(dims["layers"])]
        for key, (shp, dt) in layout.items()
    }
    abs_cache["block_tables"] = jax.ShapeDtypeStruct((B, MB), jnp.int32, sharding=bsh)
    abs_cache["context_lens"] = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=bsh)
    abs_tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=bsh)
    abs_rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def decode_fn(params, tok, cache, rng):
        logits, _, new_cache = trunk.apply(
            {"params": params}, tok[:, None], cache, method=trunk.paged_decode
        )
        next_tok = sample_token(rng, logits[:, -1, :], **AUDIT_GEN_KWARGS)
        return next_tok, new_cache

    # output cache shardings must equal the input's for the donated pool
    # buffers to alias (IR002); leaving them to inference breaks the aliasing
    cache_out_shardings = jax.tree.map(lambda _: repl, abs_cache)
    cache_out_shardings["block_tables"] = bsh
    cache_out_shardings["context_lens"] = bsh

    return EntryArtifacts(
        fn=decode_fn,
        args=(abs_params, abs_tok, abs_cache, abs_rng),
        donate_argnums=(2,),
        out_shardings=(bsh, cache_out_shardings),
        compute_dtype="bfloat16",
        # the paged-attention reference accumulates scores and probs@V in f32
        # (preferred_element_type, flash-kernel algebra): 2 dots/layer
        f32_allow=frozenset({"dot_general:4"}),
        meta=dict(batch=B, num_blocks=NB, block_size=BS,
                  hidden_size=dims["hidden"], num_layers=dims["layers"]),
    )


@register_entrypoint("spec_verify_step", specs=("small", "xl"))
def build_spec_verify_step(spec: str, mesh) -> EntryArtifacts:
    """The speculative-verify round as graftcheck-ir audits it: ``K + 1``
    tokens per slot (pending token + K n-gram drafts) through
    ``TransformerLM.paged_verify`` — multi-position paged-KV write + the
    widened verify attention — then per-position sampling and the on-device
    accept count, exactly the jitted ``_verify_step`` the serving engine runs
    when ``serving.spec_k > 0``.

    ``small`` mirrors ``paged_decode_step``'s dims (int8-KV, per-layer pool
    lists) and is what CI compiles and gates against the budget. ``xl`` is
    the GPT-2-XL blueprint — scanned layers over *stacked* ``[L, ...]``
    pools — and exists to be lowered deviceless so paged/speculative decode
    evidence reaches past gpt2-small (ROADMAP big-model item).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.ops.sampling import (
        AUDIT_GEN_KWARGS, count_accepted_drafts, sample_token,
    )
    from trlx_tpu.parallel.mesh import BATCH_AXES
    from trlx_tpu.parallel.sharding import make_param_shardings

    dims = {
        "small": dict(hidden=64, layers=2, heads=4, vocab=256, B=8,
                      num_blocks=24, block_size=8, max_blocks=4, spec_k=4,
                      scan_layers=False),
        # GPT-2-XL shapes (~1.5B params), scanned layers + stacked pools
        "xl": dict(hidden=1600, layers=48, heads=25, vocab=50257, B=8,
                   num_blocks=64, block_size=16, max_blocks=16, spec_k=4,
                   scan_layers=True),
    }[spec]
    model_config = PRESETS["gpt2"].replace(
        vocab_size=dims["vocab"], hidden_size=dims["hidden"],
        num_layers=dims["layers"], num_heads=dims["heads"],
        intermediate_size=4 * dims["hidden"], max_position_embeddings=1024,
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
        kv_cache_quant=True, scan_layers=dims["scan_layers"],
    )
    trunk = TransformerLM(model_config)

    params_shape = jax.eval_shape(
        lambda: trunk.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32)
        )
    )["params"]
    abs_params = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, make_param_shardings(params_shape, mesh),
    )

    B, K = dims["B"], dims["spec_k"]
    NB, BS, MB = dims["num_blocks"], dims["block_size"], dims["max_blocks"]
    kvh, dph = model_config.kv_heads, model_config.dim_per_head
    repl = NamedSharding(mesh, PartitionSpec())
    bsh = NamedSharding(mesh, PartitionSpec(BATCH_AXES))
    bsh2 = NamedSharding(mesh, PartitionSpec(BATCH_AXES, None))
    layout = paged_pool_layout(NB, BS, kvh, dph, model_config.compute_dtype, True)
    if dims["scan_layers"]:
        abs_cache = {
            key: jax.ShapeDtypeStruct((dims["layers"],) + shp, dt, sharding=repl)
            for key, (shp, dt) in layout.items()
        }
    else:
        abs_cache = {
            key: [jax.ShapeDtypeStruct(shp, dt, sharding=repl)
                  for _ in range(dims["layers"])]
            for key, (shp, dt) in layout.items()
        }
    abs_cache["block_tables"] = jax.ShapeDtypeStruct((B, MB), jnp.int32, sharding=bsh2)
    abs_cache["context_lens"] = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=bsh)
    abs_tok = jax.ShapeDtypeStruct((B, K + 1), jnp.int32, sharding=bsh2)
    abs_rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def verify_fn(params, tok, cache, rng):
        lens0 = cache["context_lens"]
        logits, _, new_cache = trunk.apply(
            {"params": params}, tok, cache, method=trunk.paged_verify
        )
        y = sample_token(rng, logits, **AUDIT_GEN_KWARGS)  # [B, K+1]
        accepted = count_accepted_drafts(y, tok)
        new_cache["context_lens"] = lens0 + accepted + 1
        return y, accepted, new_cache

    cache_out_shardings = jax.tree.map(lambda _: repl, abs_cache)
    cache_out_shardings["block_tables"] = bsh2
    cache_out_shardings["context_lens"] = bsh

    return EntryArtifacts(
        fn=verify_fn,
        args=(abs_params, abs_tok, abs_cache, abs_rng),
        donate_argnums=(2,),
        out_shardings=(bsh2, bsh, cache_out_shardings),
        compute_dtype="bfloat16",
        # verify attention accumulates scores and probs@V in f32 like the
        # decode step: 2 dots/layer
        f32_allow=frozenset({"dot_general:4"}),
        meta=dict(batch=B, spec_k=K, num_blocks=NB, block_size=BS,
                  hidden_size=dims["hidden"], num_layers=dims["layers"]),
    )
