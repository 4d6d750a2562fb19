"""Attention over the keys a learned indexer selects (DeepSeek's sparse attention, as
the language model of Keye-VL-2.0 has it): the selection, the kernels that attend over
it, and a decode step that reads only what it selected.

For the query at slot t the indexer scores every earlier key slot s,

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])

(``Hi`` query heads of ``Di`` over one key head; products in the operands' dtype,
accumulated and summed in float32), and the query attends over ``S_t``: the
``min(topk, allowed)`` allowed slots (``s <= t`` and not padding) of the largest
score. **Ties go to the earlier slot.** Selection is discrete: nothing is
differentiated through it.

:func:`select` gives a multi-token forward its selection as a mask, by one of two
paths. The selection kernel (:func:`select_keys`) sorts nothing: a row's threshold is
its ``topk``-th largest score, found by bisection over the bits of the float32 score
read as an order-preserving int32 key (:func:`_threshold`, 32 counting passes over
the row); where more keys tie at the threshold than are left to keep, the slot up to
which the earlier ones fill the rest is a second bisection, over slots
(:func:`_tie_cut`, ``ceil(log2 S)`` passes). It works a block of 128 query rows at a
time, scores held in VMEM and never written to HBM, reads only the key chunks up to
the block's diagonal, and writes the mask as int8 ``[B, Tp, Sp]`` (``T`` padded to
256 rows, ``S`` to 512 keys). The XLA path (:func:`select_mask_xla`, the CPU's)
forms the whole ``[B, T, S]`` scores and takes ``lax.top_k`` of each row, as a
decode step does.

The attention kernels (:func:`sparse_attention`, forward, ``dq`` and ``dkv``) are
flash attention with that mask in place of the causal one: a program holds one kv
head's ``rep`` query heads for 256 queries against 512 keys, and a (query block, key
block) pair that no query of the block selected from is skipped (a table of them,
scalar-prefetched); blocks above the diagonal are not even fetched. q, k, v are
taken heads-first and padded in HBM around the calls.

A decode step (:func:`decode_selected`) scores every written slot's indexer key,
takes the ``topk`` largest (``lax.top_k``, whose ties also go to the lower slot), and
gathers keys and values of those slots alone from the cache, which holds them
slot-major (``ops/kv_cache.py``: ``indexed_cache_layout``).

Named scopes: ``dsa.index`` (the indexer's scores where they are formed outside the
selection), ``dsa.select`` and ``dsa.attend``. The kernels are jitted under the names
``dsa_select`` and ``sparse_attn``, which name them on the chip (``%sparse_attn.N
custom-call``). XLA cannot partition a Mosaic kernel: a multi-device mesh is refused.
"""

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops.attention import LANE, NEG_INF, _dot, _interpret, _nt_dot, _to_cols, _to_rows
from trlx_tpu.parallel.sharding import ambient_mesh

INT_MIN = -(2 ** 31)
SELECT_ROWS = 128  # query rows of a selection program
CHUNK = 512  # keys a selection pass takes at a time
Q_BLOCK = 256  # queries of an attention program
K_BLOCK = 512  # keys of an attention grid step
_VMEM_LIMIT = 64 * 2 ** 20

MESH_REFUSAL = (
    "the selection and the sparse attention are Pallas kernels over the whole sequence, which XLA cannot "
    "partition: learned sparse attention runs on a one-device mesh only"
)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def padded_lengths(T: int, S: int) -> Tuple[int, int]:
    """(Tp, Sp): the mask's extent the kernels work over."""
    return _round_up(T, Q_BLOCK), _round_up(S, K_BLOCK)


# ------------------------------------------------------------- selection


def _monotone_key(scores):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF))


def _threshold(count_at_least, like, topk: int):
    """Per row, the largest key ``x`` with ``count_at_least(x) >= topk`` (INT_MIN
    where the row holds fewer keys above INT_MIN): built a bit at a time from the
    top, in the unsigned order of ``key ^ INT_MIN``."""

    def step(i, ans):
        cand = ans | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count_at_least(cand ^ INT_MIN) >= topk, cand, ans)

    return jax.lax.fori_loop(0, 32, step, jnp.zeros_like(like)) ^ INT_MIN


def _tie_cut(count_before, like, left, bits: int):
    """Per row, the largest slot ``p`` with ``count_before(p) < left``: the
    ``left``-th key tied at the threshold, counting from the earliest, sits at
    ``p``, and the ties at slots ``<= p`` are kept."""

    def step(i, ans):
        cand = ans | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count_before(cand) < left, cand, ans)

    return jax.lax.fori_loop(0, bits, step, jnp.zeros_like(like))


def _slot_bits(S: int) -> int:
    return max(1, (S - 1).bit_length())


def index_scores(qi, ki, w):
    """qi [B, T, Hi, Di], ki [B, S, Di], w [B, T, Hi] -> I [B, T, S] float32."""
    dots = jnp.einsum("bthd,bsd->bths", qi, ki, preferred_element_type=jnp.float32)
    # the weighted sum over heads elementwise, in float32 whatever the default matmul precision
    return (jax.nn.relu(dots) * w.astype(jnp.float32)[..., None]).sum(2)


def select_mask_xla(scores, allowed, topk: int):
    """scores [..., S] float32, allowed [..., S] bool -> the selection [..., S] bool:
    the ``min(topk, allowed)`` allowed slots of the largest score a row, by
    ``lax.top_k`` (ties to the earlier slot)."""
    top, slots = jax.lax.top_k(jnp.where(allowed, scores, -jnp.inf), min(topk, scores.shape[-1]))
    return jnp.put_along_axis(jnp.zeros(scores.shape, bool), slots, top > -jnp.inf, axis=-1, inplace=False)


def allowed_pairs(kv_valid, T: int, S: int):
    """[B, S] key validity -> [B, T, S] bool: the slots a query at slot t may select (s <= t, not padding)."""
    causal = jnp.arange(S)[None, :] <= jnp.arange(T)[:, None]
    return causal[None] & (kv_valid[:, None, :] > 0)


def _select_kernel(qi_ref, w_ref, ki_ref, valid_ref, mask_ref, key_ref, *, topk: int, heads: int, T: int):
    rows, Sp = key_ref.shape
    t0 = pl.program_id(1) * rows
    live = jnp.minimum(Sp // CHUNK, (t0 + rows + CHUNK - 1) // CHUNK)  # chunks up to the block's diagonal
    q_pos = t0 + jax.lax.broadcasted_iota(jnp.int32, (rows, CHUNK), 0)
    w = w_ref[0].astype(jnp.float32)  # [rows, heads]

    def chunk(c):
        k0 = pl.multiple_of(c * CHUNK, CHUNK)
        return k0, k0 + jax.lax.broadcasted_iota(jnp.int32, (rows, CHUNK), 1)

    def fill(c, carry):
        k0, k_pos = chunk(c)
        keys = ki_ref[0, pl.ds(k0, CHUNK), :]
        score = jnp.zeros((rows, CHUNK), jnp.float32)
        for j in range(heads):
            score = score + w[:, j : j + 1] * jnp.maximum(_nt_dot(qi_ref[0, j], keys), 0.0)
        allowed = (valid_ref[0, :, pl.ds(k0, CHUNK)] > 0) & (k_pos <= q_pos) & (q_pos < T)
        key_ref[:, pl.ds(k0, CHUNK)] = jnp.where(allowed, _monotone_key(score), INT_MIN)
        return carry

    jax.lax.fori_loop(0, live, fill, 0)

    def count(pred):
        """[rows, 1] float32: the keys of each row (over the live chunks) for which ``pred(key, slot)`` holds."""

        def body(c, total):
            k0, k_pos = chunk(c)
            return total + jnp.where(pred(key_ref[:, pl.ds(k0, CHUNK)], k_pos), 1.0, 0.0)

        partial = jax.lax.fori_loop(0, live, body, jnp.zeros((rows, CHUNK), jnp.float32))
        return jnp.sum(partial, axis=1, keepdims=True)

    like = jnp.zeros((rows, 1), jnp.int32)
    thr = _threshold(lambda x: count(lambda key, _: key >= x), like, topk)
    left = topk - count(lambda key, _: key > thr)
    ties = lambda key: (key == thr) & (key != INT_MIN)
    cut = _tie_cut(lambda p: count(lambda key, pos: ties(key) & (pos < p)), like, left, _slot_bits(Sp))

    mask_ref[0] = jnp.zeros(mask_ref.shape[1:], mask_ref.dtype)

    def write(c, carry):
        k0, k_pos = chunk(c)
        key = key_ref[:, pl.ds(k0, CHUNK)]
        keep = (key != INT_MIN) & ((key > thr) | (ties(key) & (k_pos <= cut)))
        mask_ref[0, :, pl.ds(k0, CHUNK)] = jnp.where(keep, 1, 0).astype(mask_ref.dtype)
        return carry

    jax.lax.fori_loop(0, live, write, 0)


def _named(name: str, *static_argnames):
    """jit under ``name``: the chip names the kernel's custom-call after it, and a
    model's layers, which call it at one shape, share one lowering."""

    def wrap(fn):
        @functools.wraps(fn)
        def named(*args, **kwargs):
            return fn(*args, **kwargs)

        named.__name__ = named.__qualname__ = name  # graftcheck: noqa[JX003] — the name is set once, at definition
        return jax.jit(named, static_argnames=static_argnames)

    return wrap


@_named("dsa_select", "topk", "interpret")
def select_keys(qi, ki, w, kv_valid, topk: int, interpret: bool):
    """qi [B, T, Hi, Di], ki [B, S, Di], w [B, T, Hi] float32, kv_valid [B, S] ->
    the selection as int8 ``[B, Tp, Sp]`` (:func:`padded_lengths`; 0 past the data)."""
    B, T, Hi, Di = qi.shape
    S = ki.shape[1]
    Tp, Sp = padded_lengths(T, S)
    qi_t = jnp.pad(qi.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    w = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, Tp - T), (0, 0)))
    ki = jnp.pad(ki, ((0, 0), (0, Sp - S), (0, 0)))
    valid = jnp.pad(kv_valid.astype(jnp.int32), ((0, 0), (0, Sp - S)))[:, None, :]
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, heads=Hi, T=T),
        grid=(B, Tp // SELECT_ROWS),
        in_specs=[
            pl.BlockSpec((1, Hi, SELECT_ROWS, Di), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, SELECT_ROWS, Hi), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sp, Di), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Sp), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, SELECT_ROWS, Sp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Sp), jnp.int8),
        scratch_shapes=[pltpu.VMEM((SELECT_ROWS, Sp), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT),
    )(qi_t, w, ki, valid)


def block_table(mask):
    """int8 [B, Tp, Sp] -> int32 [B * nq * nk]: whether any query of an attention
    block selected a key of a key block (flattened for the scalar prefetch)."""
    B, Tp, Sp = mask.shape
    nq, nk = Tp // Q_BLOCK, Sp // K_BLOCK
    return mask.reshape(B, nq, Q_BLOCK, nk, K_BLOCK).max(axis=(2, 4)).astype(jnp.int32).reshape(-1)


# ------------------------------------------------------- attention kernels


def _last_block(i):
    """The last key block that holds a key at or before query block ``i``'s last row."""
    return ((i + 1) * Q_BLOCK - 1) // K_BLOCK


def _first_block(j):
    """The first query block whose last row reaches key block ``j``."""
    return (j * K_BLOCK) // Q_BLOCK


def _kept(mask_ref):
    """A mask block as bool, widened from int8 first (the chip's compiler lays int8 compares out packed)."""
    return mask_ref[0].astype(jnp.int32) != 0


def _fwd_kernel(blocks_ref, mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, rep, nq, nk):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(blocks_ref[(b * nq + i) * nk + j] != 0)
    def _visit():
        bias = jnp.where(_kept(mask_ref), 0.0, NEG_INF)
        k, v = k_ref[0, 0], v_ref[0, 0]
        for h in range(rep):
            s = _nt_dot(q_ref[0, 0, h], k) * scale + bias
            m_prev = m_sc[h]
            m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m)  # a row with nothing selected yet: a later block's alpha or finish() clears it
            alpha = jnp.exp(m_prev - m)
            l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = alpha * acc_sc[h] + _dot(p.astype(v.dtype), v)
            m_sc[h] = m

    @pl.when(j == nk - 1)
    def _finish():
        for h in range(rep):
            m, l = m_sc[h], l_sc[h]
            seen = m > NEG_INF / 2
            o_ref[0, 0, h] = (acc_sc[h] * jnp.where(seen, 1.0 / l, 0.0)).astype(o_ref.dtype)
            lse = jnp.where(seen, m + jnp.log(l), NEG_INF)
            lse_ref[0, 0, h : h + 1, :] = _to_rows(jnp.broadcast_to(lse, (lse.shape[0], LANE)))


def _dq_kernel(blocks_ref, mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc, *,
               scale, rep, nq, nk):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(blocks_ref[(b * nq + i) * nk + j] != 0)
    def _visit():
        keep = _kept(mask_ref)
        k, v = k_ref[0, 0], v_ref[0, 0]
        for h in range(rep):
            s = _nt_dot(q_ref[0, 0, h], k) * scale
            p = jnp.where(keep, jnp.exp(s - _to_cols(lse_ref[0, 0, h : h + 1, :])), 0.0)
            dp = _nt_dot(do_ref[0, 0, h], v)
            ds = p * (dp - _to_cols(delta_ref[0, 0, h : h + 1, :]))
            dq_sc[h] = dq_sc[h] + _dot(ds.astype(k.dtype), k) * scale

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(blocks_ref, mask_t_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, rep, nq, nk):
    """Transposed score tiles (keys down the sublanes, queries along the lanes): the
    rows' statistics subtract as rows, and no product takes a transposed operand."""
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(blocks_ref[(b * nq + i) * nk + j] != 0)
    def _visit():
        keep = _kept(mask_t_ref)  # [keys, queries]
        k, v = k_ref[0, 0], v_ref[0, 0]
        for h in range(rep):
            q, do = q_ref[0, 0, h], do_ref[0, 0, h]
            s = _nt_dot(k, q) * scale
            p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0, h : h + 1, :]), 0.0)
            dv_sc[...] = dv_sc[...] + _dot(p.astype(do.dtype), do)
            ds = p * (_nt_dot(v, do) - delta_ref[0, 0, h : h + 1, :])
            dk_sc[...] = dk_sc[...] + _dot(ds.astype(q.dtype), q) * scale

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


@_named("sparse_attn", "scale", "interpret")
def _forward(blocks, mask, qh, kh, vh, scale: float, interpret: bool):
    """qh [B, Hkv, rep, Tp, D], kh / vh [B, Hkv, Sp, D], mask int8 [B, Tp, Sp] ->
    (o like qh, lse [B, Hkv, rep, Tp] float32)."""
    B, Hkv, rep, Tp, D = qh.shape
    Sp = kh.shape[2]
    nq, nk = Tp // Q_BLOCK, Sp // K_BLOCK
    kv = lambda b, g, i, j, t: (b, g, jnp.minimum(j, _last_block(i)), 0)
    q_spec = pl.BlockSpec((1, 1, rep, Q_BLOCK, D), lambda b, g, i, j, t: (b, g, 0, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, Q_BLOCK, K_BLOCK), lambda b, g, i, j, t: (b, i, jnp.minimum(j, _last_block(i)))),
            q_spec,
            pl.BlockSpec((1, 1, K_BLOCK, D), kv),
            pl.BlockSpec((1, 1, K_BLOCK, D), kv),
        ],
        out_specs=[q_spec, pl.BlockSpec((1, 1, rep, Q_BLOCK), lambda b, g, i, j, t: (b, g, 0, i))],
        scratch_shapes=[
            pltpu.VMEM((rep, Q_BLOCK, 1), jnp.float32),
            pltpu.VMEM((rep, Q_BLOCK, 1), jnp.float32),
            pltpu.VMEM((rep, Q_BLOCK, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, rep=rep, nq=nq, nk=nk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(qh.shape, qh.dtype), jax.ShapeDtypeStruct((B, Hkv, rep, Tp), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(),
    )(blocks, mask, qh, kh, vh)


@_named("sparse_attn", "scale", "interpret")
def _backward(blocks, mask, qh, kh, vh, oh, lse, g, scale: float, interpret: bool):
    """dq, dk, dv in the layouts of qh, kh, vh."""
    B, Hkv, rep, Tp, D = qh.shape
    Sp = kh.shape[2]
    nq, nk = Tp // Q_BLOCK, Sp // K_BLOCK
    delta = jnp.sum(g.astype(jnp.float32) * oh.astype(jnp.float32), axis=-1)  # [B, Hkv, rep, Tp]

    kv = lambda b, hk, i, j, t: (b, hk, jnp.minimum(j, _last_block(i)), 0)
    q_spec = pl.BlockSpec((1, 1, rep, Q_BLOCK, D), lambda b, hk, i, j, t: (b, hk, 0, i, 0))
    row_spec = pl.BlockSpec((1, 1, rep, Q_BLOCK), lambda b, hk, i, j, t: (b, hk, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, rep=rep, nq=nq, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nq, nk),
            in_specs=[
                pl.BlockSpec((1, Q_BLOCK, K_BLOCK), lambda b, hk, i, j, t: (b, i, jnp.minimum(j, _last_block(i)))),
                q_spec, pl.BlockSpec((1, 1, K_BLOCK, D), kv), pl.BlockSpec((1, 1, K_BLOCK, D), kv),
                q_spec, row_spec, row_spec,
            ],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((rep, Q_BLOCK, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(qh.shape, qh.dtype),
        interpret=interpret,
        compiler_params=_params(),
    )(blocks, mask, qh, kh, vh, g, lse, delta)

    # key block j outermost, query blocks below its first reach not fetched
    qb = lambda b, hk, j, i, t: (b, hk, 0, jnp.maximum(i, _first_block(j)), 0)
    rb = lambda b, hk, j, i, t: (b, hk, 0, jnp.maximum(i, _first_block(j)))
    k_spec = pl.BlockSpec((1, 1, K_BLOCK, D), lambda b, hk, j, i, t: (b, hk, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, rep=rep, nq=nq, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nk, nq),
            in_specs=[
                pl.BlockSpec((1, K_BLOCK, Q_BLOCK), lambda b, hk, j, i, t: (b, j, jnp.maximum(i, _first_block(j)))),
                pl.BlockSpec((1, 1, rep, Q_BLOCK, D), qb), k_spec, k_spec,
                pl.BlockSpec((1, 1, rep, Q_BLOCK, D), qb),
                pl.BlockSpec((1, 1, rep, Q_BLOCK), rb), pl.BlockSpec((1, 1, rep, Q_BLOCK), rb),
            ],
            out_specs=[k_spec, k_spec],
            scratch_shapes=[pltpu.VMEM((K_BLOCK, D), jnp.float32), pltpu.VMEM((K_BLOCK, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(kh.shape, kh.dtype), jax.ShapeDtypeStruct(vh.shape, vh.dtype)],
        interpret=interpret,
        compiler_params=_params(),
    )(blocks, jnp.swapaxes(mask, 1, 2), qh, kh, vh, g, lse, delta)
    return dq, dk, dv


def _heads_first(q, k, v, Tp: int, Sp: int):
    """q [B, T, H, D] -> [B, Hkv, rep, Tp, D]; k, v [B, S, Hkv, D] -> [B, Hkv, Sp, D]; zeros past the data."""
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    qh = q.reshape(B, T, Hkv, H // Hkv, D).transpose(0, 2, 3, 1, 4)
    qh = jnp.pad(qh, ((0, 0),) * 3 + ((0, Tp - T), (0, 0)))
    kv = lambda x: jnp.pad(x.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    return qh, kv(k), kv(v)


def _rows_first(xh, T: int):
    """[B, Hkv, rep, Tp, D] -> [B, T, H, D]."""
    B, Hkv, rep, _, D = xh.shape
    return xh[:, :, :, :T].transpose(0, 3, 1, 2, 4).reshape(B, T, Hkv * rep, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def sparse_attention(q, k, v, mask, blocks, scale: float, interpret: bool = False):
    """q [B, T, H, D] over the keys ``mask`` (int8 ``[B, Tp, Sp]``, :func:`select_keys`)
    selected of k / v [B, S, Hkv, D]; ``blocks`` is :func:`block_table` of the mask.
    The keys are the queries' own tokens (``S == T``). Returns [B, T, H, D] in q's
    dtype; a query that selected nothing gives zeros. Differentiable in q, k, v."""
    return _sparse_fwd(q, k, v, mask, blocks, scale, interpret)[0]


def _sparse_fwd(q, k, v, mask, blocks, scale, interpret):
    T = q.shape[1]
    assert k.shape[1] == T, (q.shape, k.shape)
    Tp, Sp = mask.shape[1:]
    qh, kh, vh = _heads_first(q, k, v, Tp, Sp)
    oh, lse = _forward(blocks, mask, qh, kh, vh, scale, interpret)
    return _rows_first(oh, T), (mask, blocks, qh, kh, vh, oh, lse)


def _sparse_bwd(scale, interpret, res, g):
    mask, blocks, qh, kh, vh, oh, lse = res
    B, T, H, D = g.shape
    Hkv, rep, Tp = qh.shape[1], qh.shape[2], qh.shape[3]
    gh = jnp.pad(g.reshape(B, T, Hkv, rep, D).transpose(0, 2, 3, 1, 4), ((0, 0),) * 3 + ((0, Tp - T), (0, 0)))
    dq, dk, dv = _backward(blocks, mask, qh, kh, vh, oh, lse, gh.astype(qh.dtype), scale, interpret)
    unpad = lambda x: x[:, :, :T].transpose(0, 2, 1, 3)
    return _rows_first(dq, T), unpad(dk), unpad(dv), None, None


sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)


def masked_attention_xla(q, k, v, selected, scale: float):
    """The XLA path and the kernels' reference: q [B, T, H, D] over the keys
    ``selected`` ([B, T, S] bool) of k / v [B, S, Hkv, D], grouped; a query that
    selected nothing gives zeros."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, D)
    s = jnp.einsum("btgrd,bsgd->bgrts", qg, k).astype(jnp.float32) * scale
    s = jnp.where(selected[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1) * selected.any(-1)[:, None, None, :, None]
    out = jnp.einsum("bgrts,bsgd->btgrd", p.astype(v.dtype), v)
    return out.reshape(B, T, H, D)


def attend_selected(q, k, v, qi, ki, w, kv_valid, *, topk: int, scale: float, kernels: bool):
    """A multi-token forward whose keys are its own tokens (cache-free, or a prefill
    from slot 0): select, then attend. q [B, T, H, D], k / v [B, T, Hkv, D], the
    indexer's qi [B, T, Hi, Di], ki [B, T, Di], w [B, T, Hi]; ``kv_valid`` [B, T].
    ``kernels``: the Pallas kernels (interpret mode on the CPU), else XLA.
    Returns ([B, T, H * D] in q's dtype, the selection: int8 [B, Tp, Sp] from the
    kernels, bool [B, T, S] from XLA)."""
    B, T, H, D = q.shape
    mesh = ambient_mesh()
    if kernels and mesh is not None and mesh.size > 1:
        raise ValueError(MESH_REFUSAL)
    interpret = _interpret(mesh)
    mask = select(qi, ki, w, kv_valid, topk, kernels=kernels, interpret=interpret)
    if kernels:
        with jax.named_scope("dsa.select"):
            blocks = block_table(mask)
        with jax.named_scope("dsa.attend"):
            out = sparse_attention(q, k, v, mask, blocks, scale, interpret)
    else:
        with jax.named_scope("dsa.attend"):
            out = masked_attention_xla(q, k, v, mask, scale)
    return out.astype(q.dtype).reshape(B, T, H * D), mask


def select(qi, ki, w, kv_valid, topk: int, *, kernels: bool, interpret: bool):
    """The keys each query of a multi-token forward attends over (its own tokens
    are the keys): the selection kernel's int8 ``[B, Tp, Sp]``, or with
    ``kernels`` False XLA's bool ``[B, T, S]``."""
    if kernels:
        with jax.named_scope("dsa.select"):
            return select_keys(qi, ki, w, kv_valid, topk, interpret)
    with jax.named_scope("dsa.index"):
        scores = index_scores(qi, ki, w)
    with jax.named_scope("dsa.select"):
        return select_mask_xla(scores, allowed_pairs(kv_valid, qi.shape[1], ki.shape[1]), topk)


def pair_counts(mask, kv_valid, topk: int):
    """(kept, causal, off) float32 scalars over the real query rows of one forward:
    the pairs selected, the pairs a query could have attended over, and the
    queries that kept other than ``min(topk, allowed)`` keys (0 where the
    selection is whole)."""
    T = kv_valid.shape[1]
    valid = kv_valid.astype(jnp.float32)
    kept_row = mask[:, :T, :T].astype(jnp.float32).sum(-1)
    allowed = jnp.cumsum(valid, axis=1)
    off = (kept_row != jnp.minimum(allowed, topk)).astype(jnp.float32)
    return (kept_row * valid).sum(), (allowed * valid).sum(), (off * valid).sum()


# ------------------------------------------------------------------ decode


def decode_selected(q, qi, w, cache, allowed, *, topk: int, scale: float):
    """One token a row over the cache: q [B, 1, H, D], the indexer's qi [B, 1, Hi, Di]
    and w [B, 1, Hi], ``cache`` a layer of ``kv_cache.indexed_cache_layout`` already
    holding this token, ``allowed`` [B, S] bool. Reads every slot's indexer key, then
    the keys and values of the ``min(topk, S)`` slots selected alone. Returns
    [B, 1, H * D] in q's dtype."""
    B, _, H, D = q.shape
    S, Hkv = cache["k"].shape[1], cache["k"].shape[2]
    with jax.named_scope("dsa.index"):
        scores = index_scores(qi, cache["ki"], w)[:, 0]  # [B, S]
    with jax.named_scope("dsa.select"):
        top, slots = jax.lax.top_k(jnp.where(allowed, scores, -jnp.inf), min(topk, S))
        chosen = top > -jnp.inf
    with jax.named_scope("dsa.attend"):
        rows = jax.vmap(lambda c, i: c[i])
        k, v = rows(cache["k"], slots), rows(cache["v"], slots)  # [B, K, Hkv, D]
        qg = q[:, 0].reshape(B, Hkv, H // Hkv, D)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, k).astype(jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, NEG_INF), axis=-1)
        out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v.dtype), v)
    return out.astype(q.dtype).reshape(B, 1, H * D)


def decode_bytes_per_step(layers: int, rows: int, slots: float, topk: int, kv_heads: int, head_dim: int,
                          index_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """(indexer-key bytes, selected key-and-value bytes) one decode step reads over
    ``layers`` layers for ``rows`` rows at ``slots`` written slots."""
    index = layers * rows * slots * index_dim * itemsize
    selected = layers * rows * min(topk, slots) * 2 * kv_heads * head_dim * itemsize
    return float(index), float(selected)


def decode_bytes_mean(layers: int, rows: int, prompt_len: int, steps: int, topk: int, kv_heads: int,
                      head_dim: int, index_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """:func:`decode_bytes_per_step` averaged over ``steps`` steps after a prefill of ``prompt_len`` slots."""
    if steps <= 0:
        return 0.0, 0.0
    parts = [decode_bytes_per_step(layers, rows, prompt_len + s, topk, kv_heads, head_dim, index_dim, itemsize)
             for s in range(1, steps + 1)]
    return tuple(math.fsum(p[i] for p in parts) / steps for i in range(2))
