"""Attention ops: Pallas TPU flash-attention forward + backward kernels, the decode kernel, XLA reference.

The reference relies on external CUDA attention kernels (HF/NeMo, SURVEY.md §2.4.5);
this is the TPU-native equivalent. Forward is an online-softmax (FlashAttention-style)
Pallas kernel; backward is the standard recompute scheme (two kernels, as in the
in-tree TPU flash attention): the forward saves only O and the per-row logsumexp and
the backward recomputes P = exp(S - L) tile by tile, so training memory is O(T·tile)
rather than the O(T·S) score matrix. :func:`xla_attention` is the plain reference
the kernels' outputs and gradients are tested against.

How the work is cut into programs is decided from the shape by one pure function,
:func:`choose_tiles`. Where the whole padded sequence fits VMEM — every length RL
runs at gpt2 widths: 64 to 1024 — a program takes the whole sequence of one or more
heads: grid (batch, heads / heads-per-program, 1, 1), nothing carried across grid
steps, and the body walks the score matrix in row tiles whose causal extents are
static, so nothing above the diagonal is computed. Where it does not (long contexts
at D = 128), the kv side is walked on the last grid axis in tiles of 128-512 —
TPU grids execute sequentially, so running max / denominator / accumulator live in
VMEM scratch across kv steps — and blocks above the diagonal are skipped with
``pl.when``. One kernel body per pass serves both.

Operands are multiplied in the dtype they arrive in (bfloat16 on the MXU in one
pass, float32 in full) and accumulated in float32; softmax statistics, the mask
arithmetic and every accumulator are float32, and P and dS are cast to the operand
dtype for their second matmuls, as the XLA path does. The backward writes dq, dk, dv
in the dtype of q, k, v.

Grouped-query attention is native: K/V arrive with their own head count ``Hkv`` and
the BlockSpec index maps hand a program its query heads' kv heads, so grouped K/V
are never materialized at full head count. ``dkv`` programs take whole query-head
groups and sum over them.

Masking model matches :mod:`trlx_tpu.models.transformer`: slot-based causality plus a
[B, S] key-validity mask (left-padded prompts). Engaged on every multi-token forward:
the training loss, the logprob/value scoring passes, and generation *prefill* (which
attends over the just-computed prefix k/v while the cache write happens separately).
Arbitrary T/S are supported via internal padding (see ``_flash_forward``). Single-token
decode steps over the contiguous cache have a kernel of their own, :func:`decode_attention`
(the section "decode" below), which reads the cache only up to the write index.

Layout note: per-row statistics (logsumexp, delta) travel between the kernels as
``[B, H, 1, T]`` float32 rows with T on the lanes — B·H·T·4 bytes in HBM, where a
trailing dimension of 1 or 8 would be padded to 128 lanes. The forward turns its
column of row statistics into such a row with one transpose per head; ``dq`` turns
it back; ``dkv`` works on transposed score tiles (keys down the sublanes, queries
along the lanes) and subtracts the rows as they are. The key mask travels as
``[B, 1, S]``.
"""

import functools
import math
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops import kv_cache
from trlx_tpu.parallel.mesh import BATCH_AXES, MODEL_AXIS
from trlx_tpu.parallel.sharding import ambient_mesh, batch_divisible
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

NEG_INF = -1e30

LANE = 128  # lanes of a vector register; the lane side of every score tile is a multiple
# What a program costs to start, in score elements (0.4 us of a v5e program against
# some 60 k score elements a microsecond): the chooser trades it against padding.
_PROGRAM_COST = 24 * 1024
# Score elements a program should not exceed when it takes several heads.
_PROGRAM_AREA = 2 * 1024 * 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class FlashTiles(NamedTuple):
    """How one attention shape is cut into programs (see :func:`choose_tiles`)."""

    block_q: int  # query rows a program holds; Tp where the whole sequence is one tile
    block_k: int  # key rows a grid step holds; Sp where the whole sequence is one tile
    sub: int  # rows of a score tile the body works on at a time
    Tp: int  # padded query length, a multiple of block_q
    Sp: int  # padded key length, a multiple of block_k
    Tr: int  # query rows that hold data, rounded up to the dtype's sublane tile
    Sr: int  # key rows that hold data, the same
    heads: int  # most query heads one program takes
    vmem_bytes: int  # reckoned VMEM of the largest pass (the dkv backward)

    @property
    def whole(self) -> bool:
        """One program sees the whole padded sequence: nothing is carried
        across grid steps and causal extents are static."""
        return self.block_q == self.Tp and self.block_k == self.Sp


def _reckon_vmem(block_q, block_k, sub, heads, D, rep, itemsize, whole, Dv=None) -> int:
    """VMEM bytes of the pass that holds most. Each pass: its operands twice
    (Pallas double-buffers them), score-shaped float32 temporaries of ``sub``
    rows (scores, probabilities and the mask bias in the forward; dP and dS
    besides in the two backward passes), the mask bias kept for every row tile
    where extents are static, and what it keeps in scratch. The forward and dq
    passes take ``heads`` query heads, the dkv pass their kv heads' whole groups.
    ``Dv`` is the width of v, o and dO where it is not q's and k's ``D``."""
    d_lanes, dv_lanes = _round_up(D, LANE), _round_up(Dv or D, LANE)
    kv_heads = max(1, heads // rep)
    group = kv_heads * rep
    # q, k, dq, dk are D wide; v, o, dO, dv are Dv wide
    q_tile, k_tile = block_q * d_lanes * itemsize, block_k * d_lanes * itemsize
    o_tile, v_tile = block_q * dv_lanes * itemsize, block_k * dv_lanes * itemsize
    row = 8 * block_q * 4  # a [1, block_q] float32 row takes a sublane tile
    mask = 8 * block_k * 4
    bias = block_q * block_k * 4 if whole else 0
    carried = 0 if whole else 4  # bytes of float32 scratch per carried element
    forward = (
        2 * (heads * (q_tile + o_tile) + kv_heads * (k_tile + v_tile) + heads * row + mask)
        + 3 * sub * block_k * 4 + bias
        + block_q * LANE * 4 + carried * heads * block_q * (dv_lanes + 2 * LANE)
    )
    dq = (
        2 * (heads * (2 * q_tile + o_tile) + kv_heads * (k_tile + v_tile) + 2 * heads * row + mask)
        + 5 * sub * block_k * 4 + bias
        + 2 * block_q * LANE * 4 + carried * heads * block_q * d_lanes
    )
    dkv = (
        2 * (group * (q_tile + o_tile) + 2 * kv_heads * (k_tile + v_tile) + 2 * group * row + mask)
        + 5 * sub * block_q * 4 + bias
        + block_k * LANE * 4 + carried * kv_heads * block_k * (d_lanes + dv_lanes)
    )
    return max(forward, dq, dkv)


def choose_tiles(
    T: int, S: int, D: int, rep: int, dtype, vmem_budget: int = 12 * 2**20, Dv: Optional[int] = None
) -> FlashTiles:
    """Tiles for q ``[.., T, D]`` against k ``[.., S, D]`` and v ``[.., S, Dv]``
    (``Dv`` = ``D`` where it is not given) with ``rep`` query heads to a kv
    head. A pure function of the shape; the one place tiles are chosen.

    Lengths are padded to multiples of 128 (the lane side of a score tile: keys
    in the forward and dq passes, queries in the transposed dkv pass); rows are
    worked on up to the dtype's sublane multiple only. Where the whole padded
    sequence fits ``vmem_budget`` a program takes it whole, for one or more
    heads; where it does not, the kv side is walked on the last grid axis with
    tiles of 128-512. Among the tilings that fit, the cheapest by padded score
    area plus a fixed cost per program wins."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 32 // itemsize  # 8 rows of float32, 16 of bfloat16 to a tile
    Tr, Sr = _round_up(T, sublane), _round_up(S, sublane)
    T128, S128 = _round_up(T, LANE), _round_up(S, LANE)

    best = None
    walked = [(bq, bk) for bq in (512, 256, 128) for bk in (512, 256, 128) if bq < T128 or bk < S128]
    for block_q, block_k in [(T128, S128)] + walked:
        block_q, block_k = min(block_q, T128), min(block_k, S128)
        Tp, Sp = _round_up(T, block_q), _round_up(S, block_k)
        whole = block_q == Tp and block_k == Sp
        # the smallest row tile that leaves the body at most eight to unroll
        sub = next((n for n in (128, 256, 512) if max(block_q, block_k) <= 8 * n), None)
        fits = sub is not None and _reckon_vmem(block_q, block_k, sub, 1, D, rep, itemsize, whole, Dv) <= vmem_budget
        if not fits:  # graftcheck: noqa[JX004] — static shape/int, not traced
            continue
        cost = Tp * Sp + (Tp // block_q) * (Sp // block_k) * _PROGRAM_COST
        if best is None or cost < best[0]:  # graftcheck: noqa[JX004] — static shape/int, not traced
            best = (cost, block_q, block_k, sub, Tp, Sp, whole)
    if best is None:
        raise ValueError(
            f"no flash-attention tiling of T={T} S={S} D={D} Dv={Dv or D} rep={rep} fits {vmem_budget} bytes of VMEM"
        )
    _, block_q, block_k, sub, Tp, Sp, whole = best

    def takes(heads):  # several heads to a program: only whole sequences, within the area and the budget
        return (
            whole
            and heads <= 8
            and heads * Tp * Sp <= _PROGRAM_AREA
            and _reckon_vmem(block_q, block_k, sub, heads, D, rep, itemsize, whole, Dv) <= vmem_budget
        )

    heads = max(h for h in (1, 2, 4, 8) if h == 1 or takes(h))
    return FlashTiles(
        block_q, block_k, sub, Tp, Sp, min(Tr, Tp), min(Sr, Sp), heads,
        _reckon_vmem(block_q, block_k, sub, heads, D, rep, itemsize, whole, Dv),
    )


def _heads_per_program(H: int, rep: int, most: int) -> int:
    """The most query heads, at most ``most``, that programs can share out evenly
    with their kv heads: a divisor of the group, or whole groups dividing Hkv."""
    fits = [
        g for g in range(1, min(most, H) + 1)
        if (rep % g == 0) or (g % rep == 0 and (H // rep) % (g // rep) == 0)
    ]
    return max(fits)


@functools.lru_cache(maxsize=None)
def _log_tiles(B, H, Hkv, T, S, D, Dv, dtype, tiles, heads):
    """The chooser's choice, once per traced shape."""
    kv_heads = max(1, heads // (H // Hkv))
    steps = (tiles.Tp // tiles.block_q, tiles.Sp // tiles.block_k)
    values = "" if Dv == D else f" v[{B},{Hkv},{S},{Dv}]"
    logger.info(  # graftcheck: noqa[JX003] — once per traced shape is the point
        f"flash attention q[{B},{H},{T},{D}] kv[{B},{Hkv},{S},{D}]{values} {dtype}:"
        f" tiles {tiles.block_q}x{tiles.block_k}"
        f" in rows of {tiles.sub}, padded {tiles.Tp}x{tiles.Sp}, {heads} head(s) a program, grid"
        f" {(B, H // heads) + steps} (dkv {(B, Hkv // kv_heads) + steps[::-1]}), VMEM reckoned"
        f" {tiles.vmem_bytes / 2**20:.1f} MiB"
    )


def _loop(body, *, count: int) -> None:
    """``body(i)`` for i < count; no loop around a single pass."""
    if count == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, count, lambda i, c: (body(i), c)[1], 0)


def _for_each_head(heads: int, rep: int, fn) -> None:
    """``fn(h, kh)`` for a program's query heads h with their local kv head kh:
    whole groups of ``rep`` where the program holds several kv heads, else a
    part of one group."""
    group = min(heads, rep)
    _loop(lambda kh: _loop(lambda r: fn(kh * group + r, kh), count=group), count=heads // group)


def _row_tiles(rows: int, sub: int):
    return [(r0, min(sub, rows - r0)) for r0 in range(0, rows, sub)]


def _precision(dtype):
    """Operands multiply in the dtype they arrive in, whatever the process-wide
    default says: float32 in full, bfloat16 in one MXU pass (the chip's compiler
    takes no other for it); accumulation is float32 either way."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _nt_dot(a, b):
    """a [m, d], b [n, d] -> a b^T [m, n], accumulated in float32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_precision(a.dtype), preferred_element_type=jnp.float32
    )


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=_precision(a.dtype), preferred_element_type=jnp.float32
    )


def _to_rows(col):
    """[n, LANE] (a column, lane-replicated) -> [1, n] with n on the lanes."""
    return jnp.transpose(col)[:1]


def _to_cols(row):
    """[1, n] -> [n, 1]: the lane-dense row a statistic travels as, back to
    the column a [n, keys] score tile subtracts."""
    return jnp.transpose(jnp.broadcast_to(row, (LANE, row.shape[1])))[:, :1]


def _query_row_tiles(tiles: FlashTiles, causal: bool):
    """Row tiles ``(first row, rows, keys needed)`` of a program's [queries, keys]
    score block, for the forward and dq passes. Rows past the data are left out
    where the q side is one tile; keys above the diagonal where the whole
    sequence is one program and causal extents are therefore static."""
    rows = tiles.Tr if tiles.Tp == tiles.block_q else tiles.block_q
    return [
        (r0, n, min(tiles.block_k, _round_up(r0 + n, LANE)) if causal and tiles.whole else tiles.block_k)
        for r0, n in _row_tiles(rows, tiles.sub)
    ]


def _visit_below_diagonal(visit, qi, kj, *, causal: bool, tiles: FlashTiles) -> None:
    """Run ``visit`` for the (q block, kv block) of this grid step, unless the
    kv side is walked in blocks and this one lies wholly above the diagonal."""
    if causal and not tiles.whole:
        pl.when(kj * tiles.block_k <= qi * tiles.block_q + (tiles.block_q - 1))(visit)
    else:
        visit()


def _mask_biases(kv_valid, q0, k0, *, row_tiles, causal: bool):
    """Per row tile ``(first row, rows, keys)`` of a [queries, keys] score
    block at (q0, k0): 0 where a query may see a key, NEG_INF elsewhere. Added
    to the scores; the heads of a program share it."""
    valid = jnp.where(kv_valid > 0, 0.0, NEG_INF)  # [1, block_k]
    biases = []
    for r0, n, keys in row_tiles:
        bias = valid[:, :keys]
        if causal:
            q_pos = q0 + r0 + jax.lax.broadcasted_iota(jnp.int32, (n, keys), 0)
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (n, keys), 1)
            bias = jnp.where(k_pos <= q_pos, bias, NEG_INF)
        biases.append(bias)
    return biases


def _flash_kernel(
    kv_valid_ref,  # [1, 1, block_k] int32
    q_ref,  # [1, heads, block_q, D]
    k_ref,  # [1, kv heads, block_k, D]
    v_ref,  # [1, kv heads, block_k, Dv]
    o_ref,  # [1, heads, block_q, Dv]
    *rest,  # lse_ref [1, heads, 1, block_q] f32 and its [block_q, LANE] scratch (with_lse);
    # m, l [heads, block_q, 1] and acc [heads, block_q, Dv] f32 scratch (kv walked)
    causal: bool,
    scale: float,
    tiles: FlashTiles,
    rep: int,
    with_lse: bool,
):
    rest = list(rest)
    lse_ref, lse_cols = (rest.pop(0), rest.pop(-1)) if with_lse else (None, None)
    heads = q_ref.shape[1]
    block_q, block_k = tiles.block_q, tiles.block_k
    kv_steps = tiles.Sp // block_k
    carried = kv_steps > 1  # running max / sum / accumulator live in scratch across kv steps
    qi, kj = pl.program_id(2), pl.program_id(3)
    row_tiles = _query_row_tiles(tiles, causal)

    def finish(h, r0, n, m, l, acc):
        seen = m > NEG_INF / 2  # rows with no valid key give 0, not NaN
        o_ref[0, h, r0:r0 + n, :] = (acc * jnp.where(seen, 1.0 / l, 0.0)).astype(o_ref.dtype)
        if with_lse:
            lse = jnp.where(seen, m + jnp.log(l), NEG_INF)
            lse_cols[r0:r0 + n, :] = jnp.broadcast_to(lse, (n, LANE))

    if carried:
        m_scratch, l_scratch, acc_scratch = rest

        @pl.when(kj == 0)
        def _init():
            m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
            l_scratch[...] = jnp.zeros_like(l_scratch)
            acc_scratch[...] = jnp.zeros_like(acc_scratch)

    data_rows = row_tiles[-1][0] + row_tiles[-1][1]
    if with_lse and data_rows < block_q:  # the backward must find no NaN past the data
        lse_cols[data_rows:, :] = jnp.full((block_q - data_rows, LANE), NEG_INF, jnp.float32)

    def visit():
        biases = _mask_biases(kv_valid_ref[0], qi * block_q, kj * block_k, row_tiles=row_tiles, causal=causal)

        def head(h, kh):
            k, v = k_ref[0, kh], v_ref[0, kh]  # loaded once; the row tiles slice the values
            for (r0, n, keys), bias in zip(row_tiles, biases):
                q = q_ref[0, h, r0:r0 + n, :]
                s = _nt_dot(q, k[:keys]) * scale + bias  # [n, keys]
                m = jnp.max(s, axis=1, keepdims=True)
                if carried:
                    m_prev = m_scratch[h, r0:r0 + n]
                    m = jnp.maximum(m_prev, m)
                # a row with no key seen yet has m == NEG_INF and p == 1: a later
                # visit's alpha wipes that, and finish() zeroes what is left
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=1, keepdims=True)
                acc = _dot(p.astype(v.dtype), v[:keys])  # [n, Dv]
                if carried:
                    alpha = jnp.exp(m_prev - m)
                    l_scratch[h, r0:r0 + n] = alpha * l_scratch[h, r0:r0 + n] + l
                    acc_scratch[h, r0:r0 + n] = alpha * acc_scratch[h, r0:r0 + n] + acc
                    m_scratch[h, r0:r0 + n] = m
                else:
                    finish(h, r0, n, m, l, acc)
            if with_lse and not carried:
                lse_ref[0, h] = _to_rows(lse_cols[...])

        _for_each_head(heads, rep, head)

    _visit_below_diagonal(visit, qi, kj, causal=causal, tiles=tiles)

    if carried:

        @pl.when(kj == kv_steps - 1)
        def _finalize():
            def head(h):
                for r0, n, _ in row_tiles:
                    finish(h, r0, n, m_scratch[h, r0:r0 + n], l_scratch[h, r0:r0 + n], acc_scratch[h, r0:r0 + n])
                if with_lse:
                    lse_ref[0, h] = _to_rows(lse_cols[...])

            _loop(head, count=heads)


def _kv_block_map(heads: int, rep: int):
    """Block index of a program's kv heads from the block index of its query
    heads: the same where it holds whole groups, else the group's kv head."""
    return (lambda h: h) if heads >= rep else (lambda h: (h * heads) // rep)


def _grid_semantics():
    """Every grid axis but the last is independent; the last carries scratch."""
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _pad_to(x, *, axis: int, n: int):
    """Zero-pad ``axis`` of x up to length n."""
    if x.shape[axis] == n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, widths)


def _key_mask(kv_valid, Sp: int):
    """[B, S] -> [B, 1, Sp] int32, the layout the kernels take a row of it in;
    padded keys are invalid."""
    return _pad_to(kv_valid.astype(jnp.int32), axis=1, n=Sp)[:, None, :]


def _flash_forward(
    q: jnp.ndarray,  # [B, H, T, D]
    k: jnp.ndarray,  # [B, Hkv, S, D]
    v: jnp.ndarray,  # [B, Hkv, S, Dv]
    kv_valid: jnp.ndarray,  # [B, S] int32
    causal: bool,
    scale: float,
    interpret: bool,
    with_lse: bool = False,
    tiles: Optional[FlashTiles] = None,
):
    """Pad to the chooser's tiles, run the forward kernel, slice the padding
    off. Any T/S: padded keys are masked through ``kv_valid``, padded query
    rows are sliced off. With ``with_lse`` also returns the per-row logsumexp
    as the backward takes it: ``[B, H, 1, Tp]`` float32, T on the lanes."""
    B, H, T, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    assert H % Hkv == 0, (H, Hkv)
    rep = H // Hkv
    if tiles is None:
        tiles = choose_tiles(T, S, D, rep, q.dtype, Dv=Dv)
    heads = _heads_per_program(H, rep, tiles.heads)
    _log_tiles(B, H, Hkv, T, S, D, Dv, jnp.dtype(q.dtype).name, tiles, heads)
    kv_heads = max(1, heads // rep)
    block_q, block_k = tiles.block_q, tiles.block_k
    kvh = _kv_block_map(heads, rep)

    q = _pad_to(q, axis=2, n=tiles.Tp)
    k, v = _pad_to(k, axis=2, n=tiles.Sp), _pad_to(v, axis=2, n=tiles.Sp)
    kv_valid = _key_mask(kv_valid, tiles.Sp)

    q_spec, o_spec = (pl.BlockSpec((1, heads, block_q, w), lambda b, h, i, j: (b, h, i, 0)) for w in (D, Dv))
    k_spec, v_spec = (
        pl.BlockSpec((1, kv_heads, block_k, w), lambda b, h, i, j: (b, kvh(h), j, 0)) for w in (D, Dv)
    )
    out_shape = [jax.ShapeDtypeStruct((B, H, tiles.Tp, Dv), q.dtype)]
    out_specs = [o_spec]
    scratch = []
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, tiles.Tp), jnp.float32))
        out_specs.append(pl.BlockSpec((1, heads, 1, block_q), lambda b, h, i, j: (b, h, 0, i)))
    if tiles.Sp > block_k:  # graftcheck: noqa[JX004] — static shape/int, not traced
        scratch += [
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((heads, block_q, Dv), jnp.float32),
        ]
    if with_lse:
        scratch.append(pltpu.VMEM((block_q, LANE), jnp.float32))

    res = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, scale=scale, tiles=tiles, rep=rep, with_lse=with_lse
        ),
        grid=(B, H // heads, tiles.Tp // block_q, tiles.Sp // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j)),
            q_spec, k_spec, v_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_grid_semantics(),
    )(kv_valid, q, k, v)
    out = res[0][:, :, :T, :]
    return (out, res[1]) if with_lse else out


# ----------------------------------------------------------------- backward


def _flash_bwd_dkv_kernel(
    kv_valid_ref,  # [1, 1, block_k]
    q_ref,  # [1, kv heads * rep, block_q, D]: each kv head's whole query-head group
    k_ref,  # [1, kv heads, block_k, D]
    v_ref,  # [1, kv heads, block_k, Dv]
    do_ref,  # as q_ref, Dv wide
    lse_ref,  # [1, kv heads * rep, 1, block_q] f32
    delta_ref,
    dk_ref,  # as k_ref, out
    dv_ref,  # as v_ref, out
    *scratch,  # dk, dv as their blocks, f32, where the q side is walked
    causal: bool,
    scale: float,
    tiles: FlashTiles,
    rep: int,
):
    """Works on transposed score tiles, keys down the sublanes and queries along
    the lanes: lse and delta are subtracted as the rows they arrive as, and all
    four matmuls (K Q^T, V dO^T, P^T dO, dS^T Q) contract without a transpose."""
    kv_heads = k_ref.shape[1]
    block_q, block_k = tiles.block_q, tiles.block_k
    q_steps = tiles.Tp // block_q
    carried = q_steps > 1
    kj, qi = pl.program_id(2), pl.program_id(3)
    # (first key row, rows, first query needed): key rows past the data are left out where
    # the kv side is one tile, queries before the diagonal where causal extents are static
    row_tiles = [
        (c0, n, min(c0 // LANE * LANE, block_q - LANE) if causal and tiles.whole else 0)
        for c0, n in _row_tiles(tiles.Sr if tiles.Sp == block_k else block_k, tiles.sub)
    ]

    if carried:
        dk_scratch, dv_scratch = scratch

        @pl.when(qi == 0)
        def _init():
            dk_scratch[...] = jnp.zeros_like(dk_scratch)
            dv_scratch[...] = jnp.zeros_like(dv_scratch)

    def visit():
        valid = _to_cols(jnp.where(kv_valid_ref[0] > 0, 0.0, NEG_INF))  # [block_k, 1]
        biases = []
        for c0, n, first in row_tiles:
            bias = valid[c0:c0 + n]
            if causal:
                shape = (n, block_q - first)
                k_pos = kj * block_k + c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                q_pos = qi * block_q + first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                bias = jnp.where(k_pos <= q_pos, bias, NEG_INF)
            biases.append(bias)

        def kv_head(kh):
            k, v = k_ref[0, kh], v_ref[0, kh]

            # dK/dV of a kv head sum over its query-head group
            def group(r, sums):
                h = kh * rep + r
                q, do = q_ref[0, h], do_ref[0, h]
                out = []
                for (c0, n, first), bias, (dk, dv) in zip(row_tiles, biases, sums):
                    lse = lse_ref[0, h, :, first:]  # [1, queries]
                    # fully-masked rows have lse == NEG_INF; guard the exp against inf * 0
                    lse = jnp.where(lse > NEG_INF / 2, lse, 0.0)
                    p = jnp.exp(_nt_dot(k[c0:c0 + n], q[first:]) * scale + bias - lse)  # [n, queries]
                    dv += _dot(p.astype(do.dtype), do[first:])
                    dp = _nt_dot(v[c0:c0 + n], do[first:])
                    ds = p * (dp - delta_ref[0, h, :, first:]) * scale
                    dk += _dot(ds.astype(q.dtype), q[first:])
                    out.append((dk, dv))
                return out

            sums = [
                (jnp.zeros((n, k.shape[1]), jnp.float32), jnp.zeros((n, v.shape[1]), jnp.float32))
                for _, n, _ in row_tiles
            ]
            sums = group(0, sums) if rep == 1 else jax.lax.fori_loop(0, rep, group, sums)
            for (c0, n, _), (dk, dv) in zip(row_tiles, sums):
                if carried:
                    dk_scratch[kh, c0:c0 + n] += dk
                    dv_scratch[kh, c0:c0 + n] += dv
                else:
                    dk_ref[0, kh, c0:c0 + n, :] = dk.astype(dk_ref.dtype)
                    dv_ref[0, kh, c0:c0 + n, :] = dv.astype(dv_ref.dtype)

        _loop(kv_head, count=kv_heads)

    _visit_below_diagonal(visit, qi, kj, causal=causal, tiles=tiles)

    if carried:

        @pl.when(qi == q_steps - 1)
        def _finalize():
            dk_ref[0] = dk_scratch[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scratch[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    kv_valid_ref,  # [1, 1, block_k]
    q_ref,  # [1, heads, block_q, D]
    k_ref,  # [1, kv heads, block_k, D]
    v_ref,  # [1, kv heads, block_k, Dv]
    do_ref,  # [1, heads, block_q, Dv]
    lse_ref,  # [1, heads, 1, block_q] f32
    delta_ref,
    dq_ref,  # [1, heads, block_q, D] out
    *scratch,  # dq [heads, block_q, D] f32 where the kv side is walked
    causal: bool,
    scale: float,
    tiles: FlashTiles,
    rep: int,
):
    heads = q_ref.shape[1]
    block_q, block_k = tiles.block_q, tiles.block_k
    kv_steps = tiles.Sp // block_k
    carried = kv_steps > 1
    qi, kj = pl.program_id(2), pl.program_id(3)
    row_tiles = _query_row_tiles(tiles, causal)

    if carried:
        (dq_scratch,) = scratch

        @pl.when(kj == 0)
        def _init():
            dq_scratch[...] = jnp.zeros_like(dq_scratch)

    def visit():
        biases = _mask_biases(kv_valid_ref[0], qi * block_q, kj * block_k, row_tiles=row_tiles, causal=causal)

        def head(h, kh):
            lse = _to_cols(lse_ref[0, h])  # [block_q, 1]
            lse = jnp.where(lse > NEG_INF / 2, lse, 0.0)
            delta = _to_cols(delta_ref[0, h])
            k, v = k_ref[0, kh], v_ref[0, kh]
            for (r0, n, keys), bias in zip(row_tiles, biases):
                q = q_ref[0, h, r0:r0 + n, :]
                do = do_ref[0, h, r0:r0 + n, :]
                p = jnp.exp(_nt_dot(q, k[:keys]) * scale + bias - lse[r0:r0 + n])  # [n, keys]
                dp = _nt_dot(do, v[:keys])
                ds = p * (dp - delta[r0:r0 + n]) * scale
                dq = _dot(ds.astype(k.dtype), k[:keys])  # [n, D]
                if carried:
                    dq_scratch[h, r0:r0 + n] += dq
                else:
                    dq_ref[0, h, r0:r0 + n, :] = dq.astype(dq_ref.dtype)

        _for_each_head(heads, rep, head)

    _visit_below_diagonal(visit, qi, kj, causal=causal, tiles=tiles)

    if carried:

        @pl.when(kj == kv_steps - 1)
        def _finalize():
            dq_ref[0] = dq_scratch[...].astype(dq_ref.dtype)


def _flash_backward(q, k, v, kv_valid, out, lse, g, causal, scale, interpret, tiles=None):
    """Pallas backward: recompute P per tile from the saved logsumexp (``lse`` as
    ``_flash_forward`` returns it, ``[B, H, 1, Tp]``). Returns dq, dk, dv in the
    dtype of q, k, v.

    Two kernels: ``dkv`` runs grid (B, kv-head blocks, kv steps, q steps), a
    program taking whole query-head groups so dK/dV sum over the group without
    output-block write conflicts; ``dq`` runs the forward's grid. Where the whole
    sequence is one tile neither carries anything across grid steps."""
    B, H, T, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    rep = H // Hkv
    if tiles is None:
        tiles = choose_tiles(T, S, D, rep, q.dtype, Dv=Dv)
    heads = _heads_per_program(H, rep, tiles.heads)
    kv_heads = max(1, heads // rep)
    block_q, block_k = tiles.block_q, tiles.block_k
    q_steps, kv_steps = tiles.Tp // block_q, tiles.Sp // block_k
    kvh = _kv_block_map(heads, rep)

    # padded query rows: dO == 0 and delta == 0 there, so they add nothing
    q, g, out = (_pad_to(x, axis=2, n=tiles.Tp) for x in (q, g, out))
    k, v = _pad_to(k, axis=2, n=tiles.Sp), _pad_to(v, axis=2, n=tiles.Sp)
    kv_valid = _key_mask(kv_valid, tiles.Sp)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, :, None, :]  # [B, H, 1, Tp]

    # a block of kv_heads * rep query heads at block index hk is kv-head block hk's groups
    group_spec, group_do_spec = (
        pl.BlockSpec((1, kv_heads * rep, block_q, w), lambda b, hk, kj, qi: (b, hk, qi, 0)) for w in (D, Dv)
    )
    group_row_spec = pl.BlockSpec((1, kv_heads * rep, 1, block_q), lambda b, hk, kj, qi: (b, hk, 0, qi))
    k_spec, v_spec = (pl.BlockSpec((1, kv_heads, block_k, w), lambda b, hk, kj, qi: (b, hk, kj, 0)) for w in (D, Dv))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, scale=scale, tiles=tiles, rep=rep),
        grid=(B, Hkv // kv_heads, kv_steps, q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_k), lambda b, hk, kj, qi: (b, 0, kj)),
            group_spec, k_spec, v_spec, group_do_spec, group_row_spec, group_row_spec,
        ],
        out_specs=[k_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((kv_heads, block_k, w), jnp.float32) for w in (D, Dv)] if q_steps > 1 else [],
        interpret=interpret,
        compiler_params=_grid_semantics(),
    )(kv_valid, q, k, v, g, lse, delta)

    q_spec, do_spec = (pl.BlockSpec((1, heads, block_q, w), lambda b, h, qi, kj: (b, h, qi, 0)) for w in (D, Dv))
    row_spec = pl.BlockSpec((1, heads, 1, block_q), lambda b, h, qi, kj: (b, h, 0, qi))
    dq_k_spec, dq_v_spec = (
        pl.BlockSpec((1, kv_heads, block_k, w), lambda b, h, qi, kj: (b, kvh(h), kj, 0)) for w in (D, Dv)
    )
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale, tiles=tiles, rep=rep),
        grid=(B, H // heads, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_k), lambda b, h, qi, kj: (b, 0, kj)),
            q_spec, dq_k_spec, dq_v_spec, do_spec, row_spec, row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((heads, block_q, D), jnp.float32)] if kv_steps > 1 else [],
        interpret=interpret,
        compiler_params=_grid_semantics(),
    )(kv_valid, q, k, v, g, lse, delta)

    return dq[:, :, :T, :], dk[:, :, :S, :], dv[:, :, :S, :]


def xla_attention(q, k, v, kv_valid, causal: bool, scale: float) -> jnp.ndarray:
    """Reference attention in plain XLA ([B,H,T,D] layout; grouped K/V repeated)."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    T, S = s.shape[-2], s.shape[-1]
    mask = kv_valid[:, None, None, :] > 0
    if causal:
        q_pos = jnp.arange(T)[:, None]
        k_pos = jnp.arange(S)[None, :]
        mask = jnp.logical_and(mask, (k_pos <= q_pos)[None, None])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows -> 0
    p = jnp.where(jnp.any(mask, axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(
    q, k, v, kv_valid, causal: bool = True, scale: Optional[float] = None, interpret: bool = False,
):
    """Flash attention, [B,H,T,D] layout; K/V may carry fewer (grouped) heads, and
    V (with it the output) a width of its own, ``[B,Hkv,S,Dv]``.
    Tiles come from the shape (:func:`choose_tiles`). Differentiable: backward
    runs Pallas dq/dkv kernels recomputing attention per tile from the saved
    logsumexp (O(T·tile) memory, matching the memory model of the reference's
    fused CUDA kernels — SURVEY.md §2.4.5)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash_forward(q, k, v, kv_valid, causal, scale, interpret)


def _fwd(q, k, v, kv_valid, causal, scale, interpret):
    scale_ = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash_forward(q, k, v, kv_valid, causal, scale_, interpret, with_lse=True)
    return out, (q, k, v, kv_valid, out, lse)


def _bwd(causal, scale, interpret, res, g):
    q, k, v, kv_valid, out, lse = res
    scale_ = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    dq, dk, dv = _flash_backward(q, k, v, kv_valid, out, lse, g, causal, scale_, interpret)
    return dq, dk, dv, None


flash_attention.defvjp(_fwd, _bwd)


# ----------------------------------------------------------------- decode
#
# One new token a row against the contiguous cache ``[B, Hkv, S, D]`` of the
# one-shot generator. The work is a matrix-vector product per (row, head) with
# a matrix of its own each, so the MXU has nothing to latch: it runs on the
# VPU, a lane per batch row. The kernel therefore takes the cache as
# ``[S, Hkv, D, B]`` — slots outermost, the batch on the lanes, the head
# dimension down the sublanes — which is the physical layout the chip's
# compiler gives a cache carried through the decode ``while`` anyway
# (``{0,3,1,2}``, read off the compiled program), so the transposes around the
# call are bitcasts. Scores reduce over sublanes, the weighted sum over slots,
# neither over lanes. With slots outermost the written part of the cache is a
# prefix of the buffer: a grid step holds ``block`` slots, a block past the
# write index does nothing, and its index map is clamped to the last block that
# holds a token, so the pipeline sees the same block again and moves no bytes.


class DecodeTiles(NamedTuple):
    """How one decode shape is cut into programs (see :func:`choose_decode_tiles`)."""

    rows: int  # batch rows a program takes, on the lanes
    block: int  # cache slots a grid step holds
    vmem_bytes: int  # reckoned VMEM


def choose_decode_tiles(
    B: int, Hkv: int, rep: int, S: int, D: int, dtype, vmem_budget: int = 12 * 2**20, Dv: Optional[int] = None
) -> DecodeTiles:
    """Programs for q ``[B, Hkv * rep, D]`` against a cache ``[B, Hkv, S, D]``.
    A pure function of the shape, beside :func:`choose_tiles`.

    A program takes 128 batch rows (all of them where 128 does not divide B)
    and all their heads, and the fewest slots — 8, 16 or 32 — that make its k
    block a mebibyte, within ``vmem_budget`` (k and v blocks twice, q, o, the
    float32 accumulator): at gpt2's widths and batch 128 a slot of one operand
    is 196 KB, so 8 slots and 1.5 MB an operand a grid step; the chip reads
    16 and 32 within 1 % and 4 % of that (PERF.md §6, PR 30)."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = LANE if B % LANE == 0 else B
    lanes = _round_up(rows, LANE)
    sublane = 32 // itemsize
    k_slot = Hkv * _round_up(D, sublane) * lanes * itemsize
    v_slot = Hkv * _round_up(Dv or D, sublane) * lanes * itemsize

    def reckon(block):
        q_o = rep * (k_slot + v_slot)
        carried = Hkv * rep * (_round_up(Dv or D, 8) + 2 * 8) * lanes * 4  # accumulator, max, sum
        tiles = (1 + 2 * rep) * block * lanes * 4  # mask bias, a kv head's scores and probabilities
        return 2 * block * (k_slot + v_slot) + 2 * q_o + carried + 2 * tiles

    fits = [n for n in (8, 16, 32) if n == 8 or (n <= _round_up(S, 8) and reckon(n) <= vmem_budget)]
    block = next((n for n in fits if n * k_slot >= 2**20), fits[-1])
    return DecodeTiles(rows, block, reckon(block))


@functools.lru_cache(maxsize=None)
def _log_decode_tiles(B, H, Hkv, S, D, dtype, tiles):
    """The chooser's choice, once per traced shape."""
    logger.info(  # graftcheck: noqa[JX003] — once per traced shape is the point
        f"decode attention q[{B},{H},{D}] cache[{B},{Hkv},{S},{D}] {dtype}: rows {tiles.rows} block {tiles.block},"
        f" grid {(B // tiles.rows, -(-S // tiles.block))}, VMEM reckoned {tiles.vmem_bytes / 2**20:.1f} MiB"
    )


def cache_read_share(prompt_len: int, steps: int, cache_len: int, block: Optional[int]) -> float:
    """Slots the decode kernel's grid visits over slots the cache holds, summed
    over ``steps`` decode steps after a prefill of ``prompt_len`` slots: step t
    (from 1) reads the ``prompt_len + t`` written slots rounded up to
    ``block``. 1.0 where no kernel runs (``block`` None) or no step did."""
    if block is None or steps <= 0:
        return 1.0
    visited = sum(min(cache_len, _round_up(prompt_len + t, block)) for t in range(1, steps + 1))
    return visited / (steps * cache_len)


def _decode_kernel(
    index_ref,  # scalar prefetch: [1] int32, the slot this step's token was written to
    bias_ref,  # [block, rows] f32: 0 where a row may see a slot, -1e9 elsewhere
    q_ref,  # [Hkv, rep, D, rows]
    k_ref,  # [block, Hkv, D, rows]
    v_ref,  # [block, Hkv, Dv, rows]
    o_ref,  # [Hkv, rep, Dv, rows]
    m_ref,  # [H, 1, rows] f32 running max
    l_ref,  # [H, 1, rows] f32 running sum
    acc_ref,  # [H, Dv, rows] f32
    s_ref,  # [rep, block, rows] f32: a kv head's scores, slot by slot
    p_ref,  # [rep, block, rows] f32: its probabilities
    *,
    scale: float,
):
    block, kv_heads = k_ref.shape[0], k_ref.shape[1]
    rep = q_ref.shape[1]
    j = pl.program_id(1)
    held = index_ref[0] + 1 - j * block  # slots of this block that hold a token

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(ragged: bool):
        """All of the block's slots, unrolled so that their chains interleave;
        ``ragged``: the block the write index lies in, where what is past the
        index (never written, or past the cache's end) is selected away and not
        multiplied by zero, so that nothing there can reach the result."""
        bias = bias_ref[...]
        written = jax.lax.broadcasted_iota(jnp.int32, bias.shape, 0) < held

        def kv_head(h):
            qs = [q_ref[h, r].astype(jnp.float32) for r in range(rep)]  # [D, rows]
            for s in range(block):
                k = k_ref[s, h].astype(jnp.float32)
                for r in range(rep):
                    s_ref[r, s:s + 1, :] = jnp.sum(qs[r] * k, axis=0, keepdims=True)
            accs = []
            for r in range(rep):
                hr = h * rep + r
                scores = s_ref[r] * scale + bias
                if ragged:
                    scores = jnp.where(written, scores, NEG_INF)
                m_prev = m_ref[hr]
                m = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
                p = jnp.exp(scores - m)
                alpha = jnp.exp(m_prev - m)
                l_ref[hr] = alpha * l_ref[hr] + jnp.sum(p, axis=0, keepdims=True)
                m_ref[hr] = m
                # the probabilities meet v in v's dtype, as the einsum path's do
                p_ref[r] = p.astype(v_ref.dtype).astype(jnp.float32)
                accs.append(alpha * acc_ref[hr])
            for s in range(block):
                v = v_ref[s, h].astype(jnp.float32)  # [Dv, rows]
                if ragged:
                    v = jnp.where(s < held, v, 0.0)
                for r in range(rep):
                    accs[r] = accs[r] + p_ref[r, s:s + 1, :] * v
            for r in range(rep):
                acc_ref[h * rep + r] = accs[r]

        _loop(kv_head, count=kv_heads)

    pl.when(held >= block)(lambda: visit(False))
    pl.when(jnp.logical_and(held > 0, held < block))(lambda: visit(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        def head(hr):
            o_ref[hr // rep, hr % rep] = (acc_ref[hr] / l_ref[hr]).astype(o_ref.dtype)

        _loop(head, count=kv_heads * rep)


# jitted so that a model's layers, which call it at one shape, share one trace and one
# lowering of the kernel: traced a layer, 24 layers add 5 s to every program that decodes
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "tiles"))
def decode_attention(
    q: jnp.ndarray,  # [B, H, D]: one new token a row
    k: jnp.ndarray,  # [B, Hkv, S, D]: the cache, this step's token written at slot ``index``
    v: jnp.ndarray,  # [B, Hkv, S, Dv]
    mask_bias: jnp.ndarray,  # additive, B * S elements ([B, 1, 1, S]): 0 where a row may see a slot
    index,  # scalar int32: the last slot that holds a token, the same for every row
    scale: Optional[float] = None,
    interpret: bool = False,
    tiles: Optional[DecodeTiles] = None,
) -> jnp.ndarray:
    """Attention of one query token a row over cache slots ``0..index``, in one
    Pallas call: scores, the float32 softmax (taken block by block, with a
    running max and sum) and the weighted sum. Slots past ``index`` are neither
    computed on nor, beyond the block ``index`` lies in, moved. ``mask_bias``
    is the einsum path's (left padding; its causal part is implied by
    ``index``). Grouped K/V map h -> h // rep. Returns ``[B, H, Dv]`` in q's dtype."""
    B, H, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    assert H % Hkv == 0, (H, Hkv)
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if tiles is None:
        tiles = choose_decode_tiles(B, Hkv, rep, S, D, k.dtype, Dv=Dv)
    _log_decode_tiles(B, H, Hkv, S, D, jnp.dtype(k.dtype).name, tiles)
    rows, block = tiles.rows, tiles.block

    # slots outermost, the batch on the lanes: bitcasts of the cache as the chip's compiler lays it out
    q_t = q.reshape(B, Hkv, rep, D).transpose(1, 2, 3, 0)
    k_t, v_t = k.transpose(2, 1, 3, 0), v.transpose(2, 1, 3, 0)
    bias = mask_bias.reshape(B, S).astype(jnp.float32).T
    index = jnp.asarray(index, jnp.int32).reshape(1)

    def last_held(j, idx):  # a block past the write index is the last held one again: no new DMA
        return jnp.minimum(j, idx[0] // block)

    q_spec, o_spec = (pl.BlockSpec((Hkv, rep, w, rows), lambda i, j, idx: (0, 0, 0, i)) for w in (D, Dv))
    k_spec, v_spec = (
        pl.BlockSpec((block, Hkv, w, rows), lambda i, j, idx: (last_held(j, idx), 0, 0, i)) for w in (D, Dv)
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rows, -(-S // block)),
            in_specs=[
                pl.BlockSpec((block, rows), lambda i, j, idx: (last_held(j, idx), i)),
                q_spec, k_spec, v_spec,
            ],
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((H, 1, rows), jnp.float32),
                pltpu.VMEM((H, 1, rows), jnp.float32),
                pltpu.VMEM((H, Dv, rows), jnp.float32),
                pltpu.VMEM((rep, block, rows), jnp.float32),
                pltpu.VMEM((rep, block, rows), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Hkv, rep, Dv, B), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 * 2**20, 2 * tiles.vmem_bytes),
        ),
        # a name of its own: unnamed under the model's `attn` scope it would be `%attn.N custom-call`
        # on the chip, which is how the benchmark finds the flash calls
        name="decode_attn",
    )(index, bias, q_t, k_t, v_t)
    return out.transpose(3, 0, 1, 2).reshape(B, H, Dv)


# ---- dispatch: a model states what it attends over (:func:`attend`); which path, over which mesh, is decided here


def _placed(local, mesh, operands, out_ndim: int):
    """``local(*arrays)`` plainly where ``mesh`` is None, else its SPMD
    placement over ``mesh``: Mosaic kernels cannot be auto-partitioned by XLA's
    SPMD pass (it raises at compile time on any multi-device mesh), so shard
    the embarrassingly-parallel grid axes explicitly — batch over ``BATCH_AXES``, heads over ``MODEL_AXIS`` — and run
    the kernel per shard inside a ``shard_map``. No cross-shard terms exist:
    each (batch, head) pair's softmax is independent, and the grouped-KV head
    map stays consistent because H_local/Hkv_local equals the global ratio
    when both divide the axis. Differentiable: autodiff enters the shard_map
    and applies the kernel's custom VJP per shard.

    ``operands``: ``(array, has_heads)`` pairs — dimension 0 of an array is the
    batch, dimension 1 the heads where ``has_heads``; a scalar is replicated.
    The result has ``out_ndim`` dimensions, batch then heads first."""
    if mesh is None:
        return local(*(x for x, _ in operands))
    from jax.sharding import PartitionSpec as P

    # The map must be manual over EVERY mesh axis the SPMD partitioner would
    # otherwise see — a Mosaic op under any remaining auto axis (e.g. `pipe`
    # during stacked-decode prefill) still raises cannot-be-auto-partitioned.
    # When nested inside an enclosing shard_map (the GPipe stage body is manual
    # over `pipe`), the tracing context's AbstractMesh must be named instead of
    # the concrete mesh, and its already-manual axes must be excluded.
    # jax.shard_map (not the experimental alias) carries the axis_names param.
    from jax.sharding import get_abstract_mesh

    amesh = get_abstract_mesh()
    already_manual = set()
    if amesh is not None and amesh.axis_names:
        already_manual = {
            n for n, t in zip(amesh.axis_names, amesh.axis_types) if "Manual" in str(t)
        }
        mesh = amesh
    axes = set(mesh.axis_names) - already_manual
    # Spare manual axes beyond batch/heads (e.g. `pipe` during stacked-decode
    # prefill) stay UNNAMED in the specs: each of their shards computes its
    # replica. Redundant compute, but folding them into the batch entry
    # instead miscompiled — XLA's partitioner emitted an invalid dynamic-slice
    # over the pipe-sharded stacked layer params ("slice dim size 4096 greater
    # than dynamic slice dimension: 2048", v5e compiler, scripts/scale_proof.py)
    # — and prefill under a pipe mesh is a once-per-generation cost.
    batch_entry = tuple(a for a in BATCH_AXES if a in axes)
    head_entry = MODEL_AXIS if MODEL_AXIS in axes else None

    def spec(ndim, has_heads):
        entries = [batch_entry or None, head_entry if has_heads else None] + [None] * (ndim - 2)
        return P(*entries[:ndim])

    return jax.shard_map(
        local, mesh=mesh, in_specs=tuple(spec(jnp.ndim(x), heads) for x, heads in operands),
        out_specs=spec(out_ndim, True), check_vma=False, axis_names=axes,
    )(*(x for x, _ in operands))


def _kernel_shards(mesh) -> Tuple[int, int]:
    """(shards of the batch, shards of the heads) a kernel placed over ``mesh``
    (None: a plain call) is cut into."""
    if mesh is None:
        return 1, 1
    return int(np.prod([mesh.shape.get(a, 1) for a in BATCH_AXES])), mesh.shape.get(MODEL_AXIS, 1)


def _kernel_placement(impl: str, biased: bool, B: int, heads: int, kv_heads: int):
    """(whether a Pallas attention kernel may run for these operands, the mesh
    to place it over or None for a plain call). ``biased``: the scores carry an
    additive bias of their own (alibi) or rows are prepended to the keys
    (prefix tuning) — the kernels take neither."""
    use = impl == "flash" and not biased
    # On a multi-device mesh the call must be placed explicitly (_placed), and
    # a shape that cannot divide its axes falls back to the einsum paths.
    mesh = None
    if use:
        mesh = ambient_mesh()
        if mesh is not None:
            n_batch, n_model = _kernel_shards(mesh)
            if mesh.size == 1:
                # single device: plain call. (Any larger mesh goes via the
                # shard_map even when batch/model axes are trivial — a pipe-only
                # mesh still has an auto axis the Mosaic kernel cannot sit under.)
                mesh = None
            elif B % n_batch or heads % n_model or kv_heads % n_model:
                use = False  # kernel cannot place; XLA attention
    return use, mesh


def flash_placement(impl: str, biased: bool, B: int, T: int, kv_valid, heads: int, kv_heads: int):
    """(whether this forward takes the flash kernel, the mesh to place it over
    or None for a plain call): every multi-token forward whose keys are its own
    tokens (:func:`attend` on ``kv_valid``) — training loss, the logprob/value
    scoring passes and generation prefill."""
    if kv_valid is None or T <= 1:
        return False, None
    return _kernel_placement(impl, biased, B, heads, kv_heads)


def decode_kernel_placement(impl: str, biased: bool, layer, heads: int):
    """(whether a single-token step over a layer's contiguous cache — ``layer``
    holds its arrays, concrete or abstract — takes the Pallas decode kernel,
    the mesh to place it over or None): the flash kernels' rule, and per-head
    float rows in the cache. Everything else — ``impl="xla"``, the int8 cache,
    alibi, prefix tuning, a latent cache (its own absorbed decode), a mesh the
    call cannot be placed over — keeps the einsum path."""
    if kv_cache.is_latent(layer) or kv_cache.has_row_scales(layer):
        return False, None
    B, kv_heads = layer["k"].shape[:2]
    return _kernel_placement(impl, biased, B, heads, kv_heads)


def decode_cache_read_share(impl: str, biased: bool, heads: int, layout, new_tokens: int, steps: int) -> float:
    """Cache slots the decode steps of one rollout visited over the slots the
    cache holds (``rollout/cache_read_share``): host arithmetic from a layer's
    ``layout`` (``ops/kv_cache.py``; what is not for ``new_tokens`` was
    prefilled), the ``steps`` the decode loop ran and the kernel's block; 1.0
    where the steps took the einsum path. Call under the trainer's mesh."""
    layer = {key: jax.ShapeDtypeStruct(shape, dtype) for key, (shape, dtype) in layout.items()}
    use, mesh = decode_kernel_placement(impl, biased, layer, heads)
    if not use:
        return 1.0
    B, kv_heads, cache_len, D = layer["k"].shape
    n_batch, n_model = _kernel_shards(mesh)
    tiles = choose_decode_tiles(B // n_batch, kv_heads // n_model, heads // kv_heads, cache_len, D, layer["k"].dtype)
    return cache_read_share(cache_len - new_tokens, steps, cache_len, tiles.block)


def _interpret(mesh) -> bool:
    """Interpret (XLA-emulated) mode iff a kernel is compiled for the CPU. The
    ambient mesh's devices name the target; default_backend alone is wrong
    under deviceless TPU AOT compilation (scripts/scale_proof.py lowers for a
    TPU topology from a CPU host, where interpret mode would re-materialize
    the score matrices the kernel exists to avoid)."""
    return (mesh.devices.flat[0].platform if mesh is not None else jax.default_backend()) == "cpu"


def attend(q, k, v, cache, mask_bias, kv_valid, index, scale: float, impl: str, biased: bool, prefix):
    """Every attention that is not paged: ``q`` ``[B, T, H, D]`` against this
    forward's own ``k`` ``[B, T, Hkv, D]`` / ``v`` ``[B, T, Hkv, Dv]`` or, where
    a layer's ``cache`` (``ops/kv_cache.py``; already holding this forward's
    rows from slot ``index`` on) is given, against the cache. Returns
    ``[B, T, H * Dv]`` in q's dtype, heads flattened as an output projection
    takes them.

    ``mask_bias`` is additive, ``[B, 1 | H, T, S]``; ``kv_valid`` ``[B, T]``
    marks a multi-token forward whose keys are its own tokens — cache-free
    (training, scoring) or a prefill from slot 0, where attention over the
    just-computed k / v is exactly attention over the cache since every later
    slot is still empty (TransformerLM passes it only when the write index was
    a concrete 0 at trace time, checked outside the remat wrapper). ``impl`` is
    the configured ``"xla" | "flash" | "ring"``; ``biased`` says that
    ``mask_bias`` carries more than the mask (alibi) or that ``prefix`` rows
    are prepended, which no kernel takes. ``prefix``: None or learned ``(k, v)``
    rows ``[nv, Hkv, D]`` every query sees (zero bias), joined in front of
    whatever is attended over.

    What is chosen, in this order: the Pallas decode kernel for a single-token
    step over a per-head float cache (it reads the cache up to the write index
    only; appends of several tokens keep the einsum); the ring for a cache-free
    forward under ``impl="ring"`` on a mesh that can ring; the flash kernel for
    a ``kv_valid`` forward of more than one token; else the einsum — grouped
    (the group a free axis, K/V never repeated to full head count) or
    multi-head, with an int8 cache's row scales folded into the scores and the
    probabilities. The einsum paths are the reference the kernels are tested
    against."""
    B, T, H, _ = q.shape
    kv_heads = k.shape[2]
    dtype = q.dtype

    if cache is not None and T == 1:
        use_kernel, mesh = decode_kernel_placement(impl, biased, cache, H)
        if use_kernel:
            interpret = _interpret(mesh)

            def decode(q, k, v, mask_bias, index):
                return decode_attention(q, k, v, mask_bias, index, scale, interpret)

            if mesh is not None:
                index = jnp.asarray(index, jnp.int32)  # an operand of the shard_map, replicated
            operands = [(q[:, 0], True), (cache["k"], True), (cache["v"], True), (mask_bias, False), (index, False)]
            return _placed(decode, mesh, operands, out_ndim=3).reshape(B, T, -1).astype(dtype)

    use_flash, flash_mesh = flash_placement(impl, biased, B, T, kv_valid, H, kv_heads)
    # kh/vh [B, Hkv, S, D]: the layout attention consumes (and the cache layout)
    k_row_scale = v_row_scale = None
    if cache is not None and not use_flash:
        # attend over the cache (decode step / XLA prefill)
        if kv_cache.has_row_scales(cache) and prefix is None:
            # int8 cache: bare dtype convert only — the per-row scales fold
            # into the scores (k) and the softmax weights (v) below, which is
            # algebraically dequantizing the operands but leaves the big K/V
            # streams a pure int8->bf16 cast XLA fuses into the dot (a multiply
            # on the operand blocks that fusion). int8 values are exact in
            # bf16 and the scales multiply in f32 on the small score/prob
            # tensors. (Prefix tuning prepends scale-less rows, so it keeps
            # the dequant-on-read path.)
            kh = cache["k"].astype(dtype)
            vh = cache["v"].astype(dtype)
            k_row_scale = cache["k_scale"]  # [B, Hkv, S, 1] f32
            v_row_scale = cache["v_scale"]
        else:
            kh, vh = kv_cache.read_kv_cache(cache, dtype)
    else:
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)

    # prefix tuning: learned rows prepended to whatever we attend over, visible
    # to every query (zero bias); no positions are consumed, no rotary applied
    # (parity: peft PREFIX_TUNING past_key_values, modeling_base.py:162-240).
    if prefix is not None:
        pk, pv = prefix
        nv = pk.shape[0]
        shape = (B, kv_heads, nv, pk.shape[-1])
        kh = jnp.concatenate(
            [jnp.broadcast_to(pk.astype(kh.dtype).transpose(1, 0, 2)[None], shape), kh], axis=2
        )
        vh = jnp.concatenate(
            [jnp.broadcast_to(pv.astype(vh.dtype).transpose(1, 0, 2)[None], shape), vh], axis=2
        )
        mask_bias = jnp.concatenate(
            [jnp.zeros(mask_bias.shape[:-1] + (nv,), mask_bias.dtype), mask_bias], axis=-1
        )

    if impl == "ring" and cache is None and kv_valid is not None and not biased:
        from trlx_tpu.ops.ring_attention import ring_attention

        mesh = ambient_mesh()
        n = mesh.shape.get(MODEL_AXIS, 1) if mesh is not None else 1
        if mesh is not None and n > 1 and T % n == 0 and batch_divisible(mesh, B):
            # grouped K/V ride the ring at native head count (no repeat)
            out = ring_attention(
                q.transpose(0, 2, 1, 3), kh, vh,
                mesh, axis_name=MODEL_AXIS, causal=True, scale=scale,
                kv_valid=kv_valid, batch_axes=BATCH_AXES,
            ).transpose(0, 2, 1, 3).astype(dtype)
            return out.reshape(B, T, -1)
        # fall through to XLA when the mesh/shape can't ring

    if use_flash:
        # the kernel maps query head h -> kv head h // rep natively, so grouped
        # K/V are never materialized at full head count
        interpret = _interpret(flash_mesh)

        def flash(q, k, v, kv_valid):
            return flash_attention(q, k, v, kv_valid, True, scale, interpret)

        operands = [(q.transpose(0, 2, 1, 3), True), (kh, True), (vh, True), (kv_valid, False)]
        out = _placed(flash, flash_mesh, operands, out_ndim=4).transpose(0, 2, 1, 3).astype(dtype)
    elif kv_heads != H:
        # grouped-query einsum: batch scores over kv heads with the group as
        # a free axis — the old jnp.repeat path copied the whole K/V cache to
        # full head count every decode step, multiplying HBM traffic by
        # num_heads/kv_heads on exactly the GQA models it targets
        rep = H // kv_heads
        qg = q.reshape(B, T, kv_heads, rep, q.shape[-1])
        scores = jnp.einsum("btkrd,bksd->bkrts", qg, kh).astype(jnp.float32) * scale
        if k_row_scale is not None:
            scores = scores * k_row_scale[..., 0][:, :, None, None, :]
        bias = (
            mask_bias[:, :, None]
            if mask_bias.shape[1] == 1
            else mask_bias.reshape(B, kv_heads, rep, *mask_bias.shape[2:])
        )
        probs = jax.nn.softmax(scores + bias, axis=-1)
        if v_row_scale is not None:
            probs = probs * v_row_scale[..., 0][:, :, None, None, :]
        probs = probs.astype(dtype)
        # btkrd order flattens to head h = k*rep + r, matching the q reshape
        out = jnp.einsum("bkrts,bksd->btkrd", probs, vh)
    else:
        # [B,H,T,S]
        scores = jnp.einsum("bthd,bhsd->bhts", q, kh).astype(jnp.float32) * scale
        if k_row_scale is not None:
            scores = scores * k_row_scale[..., 0][:, :, None, :]
        scores = scores + mask_bias
        probs = jax.nn.softmax(scores, axis=-1)
        if v_row_scale is not None:
            probs = probs * v_row_scale[..., 0][:, :, None, :]
        probs = probs.astype(dtype)
        out = jnp.einsum("bhts,bhsd->bthd", probs, vh)
    return out.reshape(B, T, -1)
