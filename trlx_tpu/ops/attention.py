"""Attention ops: Pallas TPU flash-attention forward + backward kernels, the decode kernel, XLA reference.

The reference relies on external CUDA attention kernels (HF/NeMo, SURVEY.md §2.4.5);
this is the TPU-native equivalent. Forward is an online-softmax (FlashAttention-style)
Pallas kernel; backward is the standard recompute scheme (two kernels, as in the
in-tree TPU flash attention): the forward saves only O and the per-row logsumexp and
the backward recomputes P = exp(S - L) tile by tile, so training memory is O(T·tile)
rather than the O(T·S) score matrix. :func:`xla_attention` is the plain reference
the kernels' outputs and gradients are tested against.

The kernels read and write the model's own arrays: q ``[B, T, H, D]``, k ``[B, S, Hkv,
D]``, v ``[B, S, Hkv, Dv]`` as the projections produce them, viewed ``[B, T, H·D]`` (a
bitcast), and O, dq, dk, dv the same way. A program's block is ``(1, rows, heads·D)``
and a head is a lane window of it, so no transpose to ``[B, H, T, D]`` runs around a
call. Any T and S: the grid's blocks overhang the arrays' ends, nothing is padded or
sliced in HBM. What an overhanging block holds past the end is garbage (NaN in
interpret mode); every load that can overhang replaces it by zeros with a select,
the overhanging part of a store is clipped, and the per-row statistics past the
data are written as constants. The backward's ``delta = rowsum(dO · O)`` is formed
inside the ``dq`` kernel, in float32 on the tiles it holds, and handed to ``dkv`` as a row.

How the work is cut into programs is decided from the shape by one pure function,
:func:`choose_tiles`. Where the whole sequence (rounded up to 128) fits VMEM — every
length RL runs at gpt2 widths: 64 to 1024 — a program takes the whole sequence of
several heads: grid (batch, heads / heads-per-program, 1, 1), nothing carried across
grid steps, and the body walks the score matrix in row tiles whose causal extents are
static, so nothing above the diagonal is computed. Where it does not (long contexts
at D = 128), the kv side is walked on the last grid axis in tiles of 128-512 —
TPU grids execute sequentially, so running max / denominator / accumulator live in
VMEM scratch across kv steps — and blocks above the diagonal are skipped with
``pl.when``. One kernel body per pass serves both. The heads of a program are whole
lane tiles of its blocks (an even number at D = 64; 2, 4 or 8 at D = 192) or all the
heads there are, which the chooser sees to.

Operands are multiplied in the dtype they arrive in (bfloat16 on the MXU in one
pass, float32 in full) and accumulated in float32; softmax statistics, the mask
arithmetic, ``delta`` and every accumulator are float32, and P and dS are cast to the
operand dtype for their second matmuls, as the XLA path does. The backward writes dq,
dk, dv in the dtype of q, k, v.

Grouped-query attention is native: K/V arrive with their own head count ``Hkv`` and
the BlockSpec index maps hand a program its query heads' kv heads, so grouped K/V
are never materialized at full head count. ``dkv`` programs take whole query-head
groups and sum over them.

Masking model matches :mod:`trlx_tpu.models.transformer`: slot-based causality plus a
[B, S] key-validity mask (left-padded prompts). Engaged on every multi-token forward:
the training loss, the logprob/value scoring passes, and generation *prefill* (which
attends over the just-computed prefix k/v while the cache write happens separately).
Single-token decode steps over the contiguous cache have a kernel of their own,
:func:`decode_attention` (the section "decode" below), which reads the cache only up
to the write index.

Layout note: per-row statistics (logsumexp, delta) travel between the kernels as
``[B, H, 1, Tp]`` float32 rows with T on the lanes — B·H·Tp·4 bytes in HBM, where a
trailing dimension of 1 or 8 would be padded to 128 lanes; they and the ``[B, 1, Sp]``
key mask, kilobytes each, are the only arrays as long as the tiles rather than the
data. The forward turns its column of row statistics into such a row with one
transpose per head, ``dq`` does the same for ``delta`` and turns the logsumexp back;
``dkv`` works on transposed score tiles (keys down the sublanes, queries along the
lanes) and subtracts the rows as they are.
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
# some 60 k score elements a microsecond): the chooser trades it against overhang.
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
    T: int  # query rows there are: the last q block overhangs the arrays by Tp - T rows
    S: int  # key rows there are
    Tp: int  # the q blocks' extent, a multiple of block_q; the length of no array but the row statistics
    Sp: int  # the kv blocks' extent, a multiple of block_k; the key mask's length
    Tr: int  # query rows that hold data, rounded up to the dtype's sublane tile
    Sr: int  # key rows that hold data, the same
    heads: int  # query heads one program takes, a lane window of its blocks each
    vmem_bytes: int  # reckoned VMEM of the largest pass

    @property
    def whole(self) -> bool:
        """One program sees the whole sequence: nothing is carried across grid
        steps and causal extents are static."""
        return self.block_q == self.Tp and self.block_k == self.Sp


def _reckon_vmem(block_q, block_k, sub, heads, D, rep, itemsize, whole, Dv=None) -> int:
    """VMEM bytes of the pass that holds most. Each pass: its operands twice
    (Pallas double-buffers them), a head's operands once more as the values the
    body works on, score-shaped float32 temporaries of ``sub`` rows (scores,
    probabilities and the mask bias in the forward; dP and dS besides in the two
    backward passes), the mask bias kept for every row tile where extents are
    static, and what it keeps in scratch. The forward and dq passes take
    ``heads`` query heads, the dkv pass their kv heads' whole groups. A block's
    heads lie side by side on the lanes. ``Dv`` is the width of v, o and dO where
    it is not q's and k's ``D``."""
    Dv = Dv or D
    kv_heads = max(1, heads // rep)
    group = kv_heads * rep

    def tile(rows, n, width):  # a block of n heads' columns
        return rows * _round_up(n * width, LANE) * itemsize

    q_tile, o_tile = tile(block_q, heads, D), tile(block_q, heads, Dv)
    k_tile, v_tile = tile(block_k, kv_heads, D), tile(block_k, kv_heads, Dv)
    group_q, group_o = tile(block_q, group, D), tile(block_q, group, Dv)
    head = tile(block_q + block_k, 1, D) + tile(block_q + block_k, 1, Dv)
    row = 8 * block_q * 4  # a [1, block_q] float32 row takes a sublane tile
    mask = 8 * block_k * 4
    bias = block_q * block_k * 4 if whole else 0
    carried = 0 if whole else 4  # bytes of float32 scratch per carried element
    forward = (
        2 * (q_tile + o_tile + k_tile + v_tile + heads * row + mask) + head
        + 3 * sub * block_k * 4 + bias
        + block_q * LANE * 4 + carried * block_q * (_round_up(heads * Dv, LANE) + 2 * heads * LANE)
    )
    dq = (
        2 * (2 * q_tile + 2 * o_tile + k_tile + v_tile + 2 * heads * row + mask) + head
        + 5 * sub * block_k * 4 + bias
        + 2 * block_q * LANE * 4 + carried * block_q * _round_up(heads * D, LANE)
    )
    dkv = (
        2 * (group_q + group_o + 2 * (k_tile + v_tile) + 2 * group * row + mask) + head
        + 5 * sub * block_q * 4 + bias
        + block_k * LANE * 4 + carried * block_k * (_round_up(kv_heads * D, LANE) + _round_up(kv_heads * Dv, LANE))
    )
    return max(forward, dq, dkv)


def _lane_heads(D: int, Dv: int) -> int:
    """The fewest heads whose columns, ``D`` and ``Dv`` wide, fill whole lane
    tiles: 1 at 128, 2 at 64 and at 192 / 128."""
    return math.lcm(LANE // math.gcd(LANE, D), LANE // math.gcd(LANE, Dv))


def _head_counts(H: int, rep: int, lane: int = 1):
    """The counts of query heads a program may take, ascending: programs share
    the heads out evenly with their kv heads (a divisor of the group, or whole
    groups dividing Hkv), and a block's columns — the program's query heads', its
    kv heads' — are a multiple of ``lane`` heads or all the heads there are
    (the chip's rule for the last dimension of a block: a multiple of 128 or the
    whole extent). All H always may."""
    kv_total = H // rep

    def may(g):
        kv = max(1, g // rep)
        shares = rep % g == 0 or (g % rep == 0 and kv_total % kv == 0)
        return shares and (g % lane == 0 or g == H) and (kv % lane == 0 or kv == kv_total)

    return [g for g in range(1, H + 1) if may(g)]


def _heads_per_program(H: int, rep: int, most: int, lane: int = 1) -> int:
    """The most query heads, at most ``most``, that :func:`_head_counts` allows;
    the fewest it allows where that is more than ``most``."""
    counts = _head_counts(H, rep, lane)
    return max((g for g in counts if g <= most), default=counts[0])


def choose_tiles(
    T: int, S: int, D: int, rep: int, dtype, vmem_budget: int = 12 * 2**20, Dv: Optional[int] = None,
    H: Optional[int] = None,
) -> FlashTiles:
    """Tiles for q ``[B, T, H·D]`` against k ``[B, S, Hkv·D]`` and v ``[B, S,
    Hkv·Dv]`` (``Dv`` = ``D`` where it is not given) with ``rep`` query heads to a
    kv head (``H`` not given: heads enough for any count a program may want). A
    pure function of the shape; the one place tiles and the heads of a program
    are chosen.

    Blocks are multiples of 128 rows (the lane side of a score tile: keys in the
    forward and dq passes, queries in the transposed dkv pass) and overhang the
    arrays' ends; rows are worked on up to the dtype's sublane multiple only. A
    program's heads are lane windows of its blocks, so their columns must fill
    whole lane tiles (:func:`_head_counts`). Where the whole sequence fits
    ``vmem_budget`` a program takes it whole, for as many heads as the budget and
    the program's area allow; where it does not, the kv side is walked on the
    last grid axis with tiles of 128-512, for the fewest heads the lanes allow.
    Among the tilings that fit, the cheapest by score area plus a fixed cost per
    program wins."""
    Dv = Dv or D
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 32 // itemsize  # 8 rows of float32, 16 of bfloat16 to a tile
    Tr, Sr = _round_up(T, sublane), _round_up(S, sublane)
    T128, S128 = _round_up(T, LANE), _round_up(S, LANE)
    lane = _lane_heads(D, Dv)
    H = H or 8 * lane * rep
    least = _heads_per_program(H, rep, 1, lane)  # the fewest heads the lanes allow a program

    best = None
    walked = [(bq, bk) for bq in (512, 256, 128) for bk in (512, 256, 128) if bq < T128 or bk < S128]
    for block_q, block_k in [(T128, S128)] + walked:
        block_q, block_k = min(block_q, T128), min(block_k, S128)
        Tp, Sp = _round_up(T, block_q), _round_up(S, block_k)
        whole = block_q == Tp and block_k == Sp
        # the smallest row tile that leaves the body at most eight to unroll
        sub = next((n for n in (128, 256, 512) if max(block_q, block_k) <= 8 * n), None)
        fits = sub is not None and (
            _reckon_vmem(block_q, block_k, sub, least, D, rep, itemsize, whole, Dv) <= vmem_budget
        )
        if not fits:  # graftcheck: noqa[JX004] — static shape/int, not traced
            continue
        cost = Tp * Sp + (Tp // block_q) * (Sp // block_k) * _PROGRAM_COST
        if best is None or cost < best[0]:  # graftcheck: noqa[JX004] — static shape/int, not traced
            best = (cost, block_q, block_k, sub, Tp, Sp, whole)
    if best is None:
        raise ValueError(
            f"no flash-attention tiling of T={T} S={S} D={D} Dv={Dv} rep={rep} heads={least} fits"
            f" {vmem_budget} bytes of VMEM"
        )
    _, block_q, block_k, sub, Tp, Sp, whole = best

    def takes(heads):  # more heads than the lanes ask for: only whole sequences, within the area and the budget
        return (
            whole
            and heads <= 8
            and heads * Tp * Sp <= _PROGRAM_AREA
            and _reckon_vmem(block_q, block_k, sub, heads, D, rep, itemsize, whole, Dv) <= vmem_budget
        )

    heads = _heads_per_program(H, rep, max((h for h in range(1, 9) if takes(h)), default=1), lane)
    return FlashTiles(
        block_q, block_k, sub, T, S, Tp, Sp, min(Tr, Tp), min(Sr, Sp), heads,
        _reckon_vmem(block_q, block_k, sub, heads, D, rep, itemsize, whole, Dv),
    )


@functools.lru_cache(maxsize=None)
def _log_tiles(B, H, Hkv, D, Dv, dtype, tiles):
    """The chooser's choice, once per traced shape."""
    T, S, heads = tiles.T, tiles.S, tiles.heads
    kv_heads = max(1, heads // (H // Hkv))
    steps = (tiles.Tp // tiles.block_q, tiles.Sp // tiles.block_k)
    values = "" if Dv == D else f" v[{B},{S},{Hkv},{Dv}]"
    logger.info(  # graftcheck: noqa[JX003] — once per traced shape is the point
        f"flash attention q[{B},{T},{H},{D}] kv[{B},{S},{Hkv},{D}]{values} {dtype}:"
        f" operands as the model holds them, blocks (1, rows, heads*D); tiles {tiles.block_q}x{tiles.block_k}"
        f" in rows of {tiles.sub}, overhanging by {tiles.Tp - T}x{tiles.Sp - S} rows (nothing padded in HBM),"
        f" {heads} head(s) a program, grid"
        f" {(B, H // heads) + steps} (dkv {(B, Hkv // kv_heads) + steps[::-1]}), delta formed in the dq kernel,"
        f" VMEM reckoned {tiles.vmem_bytes / 2**20:.1f} MiB"
    )


def _loop(body, *, count: int, unrolled: bool = False) -> None:
    """``body(i)`` for i < count; no loop around a single pass, none in the
    program where it is ``unrolled`` (``i`` is then a Python int)."""
    if unrolled or count == 1:
        for i in range(count):
            body(i)
    else:
        jax.lax.fori_loop(0, count, lambda i, c: (body(i), c)[1], 0)


def _for_each_head(heads: int, rep: int, fn, *, unrolled: bool) -> None:
    """``fn(h, kh)`` for a program's query heads h with their local kv head kh:
    whole groups of ``rep`` where the program holds several kv heads, else a
    part of one group."""
    group = min(heads, rep)
    _loop(
        lambda kh: _loop(lambda r: fn(kh * group + r, kh), count=group, unrolled=unrolled),
        count=heads // group, unrolled=unrolled,
    )


def _window(h, width: int):
    """Head h's lane window of a block ``[.., heads * width]``: a static slice
    for a Python ``h``, else a dynamic one, which the chip takes on the lanes at
    multiples of 128 only: where a head's columns are not whole lane tiles
    (:func:`_lane_heads` > 1) the loops over heads are unrolled."""
    if isinstance(h, int):
        return slice(h * width, (h + 1) * width)
    return pl.ds(pl.multiple_of(h * width, LANE), width)


def _held(x, first, limit: int, extent: int):
    """``x`` ``[rows, width]``, rows ``first ..`` of an array with ``limit``
    rows under blocks that reach to ``extent``, with zeros for what lies past
    the array's end: there an overhanging block holds garbage, which a select
    keeps out of every product (a multiply by zero would let a NaN through).
    Nothing to do where the blocks do not overhang, or ``first`` is a Python int
    and these rows end before the array does."""
    if extent == limit or (isinstance(first, int) and first + x.shape[0] <= limit):
        return x
    rows = first + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < limit, x, jnp.zeros_like(x))


def _block_offsets(tiles: "FlashTiles", qi, kj):
    """First row of this grid step's q block and of its kv block: Python ints
    where a side is one block, so that :func:`_held` can tell statically which
    rows overhang."""
    return (
        qi * tiles.block_q if tiles.Tp > tiles.block_q else 0,
        kj * tiles.block_k if tiles.Sp > tiles.block_k else 0,
    )


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
    to the scores, which are finite (:func:`_held`); the heads of a program
    share it. Keys past the data are invalid in the mask as it arrives."""
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
    q_ref,  # [1, block_q, heads * D]
    k_ref,  # [1, block_k, kv heads * D]
    v_ref,  # [1, block_k, kv heads * Dv]
    o_ref,  # [1, block_q, heads * Dv]
    *rest,  # lse_ref [1, heads, 1, block_q] f32 and its [block_q, LANE] scratch (with_lse);
    # m, l [heads, block_q, 1] and acc [block_q, heads * Dv] f32 scratch (kv walked)
    causal: bool,
    scale: float,
    tiles: FlashTiles,
    rep: int,
    widths: Tuple[int, int],
    with_lse: bool,
):
    rest = list(rest)
    lse_ref, lse_cols = (rest.pop(0), rest.pop(-1)) if with_lse else (None, None)
    D, Dv = widths
    heads = q_ref.shape[2] // D
    static = _lane_heads(D, Dv) > 1  # lane windows at static offsets: the loops over heads unrolled
    block_q, block_k = tiles.block_q, tiles.block_k
    kv_steps = tiles.Sp // block_k
    carried = kv_steps > 1  # running max / sum / accumulator live in scratch across kv steps
    qi, kj = pl.program_id(2), pl.program_id(3)
    q0, k0 = _block_offsets(tiles, qi, kj)
    row_tiles = _query_row_tiles(tiles, causal)

    def finish(h, r0, n, m, l, acc):
        seen = m > NEG_INF / 2  # rows with no valid key give 0, not NaN
        o_ref[0, r0:r0 + n, _window(h, Dv)] = (acc * jnp.where(seen, 1.0 / l, 0.0)).astype(o_ref.dtype)
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
    if with_lse and data_rows < block_q:  # the backward must find no garbage past the data
        lse_cols[data_rows:, :] = jnp.full((block_q - data_rows, LANE), NEG_INF, jnp.float32)

    def visit():
        biases = _mask_biases(kv_valid_ref[0], q0, k0, row_tiles=row_tiles, causal=causal)

        def head(h, kh):
            # loaded once; the row tiles slice the values
            k = _held(k_ref[0, :, _window(kh, D)], k0, tiles.S, tiles.Sp)
            v = _held(v_ref[0, :, _window(kh, Dv)], k0, tiles.S, tiles.Sp)
            for (r0, n, keys), bias in zip(row_tiles, biases):
                q = _held(q_ref[0, r0:r0 + n, _window(h, D)], q0 + r0, tiles.T, tiles.Tp)
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
                    acc_scratch[r0:r0 + n, _window(h, Dv)] = alpha * acc_scratch[r0:r0 + n, _window(h, Dv)] + acc
                    m_scratch[h, r0:r0 + n] = m
                else:
                    finish(h, r0, n, m, l, acc)
            if with_lse and not carried:
                lse_ref[0, h] = _to_rows(lse_cols[...])

        _for_each_head(heads, rep, head, unrolled=static)

    _visit_below_diagonal(visit, qi, kj, causal=causal, tiles=tiles)

    if carried:

        @pl.when(kj == kv_steps - 1)
        def _finalize():
            def head(h):
                for r0, n, _ in row_tiles:
                    finish(h, r0, n, m_scratch[h, r0:r0 + n], l_scratch[h, r0:r0 + n],
                           acc_scratch[r0:r0 + n, _window(h, Dv)])
                if with_lse:
                    lse_ref[0, h] = _to_rows(lse_cols[...])

            _loop(head, count=heads, unrolled=static)


def _kv_block_map(heads: int, rep: int):
    """Block index of a program's kv heads from the block index of its query
    heads: the same where it holds whole groups, else the group's kv head."""
    return (lambda h: h) if heads >= rep else (lambda h: (h * heads) // rep)


def _grid_semantics():
    """Every grid axis but the last is independent; the last carries scratch."""
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _key_mask(kv_valid, Sp: int):
    """[B, S] -> [B, 1, Sp] int32, the layout the kernels take a row of it in;
    keys past the data are invalid. Kilobytes: the one operand padded in HBM."""
    kv_valid = kv_valid.astype(jnp.int32)
    return jnp.pad(kv_valid, ((0, 0), (0, Sp - kv_valid.shape[1])))[:, None, :]


def _flat(x):
    """``[B, T, heads, width]`` as the kernels take it, ``[B, T, heads * width]``: a bitcast."""
    return x.reshape(x.shape[:2] + (-1,))


def _shared_by_layers(*static_argnames):
    """jit a function that makes one of the three flash calls, so that a model's
    layers, which make it at one shape, share one trace and one lowering of the
    kernel: the heads of a program are unrolled in the kernels' bodies, and
    traced a layer that adds half a second a layer to the lowering of every
    program (set-up time, warm or cold). Jitted under the name ``attn``, which is
    what the model's scope names the unjitted calls: the chip shows ``%attn.N
    custom-call`` either way, and the benchmark finds the kernels by that."""

    def wrap(fn):
        @functools.wraps(fn)
        def attn(*args, **kwargs):
            return fn(*args, **kwargs)

        attn.__name__ = attn.__qualname__ = "attn"
        return jax.jit(attn, static_argnames=static_argnames)

    return wrap


@_shared_by_layers("causal", "scale", "interpret", "with_lse", "tiles")
def _flash_forward(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,  # [B, S, Hkv, Dv]
    kv_valid: jnp.ndarray,  # [B, S] int32
    causal: bool,
    scale: float,
    interpret: bool,
    with_lse: bool = False,
    tiles: Optional[FlashTiles] = None,
):
    """Run the forward kernel over the operands as they are: any T/S, the last
    blocks overhang, keys past S are invalid in the mask and zeros in the
    products, query rows past T are never stored. Returns O ``[B, T, H, Dv]``;
    with ``with_lse`` also the per-row logsumexp as the backward takes it:
    ``[B, H, 1, Tp]`` float32, T on the lanes, NEG_INF past the data."""
    B, T, H, D = q.shape
    S, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    assert H % Hkv == 0, (H, Hkv)
    rep = H // Hkv
    if tiles is None:
        tiles = choose_tiles(T, S, D, rep, q.dtype, Dv=Dv, H=H)
    _log_tiles(B, H, Hkv, D, Dv, jnp.dtype(q.dtype).name, tiles)
    heads = tiles.heads
    kv_heads = max(1, heads // rep)
    block_q, block_k = tiles.block_q, tiles.block_k
    kvh = _kv_block_map(heads, rep)

    q_spec, o_spec = (pl.BlockSpec((1, block_q, heads * w), lambda b, h, i, j: (b, i, h)) for w in (D, Dv))
    k_spec, v_spec = (
        pl.BlockSpec((1, block_k, kv_heads * w), lambda b, h, i, j: (b, j, kvh(h))) for w in (D, Dv)
    )
    out_shape = [jax.ShapeDtypeStruct((B, T, H * Dv), q.dtype)]
    out_specs = [o_spec]
    scratch = []
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, tiles.Tp), jnp.float32))
        out_specs.append(pl.BlockSpec((1, heads, 1, block_q), lambda b, h, i, j: (b, h, 0, i)))
    if tiles.Sp > block_k:  # graftcheck: noqa[JX004] — static shape/int, not traced
        scratch += [
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, heads * Dv), jnp.float32),
        ]
    if with_lse:
        scratch.append(pltpu.VMEM((block_q, LANE), jnp.float32))

    res = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, scale=scale, tiles=tiles, rep=rep, widths=(D, Dv), with_lse=with_lse
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
    )(_key_mask(kv_valid, tiles.Sp), _flat(q), _flat(k), _flat(v))
    out = res[0].reshape(B, T, H, Dv)
    return (out, res[1]) if with_lse else out


# ----------------------------------------------------------------- backward


def _flash_bwd_dq_kernel(
    kv_valid_ref,  # [1, 1, block_k]
    q_ref,  # [1, block_q, heads * D]
    k_ref,  # [1, block_k, kv heads * D]
    v_ref,  # [1, block_k, kv heads * Dv]
    o_ref,  # [1, block_q, heads * Dv]
    do_ref,  # as o_ref
    lse_ref,  # [1, heads, 1, block_q] f32
    dq_ref,  # as q_ref, out
    delta_ref,  # as lse_ref, out: rowsum(dO * O), which the dkv pass subtracts
    delta_cols,  # [block_q, LANE] f32 scratch
    *scratch,  # dq [block_q, heads * D] f32 where the kv side is walked
    causal: bool,
    scale: float,
    tiles: FlashTiles,
    rep: int,
    widths: Tuple[int, int],
):
    D, Dv = widths
    heads = q_ref.shape[2] // D
    static = _lane_heads(D, Dv) > 1  # lane windows at static offsets: the loops over heads unrolled
    block_q, block_k = tiles.block_q, tiles.block_k
    kv_steps = tiles.Sp // block_k
    carried = kv_steps > 1
    qi, kj = pl.program_id(2), pl.program_id(3)
    q0, k0 = _block_offsets(tiles, qi, kj)
    row_tiles = _query_row_tiles(tiles, causal)

    if carried:
        (dq_scratch,) = scratch

        @pl.when(kj == 0)
        def _init():
            dq_scratch[...] = jnp.zeros_like(dq_scratch)

    data_rows = row_tiles[-1][0] + row_tiles[-1][1]
    if data_rows < block_q:  # rows past the data take no part: their delta is 0, not garbage
        delta_cols[data_rows:, :] = jnp.zeros((block_q - data_rows, LANE), jnp.float32)

    def visit():
        biases = _mask_biases(kv_valid_ref[0], q0, k0, row_tiles=row_tiles, causal=causal)

        def head(h, kh):
            lse = _to_cols(lse_ref[0, h])  # [block_q, 1]
            # fully-masked rows have lse == NEG_INF; guard the exp against inf * 0
            lse = jnp.where(lse > NEG_INF / 2, lse, 0.0)
            k = _held(k_ref[0, :, _window(kh, D)], k0, tiles.S, tiles.Sp)
            v = _held(v_ref[0, :, _window(kh, Dv)], k0, tiles.S, tiles.Sp)
            for (r0, n, keys), bias in zip(row_tiles, biases):
                q = _held(q_ref[0, r0:r0 + n, _window(h, D)], q0 + r0, tiles.T, tiles.Tp)
                do = _held(do_ref[0, r0:r0 + n, _window(h, Dv)], q0 + r0, tiles.T, tiles.Tp)
                o = _held(o_ref[0, r0:r0 + n, _window(h, Dv)], q0 + r0, tiles.T, tiles.Tp)
                delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=1, keepdims=True)  # [n, 1]
                delta_cols[r0:r0 + n, :] = jnp.broadcast_to(delta, (n, LANE))
                p = jnp.exp(_nt_dot(q, k[:keys]) * scale + bias - lse[r0:r0 + n])  # [n, keys]
                dp = _nt_dot(do, v[:keys])
                ds = p * (dp - delta) * scale
                dq = _dot(ds.astype(k.dtype), k[:keys])  # [n, D]
                if carried:
                    dq_scratch[r0:r0 + n, _window(h, D)] += dq
                else:
                    dq_ref[0, r0:r0 + n, _window(h, D)] = dq.astype(dq_ref.dtype)

            def write_delta():
                delta_ref[0, h] = _to_rows(delta_cols[...])

            if carried:  # the first kv block is visited for every q block, and once is enough
                pl.when(kj == 0)(write_delta)
            else:
                write_delta()

        _for_each_head(heads, rep, head, unrolled=static)

    _visit_below_diagonal(visit, qi, kj, causal=causal, tiles=tiles)

    if carried:

        @pl.when(kj == kv_steps - 1)
        def _finalize():
            dq_ref[0] = dq_scratch[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    kv_valid_ref,  # [1, 1, block_k]
    q_ref,  # [1, block_q, kv heads * rep * D]: each kv head's whole query-head group
    k_ref,  # [1, block_k, kv heads * D]
    v_ref,  # [1, block_k, kv heads * Dv]
    do_ref,  # as q_ref, Dv wide
    lse_ref,  # [1, kv heads * rep, 1, block_q] f32
    delta_ref,  # as lse_ref: the dq pass's
    dk_ref,  # as k_ref, out
    dv_ref,  # as v_ref, out
    *scratch,  # dk, dv as their blocks, f32, where the q side is walked
    causal: bool,
    scale: float,
    tiles: FlashTiles,
    rep: int,
    widths: Tuple[int, int],
):
    """Works on transposed score tiles, keys down the sublanes and queries along
    the lanes: lse and delta are subtracted as the rows they arrive as, and all
    four matmuls (K Q^T, V dO^T, P^T dO, dS^T Q) contract without a transpose."""
    D, Dv = widths
    kv_heads = k_ref.shape[2] // D
    static = _lane_heads(D, Dv) > 1  # lane windows at static offsets: the loops over heads unrolled
    block_q, block_k = tiles.block_q, tiles.block_k
    q_steps = tiles.Tp // block_q
    carried = q_steps > 1
    kj, qi = pl.program_id(2), pl.program_id(3)
    q0, k0 = _block_offsets(tiles, qi, kj)
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
                k_pos = k0 + c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                q_pos = q0 + first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                bias = jnp.where(k_pos <= q_pos, bias, NEG_INF)
            biases.append(bias)

        def kv_head(kh):
            k = _held(k_ref[0, :, _window(kh, D)], k0, tiles.S, tiles.Sp)
            v = _held(v_ref[0, :, _window(kh, Dv)], k0, tiles.S, tiles.Sp)

            # dK/dV of a kv head sum over its query-head group. Query rows past the data: q and dO are
            # zeros there and delta is 0, so dS is 0 and P meets a zero dO — they add nothing
            def group(r, sums):
                h = kh * rep + r
                q = _held(q_ref[0, :, _window(h, D)], q0, tiles.T, tiles.Tp)
                do = _held(do_ref[0, :, _window(h, Dv)], q0, tiles.T, tiles.Tp)
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

            sums = [(jnp.zeros((n, D), jnp.float32), jnp.zeros((n, Dv), jnp.float32)) for _, n, _ in row_tiles]
            if static or rep == 1:
                for r in range(rep):
                    sums = group(r, sums)
            else:
                sums = jax.lax.fori_loop(0, rep, group, sums)
            for (c0, n, _), (dk, dv) in zip(row_tiles, sums):
                if carried:
                    dk_scratch[c0:c0 + n, _window(kh, D)] += dk
                    dv_scratch[c0:c0 + n, _window(kh, Dv)] += dv
                else:
                    dk_ref[0, c0:c0 + n, _window(kh, D)] = dk.astype(dk_ref.dtype)
                    dv_ref[0, c0:c0 + n, _window(kh, Dv)] = dv.astype(dv_ref.dtype)

        _loop(kv_head, count=kv_heads, unrolled=static)

    _visit_below_diagonal(visit, qi, kj, causal=causal, tiles=tiles)

    if causal and not tiles.whole and not carried:

        @pl.when(kj * block_k > block_q - 1)  # the one q block ends above these keys: no query sees them
        def _unseen():
            dk_ref[...] = jnp.zeros_like(dk_ref)
            dv_ref[...] = jnp.zeros_like(dv_ref)

    if carried:

        @pl.when(qi == q_steps - 1)
        def _finalize():
            dk_ref[0] = dk_scratch[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scratch[...].astype(dv_ref.dtype)


@_shared_by_layers("causal", "scale", "interpret", "tiles")
def _flash_dq(q, k, v, kv_valid, out, lse, g, causal, scale, interpret, tiles):
    """The backward's first kernel, on the forward's grid: dq ``[B, T, H, D]``
    and ``delta = rowsum(g * out)`` as the second kernel takes it, ``[B, H, 1,
    Tp]`` float32 (0 past the data), formed in float32 on the tiles of ``g``
    and ``out`` the kernel holds."""
    B, T, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    rep = H // Hkv
    heads, block_q, block_k = tiles.heads, tiles.block_q, tiles.block_k
    kv_heads = max(1, heads // rep)
    kv_steps = tiles.Sp // block_k
    kvh = _kv_block_map(heads, rep)
    q_spec, do_spec = (pl.BlockSpec((1, block_q, heads * w), lambda b, h, qi, kj: (b, qi, h)) for w in (D, Dv))
    row_spec = pl.BlockSpec((1, heads, 1, block_q), lambda b, h, qi, kj: (b, h, 0, qi))
    k_spec, v_spec = (pl.BlockSpec((1, block_k, kv_heads * w), lambda b, h, qi, kj: (b, kj, kvh(h))) for w in (D, Dv))
    dq, delta = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale, tiles=tiles, rep=rep, widths=(D, Dv)),
        grid=(B, H // heads, tiles.Tp // block_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_k), lambda b, h, qi, kj: (b, 0, kj)),
            q_spec, k_spec, v_spec, do_spec, do_spec, row_spec,
        ],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * D), q.dtype), jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, LANE), jnp.float32)]
        + ([pltpu.VMEM((block_q, heads * D), jnp.float32)] if kv_steps > 1 else []),
        interpret=interpret,
        compiler_params=_grid_semantics(),
    )(_key_mask(kv_valid, tiles.Sp), _flat(q), _flat(k), _flat(v), _flat(out), _flat(g), lse)
    return dq.reshape(q.shape), delta


@_shared_by_layers("causal", "scale", "interpret", "tiles")
def _flash_dkv(q, k, v, kv_valid, lse, delta, g, causal, scale, interpret, tiles):
    """The backward's second kernel: grid (B, kv-head blocks, kv steps, q
    steps), a program taking whole query-head groups so dK/dV sum over the group
    without output-block write conflicts; ``delta`` is the first kernel's."""
    B, _, H, D = q.shape
    S, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    rep = H // Hkv
    kv_heads = max(1, tiles.heads // rep)
    block_q, block_k = tiles.block_q, tiles.block_k
    q_steps = tiles.Tp // block_q
    # a block of kv_heads * rep query heads at block index hk is kv-head block hk's groups
    group_spec, group_do_spec = (
        pl.BlockSpec((1, block_q, kv_heads * rep * w), lambda b, hk, kj, qi: (b, qi, hk)) for w in (D, Dv)
    )
    group_row_spec = pl.BlockSpec((1, kv_heads * rep, 1, block_q), lambda b, hk, kj, qi: (b, hk, 0, qi))
    k_spec, v_spec = (pl.BlockSpec((1, block_k, kv_heads * w), lambda b, hk, kj, qi: (b, kj, hk)) for w in (D, Dv))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, scale=scale, tiles=tiles, rep=rep, widths=(D, Dv)),
        grid=(B, Hkv // kv_heads, tiles.Sp // block_k, q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_k), lambda b, hk, kj, qi: (b, 0, kj)),
            group_spec, k_spec, v_spec, group_do_spec, group_row_spec, group_row_spec,
        ],
        out_specs=[k_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, Hkv * D), k.dtype), jax.ShapeDtypeStruct((B, S, Hkv * Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, kv_heads * w), jnp.float32) for w in (D, Dv)] if q_steps > 1 else [],
        interpret=interpret,
        compiler_params=_grid_semantics(),
    )(_key_mask(kv_valid, tiles.Sp), _flat(q), _flat(k), _flat(v), _flat(g), lse, delta)
    return dk.reshape(k.shape), dv.reshape(v.shape)


def _flash_backward(q, k, v, kv_valid, out, lse, g, causal, scale, interpret, tiles=None):
    """Pallas backward over the operands as they are (q, out, g ``[B, T, H, ·]``,
    k, v ``[B, S, Hkv, ·]``): recompute P per tile from the saved logsumexp
    (``lse`` as ``_flash_forward`` returns it, ``[B, H, 1, Tp]``). Returns dq, dk,
    dv shaped and typed as q, k, v.

    Two kernels: ``dq`` takes ``out`` beside ``g`` and forms ``delta`` on the way
    (:func:`_flash_dq`); ``dkv`` reads that row and therefore runs second
    (:func:`_flash_dkv`). Where the whole sequence is one tile neither carries
    anything across grid steps."""
    if tiles is None:
        B, T, H, D = q.shape
        tiles = choose_tiles(T, k.shape[1], D, H // k.shape[2], q.dtype, Dv=v.shape[3], H=H)
    dq, delta = _flash_dq(q, k, v, kv_valid, out, lse, g, causal, scale, interpret, tiles)
    dk, dv = _flash_dkv(q, k, v, kv_valid, lse, delta, g, causal, scale, interpret, tiles)
    return dq, dk, dv


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
    """Flash attention over q ``[B,T,H,D]``, k ``[B,S,Hkv,D]``, v ``[B,S,Hkv,Dv]``,
    the layout a model's projections leave them in; K/V may carry fewer (grouped)
    heads, and V (with it the output, ``[B,T,H,Dv]``) a width of its own.
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
# Fewer than 128 rows would leave lanes empty, in HBM and in every DMA: there
# the cache holds ``fold`` kv heads beside each row (``ops/kv_cache.py``), the
# kernel sees ``B * fold`` rows of ``Hkv / fold`` kv heads, and only q, the mask
# bias and the output — a token a row each — are folded and unfolded around it.


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


def choose_decode_fold(B: int, Hkv: int) -> int:
    """kv heads that stand beside each of ``B`` rows on the kernel's 128 lanes (per
    shard): the largest divisor of ``Hkv`` that still fits them where ``B`` rows
    leave lanes empty, else 1. A pure function of the shape, beside
    :func:`choose_decode_tiles`: 64 rows of 16 kv heads fold 2, 16 of 8 fold 8,
    96 and 128 rows fold nothing."""
    if B >= LANE:
        return 1
    return max(g for g in range(1, Hkv + 1) if Hkv % g == 0 and g * B <= LANE)


def _lane_fill(rows: int) -> Tuple[int, int]:
    """(lanes that hold a row, lanes held) of a program that takes ``rows`` rows."""
    return rows, _round_up(rows, LANE)


@functools.lru_cache(maxsize=None)
def _log_decode_tiles(B, H, Hkv, S, D, dtype, tiles, fold):
    """The choosers' choice, once per traced shape (``B``, ``Hkv``: the model's, before the fold)."""
    filled, lanes = _lane_fill(tiles.rows)
    logger.info(  # graftcheck: noqa[JX003] — once per traced shape is the point
        f"decode attention q[{B},{H},{D}] cache[{B},{Hkv},{S},{D}] {dtype}: rows {tiles.rows} block {tiles.block},"
        f" fold {fold}, lanes filled {filled}/{lanes},"
        f" grid {(B * fold // tiles.rows, -(-S // tiles.block))}, VMEM reckoned {tiles.vmem_bytes / 2**20:.1f} MiB"
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
    k: jnp.ndarray,  # [B * fold, Hkv / fold, S, D]: the cache, this step's token written at slot ``index``
    v: jnp.ndarray,  # [B * fold, Hkv / fold, S, Dv]
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
    ``index``). Grouped K/V map h -> h // rep. A cache of more rows than q is
    folded (``kv_cache.fold_heads``): q and the mask bias are folded to meet it
    and the output unfolded, a token a row each. Returns ``[B, H, Dv]`` in q's dtype."""
    B, H, D = q.shape
    fold = k.shape[0] // B  # kv heads the cache holds beside each row
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]  # Hkv: kv heads at one row of the cache
    assert k.shape[0] == B * fold and H % (Hkv * fold) == 0, (q.shape, k.shape)
    rep = H // (Hkv * fold)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if tiles is None:
        tiles = choose_decode_tiles(B * fold, Hkv, rep, S, D, k.dtype, Dv=Dv)
    _log_decode_tiles(B, H, Hkv * fold, S, D, jnp.dtype(k.dtype).name, tiles, fold)
    rows, block = tiles.rows, tiles.block

    # slots outermost, the batch on the lanes: bitcasts of the cache as the chip's compiler lays it out;
    # a kv head's ``rep`` query heads go to the row that holds its slots, and the row's bias with them
    q_t = kv_cache.fold_heads(q.reshape(B, Hkv * fold, rep, D), fold).transpose(1, 2, 3, 0)
    k_t, v_t = k.transpose(2, 1, 3, 0), v.transpose(2, 1, 3, 0)
    bias = mask_bias.reshape(B, S).astype(jnp.float32)
    bias = (jnp.repeat(bias, fold, axis=0) if fold > 1 else bias).T
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
            grid=(B * fold // rows, -(-S // block)),
            in_specs=[
                pl.BlockSpec((block, rows), lambda i, j, idx: (last_held(j, idx), i)),
                q_spec, k_spec, v_spec,
            ],
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((Hkv * rep, 1, rows), jnp.float32),
                pltpu.VMEM((Hkv * rep, 1, rows), jnp.float32),
                pltpu.VMEM((Hkv * rep, Dv, rows), jnp.float32),
                pltpu.VMEM((rep, block, rows), jnp.float32),
                pltpu.VMEM((rep, block, rows), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Hkv, rep, Dv, B * fold), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 * 2**20, 2 * tiles.vmem_bytes),
        ),
        # a name of its own: unnamed under the model's `attn` scope it would be `%attn.N custom-call`
        # on the chip, which is how the benchmark finds the flash calls
        name="decode_attn",
    )(index, bias, q_t, k_t, v_t)
    return kv_cache.unfold_heads(out.transpose(3, 0, 1, 2), fold).reshape(B, H, Dv)


# ---- dispatch: a model states what it attends over (:func:`attend`); which path, over which mesh, is decided here


def _placed(local, mesh, operands, out: Tuple[int, int]):
    """``local(*arrays)`` plainly where ``mesh`` is None, else its SPMD
    placement over ``mesh``: Mosaic kernels cannot be auto-partitioned by XLA's
    SPMD pass (it raises at compile time on any multi-device mesh), so shard
    the embarrassingly-parallel grid axes explicitly — batch over ``BATCH_AXES``, heads over ``MODEL_AXIS`` — and run
    the kernel per shard inside a ``shard_map``. No cross-shard terms exist:
    each (batch, head) pair's softmax is independent, and the grouped-KV head
    map stays consistent because H_local/Hkv_local equals the global ratio
    when both divide the axis. Differentiable: autodiff enters the shard_map
    and applies the kernel's custom VJP per shard.

    ``operands``: ``(array, heads_dim)`` pairs — dimension 0 of an array is the
    batch, dimension ``heads_dim`` the heads (None: it has none); a scalar is
    replicated. ``out``: the result's ``(ndim, heads_dim)``."""
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

    def spec(ndim, heads_dim):
        entries = [batch_entry or None] + [None] * (ndim - 1)
        if heads_dim is not None:
            entries[heads_dim] = head_entry
        return P(*entries[:ndim])

    return jax.shard_map(
        local, mesh=mesh, in_specs=tuple(spec(jnp.ndim(x), heads_dim) for x, heads_dim in operands),
        out_specs=spec(*out), check_vma=False, axis_names=axes,
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


def decode_kernel_placement(impl: str, biased: bool, layer, heads: int, batch_size: int):
    """(whether a single-token step of ``batch_size`` rows over a layer's
    contiguous cache — ``layer`` holds its arrays, concrete or abstract, folded
    or not — takes the Pallas decode kernel, the mesh to place it over or
    None): the flash kernels' rule, and per-head float rows in the cache.
    Everything else — ``impl="xla"``, the int8 cache, alibi, prefix tuning, a
    latent cache (its own absorbed decode), a mesh the call cannot be placed
    over — keeps the einsum path."""
    if kv_cache.is_latent(layer) or kv_cache.has_row_scales(layer):
        return False, None
    rows, held = layer["k"].shape[:2]
    return _kernel_placement(impl, biased, batch_size, heads, held * (rows // batch_size))


def decode_cache_fold(impl: str, biased: bool, batch_size: int, heads: int, kv_heads: int) -> int:
    """kv heads a per-head float layer of the contiguous cache holds beside each
    row (``kv_cache.kv_cache_layout``'s ``fold``): :func:`choose_decode_fold`
    of a shard's rows and kv heads where its single-token steps will take the
    decode kernel (:func:`decode_kernel_placement`'s rule; a shard of the mesh
    keeps whole rows and a run of neighbouring kv heads, so the fold places
    under :func:`_placed` as it stands), else 1. Call under the mesh the steps
    will run under."""
    use, mesh = _kernel_placement(impl, biased, batch_size, heads, kv_heads)
    if not use:
        return 1
    n_batch, n_model = _kernel_shards(mesh)
    return choose_decode_fold(batch_size // n_batch, kv_heads // n_model)


def _decode_shard_tiles(impl: str, biased: bool, heads: int, layout, batch_size: int) -> Optional[DecodeTiles]:
    """The programs a shard's decode kernel is cut into for a layer's
    ``layout`` (``ops/kv_cache.py``) of ``batch_size`` rows under the ambient
    mesh, or None where the steps take the einsum path."""
    layer = {key: jax.ShapeDtypeStruct(shape, dtype) for key, (shape, dtype) in layout.items()}
    use, mesh = decode_kernel_placement(impl, biased, layer, heads, batch_size)
    if not use:
        return None
    rows, held, cache_len, D = layer["k"].shape  # batch_size * fold rows of kv_heads / fold
    n_batch, n_model = _kernel_shards(mesh)
    rep = heads // (held * (rows // batch_size))
    return choose_decode_tiles(rows // n_batch, held // n_model, rep, cache_len, D, layer["k"].dtype)


def decode_cache_read_share(
    impl: str, biased: bool, heads: int, layout, batch_size: int, new_tokens: int, steps: int
) -> float:
    """Cache slots the decode steps of one rollout visited over the slots the
    cache holds (``rollout/cache_read_share``): host arithmetic from a layer's
    ``layout`` (what is not for ``new_tokens`` was prefilled), the ``steps``
    the decode loop ran and the kernel's block; 1.0 where the steps took the
    einsum path. Call under the trainer's mesh."""
    tiles = _decode_shard_tiles(impl, biased, heads, layout, batch_size)
    if tiles is None:
        return 1.0
    cache_len = layout["k"][0][2]
    return cache_read_share(cache_len - new_tokens, steps, cache_len, tiles.block)


def decode_cache_lane_fill(impl: str, biased: bool, heads: int, layout, batch_size: int) -> float:
    """(row, kv head) pairs on the decode kernel's lanes over the lanes its
    programs hold, a shard's (``rollout/cache_lane_fill``): what of the cache's
    traffic is keys and values and not padding. 1.0 where the steps take the
    einsum path, which pads nothing. Call under the trainer's mesh."""
    tiles = _decode_shard_tiles(impl, biased, heads, layout, batch_size)
    if tiles is None:
        return 1.0
    filled, lanes = _lane_fill(tiles.rows)
    return filled / lanes


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
    only; appends of several tokens keep the einsum); the flash kernels for
    a ``kv_valid`` forward of more than one token under ``impl="flash"``, which
    take q, k, v in the layout they arrive in; the ring for a cache-free
    forward under ``impl="ring"`` on a mesh that can ring; else the einsum — grouped
    (the group a free axis, K/V never repeated to full head count) or
    multi-head, with an int8 cache's row scales folded into the scores and the
    probabilities. The einsum paths are the reference the kernels are tested
    against."""
    B, T, H, _ = q.shape
    kv_heads = k.shape[2]
    dtype = q.dtype

    if cache is not None and T == 1:
        use_kernel, mesh = decode_kernel_placement(impl, biased, cache, H, B)
        if use_kernel:
            interpret = _interpret(mesh)

            def decode(q, k, v, mask_bias, index):
                return decode_attention(q, k, v, mask_bias, index, scale, interpret)

            if mesh is not None:
                index = jnp.asarray(index, jnp.int32)  # an operand of the shard_map, replicated
            operands = [(q[:, 0], 1), (cache["k"], 1), (cache["v"], 1), (mask_bias, None), (index, None)]
            return _placed(decode, mesh, operands, out=(3, 1)).reshape(B, T, -1).astype(dtype)

    use_flash, flash_mesh = flash_placement(impl, biased, B, T, kv_valid, H, kv_heads)
    if use_flash:
        # the kernels take q, k, v as the projections left them and hand the output back the same way: no
        # transpose on either side; query head h maps to kv head h // rep natively, so grouped K/V are
        # never materialized at full head count
        interpret = _interpret(flash_mesh)

        def flash(q, k, v, kv_valid):
            return flash_attention(q, k, v, kv_valid, True, scale, interpret)

        operands = [(q, 2), (k, 2), (v, 2), (kv_valid, None)]
        return _placed(flash, flash_mesh, operands, out=(4, 2)).astype(dtype).reshape(B, T, -1)

    # kh/vh [B, Hkv, S, D]: the layout attention consumes (and the cache layout)
    k_row_scale = v_row_scale = None
    if cache is not None:
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
            kh, vh = kv_cache.read_kv_cache(cache, dtype, B)
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

    if kv_heads != H:
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
