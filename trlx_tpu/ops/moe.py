"""Sparse expert FFN: route, sort by expert, grouped products over the experts
held here, weighted combine. Dropless.

The router scores every token against all ``num_experts`` published experts
(sigmoid scores in float32, the ``top_k`` largest of score + selection bias
chosen, or a softmax over all of them and the ``top_k`` largest chosen;
weights the chosen scores normalised and scaled). The layer is told
which experts it holds, ``gate.shape[0]`` of them from ``expert_offset``: an
assignment to an expert that is not held adds nothing here (another chip of the
deployment computes it), and nothing stands in for it.

Dropless means the buffer of sorted rows has room for every assignment,
``N * top_k``, whatever the routing: no token is thrown away for being one too
many. The assignments are sorted by expert, those not held last, and each held
expert's rows are laid from a row-tile boundary on (block-sparse, as MegaBlocks
lays them): a tile of the grouped products then belongs to one expert, so the
expert's weights are streamed once for each of its tiles and never again for a
tile it shares with a neighbour, and the number of tiles walked follows the
experts' loads in whole tiles, not the seed's share of held assignments row by
row. The rows that fill an expert's last tile are zeros, in and out.

The grouped product is jax's own Pallas grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and for the
rows' gradient, ``tgmm`` for the weights'), one named family of device ops,
``%gmm.N`` / ``%tgmm.N custom-call`` on the chip; it visits the row tiles that
hold a group's rows and no others. XLA cannot partition a Mosaic kernel and the
mesh has no expert axis yet, so a multi-device mesh is refused with that reason.
``jax.lax.ragged_dot`` is not used: on one v5e the chip's compiler lowers it to
row tiles of 8, and at 2048 x 1408 weights a product of 1,540 rows took 3.2 ms
against 45 us of arithmetic (PERF.md, PR 29).

Rows move between the assignments' order and the buffer by gathers in both
directions: the backward of each gather is the gather the other way, never a
scatter-add.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def route(
    h: jnp.ndarray, kernel: jnp.ndarray, bias: Optional[jnp.ndarray], *, top_k: int, norm_topk: bool, scale: float,
    norm_eps: float = 0.0, scoring: str = "sigmoid",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h [N, d], kernel [d, experts], bias [experts] -> (experts chosen [N, top_k]
    int32, their weights [N, top_k] float32). Scores are float32 at full
    precision whatever the compute dtype, as the published router's are: a
    rounded score flips the choice between near-equal experts. ``scoring``
    ``"sigmoid"``: a score an expert, the ``top_k`` largest of score + bias
    chosen; the bias only chooses, so it takes no gradient. ``"softmax"``
    (Qwen3-MoE's rule, no bias): the scores are a softmax over all experts and
    the ``top_k`` largest are chosen. ``norm_eps`` is what a published router
    adds to the sum it divides by (0 adds nothing)."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores), top_k)
        else:
            scores = jax.nn.sigmoid(logits)
            _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias.astype(jnp.float32)), top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if norm_topk:
            total = weights.sum(-1, keepdims=True)
            weights = weights / (total + norm_eps if norm_eps else total)
        return chosen.astype(jnp.int32), weights * scale


@jax.custom_vjp
def _take_rows(x, index, valid, back_index, back_valid):
    """``x[index]`` where ``valid``, zeros elsewhere, for a one-to-one map of
    some rows of ``x`` onto some rows of the result. ``back_index`` and
    ``back_valid`` are the same map read the other way, so the backward is the
    gather ``g[back_index]`` where ``back_valid``."""
    return jnp.where(valid[:, None], x[index], 0)


def _take_rows_fwd(x, index, valid, back_index, back_valid):
    return _take_rows(x, index, valid, back_index, back_valid), (back_index, back_valid)


def _take_rows_bwd(res, g):
    back_index, back_valid = res
    return jnp.where(back_valid[:, None], g[back_index], 0), None, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


MESH_REFUSAL = (
    "the grouped expert products are one Pallas kernel over the experts this device holds, which XLA "
    "cannot partition, and the mesh has no expert axis and no all-to-all yet: routed experts run on a "
    "one-device mesh only"
)


#: tokens up to which an expert layer takes its tokens in one pass; beyond, passes of
#: TOKEN_CHUNK tokens (a prefill of 16 rows of 7,680 would lay 983,040 assignments in one buffer)
ONE_PASS_TOKENS = 32768
TOKEN_CHUNK = 16384


def token_chunks(tokens: int) -> int:
    """How many passes an expert layer takes ``tokens`` in: 1 up to
    ``ONE_PASS_TOKENS`` (every learner microbatch, scorer chunk and prefill the
    other cells run), else passes of ``TOKEN_CHUNK`` tokens, the last padded.
    A pure function of the shape."""
    return 1 if tokens <= ONE_PASS_TOKENS else -(-tokens // TOKEN_CHUNK)


def _row_tile(assignments: int) -> int:
    """Rows of a grouped product's tile: 128 for a decode step's few hundred
    assignments; 512 for a learner microbatch's thousands, where a held expert's
    rows (about 190 of 12,312 at 64 experts) fit one tile."""
    return 128 if assignments <= 2048 else 512


def choose_product_tiles(row_tile: int, k: int, n: int, vmem_budget: int = 14 * 2**20) -> Tuple[int, int, int]:
    """(row, inner, column) tiles of a grouped product ``[M, k] x [E, k, n]`` whose
    rows are laid in tiles of ``row_tile``. A pure function of the shape, as the
    attention kernels' choosers are.

    The inner tile is 1024 beside a row tile of 128 and halves where the row
    tile is 512. The column tile is the largest multiple of 128 that divides
    ``n`` and fits ``vmem_budget`` of the chip's 16 MiB beside them, in the
    product and in its weights' gradient, which jax's ``gmm`` runs with the
    same tiles: both buffers of each operand tile, both of the output tile, and
    its float32 accumulator. It divides ``n`` because the kernel runs a whole
    program for a last tile that overhangs (at 1,408 a width of 1,536 ran 1,408
    columns for its last 128). 1,408 and 1,536 are taken whole; 2,048 is 17 MiB
    whole and goes as two tiles of 1,024. A width that no multiple of 128
    divides (the tests' small ones) is one tile."""
    inner = min(k, 1024 if row_tile == 128 else 512)

    def fits(column):
        product = 2 * 2 * (row_tile * inner + inner * column) + 3 * 4 * row_tile * column  # bf16 in, float32 out
        # the weights' gradient [inner, column]: bf16 rows and float32 cotangents in, bf16 out, a float32 accumulator
        gradient = 2 * row_tile * (2 * inner + 4 * column) + (2 * 2 + 4) * inner * column
        return max(product, gradient) <= vmem_budget

    column = next((tn for tn in range(n - n % 128, 0, -128) if n % tn == 0 and fits(tn)), n)
    return row_tile, inner, column


@functools.lru_cache(maxsize=None)
def _log_product_tiles(M, k, n, E, tiles):
    """The chooser's choice, once per traced shape."""
    logger.info(  # graftcheck: noqa[JX003] — once per traced shape is the point
        f"grouped product rows[{M},{k}] x weights[{E},{k},{n}]: tiles {tiles[0]} x {tiles[1]} x {tiles[2]},"
        f" {-(-n // tiles[2])} column tile(s) of {tiles[2]}"
    )


def _grouped_product(rows: jnp.ndarray, weights: jnp.ndarray, room: jnp.ndarray, tile: int) -> jnp.ndarray:
    """rows [M, k] sorted by group, each group from a tile boundary on; weights
    [E, k, n]; room [E] int32, the groups' rows in whole tiles -> [M, n]
    float32: each group's rows times its own weights. Rows past the groups hold
    nothing defined."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from trlx_tpu.parallel.sharding import ambient_mesh

    mesh = ambient_mesh()
    if mesh is not None and mesh.size > 1:
        raise ValueError(MESH_REFUSAL)
    weights = weights.astype(rows.dtype)
    E, k, n = weights.shape
    tiles = choose_product_tiles(tile, k, n)
    _log_product_tiles(rows.shape[0], k, n, E, tiles)
    # interpret (XLA-emulated) mode iff the compile target is the CPU, as the flash kernels decide it
    return gmm(rows, weights, room, jnp.float32, tiles, interpret=jax.default_backend() == "cpu")


def expert_ffn(
    x: jnp.ndarray, chosen: jnp.ndarray, weights: jnp.ndarray,
    gate: jnp.ndarray, up: jnp.ndarray, down: jnp.ndarray, *, expert_offset: int, act=jax.nn.silu,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [N, d]; chosen, weights [N, top_k]; gate, up [E, d, f] and down
    [E, f, d] of the E experts held, the first of them ``expert_offset``.
    Returns (sum over a token's held assignments of weight times the expert's
    gated FFN, [N, d] in x's dtype; the load of each held expert, [E] int32)."""
    N, k = chosen.shape
    E = gate.shape[0]
    local = chosen.reshape(-1) - expert_offset
    group = jnp.where((local >= 0) & (local < E), local, E)  # E: not held here, sorted last
    order = jnp.argsort(group, stable=True)  # sorted place -> assignment
    place = jnp.argsort(order)  # assignment -> sorted place
    load = (group[:, None] == jnp.arange(E)).sum(0, dtype=jnp.int32)  # no scatter: a compare and a column sum
    tile = _row_tile(N * k)
    room = -(-load // tile) * tile  # a group's rows in the buffer: whole tiles
    first, base = jnp.cumsum(load) - load, jnp.cumsum(room) - room  # a group's first sorted place, first buffer row
    buffer_rows = (-(-N * k // tile) + E) * tile  # sum(room) <= N * k + E * (tile - 1): every routing fits

    # buffer row -> assignment: the row's group, its place within the group, held rows only
    row = jnp.arange(buffer_rows)
    row_group = jnp.minimum((row[:, None] >= (base + room)[None, :]).sum(1), E - 1)
    within = row - base[row_group]
    row_held = within < load[row_group]
    source = order[jnp.clip(first[row_group] + within, 0, N * k - 1)]
    # assignment -> buffer row
    held = group < E
    own = jnp.minimum(group, E - 1)
    target = jnp.clip(base[own] + place - first[own], 0, buffer_rows - 1)

    rows = jnp.broadcast_to(x[:, None, :], (N, k, x.shape[-1])).reshape(N * k, -1)
    rows = _take_rows(rows, source, row_held, target, held)
    with jax.named_scope("moe.experts"):
        hidden = act(_grouped_product(rows, gate, room, tile)) * _grouped_product(rows, up, room, tile)
        out = _grouped_product(hidden.astype(x.dtype), down, room, tile)
    out = _take_rows(out, target, held, source, row_held).reshape(N, k, -1)
    return (out * weights[..., None]).sum(1).astype(x.dtype), load
