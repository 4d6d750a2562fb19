"""Ring attention: sequence-parallel causal attention over a mesh axis.

The reference has NO context parallelism — its only sequence-dim scaling is Megatron
SP activation sharding (SURVEY.md §2.3 row CP: "absent — gap to fill natively").
This implements blockwise ring attention (cf. Liu et al., Ring Attention; the
scaling-book collective recipe): Q/K/V are sharded along the sequence dimension
across a mesh axis; each step every device computes a flash-style online-softmax
block against its current K/V shard, then rotates K/V one hop around the ring with
``jax.lax.ppermute`` over ICI. Peak memory per chip is O(S_local), enabling sequences
far beyond a single chip's HBM.

Training memory is O(S_local) too: a ``jax.custom_vjp`` saves only the local Q/K/V
shards, output, and per-row logsumexp, then the backward *re-runs the ring* —
recomputing P = exp(S - L) per visiting shard while dK/dV accumulators ride the ring
back to their home device (n rotations = identity). Without this, autodiff through
the fori_loop of ppermutes saved every step's rotated K/V (O(S_full) residuals per
device), defeating the point of the ring.

Causal structure at shard granularity: after ``step`` rotations device ``i`` holds
the K/V shard originally on device ``(i - step) mod n``; it contributes fully when
source < i, diagonally (within-shard causal) when source == i, and is skipped when
source > i.
"""

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from trlx_tpu.parallel.mesh import MODEL_AXIS

NEG_INF = -1e30


def _block_attn(q, k, v, m, l, acc, scale, mask):
    """One online-softmax accumulation step.

    q [B,H,Tq,D]; k/v [B,H,Tk,D]; m/l [B,H,Tq,1]; acc [B,H,Tq,D];
    mask bool broadcastable to [B,H,Tq,Tk] or None (True = attend)."""
    s = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def _shard_mask(causal, src, my, valid_cur, tri):
    """Visiting-shard mask: key validity x shard-granularity causal structure."""
    mask = valid_cur[:, None, None, :] > 0  # [B,1,1,Tk]
    # static python bool: the branch specializes the trace, it never sees an array
    if causal:  # graftcheck: noqa[JX004]
        sm = jnp.logical_or(src < my, jnp.logical_and(src == my, tri))
        mask = jnp.logical_and(mask, sm[None, None])
    return mask


def _fold_q(x, Hkv):
    """Fold grouped query heads into the row axis: [B, Hkv*rep, T, ...] ->
    [B, Hkv, rep*T, ...] (row r*T + t ↔ query head k*rep+r at position t).
    K/V then stay at their native Hkv heads through every einsum and ppermute —
    no repeat, so GQA models move 1/rep of the ICI bytes per rotation."""
    B, H, T = x.shape[:3]
    rep = H // Hkv
    return x.reshape((B, Hkv, rep * T) + x.shape[3:]), rep


def _unfold_q(x, rep):
    B, Hkv, RT = x.shape[:3]
    T = RT // rep
    return x.reshape((B, Hkv * rep, T) + x.shape[3:])


def _ring_fwd_local(q_loc, k_loc, v_loc, valid_loc, *, axis_name, n, causal, scale):
    """Forward ring on local shards; returns (out, lse) with lse = m + log(l)."""
    B, H, T, D = q_loc.shape
    Hkv = k_loc.shape[1]
    q_loc, rep = _fold_q(q_loc, Hkv)
    my = jax.lax.axis_index(axis_name)
    tri = jnp.tril(jnp.ones((T, T), dtype=bool))
    # rep is shape-derived (static at trace time): specialization, not data branching
    if rep > 1:  # graftcheck: noqa[JX004]
        tri = jnp.tile(tri, (rep, 1))  # folded row r*T+t keeps position t's row

    def body(step, carry):
        k_cur, v_cur, valid_cur, m, l, acc = carry
        src = (my - step) % n
        mask = _shard_mask(causal, src, my, valid_cur, tri)
        m, l, acc = _block_attn(q_loc, k_cur, v_cur, m, l, acc, scale, mask)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        valid_next = jax.lax.ppermute(valid_cur, axis_name, perm)
        return (k_next, v_next, valid_next, m, l, acc)

    rows = q_loc.shape[2]
    m0 = jnp.full((B, Hkv, rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rows, 1), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, rows, D), jnp.float32)
    _, _, _, m, l, acc = jax.lax.fori_loop(
        0, n, body, (k_loc, v_loc, valid_loc, m0, l0, acc0)
    )
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = _unfold_q((acc / safe_l), rep).astype(q_loc.dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF)[..., 0]
    return out, _unfold_q(lse, rep)  # [B,H,T]


def _ring_bwd_local(q_loc, k_loc, v_loc, valid_loc, out_loc, lse_loc, g_loc,
                    *, axis_name, n, causal, scale):
    """Backward ring: dQ accumulates locally; dK/dV accumulators travel with
    their K/V shard and arrive home after the full circle of n rotations."""
    B, H, T, D = q_loc.shape
    Hkv = k_loc.shape[1]
    q_loc, rep = _fold_q(q_loc, Hkv)
    my = jax.lax.axis_index(axis_name)
    tri = jnp.tril(jnp.ones((T, T), dtype=bool))
    if rep > 1:
        tri = jnp.tile(tri, (rep, 1))
    g32 = _fold_q(g_loc, Hkv)[0].astype(jnp.float32)
    out32 = _fold_q(out_loc, Hkv)[0].astype(jnp.float32)
    lse = _fold_q(lse_loc, Hkv)[0][..., None]  # [B,Hkv,rep*T,1]
    lse_safe = jnp.where(lse > NEG_INF / 2, lse, 0.0)
    delta = jnp.sum(g32 * out32, axis=-1, keepdims=True)

    def body(step, carry):
        k_cur, v_cur, valid_cur, dk_cur, dv_cur, dq = carry
        src = (my - step) % n
        mask = _shard_mask(causal, src, my, valid_cur, tri)
        s = jnp.einsum(
            "bhtd,bhsd->bhts", q_loc.astype(jnp.float32), k_cur.astype(jnp.float32)
        ) * scale
        p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)  # [B,H,T,Tk]
        dv_cur = dv_cur + jnp.einsum("bhts,bhtd->bhsd", p, g32)
        dp = jnp.einsum("bhtd,bhsd->bhts", g32, v_cur.astype(jnp.float32))
        ds = p * (dp - delta) * scale
        dq = dq + jnp.einsum("bhts,bhsd->bhtd", ds, k_cur.astype(jnp.float32))
        dk_cur = dk_cur + jnp.einsum("bhts,bhtd->bhsd", ds, q_loc.astype(jnp.float32))
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        valid_next = jax.lax.ppermute(valid_cur, axis_name, perm)
        dk_next = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_next = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (k_next, v_next, valid_next, dk_next, dv_next, dq)

    zeros_kv = jnp.zeros((B, Hkv, T, D), jnp.float32)
    zeros_q = jnp.zeros(q_loc.shape, jnp.float32)
    _, _, _, dk, dv, dq = jax.lax.fori_loop(
        0, n, body, (k_loc, v_loc, valid_loc, zeros_kv, zeros_kv, zeros_q)
    )
    return (
        _unfold_q(dq, rep).astype(q_loc.dtype),
        dk.astype(k_loc.dtype),
        dv.astype(v_loc.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring_core(q, k, v, kv_valid, mesh, axis_name, causal, scale, batch_axes):
    out, _ = _ring_fwd_sharded(q, k, v, kv_valid, mesh, axis_name, causal, scale, batch_axes)
    return out


def _specs(axis_name, batch_axes):
    spec = P(batch_axes, None, axis_name, None)
    vspec = P(batch_axes, axis_name)
    rowspec = P(batch_axes, None, axis_name)
    return spec, vspec, rowspec


def _ring_fwd_sharded(q, k, v, kv_valid, mesh, axis_name, causal, scale, batch_axes):
    n = mesh.shape[axis_name]
    spec, vspec, rowspec = _specs(axis_name, batch_axes)
    fn = functools.partial(
        _ring_fwd_local, axis_name=axis_name, n=n, causal=causal, scale=scale
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec, vspec),
        out_specs=(spec, rowspec), check_vma=False,
    )(q, k, v, kv_valid)


def _ring_core_fwd(q, k, v, kv_valid, mesh, axis_name, causal, scale, batch_axes):
    out, lse = _ring_fwd_sharded(q, k, v, kv_valid, mesh, axis_name, causal, scale, batch_axes)
    # O(S_local) residuals per device: local shards + output + logsumexp only
    return out, (q, k, v, kv_valid, out, lse)


def _ring_core_bwd(mesh, axis_name, causal, scale, batch_axes, res, g):
    q, k, v, kv_valid, out, lse = res
    n = mesh.shape[axis_name]
    spec, vspec, rowspec = _specs(axis_name, batch_axes)
    fn = functools.partial(
        _ring_bwd_local, axis_name=axis_name, n=n, causal=causal, scale=scale
    )
    dq, dk, dv = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec, vspec, spec, rowspec, spec),
        out_specs=(spec, spec, spec), check_vma=False,
    )(q, k, v, kv_valid, out, lse, g)
    return dq, dk, dv, None


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = MODEL_AXIS,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_valid: Optional[jnp.ndarray] = None,
    batch_axes: Optional[Any] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention. q/k/v: [B, H, S, D] with S sharded over
    ``axis_name`` (batch dim sharded per ``batch_axes``, head dim replicated).
    K/V may carry fewer (grouped) heads than q: they ride the ring at their
    native head count (1/rep of the ICI bytes per rotation for GQA models).
    ``kv_valid`` [B, S] masks out padding keys (left-padded prompts); it rides
    the ring alongside K/V. Returns the attention output sharded like q.

    Each step computes ONE online-softmax block: the shard-granularity causal
    structure (full / diagonal / skip) is folded into the block's mask instead of
    computing masked and unmasked variants and selecting afterwards.

    Differentiable with O(S_local) training memory (see module docstring)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if kv_valid is None:
        kv_valid = jnp.ones((q.shape[0], k.shape[2]), jnp.int32)
    return _ring_core(
        q, k, v, kv_valid.astype(jnp.int32), mesh, axis_name, causal, scale,
        batch_axes,
    )
