"""Overlapped-collective FSDP train step (``train.learner_overlap``).

The default train step (``MeshRLTrainer.make_grad_accum_step``) leaves every
cross-device decision to GSPMD propagation. That is correct, but on the CPU
lowering backend — and, observed in the committed IR budget, on the real
step — the gradient reduction materializes as **full-gradient all-reduce**
over the ``fsdp`` axis: every device receives every gradient element, holds a
full-size gradient tree during the update, and the ZeRO promise of the
``fsdp`` axis stops at the parameters. ``graftcheck-ir-budget.json``'s
``ppo_train_step@small`` entry shows the smoking gun: 15 ``all-reduce:fsdp``
ops and zero reduce-scatters.

This module rebuilds the step with **explicit** collectives under
``shard_map``, the bandwidth-optimal FSDP schedule:

- **Parameter all-gather per leaf, re-issued per microbatch.** Each fsdp-
  sharded leaf is ``lax.all_gather(..., tiled=True)``'d on its shard dim
  right where the forward needs it; XLA's latency-hiding scheduler hoists the
  async ``all-gather-start`` ops ahead of the compute that consumes them
  (the "prefetch one layer ahead" schedule on TPU).
- **Gradient reduce-scatter during the backward.** Differentiating *through*
  the gather makes JAX transpose each ``all_gather`` into a ``psum_scatter``
  — the reduce-scatter happens per-leaf as the backward reaches it, not as
  one end-of-step barrier, and each device only ever owns its 1/fsdp
  gradient shard.
- **Sharded accumulation carry.** The grad-accum ``lax.scan`` carries the
  gradient *shard*, so accumulating N microbatches costs 1/fsdp of the
  full-gradient memory (the enabler for 1.5B+ effective batches).
- **Shard-local optimizer update (ZeRO).** Adam (or the int8
  :func:`trlx_tpu.ops.quantized_adam.adamw_8bit` state) reads and writes only
  the local shard; 8-bit moment blocks are quantized over the *local* shard,
  so block boundaries never straddle devices.

Constraints: the body is manually mapped over every mesh axis, so tensor
parallelism (``model > 1``) and pipelining (``pipe > 1``) are not expressible
here — the trainer gates on :func:`can_overlap` and falls back to the GSPMD
step. Batch statistics (PPO advantage whitening, masked means) reduce over
each device's *local* microbatch rather than the global one; grad-accum
already normalizes per microbatch, this narrows the group by the
data-parallel degree (docs/parallelism.md "Learner overlap & FSDP").

Seeded regression: ``TRLX_IR_SEED_REGRESSION=allreduce_under_fsdp`` swaps the
differentiate-through-gather path for a full-gradient ``lax.psum`` over
``fsdp`` followed by a local slice — numerically identical, but the compiled
HLO regains the all-reduce the committed budget forbids, so the graftcheck-ir
gate must fail (proven in ``scripts/ci.sh``).
"""

import os
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from trlx_tpu.parallel.mesh import BATCH_AXES, DATA_AXIS, FSDP_AXIS, MODEL_AXIS, PIPE_AXIS
from trlx_tpu.parallel.sharding import (
    Rule,
    _iter_paths,
    make_param_specs,
    manual_axes,
)
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

#: optimizer-state leaf names holding blockwise-quantized moments (their block
#: dim shards over fsdp iff the owning param is fsdp-sharded)
_QUANT_LEAVES = ("m_q", "v_q", "m_scale", "v_scale")

_is_spec = lambda x: isinstance(x, PartitionSpec)  # noqa: E731


def can_overlap(mesh: Mesh) -> bool:
    """Whether the overlapped step is expressible on this mesh: the shard_map
    body computes the full model locally, so TP/PP axes must be trivial."""
    return mesh.shape.get(MODEL_AXIS, 1) == 1 and mesh.shape.get(PIPE_AXIS, 1) == 1


def fsdp_shard_dim(spec: PartitionSpec) -> int:
    """Dim of ``spec`` sharded over ``fsdp``, or -1 (replicated over fsdp)."""
    for i, entry in enumerate(spec):
        axes = entry if isinstance(entry, tuple) else ((entry,) if entry else ())
        if FSDP_AXIS in axes:
            return i
    return -1


def _local_struct(leaf, spec: PartitionSpec, mesh: Mesh) -> jax.ShapeDtypeStruct:
    """Per-device block shape of ``leaf`` under ``spec`` (what the shard_map
    body sees)."""
    shape = list(leaf.shape)
    for i, entry in enumerate(list(spec)[: len(shape)]):
        axes = entry if isinstance(entry, tuple) else ((entry,) if entry else ())
        for a in axes:
            shape[i] //= mesh.shape[a]
    return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)


@dataclass
class OverlapSpecs:
    """Everything the overlapped step needs to know about the layouts:
    parameter PartitionSpecs, per-leaf fsdp shard dims (-1 = replicated), and
    the optimizer-state PartitionSpecs matching ``tx.init`` on local shards."""

    param_specs: Any  #: PartitionSpec pytree matching params
    shard_dims: Any  #: int pytree matching params (-1 when not fsdp-sharded)
    state_specs: Any  #: PartitionSpec pytree matching tx.init's state
    local_state: Any  #: ShapeDtypeStruct pytree of the per-device state block


def make_overlap_specs(
    params_like: Any,
    tx,
    mesh: Mesh,
    rules: Optional[Sequence[Rule]] = None,
) -> OverlapSpecs:
    """Derive the shard_map in/out specs for params and optimizer state.

    Moment leaves inherit their parameter's spec (the state pytree mirrors the
    param tree, so each state path ends with exactly one parameter path —
    longest suffix wins). Quantized-moment blocks shard their block dim over
    ``fsdp`` when the owning param does; scalars replicate.
    """
    from jax.tree_util import tree_flatten_with_path

    from trlx_tpu.parallel.sharding import _path_str

    param_specs = make_param_specs(params_like, mesh, rules)
    shard_dims = jax.tree.map(fsdp_shard_dim, param_specs, is_leaf=_is_spec)
    local_params = jax.tree.map(
        lambda leaf, spec: _local_struct(leaf, spec, mesh),
        params_like, param_specs,
    )
    local_state = jax.eval_shape(tx.init, local_params)

    # "path/of/param" -> its spec, for suffix lookups from state paths
    by_path = {
        path: spec
        for (path, _), (_, spec) in zip(
            _iter_paths(params_like), _iter_paths_specs(param_specs)
        )
    }

    def lookup(path: str) -> Optional[PartitionSpec]:
        best = None
        for ppath, spec in by_path.items():
            if path == ppath or path.endswith("/" + ppath):
                if best is None or len(ppath) > len(best[0]):
                    best = (ppath, spec)
        return best[1] if best else None

    leaves, treedef = tree_flatten_with_path(local_state)
    specs = []
    for path, leaf in leaves:
        pstr = _path_str(path)
        ndim = len(getattr(leaf, "shape", ()))
        last = pstr.rsplit("/", 1)[-1]
        if ndim == 0:
            specs.append(PartitionSpec())
        elif last in _QUANT_LEAVES:
            owner = lookup(pstr.rsplit("/", 1)[0])
            sharded = owner is not None and fsdp_shard_dim(owner) >= 0
            specs.append(PartitionSpec(FSDP_AXIS) if sharded else PartitionSpec())
        else:
            spec = lookup(pstr)
            specs.append(spec if spec is not None else PartitionSpec())
    return OverlapSpecs(
        param_specs=param_specs,
        shard_dims=shard_dims,
        state_specs=treedef.unflatten(specs),
        local_state=local_state,
    )


def _iter_paths_specs(specs: Any, prefix: str = ""):
    """(path, spec) pairs of a PartitionSpec pytree (specs are leaves)."""
    if isinstance(specs, PartitionSpec):
        yield prefix, specs
        return
    if isinstance(specs, dict):
        for k, v in specs.items():
            yield from _iter_paths_specs(v, f"{prefix}/{k}" if prefix else str(k))
    else:
        yield prefix, specs


def global_state_struct(specs: OverlapSpecs, mesh: Mesh) -> Any:
    """Abstract global optimizer state (ShapeDtypeStructs with NamedShardings):
    the per-device block shapes from ``tx.init`` on local shards, scaled back
    up by each spec's mesh axes — what :func:`make_sharded_opt_init` returns."""

    def scale(leaf, spec):
        shape = list(leaf.shape)
        for i, entry in enumerate(list(spec)[: len(shape)]):
            axes = entry if isinstance(entry, tuple) else ((entry,) if entry else ())
            for a in axes:
                shape[i] *= mesh.shape[a]
        return jax.ShapeDtypeStruct(
            tuple(shape), leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree.map(scale, specs.local_state, specs.state_specs, is_leaf=None)


def make_sharded_opt_init(tx, specs: OverlapSpecs, mesh: Mesh) -> Callable:
    """``init(params) -> opt_state`` with ZeRO-sharded state: ``tx.init`` runs
    on each device's parameter shard, so moments (and int8 moment blocks) are
    born shard-local — no full-size state ever exists, on any device."""
    body = jax.shard_map(
        tx.init, mesh=mesh,
        in_specs=(specs.param_specs,), out_specs=specs.state_specs,
        check_vma=False,
    )
    return jax.jit(body)


def _gather(shards: Any, shard_dims: Any) -> Any:
    """Per-leaf fsdp all-gather on the spec-derived shard dim. Differentiating
    through this is the whole trick: the transpose of a tiled ``all_gather``
    is ``psum_scatter``, so the backward emits per-leaf reduce-scatters."""
    return jax.tree.map(
        lambda x, d: x if d < 0 else lax.all_gather(x, FSDP_AXIS, axis=d, tiled=True),
        shards, shard_dims,
    )


def _slice_local(x: jnp.ndarray, dim: int, mesh: Mesh) -> jnp.ndarray:
    """This device's fsdp shard of a full array (the seeded-defect path)."""
    size = x.shape[dim] // mesh.shape[FSDP_AXIS]
    start = lax.axis_index(FSDP_AXIS) * size
    return lax.dynamic_slice_in_dim(x, start, size, axis=dim)


def _clip_by_global_norm_sharded(
    grads: Any, shard_dims: Any, mesh: Mesh, max_norm: float
) -> Tuple[Any, jnp.ndarray]:
    """optax ``clip_by_global_norm`` semantics over *sharded* grads: fsdp-
    sharded leaves hold disjoint shards (their sum-of-squares needs the fsdp
    reduction), replicated leaves count once. The two partial sums are folded
    into ONE scalar psum over ``(data, fsdp)`` — the group the stats pmean
    already uses — so the good path never emits an ``all-reduce:fsdp`` key
    that would blur the IR005 budget's line against the seeded regression.
    Grads are data-replicated here (post data-psum), hence the static
    pre-division by the group sizes."""
    d = mesh.shape[DATA_AXIS]
    f = mesh.shape[FSDP_AXIS]
    sh_sq = jnp.zeros((), jnp.float32)
    rep_sq = jnp.zeros((), jnp.float32)
    for g, dim in zip(jax.tree.leaves(grads), jax.tree.leaves(shard_dims)):
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        sh_sq, rep_sq = (sh_sq + s, rep_sq) if dim >= 0 else (sh_sq, rep_sq + s)
    g_sq = lax.psum(sh_sq / d + rep_sq / (d * f), (DATA_AXIS, FSDP_AXIS))
    g_norm = jnp.sqrt(g_sq)
    scale = jnp.where(g_norm < max_norm, 1.0, max_norm / (g_norm + 1e-16))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), g_norm


def _opt_step_count(opt_state) -> jnp.ndarray:
    """Best-effort optax step count for LR logging (mirror of the trainer's)."""
    for leaf in jax.tree.leaves(opt_state):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.integer) and leaf.ndim == 0:
            return leaf
    return jnp.array(0)


def make_overlapped_grad_accum_step(
    loss_fn: Callable,
    tx,
    specs: OverlapSpecs,
    mesh: Mesh,
    num_mb: int,
    *,
    has_aux: bool = True,
    max_grad_norm: Optional[float] = None,
    lr_schedule: Optional[Callable] = None,
    donate: bool = True,
    name: str = "train_step",
) -> Callable:
    """Build the jitted overlapped step: ``step(params, opt_state, batch) ->
    (params, opt_state, stats)`` (stats ``{}`` when ``has_aux=False``).

    ``loss_fn(full_params, microbatch) -> (loss, stats)`` (or just the loss
    with ``has_aux=False``) — the same callable the GSPMD step takes; it sees
    fully-gathered parameters and this device's microbatch shard. ``tx`` must
    be elementwise (adam-family / :func:`adamw_8bit`, optionally under
    ``optax.multi_transform``); global-norm clipping is shard-aware and
    handled here via ``max_grad_norm``, NOT by chaining
    ``optax.clip_by_global_norm`` into ``tx``.
    """
    dp = mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]
    seeded_allreduce = (
        os.environ.get("TRLX_IR_SEED_REGRESSION", "") == "allreduce_under_fsdp"
    )
    shard_dims = specs.shard_dims

    def body(param_shards, opt_state, batch_shards):
        # the model's GSPMD sharding-constraint helpers must stand down: every
        # mesh axis is manual in here, and a with_sharding_constraint naming
        # one would fail to trace
        with manual_axes():
            return _body(param_shards, opt_state, batch_shards)

    def _body(param_shards, opt_state, batch_shards):
        mbs = jax.tree.map(
            lambda x: x.reshape((num_mb, x.shape[0] // num_mb) + x.shape[1:]),
            batch_shards,
        )

        def local_loss(p_sh, mb):
            full = _gather(p_sh, shard_dims)
            out = loss_fn(full, mb)
            return out if has_aux else (out, {})

        def accum_good(carry, mb):
            # grads arrive per-leaf reduce-scattered over fsdp (all_gather's
            # AD transpose), already shard-shaped — the carry stays 1/fsdp
            (loss, stats), g_sh = jax.value_and_grad(local_loss, has_aux=True)(
                param_shards, mb
            )
            with jax.named_scope("accumulate"):  # as in make_grad_accum_step
                carry = jax.tree.map(jnp.add, carry, g_sh)
            return carry, (loss, stats)

        def accum_seeded(carry, mb):
            # the deliberate regression: full-gradient all-reduce over fsdp,
            # then a local slice — numerically identical, bandwidth-pessimal,
            # and exactly what the IR005 budget must reject
            full = _gather(param_shards, shard_dims)
            (loss, stats), g_full = jax.value_and_grad(
                lambda p, m: (loss_fn(p, m) if has_aux else (loss_fn(p, m), {})),
                has_aux=True,
            )(full, mb)
            g_full = jax.tree.map(lambda g: lax.psum(g, FSDP_AXIS), g_full)
            g_sh = jax.tree.map(
                lambda g, d: g if d < 0 else _slice_local(g, d, mesh),
                g_full, shard_dims,
            )
            with jax.named_scope("accumulate"):
                carry = jax.tree.map(jnp.add, carry, g_sh)
            return carry, (loss, stats)

        zero = jax.tree.map(jnp.zeros_like, param_shards)
        accum = accum_seeded if seeded_allreduce else accum_good
        g_sh, (losses, stats) = lax.scan(accum, zero, mbs)

        if seeded_allreduce:
            # fsdp contributions were already psum'd inside the scan
            g_sh = jax.tree.map(lambda g: lax.psum(g, DATA_AXIS) / (num_mb * dp), g_sh)
        else:
            # sharded leaves: fsdp members were summed by the reduce-scatter;
            # replicated leaves: each fsdp member saw a distinct batch shard
            g_sh = jax.tree.map(
                lambda g, d: (
                    lax.psum(g, DATA_AXIS)
                    if d >= 0
                    else lax.psum(g, (DATA_AXIS, FSDP_AXIS))
                ) / (num_mb * dp),
                g_sh, shard_dims,
            )

        if max_grad_norm:
            g_sh, _ = _clip_by_global_norm_sharded(g_sh, shard_dims, mesh, max_grad_norm)

        updates, new_opt_state = tx.update(g_sh, opt_state, param_shards)
        import optax

        new_params = optax.apply_updates(param_shards, updates)

        mean_stats = jax.tree.map(
            lambda x: lax.pmean(jnp.mean(x, axis=0), (DATA_AXIS, FSDP_AXIS)), stats
        )
        if lr_schedule is not None:
            mean_stats["learning_rate_group_0"] = lr_schedule(_opt_step_count(opt_state))
        return new_params, new_opt_state, mean_stats

    def step(params, opt_state, batch):
        batch_specs = jax.tree.map(
            lambda x: PartitionSpec(BATCH_AXES, *([None] * (x.ndim - 1))), batch
        )
        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs.param_specs, specs.state_specs, batch_specs),
            out_specs=(specs.param_specs, specs.state_specs, PartitionSpec()),
            check_vma=False,
        )
        return mapped(params, opt_state, batch)

    step.__name__ = name  # the compiled program's: module events read ``jit_<name>``
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def overlap_batch_divisible(mesh: Mesh, batch_size: int, num_mb: int) -> bool:
    """Whether ``batch_size`` splits evenly into per-device microbatches."""
    dp = int(np.prod([mesh.shape.get(a, 1) for a in BATCH_AXES]))
    return batch_size % (dp * num_mb) == 0
