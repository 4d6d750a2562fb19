"""Device-mesh runtime: the single SPMD backend of the framework.

The reference ships two distributed backends (Accelerate/DeepSpeed over NCCL and
NeMo/Megatron/Apex over NCCL — SURVEY.md §2.3, §5.8). Under JAX SPMD both collapse
into one: a ``jax.sharding.Mesh`` with axes ``("data", "fsdp", "model")`` where

- ``data``  = pure data parallelism (reference: DDP / NeMo DP groups),
- ``fsdp``  = ZeRO-style parameter/optimizer sharding (reference: DeepSpeed ZeRO 2/3),
- ``model`` = tensor parallelism (reference: Apex Column/RowParallelLinear), and the
  sequence dimension of activations may additionally be sharded over ``model``
  (reference: Megatron sequence parallelism).

Collectives are inserted by XLA from shardings — psum/all_gather/reduce_scatter over
ICI — replacing every explicit NCCL call in the reference.
"""

import os
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
PIPE_AXIS = "pipe"
MODEL_AXIS = "model"
# pipe sits between fsdp and model so that model (TP, chattiest) maps to
# physically-adjacent chips and pipe's stage-to-stage ppermute rides ICI too
MESH_AXES = (DATA_AXIS, FSDP_AXIS, PIPE_AXIS, MODEL_AXIS)

# Batch dims are sharded over both data axes (data-parallel + fsdp act as a combined
# data axis for inputs, the standard JAX FSDP recipe).
BATCH_AXES = (DATA_AXIS, FSDP_AXIS)


def initialize_distributed(coordinator_address: Optional[str] = None) -> None:
    """Initialize multi-host JAX if running under a multi-process launcher.

    Replaces the reference's NCCL process-group init (`accelerate_base_trainer.py:56`)
    and slurm/MPI env plumbing (`scripts/slurm_train.sh`). Env contract:
    ``TRLX_NUM_PROCESSES`` + ``TRLX_COORDINATOR`` (host:port) + ``TRLX_PROCESS_ID``
    for manual launches; on TPU pods jax auto-detects and only
    ``TRLX_NUM_PROCESSES`` (or nothing) is needed. No-op when single-process or
    already initialized.
    """
    # NB: do not probe jax.process_count() here — it would itself initialize
    # the backend, making the jax.distributed.initialize below illegal
    if jax.distributed.is_initialized():
        return
    num_processes = os.environ.get("TRLX_NUM_PROCESSES")
    coordinator_address = coordinator_address or os.environ.get("TRLX_COORDINATOR")
    if coordinator_address or num_processes:
        process_id = os.environ.get("TRLX_PROCESS_ID")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=int(num_processes) if num_processes else None,
            process_id=int(process_id) if process_id is not None else None,
        )
        logger.info(
            f"jax.distributed initialized: process {jax.process_index()}/{jax.process_count()}",
            ranks=[-1],
        )


def make_mesh(
    data: int = -1,
    fsdp: int = 1,
    model: int = 1,
    pipe: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build the global ``data × fsdp × pipe × model`` mesh.

    Any axis given as -1 is inferred from the device count (at most one). Axis
    products must equal the number of devices. ``mesh_utils.create_device_mesh``
    lays axes out so the innermost (``model``) axis maps to physically-adjacent
    chips, keeping TP collectives on ICI.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = [data, fsdp, pipe, model]
    unknown = [i for i, s in enumerate(sizes) if s == -1]
    if len(unknown) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {sizes}")
    if unknown:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known != 0:
            raise ValueError(f"Device count {n} not divisible by fixed axes {sizes}")
        sizes[unknown[0]] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(f"Mesh {sizes} does not match device count {n}")
    device_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    mesh = Mesh(device_array, MESH_AXES)
    logger.info(
        f"Mesh: data={sizes[0]} fsdp={sizes[1]} pipe={sizes[2]} model={sizes[3]} "
        f"over {n} devices"
    )
    return mesh


def make_deviceless_mesh(
    data: int = 1, fsdp: int = 1, pipe: int = 1, model: int = 1
) -> Mesh:
    """Mesh over *virtual* CPU host devices, for deviceless AOT lowering
    (``trlx_tpu/analysis/ir``, compile-only tests).

    The process must already expose enough CPU devices —
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax's first
    import (``tests/conftest.py`` and ``python -m trlx_tpu.analysis.ir`` both
    arrange this). Unlike :func:`make_mesh` this bypasses
    ``mesh_utils.create_device_mesh`` and lays devices out in flat index
    order: there is no physical topology to optimize for, and the
    deterministic order is what lets the IR auditor map compiled-HLO
    ``replica_groups`` back to named mesh axes.
    """
    n = data * fsdp * pipe * model
    devices = [d for d in jax.devices() if d.platform == "cpu"][:n]
    if len(devices) < n:
        raise ValueError(
            f"deviceless mesh {data}x{fsdp}x{pipe}x{model} needs {n} cpu "
            f"devices but only {len(devices)} exist; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax imports"
        )
    return Mesh(np.array(devices).reshape(data, fsdp, pipe, model), MESH_AXES)


class IslandPlacement(NamedTuple):
    """Device carve for the Sebulba split (docs/parallelism.md "Islands"):
    which devices host the generation island (serving engine) and which host
    the learner island (PPO train step). ``shared`` marks the single-device
    degenerate case where both islands are thread-level tenants of one chip."""

    gen: Tuple
    learn: Tuple
    shared: bool


def carve_islands(gen_devices: int = 1, devices: Optional[Sequence] = None) -> IslandPlacement:
    """Carve the flat device set into disjoint generation and learner islands.

    The generation island takes the *last* ``gen_devices`` devices and the
    learner keeps the lowest-index prefix — so the learner mesh built from
    the remainder lays out identically to a smaller single-island run, and
    the generation devices sit at the far end of the ICI order where their
    decode traffic does not cross the learner's collective paths. With a
    single device both islands share it (thread-level islands, the CPU-test
    and single-chip topology); with more, the carve is strictly disjoint.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    g = int(gen_devices)
    if g < 1:
        raise ValueError(f"gen_devices must be >= 1, got {g}")
    if n == 1:
        return IslandPlacement((devices[0],), (devices[0],), True)
    if g >= n:
        raise ValueError(
            f"gen_devices={g} leaves no learner devices out of {n}: the carve "
            f"needs at least one device per island"
        )
    return IslandPlacement(tuple(devices[n - g:]), tuple(devices[:n - g]), False)


def island_meshes(
    placement: IslandPlacement,
    data: int = -1,
    fsdp: int = 1,
    model: int = 1,
    pipe: int = 1,
) -> Tuple[Mesh, Mesh]:
    """Build ``(gen_mesh, learn_mesh)`` over a carve from :func:`carve_islands`.

    The generation mesh is pure data-parallel over its devices (each replica
    runs the single-device paged-decode step — the kernel is deliberately not
    SPMD-partitioned, docs/parallelism.md); the learner mesh takes the
    requested ``data × fsdp × pipe × model`` axes over the learner devices.
    """
    gen_mesh = make_mesh(
        data=len(placement.gen), fsdp=1, model=1, pipe=1, devices=list(placement.gen)
    )
    learn_mesh = make_mesh(
        data=data, fsdp=fsdp, model=model, pipe=pipe, devices=list(placement.learn)
    )
    return gen_mesh, learn_mesh


def mesh_from_config(mesh_config, devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh from a :class:`trlx_tpu.data.configs.MeshConfig`."""
    return make_mesh(
        data=mesh_config.data, fsdp=mesh_config.fsdp, model=mesh_config.model,
        pipe=mesh_config.pipe, devices=devices,
    )


def batch_spec(extra_dims: int = 0) -> PartitionSpec:
    """PartitionSpec sharding a batch-leading array over the combined data axes."""
    return PartitionSpec(BATCH_AXES, *([None] * extra_dims))


def batch_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(extra_dims))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def dp_size(mesh: Mesh) -> int:
    """Total data-parallel degree (data × fsdp)."""
    return mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]


def put_batch(mesh: Mesh, batch):
    """Place a host-global numpy pytree onto the mesh, sharded along the batch dim.

    In multi-host, each process holds the *full* global batch (single-controller
    style data loading with identical seeds), so the array is assembled with
    ``make_array_from_callback``: every host slices ITS devices' shards out of
    the same global array. (``make_array_from_process_local_data`` would instead
    treat each host's copy as a distinct portion and double the batch.)
    """
    dp = dp_size(mesh)

    def _put(x):
        x = np.asarray(x)
        if x.ndim == 0 or x.shape[0] % dp != 0:
            # uneven batches (e.g. small eval sets) replicate rather than fail
            sharding = replicated(mesh)
        else:
            sharding = batch_sharding(mesh, extra_dims=x.ndim - 1)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])

    return jax.tree.map(_put, batch)
