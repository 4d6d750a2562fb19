"""Parameter sharding: regex partition rules → NamedShardings over the mesh.

This replaces the reference's per-backend parallelism plumbing — DeepSpeed ZeRO stage
configs (`configs/accelerate/zero2-bf16.yaml`), Apex ``ColumnParallelLinear`` /
``RowParallelLinear`` modules (`modeling_nemo_ppo.py:95-120`) and TP-rank-sharded
checkpoints — with a declarative table: each parameter path (joined with ``/``) is
matched against ordered regex rules yielding a ``PartitionSpec``. FSDP shards the
largest remaining dim over ``fsdp``; TP shards feature dims over ``model``.
"""

import contextlib
import re
import threading
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from trlx_tpu.parallel.mesh import FSDP_AXIS, MODEL_AXIS, PIPE_AXIS
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

# A rule: (path regex, PartitionSpec). First match wins. Specs name axes per dim.
Rule = Tuple[str, PartitionSpec]


def default_lm_rules() -> List[Rule]:
    """Partition rules for :class:`trlx_tpu.models.transformer.TransformerLM` params.

    Megatron-style TP layout (column-parallel QKV/up-proj, row-parallel out/down-proj)
    with FSDP on the other matmul dim; embeddings sharded on vocab over ``model``;
    norms and biases replicated (biases of row-parallel layers must be replicated since
    their outputs are psum-reduced).

    Stacked-layer (pipeline-parallel) rules come first: when the model is built
    with ``pipeline_stages > 1`` the block params live under ``layers_scan`` with
    a leading ``[num_layers]`` dim, sharded over ``pipe`` so each stage holds only
    its own layers; the remaining dims follow the same column/row TP + FSDP layout
    shifted by one. Harmless when no ``layers_scan`` subtree exists.
    """
    stacked = [
        (r".*layers_scan.*(q_proj|k_proj|v_proj|up_proj|gate_proj)/kernel$",
         PartitionSpec(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS)),
        (r".*layers_scan.*(q_proj|k_proj|v_proj|up_proj|gate_proj)/bias$",
         PartitionSpec(PIPE_AXIS, MODEL_AXIS)),
        (r".*layers_scan.*(o_proj|down_proj)/kernel$",
         PartitionSpec(PIPE_AXIS, MODEL_AXIS, FSDP_AXIS)),
        (r".*layers_scan.*", PartitionSpec(PIPE_AXIS)),
    ]
    return stacked + [
        # embeddings: [vocab, hidden] — vocab over model (TP), hidden over fsdp
        (r".*embed_tokens/embedding$", PartitionSpec(MODEL_AXIS, FSDP_AXIS)),
        (r".*embed_positions/embedding$", PartitionSpec(None, FSDP_AXIS)),
        # attention: qkv column-parallel [hidden, heads*dim]; out row-parallel
        (r".*(q_proj|k_proj|v_proj)/kernel$", PartitionSpec(FSDP_AXIS, MODEL_AXIS)),
        (r".*(q_proj|k_proj|v_proj)/bias$", PartitionSpec(MODEL_AXIS)),
        (r".*o_proj/kernel$", PartitionSpec(MODEL_AXIS, FSDP_AXIS)),
        # latent attention: the down-projection to latent + rotary key is narrow (fsdp
        # alone), the per-head expansion out of the latent is column-parallel
        (r".*kv_a_proj/kernel$", PartitionSpec(FSDP_AXIS, None)),
        (r".*kv_b_proj/kernel$", PartitionSpec(FSDP_AXIS, MODEL_AXIS)),
        # routed experts [experts held, in, out]: experts stay whole on their leading
        # axis (the mesh has no expert axis yet), fsdp / model over the weight dims
        # as in the dense mlp; the router [hidden, experts] over fsdp
        (r".*experts/(gate|up)$", PartitionSpec(None, FSDP_AXIS, MODEL_AXIS)),
        (r".*experts/down$", PartitionSpec(None, MODEL_AXIS, FSDP_AXIS)),
        (r".*router/kernel$", PartitionSpec(FSDP_AXIS, None)),
        # looped layers: the exit gate [hidden, 1] over fsdp; its bias and the sandwich
        # norms' scales (ln_1_post, ln_2_post) are replicated like every norm
        (r".*exit_gate/kernel$", PartitionSpec(FSDP_AXIS, None)),
        (r".*exit_gate/bias$", PartitionSpec()),
        (r".*ln_[12]_post/scale$", PartitionSpec()),
        # gated short convolution: the product to [b, c, x] column-parallel, the product back
        # row-parallel, the depthwise filter [hidden, taps] by channel; the query / key head
        # norms' scales [head_dim] are replicated like every norm
        (r".*in_proj/kernel$", PartitionSpec(FSDP_AXIS, MODEL_AXIS)),
        (r".*out_proj/kernel$", PartitionSpec(MODEL_AXIS, FSDP_AXIS)),
        (r".*conv/kernel$", PartitionSpec(MODEL_AXIS, None)),
        (r".*(q_norm|k_norm)/scale$", PartitionSpec()),
        # mlp: up/gate column-parallel; down row-parallel
        (r".*(up_proj|gate_proj)/kernel$", PartitionSpec(FSDP_AXIS, MODEL_AXIS)),
        (r".*(up_proj|gate_proj)/bias$", PartitionSpec(MODEL_AXIS)),
        (r".*down_proj/kernel$", PartitionSpec(MODEL_AXIS, FSDP_AXIS)),
        # lm head: [hidden, vocab] — vocab over model
        (r".*lm_head/kernel$", PartitionSpec(FSDP_AXIS, MODEL_AXIS)),
        # T5: shared embedding, q/k/v column-parallel, o row-parallel, wi/wo mlp
        (r".*shared/embedding$", PartitionSpec(MODEL_AXIS, FSDP_AXIS)),
        (r".*/(q|k|v)/kernel$", PartitionSpec(FSDP_AXIS, MODEL_AXIS)),
        (r".*/o/kernel$", PartitionSpec(MODEL_AXIS, FSDP_AXIS)),
        (r".*/(wi|wi_0|wi_1)/kernel$", PartitionSpec(FSDP_AXIS, MODEL_AXIS)),
        (r".*/wo/kernel$", PartitionSpec(MODEL_AXIS, FSDP_AXIS)),
        # value / Q heads: Megatron column->row parallel over the model axis (the
        # reference's ParallelLinear heads, modeling_nemo_ppo.py:95-130). FSDP on
        # dim 0 would conflict with the batch-sharded activation and trigger XLA
        # involuntary-remat resharding (observed in round-2 dryrun).
        (r".*(value_head|q_head|target_q_head|v_head).*fc_in/kernel$",
         PartitionSpec(None, MODEL_AXIS)),
        (r".*(value_head|q_head|target_q_head|v_head).*fc_in/bias$",
         PartitionSpec(MODEL_AXIS)),
        (r".*(value_head|q_head|target_q_head|v_head).*fc_out/kernel$",
         PartitionSpec(MODEL_AXIS, None)),
        # everything else (norms, biases, scalars): replicated
        (r".*", PartitionSpec()),
    ]


def spec_for_path(path: str, rules: Sequence[Rule]) -> PartitionSpec:
    for pattern, spec in rules:
        if re.match(pattern, path):
            return spec
    return PartitionSpec()


def _iter_paths(tree: Any, prefix: str = ""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _iter_paths(v, f"{prefix}/{k}" if prefix else str(k))
    else:
        yield prefix, tree


def _clip_spec(spec: PartitionSpec, shape: Tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """Drop named axes that don't divide the corresponding dim, exceed the rank,
    or name an axis the mesh doesn't have (e.g. ``pipe`` on a custom 3-axis mesh)."""
    entries = list(spec)[: len(shape)]
    out = []
    for i, entry in enumerate(entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if any(a not in mesh.shape for a in axes):
            out.append(None)
            continue
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if shape[i] % size == 0:
            out.append(entry)
        else:
            out.append(None)
    return PartitionSpec(*out)


def make_param_specs(params: Any, mesh: Mesh, rules: Optional[Sequence[Rule]] = None) -> Any:
    """PartitionSpec pytree matching ``params`` (dims that don't divide are dropped)."""
    rules = rules if rules is not None else default_lm_rules()

    def build(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: build(v, f"{prefix}/{k}" if prefix else str(k)) for k, v in tree.items()}
        spec = spec_for_path(prefix, rules)
        # .shape covers abstract leaves too (ShapeDtypeStruct, orbax metadata)
        shape = tree.shape if hasattr(tree, "shape") else np.shape(tree)
        return _clip_spec(spec, tuple(shape), mesh)

    return build(params)


def make_param_shardings(params: Any, mesh: Mesh, rules: Optional[Sequence[Rule]] = None) -> Any:
    specs = make_param_specs(params, mesh, rules)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )


def shard_params(params: Any, mesh: Mesh, rules: Optional[Sequence[Rule]] = None) -> Any:
    """Place ``params`` onto the mesh according to the rules (device_put reshards)."""
    shardings = make_param_shardings(params, mesh, rules)
    return jax.tree.map(jax.device_put, params, shardings)


def _path_str(path) -> str:
    """jax key-path -> the "a/b/c" strings the partition rules match (handles
    dict keys, namedtuple fields, and sequence indices)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def make_state_shardings(state_tree: Any, mesh: Mesh, rules: Optional[Sequence[Rule]] = None) -> Any:
    """NamedShardings for an OPTIMIZER STATE pytree (optax namedtuples wrapping
    param-shaped moment trees). Moment leaves keep their param's layout because
    their key paths end with the same parameter path the regex rules match
    (``.../mu/transformer/layers_0/attn/q_proj/kernel``); scalars and
    quantized-moment blocks hit the replicated catch-all.

    This must be applied EXPLICITLY (``jit(tx.init, out_shardings=...)``):
    leaving the state placement to GSPMD propagation replicates the moments —
    ``zeros_like`` outputs carry no input-derived sharding, and a replicated
    Adam state for a full-finetune 7B is 54G on EVERY device (measured by the
    v5e compiler in scripts/scale_proof.py's earlier runs)."""
    from jax.tree_util import tree_flatten_with_path

    rules = list(rules) if rules is not None else default_lm_rules()
    # 8-bit Adam stores blockwise-quantized moments ([n_blocks, 256] int8 +
    # per-block scales) whose paths end in m_q/v_q/..., never matching the
    # kernel rules — shard their block dim over fsdp rather than replicating
    # (dropped by _clip_spec when n_blocks doesn't divide)
    rules = [
        (r".*/(m_q|v_q|m_scale|v_scale)$", PartitionSpec(FSDP_AXIS)),
    ] + rules
    leaves, treedef = tree_flatten_with_path(state_tree)
    shardings = []
    for path, leaf in leaves:
        shape = tuple(leaf.shape if hasattr(leaf, "shape") else np.shape(leaf))
        spec = _clip_spec(spec_for_path(_path_str(path), rules), shape, mesh)
        shardings.append(NamedSharding(mesh, spec))
    return treedef.unflatten(shardings)


_manual_mode = threading.local()


@contextlib.contextmanager
def manual_axes():
    """Mark the enclosing trace as *manually mapped* (inside a ``shard_map``
    body, e.g. the overlapped FSDP step in :mod:`trlx_tpu.parallel.fsdp`).

    ``with_sharding_constraint`` is illegal on axes that are manual —
    :func:`constrain_gathered` / :func:`constrain_seq` become no-ops under
    this context so the model code can run unchanged inside shard_map.
    Checking ``ambient_mesh()`` is not enough: the trainer traces the
    shard_map body under ``with self.mesh:``, where the ambient mesh is live.
    """
    prev = getattr(_manual_mode, "depth", 0)
    # trace-time-only mutation is the POINT: the guard changes how constrain_*
    # helpers trace, not what the compiled step computes per-iteration
    _manual_mode.depth = prev + 1  # graftcheck: noqa[JX003]
    try:
        yield
    finally:
        _manual_mode.depth = prev  # graftcheck: noqa[JX003]


def in_manual_axes() -> bool:
    return getattr(_manual_mode, "depth", 0) > 0


_warned_no_mesh_api = False


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` context, or None."""
    global _warned_no_mesh_api  # graftcheck: noqa[JX003] — a once-a-process latch, read where the program is traced
    try:
        from jax._src import mesh as _mesh_lib

        m = _mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        if not _warned_no_mesh_api:
            _warned_no_mesh_api = True
            logger.warning(  # graftcheck: noqa[JX003] — once a process is the point
                "Could not read the ambient mesh (jax internals moved?): ring "
                "attention and sequence sharding are DISABLED. Update "
                "trlx_tpu.parallel.sharding.ambient_mesh for this jax version."
            )
        return None


def batch_divisible(mesh: Mesh, batch_size: int) -> bool:
    """Whether a leading batch dim can shard evenly over the combined data axes."""
    from trlx_tpu.parallel.mesh import BATCH_AXES

    return batch_size % int(np.prod([mesh.shape.get(a, 1) for a in BATCH_AXES])) == 0


def constrain_gathered(x: jax.Array) -> jax.Array:
    """Gather the sequence dim back before the LM/value heads (the analogue of
    Megatron's ``gather_from_sequence_parallel_region``, reference
    modeling_nemo_ppo.py:160-164): batch stays sharded, everything else whole."""
    if in_manual_axes():
        return x
    mesh = ambient_mesh()
    if mesh is None or not batch_divisible(mesh, x.shape[0]):
        return x
    from trlx_tpu.parallel.mesh import BATCH_AXES

    entries = [None] * x.ndim
    entries[0] = BATCH_AXES
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*entries)))


def constrain_seq(x: jax.Array, seq_dim: int = 1) -> jax.Array:
    """Sequence-parallel activation constraint (Megatron-SP analogue,
    reference modeling_nemo_ppo.py:160-164): shard the sequence dim of an
    activation over the ``model`` axis (batch over the data axes). XLA inserts
    the all-gather before TP matmuls and the reduce-scatter after, which is
    exactly Megatron SP's gather/scatter pair. No-op outside a mesh context or
    when the sequence length does not divide the axis."""
    if in_manual_axes():
        return x
    mesh = ambient_mesh()
    if mesh is None:
        return x
    size = mesh.shape.get(MODEL_AXIS, 1)
    if size <= 1 or x.shape[seq_dim] % size != 0 or not batch_divisible(mesh, x.shape[0]):
        return x
    from trlx_tpu.parallel.mesh import BATCH_AXES

    entries = [None] * x.ndim
    entries[0] = BATCH_AXES
    entries[seq_dim] = MODEL_AXIS
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*entries)))
