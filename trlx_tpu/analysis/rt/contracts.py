"""Declared shape contracts: the sanctioned bucketing ladders.

The whole fixed-shape promise rests on a handful of *quantizers* — functions
that collapse a data-dependent Python int (a ragged response length, a prompt
length, an admission-wave width) onto a small committed ladder of padded
shapes before it can reach a jitted call site. SH001 flags any shape that
reaches a jit boundary without passing through one of these; this registry is
how SH001 knows which functions count, instead of special-casing names inside
the rule.

Each :class:`ShapeContract` declares, for one jit-cache family:

- ``quantizers`` — the functions whose return value is a sanctioned shape
  (``pad_to_bucket``, ``quantize_stream_response``, ...). A value produced by
  any of these is bucketed by construction.
- ``guard`` — the runtime assertion that bounds the family
  (``check_stream_bucket_family``), if one exists. PR 13 introduced that
  guard ad hoc inside the trainer; registering it here makes it a declared
  contract the rt suite owns: the guard's ``limit`` default reads
  ``max_shapes`` from this registry, and the CompileWatcher probes use the
  same number as the warmup-compile ceiling.
- ``max_shapes`` — the committed jit-cache bound for the family.

This module is import-light on purpose: the trainer and serving engine import
it at module scope (to read ``max_shapes``), so it must not pull in jax or
any analysis machinery.
"""

from dataclasses import dataclass
from typing import Dict, FrozenSet, Optional, Tuple


@dataclass(frozen=True)
class ShapeContract:
    """One declared bucketing ladder and its jit-cache bound."""

    name: str
    #: dotted module owning the quantizer/guard implementations
    module: str
    #: function names whose return value is a sanctioned (bucketed) shape
    quantizers: Tuple[str, ...]
    #: committed max distinct shapes per family (the jit-cache bound)
    max_shapes: int
    #: runtime assertion bounding the family, when one exists
    guard: Optional[str] = None
    description: str = ""


#: name -> contract; populated below at import time. Adding a new bucketing
#: ladder anywhere in the tree means adding a declaration here — SH001 trusts
#: exactly this list.
CONTRACTS: Dict[str, ShapeContract] = {}


def register_shape_contract(contract: ShapeContract) -> ShapeContract:
    if contract.name in CONTRACTS:
        raise ValueError(f"duplicate shape contract {contract.name!r}")
    CONTRACTS[contract.name] = contract
    return contract


def get(name: str) -> ShapeContract:
    return CONTRACTS[name]


def quantizer_names() -> FrozenSet[str]:
    """Every registered quantizer function name (last dotted component) —
    the SH001 sanction list."""
    out = set()
    for c in CONTRACTS.values():
        out.update(c.quantizers)
    return frozenset(out)


def guard_names() -> FrozenSet[str]:
    out = set()
    for c in CONTRACTS.values():
        if c.guard:
            out.add(c.guard)
    return frozenset(out)


# -- the committed contracts --------------------------------------------------

#: PR 13's streamed-scoring ladder, promoted from an ad-hoc assertion inside
#: the trainer into a declared contract: ≤4 pow2 response-length shapes per
#: (batch, prompt-bucket) score-fn family. ``check_stream_bucket_family``
#: reads its default ``limit`` from here, and the ``stream_score_bucket``
#: CompileWatcher probe uses the same bound as its warmup ceiling.
register_shape_contract(ShapeContract(
    name="stream_score_ladder",
    module="trlx_tpu.trainer.ppo_trainer",
    quantizers=("quantize_stream_response", "overlap_r_buckets", "pad_to_bucket"),
    guard="check_stream_bucket_family",
    max_shapes=4,
    description=(
        "streamed scoring microbuckets: varied completion lengths quantize "
        "onto a <=4-entry pow2 ladder per (B, P) family"
    ),
))

#: The one-shot/serving prompt-length families: prompts pad onto the shared
#: pow2 bucket list before any prefill or generate compile. ``pad_to_bucket``'s
#: ``cap`` (the longest length a configuration allows) only lowers a rung to
#: that cap, so the family stays within the ladder's rungs and the one cap.
register_shape_contract(ShapeContract(
    name="prompt_buckets",
    module="trlx_tpu.ops.generation",
    quantizers=("pad_to_bucket", "left_pad_batch"),
    max_shapes=8,
    description=(
        "prompt lengths pad onto the shared pow2 bucket ladder, "
        "no rung past the configuration's cap"
    ),
))

#: Serving-engine prefill waves: admission groups compile one wave program
#: per (pow2 prompt bucket, group width) pair.
register_shape_contract(ShapeContract(
    name="prefill_buckets",
    module="trlx_tpu.serving.engine",
    quantizers=("_pow2_at_least",),
    max_shapes=8,
    description="serving prefill waves bucket prompt lengths to pow2",
))
