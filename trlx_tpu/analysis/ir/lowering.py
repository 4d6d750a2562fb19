"""Deviceless AOT lowering + IR extraction for registered entrypoints.

``lower_entry`` takes one :class:`~trlx_tpu.analysis.ir.entrypoints.EntryPoint`
and produces a :class:`LoweredEntry`: the closed jaxpr (trace-level view, for
IR001/IR003/IR004), the compiled HLO text and memory stats (compiled-artifact
view, for IR002/IR005/IR006). Nothing touches a device: arguments are
``ShapeDtypeStruct``s, the mesh is virtual CPU devices
(:func:`trlx_tpu.parallel.mesh.make_deviceless_mesh`), and ``.lower()`` /
``.compile()`` run the compiler only — the same recipe that proved 7B/20B
placement in ``scripts/scale_proof.py``.

Collective attribution: XLA emits ``replica_groups`` as flat partition ids in
the mesh's device order. Because the deviceless mesh lays devices out in flat
index order, the groups for "a collective over mesh axes S" are computable
from the mesh shape alone — we precompute them for every axis subset and name
each parsed collective by the matching subset (``fsdp``, ``data+fsdp``, ...),
falling back to an anonymous ``g<n>x<size>`` signature. These names are the
budget keys in ``graftcheck-ir-budget.json``.
"""

import itertools
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional

from trlx_tpu.analysis.ir.entrypoints import EntryArtifacts, EntryPoint

#: HLO op names audited by IR005 (the ``-start`` async forms fold into the
#: same key; ``-done`` carries no shape work of its own).
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

#: ``f32[2,64]{1,0}`` / ``bf16[8]`` / ``u32[]`` inside a result-shape token
_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[^\s(]+)\s+"
    r"(" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\{((?:\{[\d,]+\},?)+)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]")


@dataclass
class LoweredEntry:
    """One entrypoint, lowered (and normally compiled) at one spec."""

    entry: EntryPoint
    spec: str
    mesh: Any
    artifacts: EntryArtifacts
    jaxpr: Any  #: ClosedJaxpr of the step over the abstract args
    lowered: Any
    compiled: Any = None  #: None when compile=False (lower-only smoke)
    hlo_text: str = ""
    #: per-device byte accounting from compiled.memory_analysis()
    memory: Dict[str, int] = field(default_factory=dict)
    #: "<kind>:<axes>" -> {"count": n, "bytes": b}
    collectives: Dict[str, Dict[str, int]] = field(default_factory=dict)

    @property
    def key(self) -> str:
        """Budget key: ``entrypoint@spec``."""
        return f"{self.entry.name}@{self.spec}"


def iter_eqns(jaxpr) -> Iterable[Any]:
    """All equations of a (Closed)Jaxpr, recursing into call/control-flow
    sub-jaxprs (pjit, scan, while, cond, custom_vjp, ...)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _sub_jaxprs(params: Dict[str, Any]):
    for v in params.values():
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    yield item


def lower_entry(
    entry: EntryPoint,
    spec: str = "small",
    mesh: Optional[Any] = None,
    compile: bool = True,
) -> LoweredEntry:
    """Build, trace, lower and (optionally) compile one entrypoint."""
    import jax

    from trlx_tpu.parallel.mesh import make_deviceless_mesh

    if spec not in entry.specs:
        raise ValueError(f"{entry.name} has specs {entry.specs}, not {spec!r}")
    if mesh is None:
        mesh = make_deviceless_mesh(**entry.mesh_shape)
    art = entry.builder(spec, mesh)

    closed = jax.make_jaxpr(art.fn)(*art.args)
    jit_kwargs: Dict[str, Any] = {"donate_argnums": art.donate_argnums}
    if art.out_shardings is not None:
        jit_kwargs["out_shardings"] = art.out_shardings
    jitted = jax.jit(art.fn, **jit_kwargs)
    with mesh:
        lowered = jitted.lower(*art.args)

    out = LoweredEntry(
        entry=entry, spec=spec, mesh=mesh, artifacts=art, jaxpr=closed,
        lowered=lowered,
    )
    if compile:
        out.compiled = lowered.compile()
        out.hlo_text = out.compiled.as_text()
        out.memory = memory_summary(out.compiled)
        out.collectives = parse_collectives(out.hlo_text, mesh)
    return out


def memory_summary(compiled) -> Dict[str, int]:
    """Per-device byte accounting. The budgeted metric is ``audit_bytes``:
    the compiler's ``peak_memory_in_bytes`` where exposed (TPU), otherwise
    arguments + outputs + temp − donation aliases (the CPU backend exposes
    the components but not the high-water mark)."""
    ma = compiled.memory_analysis()
    d = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is None:
        peak = d["argument_bytes"] + d["output_bytes"] + d["temp_bytes"] - d["alias_bytes"]
    d["audit_bytes"] = int(peak)
    return d


# -- collective parsing ------------------------------------------------------


def _axis_group_index(mesh) -> Dict[frozenset, str]:
    """frozenset-of-replica-groups -> axis-subset name, for every non-empty
    subset of mesh axes (size-1 axes excluded: they produce no collective)."""
    import numpy as np

    shape = [mesh.shape[a] for a in mesh.axis_names]
    ids = np.arange(int(np.prod(shape))).reshape(shape)
    axes = [i for i, n in enumerate(shape) if n > 1]
    index: Dict[frozenset, str] = {}
    for r in range(1, len(axes) + 1):
        for combo in itertools.combinations(axes, r):
            moved = np.moveaxis(ids, combo, range(ids.ndim - len(combo), ids.ndim))
            groups = moved.reshape(-1, int(np.prod([shape[a] for a in combo])))
            key = frozenset(frozenset(int(x) for x in g) for g in groups)
            name = "+".join(mesh.axis_names[a] for a in combo)
            index.setdefault(key, name)
    return index


def _parse_groups(attrs: str) -> Optional[frozenset]:
    m = _GROUPS_RE.search(attrs)
    if m:
        return frozenset(
            frozenset(int(x) for x in g.split(",") if x)
            for g in re.findall(r"\{([\d,]+)\}", m.group(1))
        )
    m = _IOTA_GROUPS_RE.search(attrs)
    if m:  # iota v2 form [G,S]<=[N]: group g = {g*S .. g*S+S-1}
        n_groups, size, total = (int(x) for x in m.groups())
        if n_groups * size == total:
            return frozenset(
                frozenset(range(g * size, (g + 1) * size)) for g in range(n_groups)
            )
    return None


def _shape_bytes(shape_token: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_token):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES.get(dtype, 4)
    return total


def parse_collectives(hlo_text: str, mesh) -> Dict[str, Dict[str, int]]:
    """Count collectives in compiled HLO, keyed ``<kind>:<axes>`` with the
    per-device output bytes each moves. Unattributable replica groups get an
    anonymous ``g<groups>x<size>`` axes name rather than being dropped —
    a collective we cannot name is still a collective we must budget."""
    index = _axis_group_index(mesh)
    out: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if m is None:
            continue
        shape_token, kind = m.groups()
        groups = _parse_groups(line)
        if groups is not None and groups in index:
            axes = index[groups]
        elif groups is not None:
            sizes = sorted(len(g) for g in groups)
            axes = f"g{len(groups)}x{sizes[-1]}"
        else:
            axes = "all"
        key = f"{kind}:{axes}"
        slot = out.setdefault(key, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += _shape_bytes(shape_token)
    return dict(sorted(out.items()))


def measure(lowered: LoweredEntry) -> Dict[str, Any]:
    """The budget-facing measurement record for one compiled entrypoint —
    exactly what ``graftcheck-ir-budget.json`` commits."""
    if lowered.compiled is None:
        raise ValueError("measure() needs a compiled entry (compile=True)")
    return {
        "mesh": {a: int(lowered.mesh.shape[a]) for a in lowered.mesh.axis_names},
        "collectives": lowered.collectives,
        "memory_bytes": lowered.memory["audit_bytes"],
        "memory": lowered.memory,
    }


def flat_donated_leaves(art: EntryArtifacts) -> List[Any]:
    """Abstract leaves of the donated arguments (IR002's declared set)."""
    import jax

    leaves: List[Any] = []
    for i in art.donate_argnums:
        leaves.extend(jax.tree.leaves(art.args[i]))
    return leaves
