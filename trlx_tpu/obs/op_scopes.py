"""Every compiled program's table of its own instructions: which named scope
each belongs to.

``jax.named_scope`` marks the phases of the device programs (``loss``,
``accumulate``, ``optimizer``, ``logprobs``, ``decode`` ..., the
:data:`VOCABULARY`), and the marks reach the ``op_name`` of every instruction
of the compiled program. A profiler's device event, though, carries only its
instruction's *name* (``%fusion.21``) on this chip, so a trace cannot say what
an op is. The join is this module's: a table ``instruction name -> scope``
taken from the executable that ran.

- :func:`note` is called at the dispatch sites of the hot programs (the ones
  ``compile_log.attributed`` marks). The first call for a jitted function keeps
  the function and its arguments' abstract values with their shardings: no
  array, no text, no compile. Every later call is a dictionary look-up. The
  trainers build one jitted function per shape key, so an entry is one
  (program, shapes).
- :func:`table` builds the table lazily: ``jitted.lower(*avals).compile()``
  under the noted mesh returns the executable jax already holds for those
  abstract arguments (no backend compile), and its optimized HLO is walked
  once and cached. Nothing is lowered, compiled or printed unless
  :func:`table` or :func:`write` is called; :data:`built` counts the tables
  built, so a test can hold that a run which never asked built none. Neither is
  to be called inside a profiler's window.
- :func:`write` puts every noted program's table into one JSON file, beside
  a ``train.profile_dir`` trace: the key to join a Perfetto / xprof view of
  the run to the program's scopes.

**The one rule for fusions.** A fusion that holds a ``dot`` or a
``convolution`` takes that instruction's scope (the time is the product's: an
add fused onto its output rides on a write that happens anyway); any other
fusion takes its root's. The table also keeps every vocabulary scope found
inside a fusion (``inside``), so a reader can say how much time sits in
fusions that span two. The limit of the rule: a fusion's time is never split.

**What the table can be no better than.** The scopes are read off the
executable's own metadata. jax's persistent compile cache leaves debug
information out of its key, so a program whose only change is a scope's name
could be handed an executable compiled before the change, with the older
``op_name`` on every instruction. The hot programs here hold Pallas kernels,
whose payloads carry source locations inside the key (a line shifted in a
traced file compiles them anew), which is why a tree's first run pays a
compile and reads its own scopes; a reader that finds a scope missing that the
source has should look there first.

The registry holds the jitted functions themselves, so an executable outlives
the trainer's own dictionaries being cleared; it is bounded
(:data:`CAPACITY` entries, the oldest dropped).
"""

import contextlib
import json
import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

#: the scopes the device programs open (docs/observability.md, "Names of the
#: device programs"); an instruction under none of them reads unscoped
VOCABULARY = (
    "prefill", "decode", "policy_forward", "reference_forward", "logprobs", "loss", "accumulate",
    "optimizer", "mla", "moe.route", "moe.experts", "moe.shared", "loop.pass", "conv",
    "dsa.index", "dsa.select", "dsa.attend",
)
CAPACITY = 64

#: instructions that only contain others: their time is their bodies'
CONTAINERS = ("while", "conditional", "call")
#: instructions that never run (no device event bears their name)
_FREE = ("parameter", "tuple", "get-tuple-element")
_PRODUCTS = ("dot", "convolution")
_MOVES = frozenset((
    "copy", "reshape", "transpose", "bitcast", "pad", "slice", "dynamic-slice", "concatenate",
    "copy-start", "copy-done", "slice-start", "slice-done",
))
#: inside a fusion of moves these change nothing
_NEUTRAL = frozenset(_FREE + ("constant",))
#: custom-calls that are the compiler's own bookkeeping, no kernels
_COMPILER_CALLS = ("ConcatBitcast", "AllocateBuffer", "AssumeGatherIndicesInBound")


class Noted(NamedTuple):
    program: str
    jitted: Any
    args: Tuple
    kwargs: Dict[str, Any]
    mesh: Any


_lock = threading.Lock()
_noted: "OrderedDict[Tuple[str, int], Noted]" = OrderedDict()
_latest: Dict[str, Tuple[str, int]] = {}  # program -> the key last dispatched
_tables: Dict[Tuple[str, int], Dict[str, Dict[str, Any]]] = {}
#: tables built since the process started: stays 0 in a run nobody asked
built = 0


# ------------------------------------------------------------- the registry


def _abstract(x):
    """An array's shape, dtype and (where it is committed to one) sharding;
    anything else as it is."""
    import jax

    if isinstance(x, jax.Array):
        sharding = x.sharding if getattr(x, "committed", True) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding, weak_type=getattr(x, "weak_type", False))
    if hasattr(x, "shape") and hasattr(x, "dtype"):  # numpy
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def note(program: str, jitted, args: Tuple = (), kwargs: Optional[Dict[str, Any]] = None, mesh=None) -> None:
    """Remember what ``program`` is being dispatched with. After the first
    call for a jitted function this is one dictionary look-up; a callable that
    cannot be lowered is not kept."""
    key = (program, id(jitted))
    if key in _noted:
        _latest[program] = key
        return
    if not hasattr(jitted, "lower"):  # a plain wrapper (the health guard's ``run``): what it wraps notes itself
        return
    import jax

    entry = Noted(program, jitted, *jax.tree.map(_abstract, (tuple(args), dict(kwargs or {}))), mesh)
    with _lock:
        _noted[key] = entry
        _latest[program] = key
        while len(_noted) > CAPACITY:
            old, _ = _noted.popitem(last=False)
            _tables.pop(old, None)


def programs() -> List[str]:
    """The programs noted so far."""
    return list(_latest)


def noted(program: str) -> Optional[Noted]:
    """What was kept of the program's latest dispatch, or None."""
    return _noted.get(_latest.get(program))


def reset() -> None:
    global built
    with _lock:
        _noted.clear()
        _latest.clear()
        _tables.clear()
        built = 0


# ---------------------------------------------------------------- the table


def compiled_text(entry: Noted) -> str:
    """The optimized HLO of the executable the entry's dispatches ran: jit and
    the ahead-of-time path share it, so nothing compiles here."""
    with entry.mesh if entry.mesh is not None else contextlib.nullcontext():
        return entry.jitted.lower(*entry.args, **entry.kwargs).compile().as_text()


def table(program: str) -> Optional[Dict[str, Dict[str, Any]]]:
    """``{instruction name: {opcode, kind, scope, pass, source, inside}}`` of
    the program's latest dispatch; None for a program never noted."""
    global built
    key = _latest.get(program)
    if key is None or key not in _noted:
        return None
    if key not in _tables:
        rows = walk(compiled_text(_noted[key]))
        with _lock:
            _tables[key] = rows
            built += 1
    return _tables[key]


def write(path: str) -> Dict[str, int]:
    """Every noted program's table as one JSON object ``{program: table}``;
    returns the rows written by program."""
    tables = {program: table(program) for program in programs()}
    with open(path, "w") as f:
        json.dump({"vocabulary": list(VOCABULARY), "programs": tables}, f)
    return {program: len(rows) for program, rows in tables.items()}


# ------------------------------------------------- the walk over the HLO text

_COMPUTATION = re.compile(r"^(ENTRY\s+)?(%?[\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?(%?[\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(")
_OPERAND = re.compile(r"%[\w.\-]+")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(calls|body|condition|to_apply|true_computation|false_computation)=(%?[\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
#: the computations a container or an asynchronous start runs as device events of their own
_RUNS = {
    "while": ("body", "condition"), "conditional": ("true_computation", "false_computation", "branch"),
    "call": ("to_apply",), "async-start": ("calls",),
}


def _closing(text: str, opening: int) -> int:
    """The index of the bracket that closes the one at ``opening``."""
    depth = 0
    for i in range(opening, len(text)):
        depth += text[i] == "("
        depth -= text[i] == ")"
        if depth == 0:
            return i
    return len(text) - 1


def _opcode(rest: str) -> Tuple[str, List[str]]:
    """(opcode, operand names) of ``<type> <opcode>(<operands>), ...``: the
    first lower-case word followed by ``(`` after the type (a tuple type holds
    brackets of its own and no such word)."""
    if rest.startswith("("):
        rest = rest[_closing(rest, 0) + 1:]
    found = _OPCODE.search(rest)
    if not found:
        return "", []
    return found.group(1), _OPERAND.findall(rest[found.end() - 1:_closing(rest, found.end() - 1)])


class Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: str
    operands: List[str]
    called: Dict[str, List[str]]  # role -> computations
    target: str  # a custom-call's
    is_root: bool


def parse(text: str) -> Tuple[Dict[str, List[Instruction]], Optional[str]]:
    """({computation: its instructions in order}, the entry computation) of
    an HLO module's text."""
    computations: Dict[str, List[Instruction]] = {}
    entry, current = None, None
    for line in text.splitlines():
        if current is None:
            opened = _COMPUTATION.match(line)
            if opened:
                current = opened.group(2)
                computations[current] = []
                if opened.group(1):
                    entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        root, name, rest = found.groups()
        opcode, operands = _opcode(rest)
        called: Dict[str, List[str]] = {}
        for role, computation in _CALLED.findall(rest):
            called.setdefault(role, []).append(computation)
        branches = _BRANCHES.search(rest)
        if branches:
            called["branch"] = [b.strip() for b in branches.group(1).split(",") if b.strip()]
        op_name, target = _OP_NAME.search(rest), _TARGET.search(rest) if opcode == "custom-call" else None
        computations[current].append(Instruction(
            name if name.startswith("%") else "%" + name, opcode, op_name.group(1) if op_name else "",
            operands, called, target.group(1) if target else "", bool(root)))
    return computations, entry


def _elements(op_name: str) -> List[str]:
    """The ``/``-separated elements of an ``op_name``, those inside brackets
    kept whole; of names XLA joined with ``;`` the first."""
    out, depth, start = [], 0, 0
    op_name = op_name.split(";")[0]
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


def scope_of(op_name: str) -> Tuple[List[str], str]:
    """(the vocabulary scopes on the ``op_name``, outermost first; ``forward``
    or ``backward``). jax wraps the first element under a transformation,
    ``transpose(jvp(loss))``: the wrappers are stripped, and a ``transpose``
    among them makes the instruction the backward's; ``jit(...)`` elements,
    module names and the trailing primitive are no scopes and fall away."""
    scopes, backward = [], False
    for element in _elements(op_name):
        wrapped = _WRAPPER.match(element)
        while wrapped:
            backward = backward or wrapped.group(1) == "transpose"
            element = wrapped.group(2)
            wrapped = _WRAPPER.match(element)
        if element in VOCABULARY and scopes[-1:] != [element]:  # a module of the scope's name opens it: once
            scopes.append(element)
    return scopes, "backward" if backward else "forward"


def _fusion_row(body: List[Instruction], own_op_name: str) -> Tuple[str, str, List[str]]:
    """(kind, the op_name the fusion answers to, the vocabulary scopes inside
    it) by the one rule: the product's where it holds one, else its root's."""
    inside, product, root, only_moves = [], None, own_op_name, bool(body)
    for inner in body:
        for scope in scope_of(inner.op_name)[0][-1:]:  # the innermost of each instruction
            if scope not in inside:
                inside.append(scope)
        if inner.opcode in _PRODUCTS and product is None:
            product = inner.op_name
        if inner.is_root and inner.op_name:
            root = inner.op_name
        only_moves = only_moves and (inner.opcode in _MOVES or inner.opcode in _NEUTRAL)
    if product is not None:
        return "product", product or own_op_name, inside
    return ("move" if only_moves else "other"), root, inside


def _kind(instruction: Instruction) -> str:
    opcode = instruction.opcode
    if opcode in CONTAINERS:
        return "container"
    if opcode == "custom-call":
        if instruction.target in _COMPILER_CALLS:
            return "move" if instruction.target == "ConcatBitcast" else "other"
        return "kernel"
    if opcode in _PRODUCTS:
        return "product"
    return "move" if opcode in _MOVES else "other"


def _lend(instructions: List[Instruction], named: Dict[str, str]) -> Dict[str, Tuple[str, str]]:
    """For the instructions of one computation that the compiler put in itself
    and that carry no ``op_name`` (the moves between memory spaces:
    ``copy-start`` ... ``slice-done``, ``ConcatBitcast``): ``{name: (the
    op_name lent to it, the instruction it came from)}``. Such an instruction
    exists for the one that reads what it moves, so it takes the ``op_name`` of
    its first user that has one (through users that have none themselves), else
    that of the instruction that made its operand."""
    by_name = {i.name: i for i in instructions}
    users: Dict[str, List[str]] = {}
    for i in instructions:
        for operand in i.operands:
            users.setdefault(operand, []).append(i.name)

    def seek(name: str, neighbours, seen) -> Optional[Tuple[str, str]]:
        for other in neighbours(name):
            if other in seen or other not in by_name:
                continue
            seen.add(other)
            if named.get(other):
                return named[other], other
            found = seek(other, neighbours, seen)
            if found:
                return found
        return None

    out = {}
    for i in instructions:
        if named.get(i.name) or i.opcode in _FREE or i.opcode in CONTAINERS:
            continue
        found = (seek(i.name, lambda n: users.get(n, ()), {i.name})
                 or seek(i.name, lambda n: by_name[n].operands, {i.name}))
        if found:
            out[i.name] = found
    return out


def walk(text: str) -> Dict[str, Dict[str, Any]]:
    """The table of one optimized HLO module: a row for every instruction that
    can appear as a device event — the entry computation's and those of
    ``while`` / ``conditional`` / ``call`` bodies, not the insides of fusions."""
    computations, entry = parse(text)
    rows: Dict[str, Dict[str, Any]] = {}
    seen, queue = set(), [entry] if entry else []
    while queue:
        computation = queue.pop()
        if computation in seen or computation not in computations:
            continue
        seen.add(computation)
        instructions = computations[computation]
        described = {}
        for i in instructions:
            for role in _RUNS.get(i.opcode, ()):
                queue.extend(i.called.get(role, ()))
            if i.opcode == "fusion":
                body = [inner for called in i.called.get("calls", ()) for inner in computations.get(called, ())]
                described[i.name] = _fusion_row(body, i.op_name)
            else:
                described[i.name] = (_kind(i), i.op_name, [])
        lent = _lend(instructions, {name: op_name for name, (_, op_name, _) in described.items()})
        for i in instructions:
            if i.opcode in _FREE:
                continue
            kind, op_name, inside = described[i.name]
            via = None
            if not op_name and i.name in lent:
                op_name, via = lent[i.name]
            scopes, direction = scope_of(op_name)
            rows[i.name] = {
                "opcode": i.opcode, "kind": kind, "scope": scopes, "pass": direction,
                "source": op_name, "inside": inside,
            }
            if via:
                rows[i.name]["via"] = via
    return rows
