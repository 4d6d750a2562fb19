"""Device time by the program's own scopes: the op events of a traced run
joined, by instruction name, to the table each hot program keeps of its own
instructions (``trlx_tpu/obs/op_scopes.py``: name -> named scope, kind, pass).

On this chip a device event carries its HLO instruction's name and no
``op_name``, so the trace alone cannot say what ``%fusion.21`` is; the program
can, from the executable that ran. An op event belongs to the program whose
module event (``XLA Modules``: ``jit_<program>(<id>)``) contains it in time;
two programs may both hold a ``%fusion.21``. Containers (``while`` ...) are
left out as in ``trace_reduce``: their time is their bodies'.

The join is made once a run (``_joined``) on plain ``(name, start_ns,
end_ns)`` tuples and plain dictionaries, so ``benchmark/tests`` rehearses it
off the chip. A program without ``op_scopes`` (any commit before it) gives
``None`` everywhere, and so does a run that was not traced. The tables are
asked for here, after the window and after the program's state was freed:
they come from abstract values alone.
"""

import bisect
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmark import trace_reduce
from benchmark.readers import program_trace
from benchmark.trace_reduce import Event

UNSCOPED, UNKNOWN = "unscoped", "unknown"
NAMED_OPS = 20


def _op_scopes():
    try:
        from trlx_tpu.obs import op_scopes
    except ImportError:  # a program from before it kept the table
        return None
    return op_scopes


def module_pattern(program: str) -> str:
    """The module events of a noted program: the chip names them ``jit_<fn>(<id>)``."""
    return rf"^jit_{re.escape(program)}\b"


def seconds_by_program(ops: List[Event], modules: Dict[str, List[Event]]) -> Dict[Tuple[Optional[str], str], float]:
    """``{(program, op name): seconds}`` over the op events, containers left
    out; the program is the one whose module event contains the op event in
    time, None for an op inside none of ``modules`` (program -> its events)."""
    spans = sorted((start, end, program) for program, events in modules.items() for _, start, end in events)
    starts = [start for start, _, _ in spans]
    out: Dict[Tuple[Optional[str], str], float] = {}
    for name, start, end in ops:
        if name.rpartition(" ")[2] in trace_reduce.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, start) - 1
        program = spans[i][2] if i >= 0 and end <= spans[i][1] else None
        out[program, name] = out.get((program, name), 0.0) + (end - start) / 1e9
    return out


def label(row: Optional[Dict[str, Any]]) -> str:
    """A row's place in ``info.device_s_by_scope``: its scopes outermost first,
    ``loss/logprobs``, forward and backward apart under ``loss``."""
    if row is None:
        return UNKNOWN
    if not row["scope"]:
        return UNSCOPED
    path = "/".join(row["scope"])
    return f"{path}:{row['pass']}" if row["scope"][0] == "loss" else path


def join(seconds: Dict[Tuple[Optional[str], str], float], tables: Dict[str, Dict[str, Dict[str, Any]]]):
    """Every (program, op name) with its seconds and its row of the program's
    table (None where the program is none of ours or the table lacks the
    name): ``[(program, op name, seconds, row)]``, the longest first."""
    out = []
    for (program, name), s in seconds.items():
        row = tables.get(program, {}).get(name.partition(" ")[0]) if program is not None else None
        out.append((program, name, s, row))
    return sorted(out, key=lambda r: -r[2])


def _joined(ctx):
    """The join of this run, made once: None where the run was not traced or
    the program keeps no tables. What it summed goes to ``info``."""
    if getattr(ctx, "_scopes_joined", False) is not False:
        return ctx._scopes_joined
    ctx._scopes_joined = None
    op_scopes, loaded = _op_scopes(), program_trace._program_trace(ctx)
    if op_scopes is None or loaded is None:
        return None
    tables, took = {}, {}
    for program in op_scopes.programs():
        t0 = time.monotonic()
        rows = op_scopes.table(program)
        if rows is not None:
            tables[program], took[program] = rows, time.monotonic() - t0
    modules = {program: program_trace.matching(loaded[0], module_pattern(program)) for program in tables}
    if not tables or not any(modules.values()):
        return None
    joined = join(seconds_by_program(ctx.trace["ops"], modules), tables)
    ctx._scopes_joined = joined

    by_scope: Dict[str, Dict[str, float]] = {}
    mixed: Dict[str, Dict[str, float]] = {}  # program -> the scopes inside, "accumulate+loss" -> seconds
    named: Dict[str, List[float]] = {}  # program -> [seconds whose name the table knows, all seconds]
    for program, _, s, row in joined:
        if program is None:
            continue
        split, place = by_scope.setdefault(program, {}), label(row)
        split[place] = split.get(place, 0.0) + s
        known = named.setdefault(program, [0.0, 0.0])
        known[0], known[1] = known[0] + (s if row is not None else 0.0), known[1] + s
        if row is not None and len(row["inside"]) > 1:
            held, inside = mixed.setdefault(program, {}), "+".join(sorted(row["inside"]))
            held[inside] = held.get(inside, 0.0) + s
    ctx.notes["device_s_by_scope"] = by_scope
    ctx.notes["device_s_mixed_fusions"] = mixed
    ctx.notes["device_named_pct_by_program"] = {p: 100.0 * k / a for p, (k, a) in named.items() if a}
    ctx.notes["device_ops_named"] = [
        [name, s, program, label(row), row["kind"] if row else None, row["source"] if row else None]
        for program, name, s, row in joined[:NAMED_OPS]]
    ctx.notes["op_scopes_table_s"] = took
    ctx.notes["op_scopes_rows"] = {program: len(rows) for program, rows in tables.items()}
    return joined


def device_share(ctx, program: Optional[str] = None, scopes: Optional[List[str]] = None,
                 under: Optional[List[str]] = None, kinds: Optional[List[str]] = None,
                 exclude: Optional[str] = None, per: Optional[str] = None):
    """Device seconds of the op events inside ``program`` (``op_scopes``' name
    for it; every noted program where None) whose instruction's innermost
    vocabulary scope is in ``scopes`` (or any of whose scopes is in ``under``),
    whose ``kind`` is in ``kinds`` and whose name does not match ``exclude`` —
    as percent of the traced interval, or with ``per="decode_step"`` in
    milliseconds a decode step. An argument left None filters nothing; an
    instruction the table does not know matches no filter."""
    joined = _joined(ctx)
    if joined is None:
        return None
    excluded = re.compile(exclude) if exclude else None
    total, matched = 0.0, False
    for of_program, name, s, row in joined:
        if of_program is None or row is None or (program is not None and of_program != program):
            continue
        if scopes is not None and (not row["scope"] or row["scope"][-1] not in scopes):
            continue
        if under is not None and not set(under) & set(row["scope"]):
            continue
        if kinds is not None and row["kind"] not in kinds:
            continue
        if excluded is not None and excluded.search(name):
            continue
        total, matched = total + s, True
    if not matched:
        return None
    if per == "decode_step":
        calls = program_trace.matching(program_trace._program_trace(ctx)[0], module_pattern(program or "generate"))
        steps = len(calls) * (ctx.cell["new_tokens"] - 1)
        return total * 1e3 / steps if steps else None
    start, end = ctx.interval
    return 100.0 * total / (end - start)


def scoped_share(ctx):
    """Of all device-op seconds of the traced iteration (containers left out),
    the percent whose event lies in a noted program, whose instruction name
    the program's table knows and which stands under a scope of the vocabulary."""
    joined = _joined(ctx)
    total = sum(s for _, _, s, _ in joined) if joined else 0.0
    if not total:
        return None
    return 100.0 * sum(s for program, _, s, row in joined if program is not None and row and row["scope"]) / total
