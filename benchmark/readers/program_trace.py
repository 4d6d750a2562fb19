"""Readers of what the program says about itself: its device programs by name
(the ``XLA Modules`` line of a traced run), its own host spans on the trace's
clock, and its compile log.

The traced run's xplane is loaded once more here (cached by path) for the
module events and the program's spans; the op events, for the decode loop and
the busy intervals, are the ones ``run.py`` already holds in ``ctx.trace``.
Everything below ``_load`` works on plain ``(name, start_ns, end_ns)`` tuples,
so the tests check it on a small recording. A program without these names,
spans or log (any commit before they were added) gives ``None`` everywhere.
"""

import functools
import re
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce
from benchmark.trace_reduce import Event

#: the spans the program opens itself (docs/observability.md, "Span vocabulary"),
#: as the profiler holds them: the harness's own ``learn``, ``score`` and
#: ``reward`` are around the same calls, without the prefix
PREFIX = "trlx/"
PROGRAM_SPANS = tuple(PREFIX + name for name in (
    "experience", "generate", "reward", "score", "learn", "learn.put", "learn.step",
    "learn.sync", "data", "log", "evaluate", "checkpoint",
))
MODULES_LINE = "XLA Modules"


@functools.lru_cache(maxsize=2)
def _load(path: str) -> Tuple[List[Event], List[Event]]:
    """(the first device's module events by start, the program's host spans)."""
    devices, host = trace_reduce.load(path, PROGRAM_SPANS, ops_line=MODULES_LINE)
    modules = devices[sorted(devices)[0]] if devices else []
    return sorted(modules, key=lambda e: e[1]), host


def _program_trace(ctx) -> Optional[Tuple[List[Event], List[Event]]]:
    if ctx.trace is None:
        return None
    return _load(trace_reduce.find_xplane(ctx.session.trace_dir))


def matching(modules: List[Event], program: str) -> List[Event]:
    """The module events of one program; the chip names them ``jit_<fn>(<id>)``."""
    pattern = re.compile(program)
    return [e for e in modules if pattern.search(e[0])]


def outermost_whiles(ops: List[Event], inside: List[Event]) -> List[Event]:
    """The ``while`` op events that lie inside one of the ``inside`` events and
    inside no other ``while``."""
    loops = sorted(
        (e for e in ops if e[0].endswith(" while")
         and any(s <= e[1] and e[2] <= t for _, s, t in inside)),
        key=lambda e: (e[1], -e[2]),
    )
    kept: List[Event] = []
    for loop in loops:
        if not kept or loop[1] >= kept[-1][2]:
            kept.append(loop)
    return kept


def busy_between(ops: List[Event], start: int, end: int) -> List[Tuple[int, int]]:
    """The busy intervals of ``ops`` cut to ``[start, end]``, with an empty
    interval at an end the device was idle at, so that the gap there is one too."""
    inside = [e for e in ops if e[2] > start and e[1] < end]
    cut = [(max(a, start), min(b, end)) for a, b in trace_reduce.busy_intervals(inside)]
    if not cut or cut[0][0] > start:
        cut.insert(0, (start, start))
    if cut[-1][1] < end:
        cut.append((end, end))
    return cut


def span_segments(spans: List[Event]) -> List[Tuple[int, int, str]]:
    """The spans as one flat timeline, ``(start, end, name)`` by start: over
    each stretch between two span boundaries, the covering span that started
    last (of two that start together the shorter: the innermost); stretches
    under no span are left out."""
    cuts = sorted({t for _, start, end in spans for t in (start, end)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [span for span in spans if span[1] <= a and span[2] >= b]
        if covering:
            out.append((a, b, max(covering, key=lambda span: (span[1], -span[2]))[0]))
    return out


def idle_by_span(busy: List[Tuple[int, int]], spans: List[Event]) -> Dict[str, float]:
    """Seconds of the gaps between the ``busy`` intervals, each gap shared out
    over the innermost spans it runs under, by the time under each, under the
    spans' plain names; time under no span goes to ``other``.

    ``trace_reduce.idle_by_annotation`` gives a whole gap to the span over its
    middle. The learner's gap runs from the device's end of one step (the host
    in ``learn.sync``) over ``log``, ``data`` and ``learn.put`` to the dispatch
    in ``learn.step``, with its middle near the end of ``data``: whole runs
    flip between ``data`` and ``learn.put`` by that rule (PERF.md, PR 27)."""
    segments, first, out = span_segments(spans), 0, {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        while first < len(segments) and segments[first][1] <= gap_start:
            first += 1
        under, i = 0, first
        while i < len(segments) and segments[i][0] < gap_end:
            start, end, name = segments[i]
            part = min(end, gap_end) - max(start, gap_start)
            out[name[len(PREFIX):]] = out.get(name[len(PREFIX):], 0) + part
            under, i = under + part, i + 1
        if gap_end - gap_start > under:
            out["other"] = out.get("other", 0) + gap_end - gap_start - under
    return {name: ns / 1e9 for name, ns in out.items()}


# ------------------------------------------------------------ the window


def device_share(ctx, program: str):
    """Percent of the traced iteration in which the device ran the program:
    the summed durations of its module events over the interval."""
    loaded = _program_trace(ctx)
    events = matching(loaded[0], program) if loaded else []
    if not events:
        return None
    start, end = ctx.interval
    return 100.0 * sum(t - s for _, s, t in events) / 1e9 / (end - start)


def decode_step_ms(ctx, program: str):
    """Device milliseconds per decode step: the generator's outermost
    ``while`` loops over its calls times the steps of each (the first token
    comes from the prefill, so ``new_tokens - 1``)."""
    loaded = _program_trace(ctx)
    calls = matching(loaded[0], program) if loaded else []
    loops = outermost_whiles(ctx.trace["ops"], calls) if calls else []
    if not loops:
        return None
    steps = len(calls) * (ctx.cell["new_tokens"] - 1)
    return sum(t - s for _, s, t in loops) / 1e6 / steps


def learn_gap_ms(ctx, program: str):
    """Device idle milliseconds per optimizer step, from the start of the
    iteration's first train step program to the end of its last. The same
    idle seconds, shared out over the program's spans they ran under, go to
    ``info.learn_gap_s_by_span``."""
    loaded = _program_trace(ctx)
    steps = matching(loaded[0], program) if loaded else []
    if not steps:
        return None
    busy = busy_between(ctx.trace["ops"], steps[0][1], steps[-1][2])
    idle_ns = sum(b[0] - a[1] for a, b in zip(busy, busy[1:]))
    ctx.notes["learn_gap_s_by_span"] = idle_by_span(busy, loaded[1])
    return idle_ns / 1e6 / len(steps)


# ---------------------------------------------------------------- set-up


def _compile_log():
    try:
        from trlx_tpu.obs import compile_log
    except ImportError:  # a program from before it had one
        return None
    return compile_log.log


def _setup_log(ctx):
    """(the program's compile log, the time the window opened), or None where
    there is no log or it holds nothing from before the window."""
    log, opened = _compile_log(), ctx.session.marks[0]
    return (log, opened) if log is not None and log.compiles(before=opened) else None


def setup_compile_s(ctx):
    """Seconds inside backend-compile events (a cache hit's retrieval is one
    too) before the window opened, by the program's own compile log; how
    many and how long by attributed entry go to ``info.setup_compiles_by_entry``."""
    found = _setup_log(ctx)
    if found is None:
        return None
    log, opened = found
    ctx.notes["setup_compiles_by_entry"] = {  # entry -> [compiles, seconds]
        entry: list(counted) for entry, counted in log.by_entry(before=opened).items()}
    return log.compile_seconds(before=opened)


def setup_compiled_anew(ctx):
    """Programs the persistent cache did not give back before the window
    opened: its look-ups less its hits."""
    found = _setup_log(ctx)
    return float(found[0].compiled_anew(before=found[1])) if found else None
