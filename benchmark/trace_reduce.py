"""From a profiler trace to numbers: device busy time, idle gaps named by what
the host was doing, kernel time by name pattern, the operations with most time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with nothing
but jax; everything after it works on plain ``(name, start_ns, end_ns)``
tuples, so the tests check it on a small recorded trace.
"""

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]  # name, start_ns, end_ns

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = "XLA Ops"
HOST_PLANE = r"^/host:CPU$"
#: operations that only contain others (the decode loop is one ``while`` event
#: with its body's events inside it): in the union, out of the table by name
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


@functools.lru_cache(maxsize=None)  # millions of events, some thousands of names
def short_name(hlo: str) -> str:
    """The chip names a device event by its whole HLO instruction,
    ``%attn.86 = (f32[32,12,520,64]{...}, ...) custom-call(...)``, thousands
    of characters for a loop. Kept: the instruction's name and its opcode,
    ``%attn.86 custom-call``."""
    name, _, rest = hlo.partition(" = ")
    opcode = _OPCODE.search(" " + rest)
    return f"{name.strip()} {opcode.group(1)}" if opcode else name.strip()


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, annotations: Iterable[str], device_plane: str = DEVICE_PLANE,
         ops_line: str = OPS_LINE, host_plane: str = HOST_PLANE):
    """({device plane name: its op events}, the host's events whose name is
    one of ``annotations``)."""
    from jax.profiler import ProfileData

    wanted = set(annotations)
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if re.match(device_plane, plane.name):
            for line in plane.lines:
                if line.name == ops_line:
                    devices.setdefault(plane.name, []).extend(
                        (short_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    )
        elif re.match(host_plane, plane.name):
            for line in plane.lines:
                host.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name in wanted
                )
    return devices, host


def busy_intervals(ops: List[Event]) -> List[Tuple[int, int]]:
    """The union of the intervals in which an operation ran, merged."""
    merged: List[List[int]] = []
    for _, start, end in sorted(ops, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(ops: List[Event]) -> float:
    return sum(b - a for a, b in busy_intervals(ops)) / 1e9


def idle_by_annotation(busy: List[Tuple[int, int]], host: List[Event]) -> Dict[str, float]:
    """Seconds of each gap between the ``busy`` intervals, summed under the
    name of the innermost host annotation that covered the gap's middle
    (``host`` for none)."""
    out: Dict[str, float] = {}
    spans = sorted(host, key=lambda e: e[1])
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        middle = (gap_start + gap_end) // 2
        name, latest = "host", -1
        for span_name, start, end in spans:
            if start > middle:
                break
            if end >= middle and start > latest:
                name, latest = span_name, start
        out[name] = out.get(name, 0.0) + (gap_end - gap_start) / 1e9
    return out


def seconds_by_name(ops: List[Event]) -> Dict[str, float]:
    """Summed durations by name, the containers left out."""
    out: Dict[str, float] = {}
    for name, start, end in ops:
        if name.rpartition(" ")[2] not in CONTAINERS:
            out[name] = out.get(name, 0.0) + (end - start) / 1e9
    return out


def kernel_seconds(ops: List[Event], patterns: List[str]) -> Optional[float]:
    """Summed durations of the events whose name matches any pattern; None
    where nothing matches (a reader then reports nothing)."""
    compiled = [re.compile(p) for p in patterns]
    matched = [end - start for name, start, end in ops if any(c.search(name) for c in compiled)]
    return sum(matched) / 1e9 if matched else None


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(devices: Dict[str, List[Event]], host: List[Event]) -> Dict:
    """What the readers and the result line need of one traced window. Busy
    time is averaged over the device planes; the tables are the first's."""
    if not devices:
        raise ValueError("the trace holds no device plane")
    names = sorted(devices)
    first = devices[names[0]]
    busy = {n: busy_intervals(devices[n]) for n in names}  # millions of events: merged once
    return {
        "busy_s": sum(b - a for n in names for a, b in busy[n]) / 1e9 / len(names),
        "ops": first,
        "device_ops": top(seconds_by_name(first)),
        "idle_gaps": top(idle_by_annotation(busy[names[0]], host)),
    }
