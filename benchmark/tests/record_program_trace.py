"""Cut a traced run's profile down to what ``readers/program_trace.py`` reads,
small enough to keep with the tests: every module event of the first device,
every ``while`` op event, the program's host spans, and the ``--events`` op
events around the boundary between the iteration's first two train steps (the
gap a learner's host leaves). Beside them, the same quantities over the whole
trace, and the stats one op event and one module event carry.

    python benchmark/tests/record_program_trace.py <trace_dir or .xplane.pb> <out.json> [--events N]
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.readers import program_trace  # noqa: E402


def event_stats(path: str):
    """{line name: the stats of its first event} on the first device plane."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not re.match(trace_reduce.DEVICE_PLANE, plane.name):
            continue
        for line in plane.lines:
            for event in line.events:
                out[line.name] = {"name": event.name[:200], **{k: str(v)[:200] for k, v in event.stats}}
                break
        return out
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace")
    parser.add_argument("out")
    parser.add_argument("--events", type=int, default=3000)
    args = parser.parse_args(argv)

    path = args.trace if args.trace.endswith(".pb") else trace_reduce.find_xplane(args.trace)
    specs = {name: harness.load_json("metrics", f"{name}.json")["args"]["program"]
             for name in ("rollout_device_pct", "score_device_pct", "learn_device_pct")}
    modules, spans = program_trace._load(path)
    devices, harness_spans = trace_reduce.load(path, harness.ANNOTATIONS)
    ops = sorted(devices[sorted(devices)[0]], key=lambda e: e[1])
    steps = program_trace.matching(modules, specs["learn_device_pct"])
    calls = program_trace.matching(modules, specs["rollout_device_pct"])
    whiles = [e for e in ops if e[0].endswith(" while")]
    recording = {
        "source": os.path.basename(path), "modules": modules, "spans": spans, "whiles": whiles,
        "event_stats": event_stats(path), "all_ops": len(ops),
        "harness_spans": {name: sum(1 for n, _, _ in harness_spans if n == name) for name in harness.ANNOTATIONS},
    }
    if len(steps) >= 2:
        # the last half of --events before the first step's end, the first half after the second's start
        at_end = max(i for i, e in enumerate(ops) if e[2] <= steps[0][2])
        at_start = next(i for i, e in enumerate(ops) if e[1] >= steps[1][1])
        half = args.events // 2
        recording["ops"] = ops[max(0, at_end - half):at_end + 1] + ops[at_start:at_start + half]
        busy = program_trace.busy_between(ops, steps[0][1], steps[-1][2])
        recording["whole"] = {
            "device_s": {name: sum(t - s for _, s, t in program_trace.matching(modules, p)) / 1e9
                         for name, p in specs.items()},
            "outermost_while_s": sum(t - s for _, s, t in program_trace.outermost_whiles(ops, calls)) / 1e9,
            "generate_calls": len(calls), "train_steps": len(steps),
            "learn_idle_s": sum(b[0] - a[1] for a, b in zip(busy, busy[1:])) / 1e9,
            "learn_idle_s_by_span": program_trace.idle_by_span(busy, spans),
        }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(recording, f)
    print(json.dumps({k: recording[k] for k in recording if k not in ("ops", "whiles", "spans")}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
