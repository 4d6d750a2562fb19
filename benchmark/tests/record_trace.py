"""Cut a profiler trace down to a recording small enough to keep with the
tests: the planes and lines it has, the first ``--events`` device operations
with the host annotations beside them, and the device operations by total time.

    python benchmark/tests/record_trace.py <trace_dir or .xplane.pb> <out.json> [--events N]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness, trace_reduce  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace")
    parser.add_argument("out")
    parser.add_argument("--events", type=int, default=4000)
    args = parser.parse_args(argv)
    from jax.profiler import ProfileData

    path = args.trace if args.trace.endswith(".pb") else trace_reduce.find_xplane(args.trace)
    data = ProfileData.from_file(path)
    layout = {
        plane.name: {line.name: sum(1 for _ in line.events) for line in plane.lines}
        for plane in data.planes
    }
    devices, host = trace_reduce.load(path, harness.ANNOTATIONS)
    name = sorted(devices)[0]
    ops = sorted(devices[name], key=lambda e: e[1])
    kept = ops[: args.events]
    end = kept[-1][2]
    recording = {
        "source": os.path.basename(path), "layout": layout, "device_plane": name,
        "ops": kept,
        "host": [e for e in host if e[1] <= end],
        "by_name": trace_reduce.top(trace_reduce.seconds_by_name(ops), 80),
        "all_ops": len(ops), "busy_s": trace_reduce.busy_seconds(ops),
        "idle_gaps": trace_reduce.top(trace_reduce.idle_by_annotation(trace_reduce.busy_intervals(ops), host)),
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(recording, f)
    print(json.dumps({k: recording[k] for k in ("layout", "by_name", "all_ops", "busy_s", "idle_gaps")}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
