"""One run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It sets no platform and exits non-zero at once, with no result,
when jax's first device is no TPU, is of a ``device_kind`` that
``benchmark/peaks.json`` does not know, or there are fewer chips than the
cell asks for. The last line of standard output is the result's one JSON
object; the last lines of standard error are each number compared, beside its
limit.
"""

import time

T0 = time.monotonic()  # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


class Context:
    """What a reader may read."""

    def __init__(self, session, peaks, memory_peak_bytes, trace):
        self.session, self.cell, self.config, self.family = session, session.cell, session.config, session.family
        self.peaks, self.memory_peak_bytes, self.trace = peaks, memory_peak_bytes, trace
        self.notes = {}
        # per-layer metrics of a traced run are read over the traced iteration
        self.interval = session.traced if session.traced else session.window


def read_metrics(ctx, section, cell_name):
    """Every metric of ``section`` that lists the cell, through its own
    reader; a reader that finds nothing to read leaves its metric out."""
    from benchmark import harness

    out = {}
    for entry in section:
        if "workloads" in entry and cell_name not in entry["workloads"]:
            continue
        spec = harness.load_json("metrics", f"{entry['name']}.json")
        module, function = spec["reader"].rsplit(".", 1)
        reader = getattr(importlib.import_module(f"benchmark.readers.{module}"), function)
        value = reader(ctx, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def measure(cell_name, cell, config, seed, seconds, trace, device, peaks, benchmark, out_dir=None):
    """Everything of a run after the look for a chip: set-up, the window, the
    metrics, the comparison. Returns the result's object."""
    from benchmark import compare, harness, trace_reduce

    out_dir = out_dir or harness.OUT_DIR
    shutil.rmtree(out_dir, ignore_errors=True)  # an earlier run's trace and logs
    session = harness.run_cell(cell, config, seed, seconds, bool(trace), T0, out_dir)
    memory_peak = harness.peak_bytes()
    harness.free_program_state(session)

    reduced = None
    if trace:
        devices, host = trace_reduce.load(
            trace_reduce.find_xplane(session.trace_dir), harness.ANNOTATIONS)
        reduced = trace_reduce.reduce(devices, host)
    ctx = Context(session, peaks, memory_peak, reduced)
    metrics = read_metrics(ctx, benchmark["per_layer" if trace else "end_to_end"], cell_name)

    numbers, info = compare.readings(session)
    correct, compared = compare.decide(numbers, cell["limits"])
    info["not_compared"] = {k: v for k, v in numbers.items() if k not in compared}
    info.update(ctx.notes)
    info["iteration_s"] = [b - a for a, b in zip(session.marks, session.marks[1:])]

    result = {
        "correct": bool(correct and session.iterations > 0),
        "attempted": session.iterations,
        "failed": 0,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": memory_peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = ctx.interval[1] - ctx.interval[0]
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    result["info"] = info
    result["compared"] = compared  # last, as the contract asks
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    import jax

    from benchmark import compare, harness

    cell, config = harness.load_cell(args.workload)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"benchmark: jax found {device.platform!r} ({device.device_kind}), not a TPU; "
              "nothing was run", file=sys.stderr)
        return 1
    peaks = harness.load_peaks(device.device_kind)  # an unknown kind raises
    if jax.device_count() < cell["chips"]:
        print(f"benchmark: the cell asks for {cell['chips']} chips, jax has {jax.device_count()}",
              file=sys.stderr)
        return 1

    from trlx_tpu.utils.compilation_cache import configure_compilation_cache

    configure_compilation_cache()  # $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    described = {"platform": device.platform, "kind": device.device_kind, "count": jax.device_count()}
    result = measure(args.workload, cell, config, args.seed, args.seconds, args.trace,
                     described, peaks, benchmark)
    sys.stdout.flush()
    print(compare.compared_lines(result["compared"]), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
