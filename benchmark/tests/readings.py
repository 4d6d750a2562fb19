"""Readings for the limits, on the chip at a cell's own size: the numbers the
comparison reads on sound runs, under the control's configuration and under
each planted fault, one trainer after another in one process. Training's
readings need no measured window, so each run ends after its third optimizer step.

    python benchmark/tests/readings.py --workload <cell> --out <file.jsonl> \
        sound:1,2,3 control-bf16-masters:4,5,6 half_batch:7,8,9 token_altered:10,11,12
"""

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import faults  # noqa: E402

from benchmark import compare, harness  # noqa: E402


class ReadingSession(harness.Session):
    def after_checked_steps(self):
        raise harness.WindowClosed()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("variants", nargs="+", help="<sound|control-...|fault>:<seed>,<seed>,...")
    args = parser.parse_args(argv)

    import jax

    from trlx_tpu.utils.compilation_cache import configure_compilation_cache

    configure_compilation_cache()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    harness.Session = ReadingSession
    for variant in args.variants:
        name, seeds = variant.split(":")
        for seed in map(int, seeds.split(",")):
            control = name if name.startswith("control-") else None
            cell, config = harness.load_cell(args.workload)
            if control is not None:
                config = harness.load_json("configs", f"{cell['config']}.{control}.json")
            fault = faults.FAULTS[name]() if name in faults.FAULTS else contextlib.nullcontext()
            started = time.monotonic()
            row = {"workload": args.workload, "variant": name, "seed": seed,
                   "device": jax.devices()[0].device_kind}
            try:
                with fault:
                    session = harness.run_cell(cell, config, seed, 0.0, False, started)
                harness.free_program_state(session)
                numbers, info = compare.readings(session)
                row.update(numbers=numbers, info=info)
            except Exception as e:  # a control that crashes has failed: record it and go on
                row["error"] = f"{type(e).__name__}: {e}"[:500]
            row["seconds"] = time.monotonic() - started
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
