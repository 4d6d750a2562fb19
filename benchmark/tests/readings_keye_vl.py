"""``readings.py`` with the planted faults of learned sparse attention
(``faults_keye_vl.py``) beside the harness's own; the same arguments. Each row's
``info`` also carries the learner's last ``dsa/`` and ``moe/`` counters (the
pairs kept, the queries that kept other than ``min(topk, allowed)``, the held
experts' loads).

    python benchmark/tests/readings_keye_vl.py --workload keye-vl-2.0-30b-a3b.ppo-long-prompt-8k \
        --out <file.jsonl> sound:1,2 control-bf16-masters:3 selection_ignored:4 selection_last_positions:5
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import faults_keye_vl  # noqa: E402
import readings  # noqa: E402

from benchmark import compare  # noqa: E402

faults.FAULTS.update(faults_keye_vl.FAULTS)
_readings = compare.readings


def _with_counters(session):
    from trlx_tpu.utils.metrics import gauges

    numbers, info = _readings(session)
    info["counters"] = {**gauges.snapshot("dsa/"), **gauges.snapshot("moe/")}
    return numbers, info


compare.readings = _with_counters

if __name__ == "__main__":
    sys.exit(readings.main())
