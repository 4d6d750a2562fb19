"""Convert a pytest junit-xml run into a TESTS json report: a timed, complete
run of the full suite (pass/fail counts, duration, slowest tests).

Usage: python scripts/test_report.py <junit.xml> <TESTS_report.json>
"""

import json
import sys
import time
import xml.etree.ElementTree as ET


def main():
    xml_path, out_path = sys.argv[1], sys.argv[2]
    root = ET.parse(xml_path).getroot()
    suites = root.iter("testsuite")
    total = failed = errors = skipped = 0
    duration = 0.0
    cases = []
    failures = []
    for s in suites:
        total += int(s.get("tests", 0))
        failed += int(s.get("failures", 0))
        errors += int(s.get("errors", 0))
        skipped += int(s.get("skipped", 0))
        duration += float(s.get("time", 0.0))
        for c in s.iter("testcase"):
            name = f"{c.get('classname')}::{c.get('name')}"
            cases.append((name, float(c.get("time", 0.0))))
            for kind in ("failure", "error"):
                node = c.find(kind)
                if node is not None:
                    failures.append({"test": name, "kind": kind,
                                     "message": (node.get("message") or "")[:300]})
    cases.sort(key=lambda x: -x[1])
    report = {
        "total": total,
        "passed": total - failed - errors - skipped,
        "failed": failed,
        "errors": errors,
        "skipped": skipped,
        "duration_s": round(duration, 1),
        "slowest_10": [{"test": n, "s": round(t, 1)} for n, t in cases[:10]],
        "failures": failures,
        "created_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: report[k] for k in
                      ("total", "passed", "failed", "errors", "skipped", "duration_s")}))


if __name__ == "__main__":
    main()
