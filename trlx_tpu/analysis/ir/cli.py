"""graftcheck-ir command line.

Usage::

    python -m trlx_tpu.analysis.ir [options]

Options:
    --entry A,B          audit only the named entrypoints (default: all)
    --spec NAME          spec to audit at (default: small)
    --budget FILE        budget file (default: graftcheck-ir-budget.json)
    --write-budget       regenerate the budget from fresh measurements and
                         exit 0 (the escape hatch; commit the diff)
    --baseline FILE      finding baseline (default: graftcheck-baseline.txt,
                         shared with the AST graftcheck)
    --no-baseline        ignore the baseline
    --list-entrypoints   print the registry and exit
    --json FILE          also dump measurements + findings as JSON

Exit status: 1 on any new IR001–IR004 finding or any IR005/IR006 budget
deviation, else 0 — the contract the ``analysis-ir`` section of
``scripts/ci.sh`` gates on. Runs devicelessly: ``__main__`` forces a virtual
CPU platform (``TRLX_IR_DEVICES``, default 8) before jax is imported, and the
persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``) makes repeat
runs cheap.
"""

import argparse
import json
import sys
from pathlib import Path

from trlx_tpu.analysis import baseline as baseline_mod
from trlx_tpu.analysis.cli import DEFAULT_BASELINE
from trlx_tpu.analysis.core import load_context
from trlx_tpu.analysis.ir import budget as budget_mod
from trlx_tpu.analysis.ir.entrypoints import load_all
from trlx_tpu.analysis.ir.lowering import lower_entry, measure
from trlx_tpu.analysis.ir.rules_ir import audit_entry


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m trlx_tpu.analysis.ir",
        description="graftcheck-ir: deviceless jaxpr/HLO audit of compiled hot steps",
    )
    parser.add_argument("--entry", default=None, help="comma-separated entrypoint names")
    parser.add_argument("--spec", default="small")
    parser.add_argument("--budget", default=budget_mod.DEFAULT_BUDGET)
    parser.add_argument("--write-budget", action="store_true")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE)
    parser.add_argument("--no-baseline", action="store_true")
    parser.add_argument("--list-entrypoints", action="store_true")
    parser.add_argument("--json", default=None)
    args = parser.parse_args(argv)

    # repeat audits (and the trainer itself) share one on-disk compile cache;
    # must run before the first compile of the process to take effect
    from trlx_tpu.utils.compilation_cache import configure_compilation_cache

    configure_compilation_cache()

    entries = load_all()
    if args.list_entrypoints:
        for name in sorted(entries):
            ep = entries[name]
            print(f"{name}  specs={','.join(ep.specs)}  {ep.rel_path()}:{ep.lineno}")
        return 0

    names = sorted(entries)
    if args.entry:
        names = [n.strip() for n in args.entry.split(",") if n.strip()]
        unknown = [n for n in names if n not in entries]
        if unknown:
            print(f"graftcheck-ir: unknown entrypoint(s) {unknown}", file=sys.stderr)
            return 2

    measurements = {}
    findings = []
    for name in names:
        ep = entries[name]
        if args.spec not in ep.specs:
            print(f"graftcheck-ir: {name} has no spec {args.spec!r}; skipping")
            continue
        print(f"graftcheck-ir: lowering {name}@{args.spec} "
              f"(mesh {ep.mesh_shape}, deviceless)...")
        lowered = lower_entry(ep, spec=args.spec)
        ctx = None
        src = Path(ep.rel_path())
        if src.exists():  # noqa suppression needs the registration-site file
            ctx = load_context(src, rel=ep.rel_path())
        findings.extend(audit_entry(lowered, ctx))
        measurements[lowered.key] = measure(lowered)

    if args.write_budget:
        n = budget_mod.write(args.budget, measurements)
        print(f"graftcheck-ir: wrote {n} budget entr{'y' if n == 1 else 'ies'} "
              f"to {args.budget}")
        return 0

    base = baseline_mod.load("/dev/null" if args.no_baseline else args.baseline)
    new, _stale = baseline_mod.compare(findings, base)
    violations, notes = budget_mod.compare(measurements, budget_mod.load(args.budget))

    for f in new:
        print(f)
    for v in violations:
        print(f"graftcheck-ir: BUDGET {v}")
    for n in notes:
        print(f"graftcheck-ir: note: {n}")
    if args.json:
        Path(args.json).write_text(json.dumps({
            "measurements": measurements,
            "findings": [str(f) for f in findings],
            "violations": violations,
            "notes": notes,
        }, indent=1) + "\n")
    print(
        f"graftcheck-ir: {len(measurements)} entrypoint(s) audited, "
        f"{len(findings)} finding(s) ({len(new)} new), "
        f"{len(violations)} budget violation(s)"
    )
    return 1 if (new or violations) else 0


if __name__ == "__main__":
    sys.exit(main())
