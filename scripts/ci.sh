#!/usr/bin/env bash
# CI gate (parity: the reference's PR workflow, .github/workflows/build.yml:33-40,
# which runs flake8 + pre-commit + pytest). Run before merging/committing:
#   bash scripts/ci.sh [--slow]
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== syntax (compileall)"
python -m compileall -q trlx_tpu examples tests scripts __graft_entry__.py

echo "== lint (scripts/lint.py)"
python scripts/lint.py trlx_tpu examples tests scripts __graft_entry__.py

echo "== graftcheck (python -m trlx_tpu.analysis)"
# semantic gate: JAX RNG/tracing discipline, thread/lock discipline, and the
# SPMD program checks — collective axis names, donation hazards, mixed
# precision, PartitionSpec sanity (JX005-JX008, docs/static-analysis.md).
# One invocation covers every registered rule (including the interprocedural
# concurrency pass, CC001-CC005) over the repo-wide call graph; hard-fails on
# any finding that is neither noqa'd at the line nor justified in
# graftcheck-baseline.txt. --jobs fans per-file checks over a fork pool,
# clamped to the core count (serial on 1-core runners)
JAX_PLATFORMS=cpu python -m trlx_tpu.analysis trlx_tpu tests examples scripts __graft_entry__.py --jobs 4

echo "== graftcheck-conc gate (must fail on the seeded race)"
# the conc gate proves itself: the same command that must pass on the clean
# tree must exit 1 when TRLX_CONC_SEED_REGRESSION re-introduces the PR-8
# scheduler race in memory — a gate that cannot catch the bug it was built
# for is not a gate (mirrors TRLX_IR_SEED_REGRESSION below)
JAX_PLATFORMS=cpu python -m trlx_tpu.analysis trlx_tpu tests examples scripts __graft_entry__.py --select CC
if JAX_PLATFORMS=cpu TRLX_CONC_SEED_REGRESSION=scheduler_race \
    python -m trlx_tpu.analysis trlx_tpu tests examples scripts __graft_entry__.py --select CC > /dev/null 2>&1; then
    echo "FATAL: seeded scheduler_race regression was NOT caught by the CC gate" >&2
    exit 1
fi
echo "seeded scheduler_race correctly rejected"

echo "== tests"
if [[ "${1:-}" == "--slow" ]]; then
    # full suite; writes a TESTS report (pass/fail counts, duration, slowest
    # 10) — including failures, so it must be written even when pytest fails
    ROUND_TESTS="${TESTS_ARTIFACT:-TESTS_report.json}"
    rc=0
    python -m pytest tests/ -q --junit-xml=/tmp/trlx_junit.xml || rc=$?
    python scripts/test_report.py /tmp/trlx_junit.xml "$ROUND_TESTS"
    echo "wrote $ROUND_TESTS"
    if [[ $rc -ne 0 ]]; then exit $rc; fi
else
    python -m pytest tests/ -q -m "not slow"
fi

echo "== async rollout tests (CPU)"
# the async engine suite must pass on CPU regardless of the platform the main
# suite ran on; bounded so a queue/thread deadlock fails fast instead of hanging CI
JAX_PLATFORMS=cpu timeout -k 10 300 \
    python -m pytest tests/test_async_rollout.py -q -m "not slow" -p no:cacheprovider

echo "== observability tests (CPU)"
# spans/throughput/memory/watchdog/trackers; bounded for the same reason —
# a watchdog or tracer deadlock must fail fast, not hang CI
JAX_PLATFORMS=cpu timeout -k 10 300 \
    python -m pytest tests/test_obs.py tests/test_trackers.py -q -m "not slow" -p no:cacheprovider

echo "== analysis tests (CPU)"
# graftcheck's own suite: rule positives/negatives, noqa, baseline, CLI;
# bounded like the others so a runaway fixture scan fails fast
JAX_PLATFORMS=cpu timeout -k 10 300 \
    python -m pytest tests/test_analysis.py -q -m "not slow" -p no:cacheprovider

echo "== analysis-conc tests (CPU)"
# the concurrency analyzer's own suite: CC001-CC005 positives/negatives,
# thread-root modeling (Thread targets, escalation callbacks, closures),
# noqa/baseline round-trips, --jobs parity, the seeded-regression path
JAX_PLATFORMS=cpu timeout -k 10 300 \
    python -m pytest tests/test_analysis_conc.py -q -m "not slow" -p no:cacheprovider

echo "== analysis-ir tests (CPU)"
# graftcheck-ir's own suite: entrypoint registry, IR001-IR004 on tiny inline
# fns, budget round-trip/compare; the heavy full-model lowering tests are
# slow-marked and run only in --slow rounds
JAX_PLATFORMS=cpu timeout -k 10 300 \
    python -m pytest tests/test_analysis_ir.py -q -m "not slow" -p no:cacheprovider

echo "== graftcheck-ir budget gate (python -m trlx_tpu.analysis.ir)"
# the IR-level gate: AOT-lowers every registered hot step devicelessly and
# hard-fails when the compiled HLO's collective census or memory accounting
# deviates from graftcheck-ir-budget.json, or a new IR001-IR004 finding
# appears. An INTENDED profile change is committed by regenerating the budget:
#   python -m trlx_tpu.analysis.ir --write-budget   # then commit the diff
# (JAX_COMPILATION_CACHE_DIR makes repeat runs cheap.)
timeout -k 10 900 python -m trlx_tpu.analysis.ir

echo "== analysis-rt tests (CPU)"
# graftcheck-rt's own suite: SH001-SH004 positives/negatives (bucketing
# ladders, weak-type float fields, unstable static args, data-dependent
# shapes), noqa/baseline round-trips, watcher warmup-vs-steady attribution,
# budget exit codes; the live repo-tree scan and probe runs are slow-marked
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_analysis_rt.py -q -m "not slow" -p no:cacheprovider

echo "== graftcheck-rt compile-budget gate (python -m trlx_tpu.analysis.rt)"
# the recompile gate: executes every registered compile probe (serving steps,
# PPO/GRPO train steps, streamed scoring) on a virtual 8-device CPU mesh and
# hard-fails when warmup compiles deviate from graftcheck-rt-budget.json or
# ANY steady-state recompile appears — the steady-state budget is zero by
# construction, not a tunable. The SH static rules already ran in the
# full-rule graftcheck pass above, so this leg is probes-only. An INTENDED
# warmup change is committed by regenerating the budget:
#   python -m trlx_tpu.analysis.rt --write-budget   # then commit the diff
timeout -k 10 900 python -m trlx_tpu.analysis.rt --exec-only

echo "== rt seeded shape-churn gate (must fail on the seeded regression)"
# the rt gate proves itself the way the conc/IR gates do: the same probe
# command must exit non-zero when TRLX_RT_SEED_REGRESSION=shape_churn
# disables the streamed-scoring bucket ladder in memory, so every response
# length traces a fresh program — a zero-recompile gate that cannot catch
# shape churn is not a gate
if TRLX_RT_SEED_REGRESSION=shape_churn timeout -k 10 900 \
    python -m trlx_tpu.analysis.rt --exec-only --probe stream_score_bucket > /dev/null 2>&1; then
    echo "FATAL: seeded shape_churn regression was NOT caught by the rt compile-budget gate" >&2
    exit 1
fi
echo "seeded shape_churn correctly rejected"

echo "== resilience tests (CPU)"
# checkpoint atomicity, preemption, auto-resume, retry, chaos; the budget is
# wider than the other suites because the preemption/resume contract is proven
# on real (tiny) trainer runs, and a wedged writer thread must still fail fast
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_resilience.py -q -m "not slow" -p no:cacheprovider

echo "== self-healing tests (CPU)"
# producer supervision, health-guard escalation ladder, experience quarantine;
# budget sized for a handful of tiny end-to-end runs, and a wedged producer
# or supervisor livelock must fail fast instead of hanging CI
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_self_healing.py -q -m "not slow" -p no:cacheprovider

echo "== serving tests (CPU)"
# continuous-batching generation server: paged allocator invariants,
# scheduler slot turnover, kernel parity (XLA vs Pallas-interpret, bf16/int8),
# engine/client parity with the one-shot generate path; bounded so a wedged
# engine loop fails fast instead of hanging CI
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serving.py tests/test_paged_attention.py -q -m "not slow" -p no:cacheprovider

echo "== serving fault-tolerance tests (CPU)"
# deadlines/TTL expiry, watermark load shedding, KV-pressure preemption,
# supervised restart+replay, and the 64-request chaos soak; bounded so a
# wedged engine (the thing the suite injects on purpose) fails fast
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serving_resilience.py -q -m "not slow" -p no:cacheprovider

echo "== serving speculative-decode tests (CPU)"
# speculative decoding + chunked prefill: verify-kernel parity (q_len 1..K),
# greedy bit-parity of the spec path vs the one-shot reference (bf16/int8),
# accept accounting, anti-starvation aging, preemption replaying accepted
# draft tokens; bounded so a diverging accept loop fails fast
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serving_spec.py -q -m "not slow" -p no:cacheprovider

echo "== serving spec seeded-regression gate (accept_all must break parity)"
# the spec gate proves itself the way the conc/IR gates do: force every draft
# accepted (TRLX_SPEC_SEED_REGRESSION=accept_all bypasses the accept rule)
# and require the greedy-parity tests to FAIL — a parity harness that passes
# under unconditional acceptance is not checking the accept rule. The
# accept_all self-test inside the suite asserts the same thing inline; this
# gate asserts it end-to-end through the real pytest command.
if JAX_PLATFORMS=cpu TRLX_SPEC_SEED_REGRESSION=accept_all timeout -k 10 600 \
    python -m pytest tests/test_serving_spec.py -q -k "parity and not accept_all" \
    -p no:cacheprovider > /dev/null 2>&1; then
    echo "FATAL: seeded accept_all regression was NOT caught by the spec parity gate" >&2
    exit 1
fi
echo "seeded accept_all correctly rejected"

echo "== serving seeded-wedge gate (must recover in exactly one restart)"
# the serving gate proves itself the same way the conc gate does: arm the
# wedge chaos site from the environment and require the supervisor to detect
# the stall, restart once, and finish every request — a supervisor that
# cannot survive the fault it was built for is not a supervisor
JAX_PLATFORMS=cpu TRLX_CHAOS=serving-wedge:1 timeout -k 10 300 \
    python -m pytest tests/test_serving_resilience.py -q -k seeded_wedge -p no:cacheprovider

echo "== serving multi-tenant tests + scenario soak (CPU)"
# tenancy layer: registry/quota/class-shedding/fair-preemption units plus the
# sustained-traffic scenario soak (4 tenants, 2 SLO classes, every serving
# chaos site, >=1 supervised restart, exactly-once terminal accounting,
# per-class p99 ordering, zero quota violations)
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serving_tenants.py -q -m "not slow" -p no:cacheprovider

echo "== tenant seeded-starvation gate (starve_low_class must break fairness)"
# the fairness gate proves itself like the conc/IR/spec gates: disable aging
# for the lowest SLO class (TRLX_TENANT_SEED_REGRESSION=starve_low_class) and
# require the anti-starvation test to FAIL — a fairness suite that passes
# while the lowest class can be starved forever is not checking fairness
if JAX_PLATFORMS=cpu TRLX_TENANT_SEED_REGRESSION=starve_low_class timeout -k 10 600 \
    python -m pytest tests/test_serving_tenants.py -q -k "starved" \
    -p no:cacheprovider > /dev/null 2>&1; then
    echo "FATAL: seeded starve_low_class regression was NOT caught by the fairness gate" >&2
    exit 1
fi
echo "seeded starve_low_class correctly rejected"

echo "== stream-overlap tests (CPU)"
# stream-overlapped PPO: reorder-buffer determinism, overlap interval ledger,
# bounded score-fn bucket families, staged-learn seam units; bounded so a
# deadlocked reward pool or a stalled reorder cursor fails fast
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serving_overlap.py -q -m "not slow" -p no:cacheprovider

echo "== stream-overlap fraction proof (CPU)"
# the acceptance scenario by name: a streamed rollout on CPU must overlap
# >= 0.5 of its decode-busy time with reward/score/stage work, with score
# spans nested inside the decode span (live measurement, not a unit mock)
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serving_overlap.py -q -k "fraction and not serialize" \
    -p no:cacheprovider

echo "== overlap seeded-serialize gate (serialize must collapse the fraction)"
# the overlap gate proves itself like the conc/IR/spec gates: force serial
# in-memory consumption (TRLX_OVERLAP_SEED_REGRESSION=serialize blocks the
# decode loop on every reward) and require the overlap-fraction proof to
# FAIL — a pipeline that quietly serializes must not report overlap
if JAX_PLATFORMS=cpu TRLX_OVERLAP_SEED_REGRESSION=serialize timeout -k 10 600 \
    python -m pytest tests/test_serving_overlap.py -q -k "fraction and not serialize" \
    -p no:cacheprovider > /dev/null 2>&1; then
    echo "FATAL: seeded serialize regression was NOT caught by the overlap-fraction gate" >&2
    exit 1
fi
echo "seeded serialize correctly rejected"

echo "== island tests (CPU)"
# disaggregated islands: chunked-broadcast parity with the monolithic
# publisher, torn-version impossibility under concurrent readers,
# mid-broadcast crash + supervised-restart recovery, round-boundary atomic
# swaps (one prefix-cache flush per version), mesh carving
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_islands.py -q -m "not slow" -p no:cacheprovider

echo "== island idle-bubble proof (CPU)"
# the acceptance scenario by name: with chunked broadcasts interleaving at
# round boundaries, the generation island's measured idle-bubble fraction
# stays < 0.1 and weight shipping hides under decode (live measurement)
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_islands.py -q -k "idle_bubble_proof" \
    -p no:cacheprovider

echo "== island seeded-blocking gate (blocking broadcast must stall decode)"
# the island gate proves itself like the conc/IR/spec/overlap gates: force
# the publisher to squat on the round gate for entire broadcasts
# (TRLX_ISLAND_SEED_REGRESSION=blocking_broadcast) and require the
# idle-bubble proof to FAIL — a broadcast that quietly serializes decode
# must not report a hidden bubble
if JAX_PLATFORMS=cpu TRLX_ISLAND_SEED_REGRESSION=blocking_broadcast timeout -k 10 600 \
    python -m pytest tests/test_islands.py -q -k "idle_bubble_proof" \
    -p no:cacheprovider > /dev/null 2>&1; then
    echo "FATAL: seeded blocking_broadcast regression was NOT caught by the idle-bubble gate" >&2
    exit 1
fi
echo "seeded blocking_broadcast correctly rejected"

echo "== learner-overlap parity tests (CPU)"
# overlapped-collective FSDP learner: accum=N whole-batch parity, bitwise
# overlap-off identity to the pre-overlap program, donation aliasing, int8
# sharded optimizer tolerance, reduce-scatter-not-allreduce IR shape, and the
# committed IR006 memory comparison (docs/parallelism.md "Learner overlap &
# FSDP"); bounded like the other suites
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_learner_overlap.py -q -m "not slow" -p no:cacheprovider

echo "== learner-overlap seeded-allreduce gate (must fail the IR budget)"
# the overlap gate proves itself like the conc/spec/tenant gates: replace the
# differentiate-through-gather reduce-scatter path with a full-gradient
# all-reduce over fsdp (TRLX_IR_SEED_REGRESSION=allreduce_under_fsdp) and
# require the committed IR005 budget to REJECT the lowered step — a budget
# that accepts the bandwidth-pessimal schedule is not guarding the overlap
if TRLX_IR_SEED_REGRESSION=allreduce_under_fsdp timeout -k 10 900 \
    python -m trlx_tpu.analysis.ir --entry ppo_train_step_overlap > /dev/null 2>&1; then
    echo "FATAL: seeded allreduce_under_fsdp regression was NOT caught by the IR budget gate" >&2
    exit 1
fi
echo "seeded allreduce_under_fsdp correctly rejected"

echo "== serving fleet tests + chaos soak (CPU)"
# fleet layer: uid-block seating, prefix-affinity routing, autoscaler
# hysteresis, replica-kill re-route, N=1 parity, and the fleet acceptance
# soak (3 replicas, 4 tenants / 2 SLO classes, >=1 replica kill + >=1
# autoscale drain mid-run, exactly-once fleet-wide, p99 ordering, zero
# quota violations); bounded so a wedged replica loop fails fast
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serving_fleet.py -q -m "not slow" -p no:cacheprovider

echo "== fleet seeded-blind-router gate (blind_router must break affinity)"
# the fleet gate proves itself like the conc/spec/tenant gates: degenerate
# the router to pure least-loaded (TRLX_FLEET_SEED_REGRESSION=blind_router
# zeroes the warm-prefix and stickiness terms in memory) and require the
# affinity tests to FAIL — an affinity-hit-rate bar that a blind router can
# clear is not measuring affinity
if JAX_PLATFORMS=cpu TRLX_FLEET_SEED_REGRESSION=blind_router timeout -k 10 600 \
    python -m pytest tests/test_serving_fleet.py -q -k "affinity" \
    -p no:cacheprovider > /dev/null 2>&1; then
    echo "FATAL: seeded blind_router regression was NOT caught by the affinity gate" >&2
    exit 1
fi
echo "seeded blind_router correctly rejected"

echo "== request-flight telemetry tests (CPU)"
# flight journal: nearest-rank percentile fix, per-phase decomposition
# summing to wall latency (proved on the chaos soak with supervised
# restarts), fleet replica-kill flight continuity, series/exporter
# round-trips, windowed autoscaler, SLO burn-rate alerts
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_obs_flight.py -q -m "not slow" -p no:cacheprovider

echo "== flight seeded-regression gate (drop_terminal must break exactly-once)"
# the flight gate proves itself like the conc/spec/tenant gates: make the
# recorder silently drop terminal events (TRLX_FLIGHT_SEED_REGRESSION=
# drop_terminal) and require the exactly-once accounting test to FAIL — an
# accounting invariant a journal that loses terminals can satisfy is not
# being checked
if JAX_PLATFORMS=cpu TRLX_FLIGHT_SEED_REGRESSION=drop_terminal timeout -k 10 600 \
    python -m pytest tests/test_obs_flight.py -q -k "exactly_once" \
    -p no:cacheprovider > /dev/null 2>&1; then
    echo "FATAL: seeded drop_terminal regression was NOT caught by the exactly-once gate" >&2
    exit 1
fi
echo "seeded drop_terminal correctly rejected"

echo "== grpo + online loop tests (CPU)"
# GRPO method/trainer (group-normalized advantages, constant-group no-op,
# PPO plumbing parity) + the online label pipeline (bounded buffer,
# staleness drain, exactly-once harvest under replica-kill chaos, the
# e2e soak: harvest -> GRPO learner improves a scripted-reward policy with
# zero SLO burn). No "not slow" filter: the slow-marked acceptance soak and
# the replica-kill harvest MUST run here — tier-1 skips them for budget.
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_grpo.py tests/test_online.py -q \
    -p no:cacheprovider

echo "== online seeded-regression gate (double_harvest must break exactly-once)"
# the online gate proves itself like the flight/spec/tenant gates: disable
# the collector's uid dedup (TRLX_ONLINE_SEED_REGRESSION=double_harvest)
# and require the exactly-once harvest tests to FAIL — an exactly-once
# property a double-harvesting collector can satisfy is not being checked
if JAX_PLATFORMS=cpu TRLX_ONLINE_SEED_REGRESSION=double_harvest timeout -k 10 600 \
    python -m pytest tests/test_online.py -q -k "exactly_once and not seed_regression" \
    -p no:cacheprovider > /dev/null 2>&1; then
    echo "FATAL: seeded double_harvest regression was NOT caught by the exactly-once gate" >&2
    exit 1
fi
echo "seeded double_harvest correctly rejected"

echo "== chaos soak smoke (CPU)"
# the acceptance scenario by name: producer crashes + nan-loss + bad elements
# + reward faults in one run, every recovery visible in gauges/summary
JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_self_healing.py -q -k chaos_soak -p no:cacheprovider
echo "CI OK"
