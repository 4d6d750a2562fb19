"""Request-flight telemetry tests (docs/observability.md "Request flights"):
the nearest-rank percentile fix with exact small-n cases, per-uid flight
journaling with the phase-sum-equals-wall-latency invariant (proved on a real
engine and again under the 4-tenant/2-class chaos soak with supervised
restarts), exactly-once terminal flight accounting (the CI seeded-regression
gate re-runs that test under ``TRLX_FLIGHT_SEED_REGRESSION=drop_terminal``
and requires it to FAIL), fleet replica-kill flight continuity (a kill is a
``re_route`` inside the same flight, never a fork), the SeriesStore windowed
reductions, atomic JSONL + Prometheus exporter round-trips, the windowed
autoscaler (blip-proof at window>1, bit-identical at window=1), fleet SLO
burn-rate alerts, export/adopt flight continuity, the disabled no-op
contract, and the Observability runtime wiring (flight gauges + series
sampling + exporters on close)."""

import glob
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.fleet import FleetAutoscaler, FleetRouter
from trlx_tpu.fleet.ledger import SLO_BAD_KEY, FleetLedger
from trlx_tpu.models.presets import PRESETS
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.obs import (
    SeriesStore,
    read_jsonl_series,
    read_prometheus,
    write_jsonl_series,
    write_prometheus,
)
from trlx_tpu.obs.flight import (
    TERMINAL_EVENTS,
    FlightRecorder,
    flight,
)
from trlx_tpu.obs.spans import SpanTracer
from trlx_tpu.resilience.chaos import chaos
from trlx_tpu.serving import (
    ServingEngine,
    ServingResiliencePolicy,
    TenantRegistry,
    TenantTraffic,
    run_scenario,
)
from trlx_tpu.serving.scheduler import FINISH_LENGTH
from trlx_tpu.utils.metrics import gauges, nearest_rank

pytestmark = [pytest.mark.obs, pytest.mark.obs_flight]

TINY = dict(
    vocab_size=37, hidden_size=16, num_layers=2, num_heads=2,
    max_position_embeddings=64, compute_dtype=jnp.float32,
)

#: phase-sum vs wall-latency tolerance: both sides are sums of the same
#: clock readings, so only float addition error separates them
EPS = 1e-6


@pytest.fixture(autouse=True)
def _clean_state():
    """Every test starts with a fresh (enabled) global recorder and ends with
    it disabled, chaos disarmed, and the gauge registry clean."""
    flight.reset()
    flight.configure(enabled=True)
    yield
    flight.configure(enabled=False)
    flight.reset()
    chaos.configure(None)
    gauges.clear()


@pytest.fixture(scope="module")
def tiny_engine_parts():
    config = PRESETS["gpt2"].replace(**TINY)
    model = TransformerLM(config)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    return model, params, config


def _make_engine(parts, **kw):
    model, params, _ = parts
    kw.setdefault("num_slots", 3)
    kw.setdefault("num_blocks", 0)
    kw.setdefault("max_seq_len", 32)
    return ServingEngine(
        model, params, block_size=4, eos_token_id=None, pad_token_id=0,
        gen_kwargs=dict(do_sample=False), seed=0, **kw,
    )


def _terminal_count(fl) -> int:
    return sum(fl.counts.get(e, 0) for e in TERMINAL_EVENTS)


# ---------------------------------------------------------- S1 nearest-rank


def test_nearest_rank_small_n_exact():
    """The old ``int(q*n)`` indexing sat one rank too high; nearest-rank is
    ``ceil(q*n)`` (1-indexed). The n=2 median is the SMALLER element."""
    assert nearest_rank([1.0, 2.0], 0.5) == 1.0  # int(0.5*2)=1 gave 2.0
    assert nearest_rank([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
    assert nearest_rank([5.0], 0.99) == 5.0
    xs = [float(v) for v in range(1, 101)]  # 1..100 sorted
    assert nearest_rank(xs, 0.99) == 99.0  # int(0.99*100)=99 gave 100.0
    assert nearest_rank(xs, 0.50) == 50.0
    assert nearest_rank(xs, 1.0) == 100.0
    assert nearest_rank(xs, 0.0) == 1.0  # clamped to the first rank


def test_ledger_p99_uses_nearest_rank():
    from trlx_tpu.fleet.ledger import _nearest_rank_p99

    assert _nearest_rank_p99([]) == 0.0
    assert _nearest_rank_p99([3.0, 1.0, 2.0]) == 3.0
    xs = [float(v) for v in range(1, 101)]
    assert _nearest_rank_p99(xs) == 99.0


# --------------------------------------------------------- S2 span counts


def test_span_drain_emits_call_counts():
    tracer = SpanTracer(enabled=True)
    for _ in range(3):
        with tracer.span("reward"):
            pass
    times = tracer.drain_step_times()
    assert times["time/span/reward_n"] == 3.0
    assert times["time/span/reward"] >= 0.0
    assert tracer.drain_step_times() == {}  # counts drained with the times


# ------------------------------------------------------- recorder mechanics


def test_flight_disabled_is_a_no_op():
    rec = FlightRecorder(enabled=False)
    rec.record(1, "submit", t=0.0, tenant_id="a", slo_class=1)
    rec.record(1, "finish", t=1.0)
    assert rec.get(1) is None and rec.completed() == []
    assert rec.export_flights([1]) == {}


def test_flight_phase_state_machine():
    rec = FlightRecorder(enabled=True)
    rec.record(7, "submit", t=0.0, tenant_id="pro", slo_class=1)
    rec.record(7, "admit", t=1.0)          # queue_wait += 1
    rec.record(7, "prefill_chunk", t=1.5)  # prefill += 0.5 (stays prefill)
    rec.record(7, "decode_round", t=2.0)   # prefill += 0.5
    rec.record(7, "preempt", t=3.0)        # decode += 1
    rec.record(7, "admit", t=4.0)          # preempt_replay += 1 (replay tax)
    rec.record(7, "decode_round", t=5.0)   # preempt_replay += 1 (until decode resumes)
    rec.record(7, "finish", t=6.0, reason="length")  # decode += 1
    rec.record(7, "reward_dispatch", t=7.0)  # store_wait += 1
    rec.record(7, "reward_done", t=9.0)      # reward += 2
    rec.record(7, "store", t=10.0)           # store_wait += 1

    fl = rec.get(7)
    assert fl.phases == {
        "queue_wait": 1.0, "prefill": 1.0, "decode": 2.0,
        "preempt_replay": 2.0, "reward": 2.0, "store_wait": 2.0,
    }
    assert fl.engine_wall_s == 6.0
    assert fl.engine_phase_sum() == pytest.approx(6.0, abs=EPS)
    assert _terminal_count(fl) == 1 and fl.terminal_reason == "length"
    assert fl.closed
    assert [fl] == rec.completed()


def test_flight_ring_eviction_bounds_memory():
    rec = FlightRecorder(enabled=True, ring=2)
    for uid in range(4):
        rec.record(uid, "submit", t=float(uid))
        rec.record(uid, "finish", t=uid + 1.0)
    assert len(rec.completed()) == 2
    assert rec.get(0) is None and rec.get(1) is None  # evicted uid index too
    assert rec.get(3) is not None


def test_flight_export_adopt_continues_same_flight():
    """The snapshot seam: a cross-process adopter rebuilds the flight with
    phases/counts intact, and the terminal lands on the adopted flight —
    one flight, one terminal, continuous arithmetic."""
    rec = FlightRecorder(enabled=True)
    rec.record(3, "submit", t=0.0, tenant_id="t", slo_class=1)
    rec.record(3, "admit", t=2.0)
    snaps = rec.export_flights([3])
    assert snaps[3]["phases"]["queue_wait"] == 2.0

    adopter = FlightRecorder(enabled=True)
    adopter.adopt_flights(snaps, t=5.0, seat=1)
    fl = adopter.get(3)
    assert fl.counts.get("adopt") == 1 and fl.seats == [1]
    adopter.record(3, "decode_round", t=6.0)
    adopter.record(3, "finish", t=7.0, reason="eos")
    assert _terminal_count(fl) == 1
    assert fl.engine_wall_s == 7.0
    assert fl.engine_phase_sum() == pytest.approx(7.0, abs=EPS)
    assert fl.phases["queue_wait"] == 2.0  # exported history survived


def test_flight_seed_regression_env_validated(monkeypatch):
    monkeypatch.setenv("TRLX_FLIGHT_SEED_REGRESSION", "bogus")
    flight.record(1, "submit", t=0.0)
    with pytest.raises(ValueError, match="TRLX_FLIGHT_SEED_REGRESSION"):
        flight.record(1, "finish", t=1.0)


def test_flight_trace_events_are_balanced_async_lanes():
    rec = FlightRecorder(enabled=True)
    rec.record(1, "submit", t=0.0, tenant_id="a", slo_class=0)
    rec.record(1, "admit", t=1.0)
    rec.record(1, "finish", t=2.0)
    events = rec.trace_events(epoch=0.0)
    assert events and all(ev["cat"] == "flight" for ev in events)
    assert all(ev["id"] == 1 for ev in events)
    begins = [ev for ev in events if ev["ph"] == "b"]
    ends = [ev for ev in events if ev["ph"] == "e"]
    assert len(begins) == len(ends)
    # the enclosing per-uid lane spans submit -> last event
    lane = [ev for ev in events if ev["name"] == "flight uid=1"]
    assert lane[0]["ts"] == 0.0 and lane[-1]["ts"] == pytest.approx(2e6)
    # merges into a SpanTracer under its event bound
    tracer = SpanTracer(enabled=True, trace_path="unused.json", max_events=3)
    tracer.add_events(events)
    assert len(tracer.snapshot_events()) == 3
    assert tracer._dropped_events == len(events) - 3


# ------------------------------------------------- engine phase decomposition


def test_engine_flights_phase_sum_equals_wall_latency(tiny_engine_parts):
    """Real engine, no chaos: every finished request's flight phases sum to
    its measured wall latency, and per-phase gauges export."""
    eng = _make_engine(tiny_engine_parts)
    rng = np.random.default_rng(0)
    uids = [
        eng.submit(rng.integers(1, 37, size=n).tolist(), 4)
        for n in (4, 6, 5, 8, 3)
    ]
    done = eng.run(uids)
    for uid in uids:
        fl = flight.get(uid)
        assert fl is not None and _terminal_count(fl) == 1
        assert fl.engine_wall_s == pytest.approx(done[uid].latency_s, abs=EPS)
        assert fl.engine_phase_sum() == pytest.approx(fl.engine_wall_s, abs=EPS)
        assert fl.counts.get("decode_round", 0) >= 1
    flight.export_gauges()
    snap = gauges.snapshot("obs/flight/")
    assert snap["obs/flight/completed"] == float(len(uids))
    assert any(k.endswith("/decode_p99") for k in snap)
    flight.clear_gauges()
    eng.close()


# ------------------------------------------------------ S3 chaos soak proofs


def _soak_registry():
    reg = TenantRegistry(class_ttl_s={0: 8.0, 1: 16.0})
    reg.register("free1", slo_class=0, kv_block_quota=6)
    reg.register("free2", slo_class=0, kv_block_quota=6)
    reg.register("pro1", slo_class=1)
    reg.register("pro2", slo_class=1)
    return reg


def _soak_traffic():
    return [
        TenantTraffic("free1", num_requests=12, arrivals_per_round=2.0,
                      prompt_len=(4, 10), max_new=(4, 8), vocab=37),
        TenantTraffic("free2", num_requests=12, arrivals_per_round=2.0,
                      prompt_len=(4, 10), max_new=(4, 8), vocab=37),
        TenantTraffic("pro1", num_requests=6, arrivals_per_round=0.5,
                      prompt_len=(4, 10), max_new=(4, 8), vocab=37,
                      shared_prefix=4),
        TenantTraffic("pro2", num_requests=6, arrivals_per_round=0.5,
                      prompt_len=(6, 12), max_new=(4, 8), vocab=37),
    ]


def test_flight_exactly_once_terminal_under_chaos_soak(tiny_engine_parts, tmp_path):
    """The acceptance proof: 4 tenants / 2 SLO classes under every serving
    chaos site with >=1 supervised restart — every accepted uid's flight
    records EXACTLY one terminal event, the flight's terminal reason matches
    the scheduler's, and the per-phase decomposition sums to the request's
    wall latency. scripts/ci.sh re-runs this test under
    ``TRLX_FLIGHT_SEED_REGRESSION=drop_terminal`` and requires it to fail."""
    model, params, _ = tiny_engine_parts
    reg = _soak_registry()
    policy = ServingResiliencePolicy(max_pending=8, high_watermark=0.75,
                                     low_watermark=0.5, preemption=True)

    def factory():
        return ServingEngine(
            model, params, num_slots=3, max_seq_len=32, block_size=4,
            num_blocks=20, eos_token_id=None, pad_token_id=0,
            gen_kwargs=dict(do_sample=False), seed=0, policy=policy,
            prefix_caching=True, tenants=reg,
        )

    report = run_scenario(
        factory, reg, _soak_traffic(),
        chaos_spec="serving-prefill:1,serving-decode:1,serving-alloc:2,serving-wedge:1",
        dt_s=0.05, max_rounds=400, seed=0, wedge_timeout_s=0.25,
        diagnostics_dir=str(tmp_path),
    )
    assert report.restarts >= 1, "chaos never forced a supervised restart"
    accepted = report.submitted - report.rejected
    assert len(report.terminal) == accepted and accepted >= 30
    replayed = 0
    for uid, reason in report.terminal.items():
        fl = flight.get(uid)
        assert fl is not None, f"uid {uid} left no flight"
        n_term = _terminal_count(fl)
        assert n_term == 1, (
            f"uid {uid} recorded {n_term} terminal flight events "
            f"(scheduler says {reason!r})"
        )
        assert fl.terminal_reason == reason
        req = report.requests[uid]
        assert fl.engine_wall_s == pytest.approx(req.latency_s, abs=EPS)
        assert fl.engine_phase_sum() == pytest.approx(
            fl.engine_wall_s, abs=EPS
        ), f"uid {uid}: phases {fl.phases} do not sum to wall {fl.engine_wall_s}"
        assert fl.tenant_id == req.tenant_id and fl.slo_class == req.slo_class
        replayed += fl.counts.get("re_route", 0)
    # the supervised restarts re-routed at least one in-flight request, and
    # that replay tax is visible in the decomposition
    assert replayed >= 1
    assert len(flight.completed()) == accepted


def test_fleet_replica_kill_keeps_flight_continuity(tiny_engine_parts, tmp_path):
    """A chaos replica kill must read as a ``re_route`` INSIDE the same
    flight (seat recorded, one terminal event), never as a second flight."""
    def factory(seat):
        return _make_engine(tiny_engine_parts, num_slots=2)

    router = FleetRouter(
        factory, 2, wedge_timeout_s=None, backoff_base_s=0.01,
        diagnostics_dir=str(tmp_path),
    )
    try:
        uids = [router.submit([i + 1, i + 2, i + 3], 4) for i in range(6)]
        assert {router.replica_of(u) for u in uids} == {0, 1}
        router.step()  # decode at least one token so replay carries state
        chaos.configure("fleet-replica-kill:1")
        done = router.run(uids)
        assert set(done) == set(uids)
        survivor = router._active_handles()[0].seat
        rerouted = 0
        for uid in uids:
            fl = flight.get(uid)
            assert fl is not None and _terminal_count(fl) == 1
            assert fl.terminal_reason == FINISH_LENGTH
            assert fl.engine_phase_sum() == pytest.approx(
                fl.engine_wall_s, abs=EPS
            )
            if fl.counts.get("re_route", 0):
                rerouted += 1
                assert fl.counts.get("adopt", 0) >= 1
                assert fl.seats and fl.seats[-1] == survivor
        assert rerouted >= 1, "the kill re-routed no flight"
        # continuity: 6 submits -> exactly 6 completed flights, no forks
        assert len(flight.completed()) == 6
    finally:
        router.close()


# ------------------------------------------------------------- series store


def test_series_store_windowed_stats_and_reduce():
    ss = SeriesStore(capacity=4)
    for i in range(6):
        ss.append("k", float(i), t=float(i))
    assert ss.window("k") == [2.0, 3.0, 4.0, 5.0]  # retention cap bites
    assert ss.window("k", 2) == [4.0, 5.0]
    st = ss.stats("k", window=3)
    assert st["n"] == 3.0 and st["min"] == 3.0 and st["max"] == 5.0
    assert st["mean"] == pytest.approx(4.0) and st["p50"] == 4.0
    assert ss.reduce("k", "min", 2) == 4.0
    assert ss.reduce("k", "sum") == 14.0
    assert ss.reduce("missing", "mean", default=7.0) == 7.0
    assert ss.stats("missing") == {}
    with pytest.raises(ValueError, match="unknown reduction"):
        ss.reduce("k", "median")
    with pytest.raises(ValueError, match="capacity"):
        SeriesStore(capacity=0)


def test_series_store_samples_registry():
    gauges.set("obs/test/x", 1.0)
    ss = SeriesStore(capacity=8)
    assert ss.sample("obs/test/") == 1
    gauges.set("obs/test/x", 2.0)
    ss.sample("obs/test/")
    assert ss.window("obs/test/x") == [1.0, 2.0]
    assert ss.sample_rounds == 2
    ss.clear("obs/test/")
    assert ss.keys() == []


# ---------------------------------------------------------------- exporters


def test_jsonl_series_round_trip_is_exact(tmp_path):
    ss = SeriesStore(capacity=8)
    ss.append("a/b", 1.5, t=0.25)
    ss.append("a/b", -2.0, t=0.5)
    ss.append("c", 0.0, t=1.0)
    path = str(tmp_path / "series.jsonl")
    write_jsonl_series(ss, path)
    back = read_jsonl_series(path)
    assert back == {"a/b": [(0.25, 1.5), (0.5, -2.0)], "c": [(1.0, 0.0)]}
    # atomic: no temp files left behind
    assert sorted(os.listdir(tmp_path)) == ["series.jsonl"]


def test_prometheus_round_trip_with_escaping(tmp_path):
    values = {"fleet/alert/fast_burn": 2.5, 'odd"key\\n': 1.0, "x": -0.125}
    path = str(tmp_path / "metrics.prom")
    write_prometheus(path, values=values)
    text = open(path).read()
    assert "# TYPE trlx_gauge gauge" in text
    assert read_prometheus(path) == values
    assert glob.glob(str(tmp_path / "*.tmp*")) == []


# ------------------------------------------------------- windowed autoscaler


def test_autoscaler_window_smooths_one_round_blip(tiny_engine_parts, tmp_path):
    """With ``window_rounds=2`` a single hot round between idle rounds can
    never count as a breach (min over the window stays 0), while sustained
    pressure still scales; ``window_rounds`` is validated."""
    def factory(seat):
        return _make_engine(tiny_engine_parts, num_slots=2)

    router = FleetRouter(
        factory, 1, wedge_timeout_s=None, backoff_base_s=0.01,
        diagnostics_dir=str(tmp_path),
    )
    scaler = FleetAutoscaler(
        router, min_replicas=1, max_replicas=2,
        scale_up_pending_per_slot=1.0, breach_rounds=1, cooldown_rounds=0,
        window_rounds=2,
    )
    try:
        with pytest.raises(ValueError, match="window_rounds"):
            FleetAutoscaler(router, window_rounds=0)
        # the gauges are the autoscaler's only input: drive them directly
        def observe(pending):
            gauges.set("serving/replica/0/pending_depth", float(pending))
            gauges.set("serving/replica/0/live_slots", 2.0)
            scaler.observe()

        observe(0)
        observe(10)  # blip: window [0, 10] -> min 0, no breach
        observe(0)
        assert scaler.events == [] and router.num_replicas == 1
        observe(10)
        observe(10)  # sustained: window [10, 10] -> min 10, breach
        assert [a for _, a in scaler.events] == ["up"]
        assert router.num_replicas == 2
        # the series kept the fleet aggregates for post-hoc inspection
        assert scaler.series.window("fleet/series/pending_per_slot")[-1] == 5.0
    finally:
        router.close()


# ------------------------------------------------------- SLO burn-rate alerts


def _terminal(reason, slo_class=0, tenant="t", latency=0.1):
    return types.SimpleNamespace(
        finish_reason=reason, slo_class=slo_class, tenant_id=tenant,
        latency_s=latency,
    )


def test_ledger_burn_rate_alerts_fire_and_clear():
    led = FleetLedger(slo_target=0.9, fast_window=4, slow_window=8,
                      burn_threshold=1.0)
    for _ in range(8):
        led.record(_terminal("length"))
    burn = led.burn_rates()
    assert burn == {"fast_burn": 0.0, "slow_burn": 0.0, "firing": 0.0}
    # 4 consecutive sheds: fast window all-bad (burn 1/0.1 = 10), slow
    # window half-bad (burn 5) -> both over threshold -> firing
    for _ in range(4):
        led.record(_terminal("shed"))
    burn = led.burn_rates()
    assert burn["fast_burn"] == pytest.approx(10.0)
    assert burn["slow_burn"] == pytest.approx(5.0)
    assert burn["firing"] == 1.0
    led.export_gauges(replicas=1, pending_depth=0, restarts=0)
    assert gauges.get("fleet/alert/fast_burn") == pytest.approx(10.0)
    assert gauges.get("fleet/alert/firing") == 1.0
    led.close()
    assert gauges.snapshot("fleet/") == {}
    # recovery: good outcomes push the fast window under threshold -> clears
    for _ in range(4):
        led.record(_terminal("eos"))
    assert led.burn_rates()["firing"] == 0.0
    assert led.series.window(SLO_BAD_KEY, 4) == [0.0] * 4


def test_ledger_burn_rate_validates_params():
    with pytest.raises(ValueError, match="slo_target"):
        FleetLedger(slo_target=1.0)
    with pytest.raises(ValueError, match="fast_window"):
        FleetLedger(fast_window=8, slow_window=4)


def test_ledger_fast_slow_window_asymmetry():
    """A brief blip trips the fast window but not the slow one — the
    multi-window guard: no alert fires."""
    led = FleetLedger(slo_target=0.9, fast_window=2, slow_window=64,
                      burn_threshold=1.0)
    for _ in range(62):
        led.record(_terminal("eos"))
    led.record(_terminal("shed"))
    led.record(_terminal("shed"))
    burn = led.burn_rates()
    assert burn["fast_burn"] == pytest.approx(10.0)  # fast window all-bad
    assert burn["slow_burn"] < 1.0  # 2/64 bad, well inside budget
    assert burn["firing"] == 0.0


# --------------------------------------------------------- runtime wiring


def test_observability_runtime_wires_flight_series_and_exporters(tmp_path):
    from trlx_tpu.data.configs import ObservabilityConfig
    from trlx_tpu.obs import Observability

    cfg = ObservabilityConfig(
        enabled=True, trace_path=str(tmp_path / "trace.json"),
        mfu=False, memory_interval=0,
        flight=True, series_capacity=16,
        series_path=str(tmp_path / "series.jsonl"),
        prom_path=str(tmp_path / "metrics.prom"),
    )
    obs = Observability(cfg)
    assert flight.enabled
    flight.record(1, "submit", t=0.0, tenant_id="a", slo_class=0)
    flight.record(1, "finish", t=1.0)
    gauges.set("obs/test/y", 3.0)
    stats = obs.step_stats(tokens=10, samples=1)
    assert stats["obs/test/y"] == 3.0
    assert stats["obs/flight/completed"] == 1.0
    assert obs.series.sample_rounds == 1
    obs.close()
    assert not flight.enabled
    back = read_jsonl_series(str(tmp_path / "series.jsonl"))
    assert back["obs/test/y"][-1][1] == 3.0
    prom = read_prometheus(str(tmp_path / "metrics.prom"))
    assert prom["obs/flight/completed"] == 1.0
    # the flight lane rode into the Chrome trace as async events
    import json

    doc = json.load(open(tmp_path / "trace.json"))
    assert any(ev.get("cat") == "flight" for ev in doc["traceEvents"])


def test_observability_off_leaves_flight_disabled():
    from trlx_tpu.data.configs import ObservabilityConfig
    from trlx_tpu.obs import Observability

    flight.configure(enabled=False)
    obs = Observability(ObservabilityConfig(enabled=False))
    assert not flight.enabled and obs.series is None
    assert obs.step_stats(tokens=1, samples=1) == {}
    obs.close()
