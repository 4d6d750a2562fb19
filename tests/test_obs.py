"""Observability layer tests (trlx_tpu/obs; docs/observability.md).

CPU-only and fast: span tracer (nesting, threads, trace.json), histogram
percentiles, MFU arithmetic against hand-computed FLOPs, memory gauges,
watchdog firing on a deliberately-stalled fake producer. The full obs-enabled
tiny training run is marked ``slow``.
"""

import json
import logging as py_logging
import os
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from trlx_tpu.obs import (
    Observability,
    SpanTracer,
    StallWatchdog,
    ThroughputAccountant,
    batch_token_count,
    detect_peak_tflops,
    device_memory_stats,
    param_count,
    transformer_flops_per_token,
)
from trlx_tpu.obs import watchdog as global_watchdog
from trlx_tpu.utils.metrics import GaugeRegistry

pytestmark = pytest.mark.obs


@pytest.fixture
def trlx_caplog(caplog):
    """The library root logger has propagate=False: attach caplog's handler
    directly so warnings (stall dumps) are capturable."""
    lib_logger = py_logging.getLogger("trlx_tpu")
    lib_logger.addHandler(caplog.handler)
    try:
        yield caplog
    finally:
        lib_logger.removeHandler(caplog.handler)


# ------------------------------------------------------------------- spans


def test_span_nesting_builds_dotted_paths():
    tracer = SpanTracer(enabled=True)
    with tracer.span("rollout"):
        with tracer.span("generate"):
            pass
        with tracer.span("score"):
            pass
    with tracer.span("learn"):
        pass
    times = tracer.drain_step_times()
    paths = {
        "time/span/rollout",
        "time/span/rollout.generate",
        "time/span/rollout.score",
        "time/span/learn",
    }
    # every path drains its seconds plus a _n call count (per-call latency
    # is seconds / n downstream)
    assert set(times) == paths | {f"{p}_n" for p in paths}
    assert all(v >= 0.0 for v in times.values())
    assert all(times[f"{p}_n"] == 1.0 for p in paths)
    # outer span includes its children
    assert times["time/span/rollout"] >= times["time/span/rollout.generate"]
    # drained: a second drain is empty
    assert tracer.drain_step_times() == {}


def test_span_nesting_across_threads():
    tracer = SpanTracer(enabled=True)

    def worker():
        with tracer.span("generate"):
            time.sleep(0.01)

    with tracer.span("learn"):
        t = threading.Thread(target=worker, name="fake-producer")
        t.start()
        t.join(5.0)
    times = tracer.drain_step_times()
    # the worker's stack is its own: "generate" must NOT nest under "learn"
    assert "time/span/generate" in times
    assert "time/span/learn" in times
    assert "time/span/learn.generate" not in times
    assert times["time/span/generate"] >= 0.01


def test_span_trace_json_is_valid_chrome_trace(tmp_path):
    path = str(tmp_path / "sub" / "trace.json")  # missing dir must be created
    tracer = SpanTracer(enabled=True, trace_path=path)

    def worker():
        with tracer.span("produce"):
            with tracer.span("generate"):
                pass

    t = threading.Thread(target=worker, name="rollout-producer")
    with tracer.span("learn"):
        t.start()
        t.join(5.0)
    assert tracer.write_trace() == path
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    names = {e["name"] for e in complete}
    assert {"learn", "produce", "produce.generate"} <= names
    for e in complete:  # chrome trace contract: X events need ts + dur, µs floats
        assert e["dur"] >= 0.0 and e["ts"] >= 0.0 and e["pid"] == os.getpid()
    # two threads -> two distinct tids, with thread_name metadata for each
    tids = {e["tid"] for e in complete}
    assert len(tids) == 2
    meta_names = {
        m["args"]["name"] for m in events if m["ph"] == "M" and m["name"] == "thread_name"
    }
    assert "rollout-producer" in meta_names


def test_span_disabled_is_noop_and_records_nothing(tmp_path):
    tracer = SpanTracer(enabled=False, trace_path=str(tmp_path / "t.json"))
    with tracer.span("learn"):
        pass
    assert tracer.drain_step_times() == {}
    # nothing recorded, but write_trace still emits a valid (empty) trace
    with open(tracer.write_trace()) as f:
        assert json.load(f)["traceEvents"] == []


class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what is entered."""

    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered.append(self.name)

    def __exit__(self, *exc):
        return False


class _NoLock:
    """A lock that fails the test if anything takes it."""

    def __enter__(self):
        raise AssertionError("a disabled tracer's span() took the tracer's lock")

    def __exit__(self, *exc):
        return False


def test_span_disabled_enters_the_profiler_annotation_and_nothing_else(monkeypatch):
    from trlx_tpu.obs import spans

    monkeypatch.setattr(spans, "TraceAnnotation", _RecordingAnnotation)
    monkeypatch.setattr(_RecordingAnnotation, "entered", [])
    tracer = SpanTracer(enabled=False, trace_path="t.json")
    tracer._lock = _NoLock()
    with tracer.span("learn"):
        with tracer.span("learn.put"):
            pass
    assert _RecordingAnnotation.entered == ["trlx/learn", "trlx/learn.put"]
    # no aggregate, no event, no per-thread stack was touched
    assert tracer._step_times == {} and tracer._step_counts == {} and tracer._events == []
    assert not hasattr(tracer._local, "stack")


def test_span_profiler_name_is_the_prefixed_leaf_name_enabled_or_not(monkeypatch):
    from trlx_tpu.obs import spans

    monkeypatch.setattr(spans, "TraceAnnotation", _RecordingAnnotation)
    names = {}
    for enabled in (False, True):
        monkeypatch.setattr(_RecordingAnnotation, "entered", [])
        tracer = SpanTracer(enabled=enabled)
        with tracer.span("experience"):
            with tracer.span("generate"):
                pass
            with tracer.span("learn"):
                with tracer.span("learn.put"):
                    pass
        names[enabled] = list(_RecordingAnnotation.entered)
        if enabled:  # the aggregates keep the dotted per-thread path, a carried prefix once
            assert {k for k in tracer.drain_step_times() if not k.endswith("_n")} == {
                "time/span/experience", "time/span/experience.generate",
                "time/span/experience.learn", "time/span/experience.learn.put"}
    assert names[False] == names[True] == [
        "trlx/experience", "trlx/generate", "trlx/learn", "trlx/learn.put"]


def test_span_event_cap_reports_dropped(tmp_path):
    path = str(tmp_path / "trace.json")
    tracer = SpanTracer(enabled=True, trace_path=path, max_events=3)
    for _ in range(10):
        with tracer.span("s"):
            pass
    tracer.write_trace()
    with open(path) as f:
        doc = json.load(f)
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 3
    assert doc["metadata"]["dropped_events"] == 7


# -------------------------------------------------------------- histograms


def test_gauge_histogram_percentiles():
    g = GaugeRegistry()
    for v in range(1, 101):  # 1..100
        g.observe("time/step", float(v))
    stats = g.hist_stats("time/step")
    # nearest-rank: p-th percentile of 1..100 is exactly the p-th value
    # (ceil(q*n) ranks, 1-indexed — not the old int(q*n) one-rank-too-high)
    assert stats["p50"] == 50.0
    assert stats["p95"] == 95.0
    assert stats["max"] == 100.0
    assert stats["mean"] == pytest.approx(50.5)
    assert stats["count"] == 100.0
    flat = g.hist_snapshot("time/")
    assert flat == {
        "time/step_p50": 50.0, "time/step_p95": 95.0, "time/step_max": 100.0
    }
    assert g.hist_stats("never_observed") == {}


def test_gauge_histogram_window_bounded():
    g = GaugeRegistry(hist_window=4)
    for v in [100.0, 100.0, 1.0, 2.0, 3.0, 4.0]:
        g.observe("h", v)
    stats = g.hist_stats("h")
    assert stats["max"] == 4.0  # the early spikes rolled out of the window
    assert stats["count"] == 6.0  # lifetime count survives the roll


def test_gauge_clear_by_prefix():
    g = GaugeRegistry()
    g.set("rollout/queue_depth", 3.0)
    g.inc("rollout/produced")
    g.observe("rollout/latency", 0.5)
    g.set("obs/stalls", 1.0)
    g.observe("time/step", 0.1)
    g.clear(prefix="rollout/")
    assert g.snapshot("rollout/") == {}
    assert g.hist_stats("rollout/latency") == {}
    assert g.get("obs/stalls") == 1.0
    assert g.hist_stats("time/step") != {}
    g.clear()  # no-prefix clear still wipes everything
    assert g.snapshot() == {} and g.hist_stats("time/step") == {}


# -------------------------------------------------------------- throughput


def test_param_count_and_peak_detection():
    tree = {"a": np.zeros((3, 4)), "b": {"c": np.zeros(5)}}
    assert param_count(tree) == 17
    assert detect_peak_tflops("TPU v4") == 275.0
    assert detect_peak_tflops("TPU v5 lite") == 197.0
    assert detect_peak_tflops("cpu") is None
    assert detect_peak_tflops("") is None


def test_mfu_arithmetic_hand_computed():
    # N = 1e6 params, 1000 tokens in 2s on 1 device with peak 1 TFLOP/s:
    #   train FLOPs = 6 * 1e6 * 1000 = 6e9; 3e9 FLOP/s vs 1e12 peak -> MFU 3e-3
    acc = ThroughputAccountant(n_params=1_000_000, num_devices=1, peak_device_tflops=1.0)
    stats = acc.step_stats(tokens=1000, samples=10, step_time_s=2.0)
    assert stats["throughput/tokens_per_sec"] == pytest.approx(500.0)
    assert stats["throughput/samples_per_sec"] == pytest.approx(5.0)
    assert stats["throughput/model_tflops_per_sec"] == pytest.approx(3e-3)
    assert stats["throughput/mfu"] == pytest.approx(3e-3)
    assert stats["throughput/total_tokens"] == 1000.0
    # second step accumulates totals
    acc.step_stats(tokens=500, samples=5, step_time_s=1.0)
    assert acc.total_tokens == 1500 and acc.total_samples == 15


def test_mfu_attention_term_and_unknown_peak():
    # attention term: 12 * L * H * S per trained token (PaLM appendix B)
    flops = transformer_flops_per_token(
        n_params=100, num_layers=2, hidden_size=8, seq_len=16, backward=True
    )
    assert flops == 6 * 100 + 12 * 2 * 8 * 16
    assert transformer_flops_per_token(100, backward=False) == 200.0
    acc = ThroughputAccountant(n_params=100, num_devices=4, peak_device_tflops=None)
    stats = acc.step_stats(tokens=10, samples=1, step_time_s=1.0)
    assert "throughput/mfu" not in stats  # never a made-up denominator
    assert "throughput/model_tflops_per_sec" in stats
    # devices scale the denominator: 2 chips at 1 TFLOP/s halve the MFU
    acc2 = ThroughputAccountant(n_params=1_000_000, num_devices=2, peak_device_tflops=1.0)
    assert acc2.step_stats(1000, 1, 2.0)["throughput/mfu"] == pytest.approx(1.5e-3)


def test_batch_token_count_shapes():
    batch = SimpleNamespace(
        attention_mask=np.ones((4, 8), np.int32),
        response_mask=np.concatenate(
            [np.ones((4, 3), np.int32), np.zeros((4, 3), np.int32)], axis=1
        ),
    )
    tokens, samples, seq_len = batch_token_count(batch)
    assert (tokens, samples, seq_len) == (4 * 8 + 4 * 3, 4, 14)
    tokens, samples, seq_len = batch_token_count({"input_ids": np.zeros((2, 6))})
    assert (tokens, samples, seq_len) == (12, 2, 6)
    tokens, samples, seq_len = batch_token_count({"input_ids": [[1, 2], [3, 4, 5]]})
    assert (tokens, samples, seq_len) == (5, 2, 3)
    assert batch_token_count({"other": 1}) == (0, 0, 0)


# ------------------------------------------------------------------ memory


def test_device_memory_stats_always_reports_something():
    stats = device_memory_stats()
    # CPU backend has no allocator counters -> host RSS fallback; either way
    # the smoke-run contract is "some memory gauge exists and is positive"
    assert stats, "expected at least one memory gauge"
    assert all(v > 0 for v in stats.values())
    assert all(k.startswith("mem/") for k in stats)


# ---------------------------------------------------------------- watchdog


def test_watchdog_fires_on_stalled_fake_producer(trlx_caplog, monkeypatch):
    """A deliberately-stalled fake producer (blocked on an Event, like a
    wedged reward RPC) must be detected: structured warning + all-thread
    stack dump naming the stalled heartbeat. The watchdog reads a clock the
    test moves, so a loaded machine cannot age the healthy heartbeat too."""
    clock = [100.0]
    # the module, not the handle of the same name that trlx_tpu.obs exports
    monkeypatch.setattr(sys.modules[StallWatchdog.__module__], "time", SimpleNamespace(monotonic=lambda: clock[0]))
    release = threading.Event()

    def stalled_producer():
        release.wait(30.0)  # deliberately stuck

    t = threading.Thread(target=stalled_producer, name="fake-rollout-producer")
    t.start()
    fired = []
    dog = StallWatchdog(timeout_s=0.05, on_stall=lambda name, age: fired.append((name, age)))
    try:
        dog.beat("rollout-producer")
        dog.beat("learner")
        clock[0] += 0.12
        dog.beat("learner")  # learner is healthy; only the producer is stale
        with trlx_caplog.at_level(py_logging.WARNING, logger="trlx_tpu.obs.watchdog"):
            dog.check()
        assert [name for name, _ in fired] == ["rollout-producer"]
        assert dog.stall_count == 1
        text = trlx_caplog.text
        assert "STALL DETECTED" in text and "'rollout-producer'" in text
        # the dump contains every thread's stack — including the stuck one
        assert "fake-rollout-producer" in text and "stalled_producer" in text
        # one dump per episode: no re-fire until the heartbeat beats again
        dog.check()
        assert dog.stall_count == 1
        dog.beat("rollout-producer")
        dog.beat("learner")
        clock[0] += 0.08
        dog.beat("learner")
        dog.check()
        assert dog.stall_count == 2
        assert [name for name, _ in fired] == ["rollout-producer", "rollout-producer"]
    finally:
        release.set()
        t.join(5.0)


def test_watchdog_no_false_positive_while_beating():
    dog = StallWatchdog(timeout_s=0.3, poll_s=0.02)
    dog.start()
    try:
        assert dog.running
        for _ in range(10):
            dog.beat("learner")
            time.sleep(0.02)
        assert dog.stall_count == 0
    finally:
        dog.stop()
    assert not dog.running


def test_watchdog_unregister_silences_finished_heartbeat():
    dog = StallWatchdog(timeout_s=0.05)
    dog.beat("rollout-producer")
    dog.unregister("rollout-producer")  # clean shutdown
    time.sleep(0.12)
    dog.check()
    assert dog.stall_count == 0
    with pytest.raises(ValueError):
        StallWatchdog(timeout_s=0.0)


def test_global_watchdog_handle_install_and_noop():
    # the null impl accepts beats without a started watchdog
    global_watchdog.beat("anything")
    assert global_watchdog.stall_count == 0
    dog = StallWatchdog(timeout_s=10.0)
    global_watchdog.install(dog)
    try:
        global_watchdog.beat("learner")
        assert dog._beats.keys() == {"learner"}
    finally:
        global_watchdog.install(None)
    global_watchdog.beat("learner")  # back to the null impl


def test_engine_stop_unregisters_heartbeat_and_clears_gauges():
    """Satellite: a finished producer's rollout/* gauges must stop being
    exported, and its heartbeat must stop paging the watchdog."""
    from trlx_tpu.rollout import (
        AsyncRolloutEngine,
        ExperienceQueue,
        ParameterPublisher,
        StalenessAccountant,
    )
    from trlx_tpu.utils.metrics import gauges

    dog = StallWatchdog(timeout_s=0.05)
    global_watchdog.install(dog)
    try:
        from tests.test_async_rollout import make_element

        pub = ParameterPublisher(copy_fn=dict)
        pub.publish({})
        engine = AsyncRolloutEngine(
            lambda params, version: [make_element(0)],
            pub, ExperienceQueue(8), StalenessAccountant(4),
        )
        engine.start()
        engine.collect(1, learner_version=0, timeout=10.0)
        assert gauges.snapshot("rollout/")  # live gauges while running
        engine.stop(timeout=10.0)
        assert gauges.snapshot("rollout/") == {}  # cleared on shutdown
        time.sleep(0.12)
        dog.check()
        assert dog.stall_count == 0  # unregistered: no posthumous page
    finally:
        global_watchdog.install(None)


# ------------------------------------------------------------------ facade


def obs_cfg(**overrides):
    from trlx_tpu.data.configs import ObservabilityConfig

    return ObservabilityConfig(**overrides)


def test_observability_disabled_is_inert():
    obs = Observability(obs_cfg(enabled=False))
    with obs.span("learn"):
        pass
    obs.beat()
    assert obs.step_stats(100, 4) == {}
    obs.close()  # no trace written, nothing to tear down
    assert obs.watchdog is None


def test_observability_enabled_step_stats_and_trace(tmp_path):
    from trlx_tpu.utils.metrics import gauges

    gauges.clear(prefix="time/")
    obs = Observability(
        obs_cfg(
            enabled=True, trace_path="trace.json",
            peak_device_tflops=1.0, watchdog_timeout_s=30.0,
        ),
        logging_dir=str(tmp_path),
    )
    try:
        obs.configure_model(
            {"w": np.zeros((10, 10))},
            SimpleNamespace(num_layers=2, hidden_size=10),
        )
        assert obs.accountant is not None and obs.accountant.n_params == 100
        with obs.span("learn"):
            time.sleep(0.01)
        first = obs.step_stats(tokens=64, samples=4, seq_len=16)
        assert first["time/span/learn"] >= 0.01
        with obs.span("learn"):
            pass
        obs.beat()
        from trlx_tpu.obs import compile_log

        compile_log.log.record_compile(0.5, "obs_test_entry")
        second = obs.step_stats(tokens=64, samples=4, seq_len=16)
        # a compile since the last step: the log's gauges ride the obs/ export
        assert second["obs/compile/obs_test_entry/compiles"] >= 1
        # from the second step on: wall step time, histogram, throughput + MFU
        assert second["time/step"] > 0
        assert "time/step_p50" in second and "time/step_p95" in second
        assert second["throughput/tokens_per_sec"] > 0
        assert "throughput/mfu" in second
        assert any(k.startswith("mem/") for k in second)
        assert obs.watchdog is not None and obs.watchdog.running
    finally:
        obs.close()
    assert obs.watchdog is None
    with open(tmp_path / "trace.json") as f:
        names = {e["name"] for e in json.load(f)["traceEvents"] if e.get("ph") == "X"}
    assert "learn" in names
    obs.close()  # idempotent


def test_observability_config_roundtrip_and_dotted_update():
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config()
    assert config.train.observability.enabled is False  # off by default
    d = config.to_dict()
    assert d["train"]["observability"]["watchdog_timeout_s"] == 0.0
    assert TRLConfig.from_dict(d).to_dict() == d

    new = TRLConfig.update(
        d,
        {
            "train.observability.enabled": True,
            "train.observability.trace_path": "trace.json",
            "train.observability.peak_device_tflops": 197.0,
            "train.observability.watchdog_timeout_s": 120.0,
        },
    )
    assert new.train.observability.enabled is True
    assert new.train.observability.peak_device_tflops == 197.0
    with pytest.raises(ValueError):
        TRLConfig.update(d, {"train.observability.bogus_knob": 1})


# ------------------------------------------------------------ compile log


def test_compile_log_records_a_forced_compile_with_its_entry():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.obs import compile_log

    compile_log.install()
    before = compile_log.log.total
    t0 = time.monotonic()

    def forced(x):
        return x * 3 + 1

    with compile_log.attributed("outer"):
        with compile_log.attributed("forced_entry"):
            jax.block_until_ready(jax.jit(forced)(jnp.zeros((3,), jnp.float32)))
    assert compile_log.log.total > before
    mine = [c for c in compile_log.log.compiles() if c[0] >= t0]
    assert mine and all(entry == "forced_entry" for _, _, entry in mine)
    assert all(seconds > 0 for _, seconds, _ in mine)
    assert compile_log.log.compiles(before=t0) == [c for c in compile_log.log.compiles() if c[0] < t0]
    assert compile_log.log.compile_seconds() >= compile_log.log.compile_seconds(before=t0)
    # outside any scope a compile is kept with no entry
    jax.block_until_ready(jax.jit(lambda x: x - 2)(jnp.zeros((5,), jnp.float32)))
    assert compile_log.log.compiles()[-1][2] is None
    registry = GaugeRegistry()
    compile_log.log.export_gauges(registry)
    assert registry.get("obs/compile/forced_entry/compiles") >= 1
    assert registry.get("obs/compile/forced_entry/compile_time_s") > 0
    assert registry.get(f"obs/compile/{compile_log.UNATTRIBUTED}/compiles") >= 1


def test_compile_log_counts_a_cache_hit_as_a_hit():
    """The dispatcher counts jax's own cache events: a look-up that hits is
    not compiled anew, one that misses is."""
    import jax.monitoring

    from trlx_tpu.obs import compile_log

    compile_log.install()
    log = compile_log.log
    anew, t0 = log.compiled_anew(), time.monotonic()
    jax.monitoring.record_event(compile_log.CACHE_REQUEST_EVENT)
    jax.monitoring.record_event(compile_log.CACHE_HIT_EVENT)
    assert log.compiled_anew() == anew  # a hit
    jax.monitoring.record_event(compile_log.CACHE_REQUEST_EVENT)
    assert log.compiled_anew() == anew + 1  # a miss
    assert log.compiled_anew(before=t0) == anew
    jax.monitoring.record_event("/jax/compilation_cache/some_other_event")
    assert log.compiled_anew() == anew + 1


def test_compile_log_is_bounded_and_total_counts_on():
    from trlx_tpu.obs.compile_log import CompileLog

    log = CompileLog(capacity=3)
    for i in range(5):
        log.record_compile(0.5, "e" if i % 2 else None, now=float(i))
    assert log.total == 5 and [c[0] for c in log.compiles()] == [2.0, 3.0, 4.0]
    assert log.compile_seconds(before=4.0) == 1.0
    assert log.by_entry() == {"e": (1, 0.5), "__unattributed__": (2, 1.0)}
    log.reset()
    assert log.total == 0 and log.compiles() == []


def test_learn_loop_warns_which_step_recompiled(trlx_caplog):
    from trlx_tpu.obs import compile_log
    from trlx_tpu.trainer.mesh_trainer import MeshRLTrainer

    settled = compile_log.log.total
    compile_log.log.record_compile(1.5, "ppo_train_step")
    compile_log.log.record_compile(0.25, None)
    with trlx_caplog.at_level(py_logging.WARNING, logger="trlx_tpu.trainer.mesh_trainer"):
        now = MeshRLTrainer._warn_recompiled(SimpleNamespace(iter_count=17), settled)
    assert now == settled + 2
    [record] = [r for r in trlx_caplog.records if "XLA compile" in r.getMessage()]
    message = record.getMessage()
    assert "step 17" in message and "2 XLA compile(s)" in message and "1.75 s" in message
    assert "ppo_train_step" in message and compile_log.UNATTRIBUTED in message


# ------------------------------------- the program in an open profiler session


class _EndOfTrace(Exception):
    pass


@pytest.fixture(scope="module")
def traced_ppo_iteration(tmp_path_factory):
    """One tiny PPO iteration (4 optimizer steps, then the next experience)
    through ``trlx_tpu.train()`` inside a CPU profiler session, the tracer
    disabled: what the profiler holds of the program's own spans and programs."""
    import jax
    from jax.profiler import ProfileData

    import trlx_tpu
    from tests.test_trainers import base_kwargs, dog_reward
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.methods.ppo import PPOConfig
    from trlx_tpu.obs import compile_log
    from trlx_tpu.trainer import register_trainer
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    tmp_path = tmp_path_factory.mktemp("traced_ppo")
    trace_dir = str(tmp_path / "trace")
    state = {"marks": 0, "t_start": None}

    @register_trainer
    class ProfiledPPOTrainer(PPOTrainer):
        def post_epoch_callback(self, epoch):
            super().post_epoch_callback(epoch)
            state["marks"] += 1
            if state["marks"] == 1:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # as the benchmark's harness: quick
                options.host_tracer_level = 2
                state["t_start"] = time.monotonic()
                jax.profiler.start_trace(trace_dir, profiler_options=options)
            else:
                jax.profiler.stop_trace()
                raise _EndOfTrace()

    kwargs = base_kwargs(tmp_path, "ProfiledPPOTrainer", total_steps=10 ** 6)
    kwargs["train"].epochs = 10 ** 6
    kwargs["train"].checkpoint_interval = kwargs["train"].eval_interval = 10 ** 6
    kwargs["train"].tracker = None
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=2, init_kl_coef=0.01, target=None,
            gen_kwargs=dict(max_new_tokens=6, min_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **kwargs,
    )
    assert config.train.observability.enabled is False
    with pytest.raises(_EndOfTrace):
        trlx_tpu.train(
            reward_fn=dog_reward, prompts=["ab", "cd ef", "gh", "a b c"] * 2,
            eval_prompts=["ab", "cd"], config=config,
        )
    [path] = [
        os.path.join(root, f) for root, _, files in os.walk(trace_dir)
        for f in files if f.endswith(".xplane.pb")
    ]
    host, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                host.append((event.name, int(event.start_ns), int(event.start_ns + event.duration_ns)))
                if line.name.startswith("tf_XLA"):
                    modules.update(str(v) for k, v in event.stats if k == "hlo_module")
    return {
        "host": host, "modules": modules, "t_start": state["t_start"],
        "compiles": compile_log.log.compiles(),
    }


def _named(events, name):
    """The program's spans of that name: the profiler holds them as ``trlx/<name>``."""
    return [(start, end) for n, start, end in events if n == f"trlx/{name}"]


def _inside(children, parents):
    return all(any(ps <= cs and ce <= pe for ps, pe in parents) for cs, ce in children)


def test_profiler_session_holds_the_span_vocabulary(traced_ppo_iteration):
    host = traced_ppo_iteration["host"]
    counts = {name: len(_named(host, name)) for name in (
        "experience", "generate", "reward", "score", "learn",
        "learn.put", "learn.step", "learn.sync", "data", "log")}
    # 8 rollouts, batch 4, 2 ppo epochs: 4 optimizer steps (and the fetch that
    # ends the loader), then one experience of two generations of 4
    assert counts == {
        "experience": 1, "generate": 2, "reward": 2, "score": 2, "learn": 4,
        "learn.put": 4, "learn.step": 4, "learn.sync": 4, "data": 5, "log": 4}, counts


def test_profiler_session_nests_children_inside_their_parents(traced_ppo_iteration):
    host = traced_ppo_iteration["host"]
    learn, experience = _named(host, "learn"), _named(host, "experience")
    for child in ("learn.put", "learn.step", "learn.sync"):
        assert _inside(_named(host, child), learn), child
    for child in ("generate", "reward", "score"):
        assert _inside(_named(host, child), experience), child
    for outside in ("data", "log"):  # between two learn spans, in neither
        assert not any(
            ls < e and s < le for s, e in _named(host, outside) for ls, le in learn), outside
    # put, step, sync follow one another inside each learn
    for (ls, le) in learn:
        inner = sorted((s, e, n) for n in ("learn.put", "learn.step", "learn.sync")
                       for s, e in _named(host, n) if ls <= s and e <= le)
        assert [n for _, _, n in inner] == ["learn.put", "learn.step", "learn.sync"]


def test_profiler_session_names_every_executed_program(traced_ppo_iteration):
    modules = traced_ppo_iteration["modules"]
    assert {"jit_generate", "jit_ppo_score", "jit_ppo_train_step"} <= modules, modules
    assert not any("lambda" in m for m in modules), modules


def test_learn_attributes_the_programs_compiles(traced_ppo_iteration):
    """The trainer installed the log as it was built; the three hot programs'
    compiles carry their names, and none happened inside the traced (second) iteration."""
    compiles, t_start = traced_ppo_iteration["compiles"], traced_ppo_iteration["t_start"]
    entries = {entry for _, _, entry in compiles}
    assert {"generate", "ppo_score", "ppo_train_step"} <= entries, entries
    hot = ("generate", "ppo_score", "ppo_train_step")
    assert not [c for c in compiles if c[0] >= t_start and c[2] in hot]


# ------------------------------------------------------------- end-to-end


@pytest.mark.slow
def test_obs_ppo_end_to_end(tmp_path, trlx_caplog):
    """CPU smoke run with the obs flags on (acceptance criterion): per-step
    phase timings, tokens/sec + MFU, memory gauges, and step-time p50/p95
    reach the jsonl tracker; trace.json is valid Chrome trace JSON; the
    watchdog logs no false-positive stall."""
    import glob

    import trlx_tpu
    from tests.test_trainers import base_kwargs, dog_reward
    from trlx_tpu.data.configs import ObservabilityConfig, TRLConfig
    from trlx_tpu.methods.ppo import PPOConfig

    kwargs = base_kwargs(tmp_path, "PPOTrainer", total_steps=4)
    kwargs["train"].async_rollouts.enabled = True
    kwargs["train"].async_rollouts.max_staleness = 4
    kwargs["train"].observability = ObservabilityConfig(
        enabled=True, trace_path="trace.json", peak_device_tflops=100.0,
        watchdog_timeout_s=300.0,  # well above any CPU compile pause
    )
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=2, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **kwargs,
    )
    with trlx_caplog.at_level(py_logging.WARNING, logger="trlx_tpu.obs.watchdog"):
        trainer = trlx_tpu.train(
            reward_fn=dog_reward,
            prompts=["ab", "cd ef", "gh", "a b c"] * 2,
            eval_prompts=["ab", "cd"],
            config=config,
        )
    assert trainer.iter_count >= 4
    assert "STALL DETECTED" not in trlx_caplog.text  # no false positives

    logs_dir = os.path.join(config.train.checkpoint_dir, "logs")
    [jsonl_path] = glob.glob(os.path.join(logs_dir, "*.jsonl"))
    with open(jsonl_path) as f:
        records = [json.loads(line) for line in f]
    steps = [r for r in records if "time/span/learn" in r]
    assert steps, "per-step span timings never reached the tracker"
    keys = set().union(*(r.keys() for r in records))
    assert "time/span/generate" in keys and "time/span/score" in keys
    assert "time/span/queue_wait" in keys  # async path: learner waited on queue
    assert "throughput/tokens_per_sec" in keys and "throughput/mfu" in keys
    assert "time/step_p50" in keys and "time/step_p95" in keys
    assert any(k.startswith("mem/") for k in keys)

    with open(os.path.join(logs_dir, "trace.json")) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {"learn", "generate", "score"} <= names
    assert len({e["tid"] for e in events if e.get("ph") == "X"}) >= 2  # two timelines
