"""``readers/program_trace.py``: on events worked by hand, and on a recording
cut from a traced run of ``gpt2.ppo-long-response`` on the chip with the
program's own names and spans (``data/program_trace_gpt2_long_response.json``,
made by ``record_program_trace.py``)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness
from benchmark.readers import program_trace

RECORDING = os.path.join(os.path.dirname(__file__), "data", "program_trace_gpt2_long_response.json")
WINDOW = ("rollout_device_pct", "score_device_pct", "learn_device_pct", "decode_step_ms", "learn_gap_ms")

# one iteration by hand, in ns: two train steps, then a generate with one
# decode loop (an inner loop inside it), then a score
MODULES = [
    ("jit_ppo_train_step(11)", 1000, 2000), ("jit_ppo_train_step(11)", 2600, 3600),
    ("jit_copy_params(3)", 3700, 3750),
    ("jit_generate(7)", 4000, 6000), ("jit_ppo_score(9)", 6500, 7000),
]
OPS = [
    ("%fusion.1 fusion", 1000, 1500), ("%while.2 while", 1500, 1990),  # the microbatch scan: no decode loop
    ("%fusion.3 fusion", 1600, 1900),
    ("%fusion.1 fusion", 2600, 3000), ("%fusion.4 fusion", 3100, 3600),  # a gap of 100 inside the second step
    ("%copy.5 copy", 3700, 3750),
    ("%fusion.6 fusion", 4000, 4400),  # prefill
    ("%while.10 while", 4400, 5900), ("%while.11 while", 4500, 4700), ("%fusion.7 fusion", 4500, 5900),
    ("%fusion.8 fusion", 6500, 7000),
]
SPANS = [("trlx/" + name, start, end) for name, start, end in [
    ("learn", 900, 2300), ("learn.put", 900, 990), ("learn.step", 990, 1100), ("learn.sync", 1100, 2300),
    ("log", 2300, 2400), ("data", 2400, 2500),
    ("learn", 2500, 3650), ("learn.put", 2500, 2590), ("learn.step", 2590, 2700), ("learn.sync", 2700, 3650),
    ("experience", 3800, 7100), ("generate", 3900, 6100), ("score", 6400, 7050),
]]


def context(modules, spans, ops, seconds, new_tokens=4, marks=(50.0, 60.0)):
    return SimpleNamespace(
        trace={"ops": ops}, interval=(marks[0], marks[0] + seconds), cell={"new_tokens": new_tokens},
        notes={}, session=SimpleNamespace(marks=list(marks), trace_dir=None),
        _loaded=(sorted(modules, key=lambda e: e[1]), spans),
    )


@pytest.fixture(autouse=True)
def no_xplane(monkeypatch):
    """The readers take the module events and spans from the context, not
    from a file."""
    monkeypatch.setattr(program_trace, "_program_trace", lambda ctx: ctx._loaded if ctx.trace else None)


def read(ctx, name):
    spec = harness.load_json("metrics", f"{name}.json")
    module, function = spec["reader"].rsplit(".", 1)
    assert module == "program_trace"
    return getattr(program_trace, function)(ctx, **spec["args"])


def test_window_metrics_on_events_worked_by_hand():
    ctx = context(MODULES, SPANS, OPS, seconds=10000e-9)
    assert read(ctx, "learn_device_pct") == pytest.approx(100 * 2000 / 10000)
    assert read(ctx, "rollout_device_pct") == pytest.approx(100 * 2000 / 10000)
    assert read(ctx, "score_device_pct") == pytest.approx(100 * 500 / 10000)
    # one call, new_tokens - 1 = 3 steps, the outer loop alone: 1500 ns
    assert read(ctx, "decode_step_ms") == pytest.approx(1500 / 1e6 / 3)
    # from 1000 to 3600: idle 1990..2600 and 3000..3100, over two steps
    assert read(ctx, "learn_gap_ms") == pytest.approx((610 + 100) / 1e6 / 2)
    # 1990..2600 runs under learn.sync to 2300, log, data, learn.put, and 10 of
    # learn.step; 3000..3100 under the second learn.sync
    assert ctx.notes["learn_gap_s_by_span"] == {
        "learn.sync": pytest.approx(410e-9), "log": pytest.approx(100e-9), "data": pytest.approx(100e-9),
        "learn.put": pytest.approx(90e-9), "learn.step": pytest.approx(10e-9)}


def test_a_gap_is_shared_out_over_the_innermost_spans_and_other():
    busy = [(0, 10), (20, 30), (50, 60), (100, 110), (200, 210)]
    spans = [("trlx/learn", 22, 58), ("trlx/data", 25, 55), ("trlx/log", 150, 160)]
    assert program_trace.span_segments(spans) == [
        (22, 25, "trlx/learn"), (25, 55, "trlx/data"), (55, 58, "trlx/learn"), (150, 160, "trlx/log")]
    assert program_trace.idle_by_span(busy, spans) == {
        # 10..20 and 60..100 under nothing, 110..200 but for the 10 of log
        "other": pytest.approx((10 + 40 + 80) * 1e-9),
        "data": pytest.approx(20e-9),  # 30..50 lies in data, inside learn
        "log": pytest.approx(10e-9)}


def test_a_trace_without_the_programs_names_reads_nothing():
    """What the parent commit's trace looks like: every module a lambda or ``step``."""
    parent = [("jit__lambda_(5)", s, e) if "generate" in n or "copy" in n else ("jit_step(6)", s, e)
              for n, s, e in MODULES]
    ctx = context(parent, [], OPS, seconds=10000e-9)
    assert [read(ctx, name) for name in WINDOW] == [None] * 5
    assert ctx.notes == {}
    untraced = context(MODULES, SPANS, OPS, seconds=10000e-9)
    untraced.trace = None
    assert [read(untraced, name) for name in WINDOW] == [None] * 5


def test_setup_metrics_count_only_what_came_before_the_first_mark(monkeypatch):
    from trlx_tpu.obs.compile_log import CACHE_HIT_EVENT, CACHE_REQUEST_EVENT, CompileLog

    log = CompileLog()
    for at, seconds, entry in ((10.0, 2.0, None), (20.0, 0.25, "generate"), (49.9, 1.0, "ppo_train_step"),
                               (50.0, 8.0, "ppo_score"), (55.0, 16.0, None)):
        log.record_compile(seconds, entry, now=at)
    for at in (10.0, 20.0, 49.9, 50.0, 55.0):
        log.record_cache_event(CACHE_REQUEST_EVENT, now=at)
    for at in (20.0, 55.0):
        log.record_cache_event(CACHE_HIT_EVENT, now=at)
    monkeypatch.setattr(program_trace, "_compile_log", lambda: log)
    ctx = context(MODULES, SPANS, OPS, seconds=1.0, marks=(50.0, 60.0))
    assert read(ctx, "setup_compile_s") == pytest.approx(3.25)
    assert ctx.notes["setup_compiles_by_entry"] == {
        "__unattributed__": [1, 2.0], "generate": [1, 0.25], "ppo_train_step": [1, 1.0]}
    assert read(ctx, "setup_compiled_anew") == 2.0  # three look-ups, one hit
    # a program with no log, or one that logged nothing before the window: nothing to read
    monkeypatch.setattr(program_trace, "_compile_log", lambda: None)
    assert read(ctx, "setup_compile_s") is None and read(ctx, "setup_compiled_anew") is None
    monkeypatch.setattr(program_trace, "_compile_log", lambda: CompileLog())
    assert read(ctx, "setup_compile_s") is None and read(ctx, "setup_compiled_anew") is None


# ------------------------------------------------- the recording from the chip

#: ``device.window_s`` of the run the recording was cut from (seed 2147480101,
#: my chip run, PR 27), and what that run's result line printed
WINDOW_S = 7.679074978000017
PRINTED = {"rollout_device_pct": 24.9352, "score_device_pct": 11.8853, "learn_device_pct": 60.4049,
           "decode_step_ms": 4.2233}


@pytest.fixture(scope="module")
def recording():
    with open(RECORDING) as f:
        data = json.load(f)
    return {key: [tuple(e) for e in data[key]] for key in ("modules", "spans", "whiles", "ops")}


def test_device_shares_and_decode_step_on_the_recording(recording):
    """23 module events: 16 train steps, a key split, an unstack, one
    generate, four scores; one ``while`` inside the generate (the 16 others,
    33 us each, are the train steps')."""
    assert len(recording["modules"]) == 23 and len(recording["whiles"]) == 17
    ctx = context(recording["modules"], recording["spans"], recording["whiles"], WINDOW_S, new_tokens=448)
    by_hand = {
        "rollout_device_pct": 100 * 1.914789010 / WINDOW_S,  # jit_generate: 4809342544 .. 6724131554
        "score_device_pct": 100 * 0.912679979 / WINDOW_S,  # four jit_ppo_score, 228 ms each
        "learn_device_pct": 100 * 4.638539448 / WINDOW_S,  # sixteen jit_ppo_train_step, 290 ms each
        "decode_step_ms": (6724126767 - 4836323890) / 1e6 / 447,  # %while.10, 447 decode steps
    }
    for name, value in by_hand.items():
        assert read(ctx, name) == pytest.approx(value, rel=1e-9), name
        assert read(ctx, name) == pytest.approx(PRINTED[name], abs=1e-4), name


def test_the_gap_between_two_train_steps_on_the_recording(recording):
    """The recorded op events run from the last thousand of the first train
    step to the first thousand of the second. Between them the device is idle
    from 334553507 to 343051594: 8.498 ms, under ``learn.sync`` for its first
    2.80 ms, ``data`` for 0.57, ``learn.put`` for 2.51 and ``learn.step`` for
    its last 2.21; 1581 gaps of a few ns between consecutive ops add 6605 ns."""
    ops = recording["ops"]
    first, second = program_trace.matching(recording["modules"], r"^jit_ppo_train_step\b")[:2]
    assert first[2] == 334563955 and second[1] == 343033377
    # two module events cut to the stretch the recorded op events cover
    modules = [(first[0], ops[0][1], first[2]), (second[0], second[1], max(e[2] for e in ops))]
    ctx = context(modules, recording["spans"], ops, WINDOW_S)
    assert read(ctx, "learn_gap_ms") == pytest.approx((8498087 + 6605) / 1e6 / 2, rel=1e-12)
    by_span = {name: round(seconds * 1e9) for name, seconds in ctx.notes["learn_gap_s_by_span"].items()}
    assert sum(by_span.values()) == 8498087 + 6605
    assert by_span["learn.put"] == 2508190 and by_span["data"] == 565010 and by_span["log"] == 67130
    assert by_span["learn"] == 260490 + 18320 + 10490  # after the sync, before the put, before the step
    assert by_span["other"] == 28320 + 2510 + 22410  # between learn and log, log and data, data and learn
    # the few-ns gaps lie under the first step's learn.sync, the second's learn.step and learn.sync
    assert by_span["learn.sync"] >= 2802569 and by_span["learn.step"] >= 2212648
    assert by_span["learn.sync"] + by_span["learn.step"] == 2802569 + 2212648 + 6605
