"""The reduction from trace events to numbers: on events worked by hand, and
on a recording cut from a traced run of ``gpt2.ppo-long-response`` on the chip
(``data/trace_gpt2_long_response.json``, made by ``record_trace.py``)."""

import json
import os

import pytest

from benchmark import trace_reduce

RECORDING = os.path.join(os.path.dirname(__file__), "data", "trace_gpt2_long_response.json")

OPS = [
    ("fusion.1", 0, 100), ("fusion.2", 50, 150),  # overlap: busy 0..150
    ("_flash_kernel", 200, 300),  # gap 150..200 under "learn"
    ("fusion.1", 300, 350),
    ("copy.3", 1000, 1100),  # gap 350..1000 under no annotation
    ("_flash_bwd_dq_kernel", 1200, 1300),  # gap 1100..1200: score inside rollout -> score
]
HOST = [("learn", 0, 400), ("rollout", 1050, 1400), ("score", 1090, 1250)]


def test_busy_is_the_union_of_the_intervals():
    assert trace_reduce.busy_intervals(OPS) == [(0, 150), (200, 350), (1000, 1100), (1200, 1300)]
    assert trace_reduce.busy_seconds(OPS) == pytest.approx(500e-9)


def test_gaps_go_to_the_innermost_annotation():
    gaps = trace_reduce.idle_by_annotation(trace_reduce.busy_intervals(OPS), HOST)
    assert gaps == {"learn": pytest.approx(50e-9), "host": pytest.approx(650e-9), "score": pytest.approx(100e-9)}


def test_kernel_time_by_pattern():
    assert trace_reduce.kernel_seconds(OPS, ["_flash_kernel", "_flash_bwd_dq_kernel"]) == pytest.approx(200e-9)
    assert trace_reduce.kernel_seconds(OPS, ["^fusion"]) == pytest.approx(250e-9)
    assert trace_reduce.kernel_seconds(OPS, ["paged"]) is None  # nothing to read: the reader reports nothing


def test_reduce_averages_busy_over_device_planes():
    reduced = trace_reduce.reduce({"/device:TPU:0": OPS, "/device:TPU:1": OPS[:2]}, HOST)
    assert reduced["busy_s"] == pytest.approx((500e-9 + 150e-9) / 2)
    assert reduced["device_ops"][0] == ["fusion.1", pytest.approx(150e-9)]
    assert reduced["idle_gaps"][0][0] == "host"
    with pytest.raises(ValueError):
        trace_reduce.reduce({}, HOST)


def test_on_the_recorded_trace():
    with open(RECORDING) as f:
        recording = json.load(f)
    ops = [tuple(e) for e in recording["ops"]]
    host = [tuple(e) for e in recording["host"]]
    assert "XLA Ops" in recording["layout"][recording["device_plane"]]
    busy = trace_reduce.busy_seconds(ops)
    span = (max(e[2] for e in ops) - min(e[1] for e in ops)) / 1e9
    assert 0 < busy <= span
    gaps = trace_reduce.idle_by_annotation(trace_reduce.busy_intervals(ops), host)
    assert sum(gaps.values()) == pytest.approx(span - busy, rel=1e-6)
    spec = json.load(open(os.path.join(os.path.dirname(__file__), "..", "metrics", "flash_attn_roofline.json")))
    names = {name for name, _ in recording["by_name"]}
    import re
    assert any(re.search(p, n) for p in spec["args"]["patterns"] for n in names)


def test_short_name_keeps_the_instruction_and_its_opcode():
    hlo = ("%attn.86 = (f32[32,12,520,64]{3,2,1,0:T(8,128)}, f32[32,12,520,64]{3,2,1,0:T(8,128)}) "
           "custom-call(s32[32,5,8,104]{3,2,1,0:T(8,128)S(1)} %copy-done.276), custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.short_name(hlo) == "%attn.86 custom-call"
    assert trace_reduce.short_name("%while.10 = (s32[128,512]{0,1:T(8,128)}, pred[128]{0:T(512)(128)(4,1)}) while(%tuple.3)") == "%while.10 while"
    assert trace_reduce.short_name("%fusion.9 = f32[32,513,50257]{2,1,0:T(8,128)} fusion(bf16[32,513,50257]{2,1,0} %gte.1), kind=kLoop") == "%fusion.9 fusion"
    assert trace_reduce.short_name("plain") == "plain"
    assert trace_reduce.seconds_by_name([("%while.10 while", 0, 10), ("%f fusion", 2, 4)]) == {"%f fusion": pytest.approx(2e-9)}
