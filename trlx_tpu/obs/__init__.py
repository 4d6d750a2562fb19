"""Unified observability layer: span tracing, throughput/MFU accounting,
device-memory gauges, and a stall watchdog (docs/observability.md).

Primitives, each usable standalone, plus the :class:`Observability`
facade the trainer drives from ``TRLConfig.train.observability``:

- :mod:`trlx_tpu.obs.spans` — thread-safe hierarchical span tracer;
  ``with span("generate"):`` times phases across the learner and the rollout
  producer thread, exports per-step aggregates, and writes Chrome-trace-event
  JSON (``trace.json``, Perfetto-viewable).
- :mod:`trlx_tpu.obs.throughput` — tokens/sec, samples/sec, and MFU from
  param count + measured step time.
- :mod:`trlx_tpu.obs.memory` — device-memory gauges from
  ``jax.Device.memory_stats()`` (host-RSS fallback on CPU).
- :mod:`trlx_tpu.obs.watchdog` — heartbeat monitor that dumps all Python
  thread stacks when the learner or producer stops making progress.
- :mod:`trlx_tpu.obs.flight` — per-uid request-flight journal reducing
  lifecycle events to a per-phase latency decomposition
  (docs/observability.md "Request flights").
- :mod:`trlx_tpu.obs.compile_log` — the always-on, bounded record of the
  process's XLA compiles with the entry each is attributed to; the one
  ``jax.monitoring`` dispatcher of the process.
- :mod:`trlx_tpu.obs.op_scopes` — every hot program's table of its own
  instructions (name -> named scope), built lazily from the executable that
  ran: the join between a device trace's events and the program's scopes.
- :mod:`trlx_tpu.obs.timeseries` / :mod:`trlx_tpu.obs.export` — bounded
  gauge time-series with windowed reductions, plus atomic JSONL and
  Prometheus text exporters.
"""

from trlx_tpu.obs.export import (
    read_jsonl_series,
    read_prometheus,
    write_jsonl_series,
    write_prometheus,
)
from trlx_tpu.obs.flight import Flight, FlightRecorder, flight
from trlx_tpu.obs.islands import IslandLedger
from trlx_tpu.obs.memory import device_memory_stats, host_rss_bytes
from trlx_tpu.obs.overlap import OverlapWindow
from trlx_tpu.obs.runtime import Observability, batch_token_count
from trlx_tpu.obs.spans import SpanTracer, span, tracer
from trlx_tpu.obs.timeseries import SeriesStore
from trlx_tpu.obs.throughput import (
    PEAK_TFLOPS_BY_DEVICE_KIND,
    ThroughputAccountant,
    detect_peak_tflops,
    param_count,
    transformer_flops_per_token,
)
from trlx_tpu.obs.watchdog import StallWatchdog, format_all_stacks, watchdog

__all__ = [
    "Flight",
    "FlightRecorder",
    "IslandLedger",
    "Observability",
    "OverlapWindow",
    "PEAK_TFLOPS_BY_DEVICE_KIND",
    "SeriesStore",
    "SpanTracer",
    "StallWatchdog",
    "ThroughputAccountant",
    "batch_token_count",
    "detect_peak_tflops",
    "device_memory_stats",
    "flight",
    "format_all_stacks",
    "host_rss_bytes",
    "param_count",
    "read_jsonl_series",
    "read_prometheus",
    "span",
    "tracer",
    "transformer_flops_per_token",
    "watchdog",
    "write_jsonl_series",
    "write_prometheus",
]
