"""Observability runtime: one facade the trainer drives once per step.

:class:`Observability` binds the obs primitives (span tracer, throughput/MFU
accountant, device-memory sampler, stall watchdog — each usable standalone)
to ``TRLConfig.train.observability``:

- ``__init__`` configures the process-global tracer and installs the global
  watchdog so subsystems that only know the module-level ``span()`` /
  ``watchdog.beat()`` (the rollout engine) feed the same run.
- :meth:`configure_model` snapshots what MFU needs (param count, device
  count, peak FLOP/s) once the params exist.
- :meth:`step_stats` is the per-step drain: span timings, tokens/sec + MFU,
  step-time histogram percentiles, and (rate-limited) device-memory gauges —
  one flat dict merged into the stats the tracker logs.
- :meth:`close` writes ``trace.json`` and stops/uninstalls the watchdog; it
  is idempotent and safe to call from ``learn()``'s finally.

When ``observability.enabled`` is False everything here short-circuits:
``step_stats`` returns ``{}``, the tracer stays disabled (a span is only its
profiler annotation), and no watchdog thread exists — per-step stats are exactly
the pre-obs ones.
"""

import os
import time
from typing import Any, Dict, Optional

from trlx_tpu.obs.compile_log import log as compile_log
from trlx_tpu.obs.flight import flight as global_flight
from trlx_tpu.obs.memory import device_memory_stats
from trlx_tpu.obs.spans import tracer as global_tracer
from trlx_tpu.obs.throughput import (
    ThroughputAccountant,
    detect_peak_tflops,
    param_count,
)
from trlx_tpu.obs.timeseries import SeriesStore
from trlx_tpu.obs.watchdog import StallWatchdog
from trlx_tpu.obs.watchdog import watchdog as global_watchdog
from trlx_tpu.utils import logging
from trlx_tpu.utils.metrics import gauges

logger = logging.get_logger(__name__)


class Observability:
    """Configured obs layer for one training run (see module docstring)."""

    def __init__(self, cfg, logging_dir: Optional[str] = None):
        self.cfg = cfg
        self.enabled = bool(cfg.enabled)
        self.tracer = global_tracer
        self.flight = global_flight
        self.accountant: Optional[ThroughputAccountant] = None
        self.watchdog: Optional[StallWatchdog] = None
        self.series: Optional[SeriesStore] = None
        self._series_path: Optional[str] = None
        self._prom_path: Optional[str] = None
        self._step_count = 0
        self._compiles_exported = 0
        self._last_step_end: Optional[float] = None
        self._closed = False
        if not self.enabled:
            return
        trace_path = cfg.trace_path
        if trace_path and not os.path.isabs(trace_path) and logging_dir:
            trace_path = os.path.join(logging_dir, trace_path)
        self.tracer.reset()
        self.tracer.configure(
            enabled=True,
            trace_path=trace_path,
            max_events=cfg.max_trace_events,
        )
        # getattr-defensive config reads: older ObservabilityConfig instances
        # (tests constructing the dataclass by hand) predate the flight fields
        if getattr(cfg, "flight", True):
            self.flight.reset()
            self.flight.configure(
                enabled=True,
                ring=getattr(cfg, "flight_ring", 2048),
                reservoir=getattr(cfg, "flight_reservoir", 256),
            )
        self.series = SeriesStore(
            capacity=int(getattr(cfg, "series_capacity", 512))
        )
        for name, attr in (
            ("series_path", "_series_path"), ("prom_path", "_prom_path")
        ):
            p = getattr(cfg, name, None)
            if p and not os.path.isabs(p) and logging_dir:
                p = os.path.join(logging_dir, p)
            setattr(self, attr, p)
        if cfg.watchdog_timeout_s > 0:
            self.watchdog = StallWatchdog(
                cfg.watchdog_timeout_s, poll_s=cfg.watchdog_poll_s
            )
            global_watchdog.install(self.watchdog)
            self.watchdog.start()

    # ------------------------------------------------------------------ model

    def configure_model(self, params: Any, model_config: Any = None):
        """Size the MFU denominator from the live params + mesh; called once
        when learning starts (params don't exist at trainer __init__)."""
        if not self.enabled or not self.cfg.mfu:
            return
        import jax

        peak = self.cfg.peak_device_tflops
        if peak is None:
            peak = detect_peak_tflops(jax.devices()[0].device_kind)
        self.accountant = ThroughputAccountant(
            param_count(params),
            num_devices=jax.device_count(),
            peak_device_tflops=peak,
            num_layers=getattr(model_config, "num_layers", 0) or 0,
            hidden_size=getattr(model_config, "hidden_size", 0) or 0,
        )
        if peak is None:
            logger.info(
                "MFU denominator unknown for device kind "
                f"{jax.devices()[0].device_kind!r}: reporting model TFLOP/s "
                "only (set train.observability.peak_device_tflops to enable mfu)"
            )

    # ------------------------------------------------------------------- step

    def span(self, name: str):
        return self.tracer.span(name)

    def beat(self, name: str = "learner"):
        if self.watchdog is not None:
            self.watchdog.beat(name)

    def step_stats(self, tokens: int, samples: int, seq_len: int = 0) -> Dict[str, float]:
        """Per-step obs stats: span timings, throughput/MFU over the wall time
        since the previous call, step-time percentiles, memory gauges."""
        if not self.enabled:
            return {}
        now = time.monotonic()
        step_time = None if self._last_step_end is None else now - self._last_step_end
        self._last_step_end = now
        self._step_count += 1
        stats = self.tracer.drain_step_times()
        if step_time is not None:
            stats["time/step"] = step_time
            gauges.observe("time/step", step_time)
            stats.update(gauges.hist_snapshot("time/step"))
            if self.accountant is not None:
                stats.update(
                    self.accountant.step_stats(tokens, samples, step_time, seq_len=seq_len)
                )
        interval = self.cfg.memory_interval
        if interval and self._step_count % interval == 0:
            stats.update(device_memory_stats())
        # flight percentiles refresh before the obs/ snapshot so the
        # per-tenant phase gauges ride the same per-step export
        self.flight.export_gauges()
        if compile_log.total != self._compiles_exported:  # only after a compile
            self._compiles_exported = compile_log.total
            compile_log.export_gauges()
        stats.update(gauges.snapshot("obs/"))
        # resilience gauges (retry counts, inflight checkpoint writes, commit
        # latency) ride the same per-step export to every tracker backend
        stats.update(gauges.snapshot("resilience/"))
        if self.series is not None:
            # one sample of EVERY gauge per step — the exporters dump these
            # rings on close, and windowed consumers (autoscaler/ledger hold
            # their own stores) stay decoupled from this one
            self.series.sample()
        return stats

    # -------------------------------------------------------------- lifecycle

    def close(self):
        """Write the trace file and tear down the watchdog (idempotent)."""
        if not self.enabled or self._closed:
            return
        self._closed = True
        if self.watchdog is not None:
            global_watchdog.install(None)  # also stops it
            self.watchdog = None
        if self.series is not None:
            from trlx_tpu.obs.export import write_jsonl_series, write_prometheus

            try:
                if self._series_path:
                    p = write_jsonl_series(self.series, self._series_path)
                    logger.info(f"wrote gauge time-series to {p}")
                if self._prom_path:
                    p = write_prometheus(self._prom_path)
                    logger.info(f"wrote Prometheus exposition to {p}")
            except OSError as e:
                logger.warning(f"could not write series exports: {e}")
        try:
            if self.flight.enabled and self.tracer.trace_path is not None:
                # merge per-uid flight lanes into the span trace: one request
                # reads as one async lane next to the host spans in Perfetto
                self.tracer.add_events(
                    self.flight.trace_events(epoch=self.tracer.epoch)
                )
            path = self.tracer.write_trace()
            if path:
                logger.info(f"wrote span trace to {path} (chrome://tracing / Perfetto)")
        except OSError as e:
            logger.warning(f"could not write span trace: {e}")
        self.tracer.configure(enabled=False)
        self.flight.configure(enabled=False)


def batch_token_count(batch: Any) -> tuple:
    """Best-effort (tokens, samples, seq_len) for a train batch — works for
    PPORLBatch (query+response masks), dict batches with attention_mask, and
    falls back to dense input_ids shapes."""
    import numpy as np

    def total(x):
        return int(np.sum(np.asarray(x)))

    attn = getattr(batch, "attention_mask", None)
    resp = getattr(batch, "response_mask", None)
    if attn is None and isinstance(batch, dict):
        attn = batch.get("attention_mask")
        resp = batch.get("response_mask")
    if attn is not None:
        tokens = total(attn) + (total(resp) if resp is not None else 0)
        samples = int(np.asarray(attn).shape[0])
        seq_len = int(np.asarray(attn).shape[1]) + (
            int(np.asarray(resp).shape[1]) if resp is not None else 0
        )
        return tokens, samples, seq_len
    ids = batch.get("input_ids") if isinstance(batch, dict) else getattr(batch, "input_ids", None)
    if ids is not None:
        arr = np.asarray(ids) if not isinstance(ids, list) else None
        if arr is not None and arr.ndim >= 2:
            return int(arr.size), int(arr.shape[0]), int(arr.shape[1])
        if isinstance(ids, list):
            return sum(len(p) for p in ids), len(ids), max((len(p) for p in ids), default=0)
    return 0, 0, 0
