"""Hierarchical span tracer: per-step phase timings + Chrome trace events.

The async rollout engine (docs/rollout.md) made the training loop concurrent —
a producer thread, a bounded queue, and the learner interleave — and the only
way to answer "where did the step time go?" is to time each phase on the
thread it runs on and line the results up on one clock. :class:`SpanTracer`
does exactly that:

- ``with tracer.span("generate")`` times a phase on the calling thread.
  Spans nest: a per-thread stack builds dotted paths (``produce.generate``),
  so the same code timed from different contexts stays distinguishable.
- Durations accumulate into a per-path aggregate that the trainer drains once
  per step (:meth:`drain_step_times`) and exports as ``time/span/<path>``
  stats through whatever tracker backend is configured.
- When ``trace_path`` is set, every span also becomes a Chrome-trace-event
  (``ph: "X"`` complete event, microsecond timestamps, real thread ids), so
  :meth:`write_trace` emits a ``trace.json`` that chrome://tracing and
  Perfetto load directly — producer and learner phases interleaved on one
  timeline, the visual answer to "did generation overlap learning?".
- Every span, whether or not the tracer is enabled, enters a
  ``jax.profiler.TraceAnnotation`` named ``trlx/<leaf name>``
  (``trlx/learn.put``), so the program's spans appear as named host ranges
  in any open profiler session (``train.profile_dir``, the benchmark's
  ``--trace 1``) on the device trace's clock, told apart from a harness's or
  an operator's own annotations of the same names. The profiler session is
  the only switch: with none open the annotation costs about a microsecond.

A disabled tracer (the default) does nothing else: ``span()`` hands back the
bare annotation before taking any lock, timestamp or dict — the "overhead is
negligible with flags off" contract.

The process-global :data:`tracer` mirrors :data:`trlx_tpu.utils.metrics.gauges`:
subsystems call the module-level :func:`span` without knowing who configured
tracing; the trainer configures/enables it from ``TRLConfig.train.observability``.
"""

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: what the profiler's name of a span starts with: the benchmark's harness (and
#: an operator) annotates ``learn``, ``score``, ``reward`` itself, around the
#: same calls, and counts its own
PROFILER_PREFIX = "trlx/"


class SpanTracer:
    """Thread-safe hierarchical span timer (see module docstring)."""

    def __init__(
        self,
        enabled: bool = False,
        trace_path: Optional[str] = None,
        max_events: int = 100_000,
    ):
        self.enabled = enabled
        self.trace_path = trace_path
        self.max_events = int(max_events)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._step_times: Dict[str, float] = {}
        self._step_counts: Dict[str, int] = {}
        self._events: List[Dict[str, Any]] = []
        self._dropped_events = 0
        self._thread_names: Dict[int, str] = {}
        # one origin for every thread's timestamps: trace events must share a clock
        self._epoch = time.perf_counter()

    def configure(
        self,
        enabled: bool,
        trace_path: Optional[str] = None,
        max_events: int = 100_000,
    ):
        """Reconfigure in place (the global tracer outlives any one trainer)."""
        with self._lock:
            self.enabled = enabled
            self.trace_path = trace_path
            self.max_events = int(max_events)

    # ------------------------------------------------------------------ spans

    def _stack(self) -> List[tuple]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str):
        """Time a phase; nested calls build a dotted path per thread. The
        profiler sees ``trlx/<name>``, the leaf, in both states of the tracer."""
        # lock-free read is the "flags off costs one attribute check" contract;
        # a configure() racing a span at worst mistimes that one span
        if not self.enabled:  # graftcheck: noqa[TH001,CC001]
            return TraceAnnotation(PROFILER_PREFIX + name)
        return self._timed_span(name)

    @contextlib.contextmanager
    def _timed_span(self, name: str):
        stack = self._stack()  # (name, path) of the open spans, the innermost last
        if not stack:
            path = name
        else:
            parent, parent_path = stack[-1]
            # ``learn.put`` under ``learn`` carries its parent's name already
            path = parent_path + (name[len(parent):] if name.startswith(parent + ".") else f".{name}")
        stack.append((name, path))
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(PROFILER_PREFIX + name):
                yield
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
            with self._lock:
                self._step_times[path] = self._step_times.get(path, 0.0) + dur
                self._step_counts[path] = self._step_counts.get(path, 0) + 1
                if self.trace_path is not None:
                    if len(self._events) < self.max_events:
                        tid = threading.get_ident()
                        self._thread_names.setdefault(
                            tid, threading.current_thread().name
                        )
                        self._events.append(
                            {
                                "name": path,
                                "ph": "X",
                                "ts": (t0 - self._epoch) * 1e6,  # microseconds
                                "dur": dur * 1e6,
                                "pid": os.getpid(),
                                "tid": tid,
                                "cat": "host",
                            }
                        )
                    else:
                        self._dropped_events += 1

    # ----------------------------------------------------------------- export

    def drain_step_times(self, prefix: str = "time/span/") -> Dict[str, float]:
        """Return accumulated per-path seconds since the last drain and reset.

        Spans recorded on worker threads between two learner steps are drained
        with the later step — per-step attribution for the overlapped phases.
        Each path also drains its call count as ``<prefix><path>_n``, so
        per-call latency is computable from tracker stats (seconds / n).
        """
        with self._lock:
            out = {f"{prefix}{k}": v for k, v in self._step_times.items()}
            for k, n in self._step_counts.items():
                out[f"{prefix}{k}_n"] = float(n)
            self._step_times.clear()
            self._step_counts.clear()
        return out

    def write_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write accumulated events as Chrome trace-event JSON; returns the path
        (None when tracing was off or nothing was recorded)."""
        with self._lock:
            path = path or self.trace_path
            events = list(self._events)
            thread_names = dict(self._thread_names)
            dropped = self._dropped_events
        if path is None:
            return None
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": os.getpid(),
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in thread_names.items()
        ]
        doc: Dict[str, Any] = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if dropped:
            doc["metadata"] = {"dropped_events": dropped}
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    @property
    def epoch(self) -> float:
        """Timestamp origin every recorded event is relative to — external
        event producers (the flight recorder's per-uid async lanes) rebase
        onto this so merged events share the trace's clock."""
        with self._lock:
            return self._epoch

    def add_events(self, events: List[Dict[str, Any]]):
        """Merge externally produced Chrome trace events (e.g. the
        FlightRecorder's per-uid async lanes) into the event stream, under
        the same ``max_events`` bound as native spans."""
        with self._lock:
            room = max(0, self.max_events - len(self._events))
            self._events.extend(events[:room])
            self._dropped_events += max(0, len(events) - room)

    def snapshot_events(self) -> List[Dict[str, Any]]:
        """Copy of the accumulated trace events (requires ``trace_path``).

        Lets tests and bench legs verify time-window relationships between
        spans on different threads (e.g. reward spans nested inside the decode
        span during stream-overlapped PPO) without writing a trace file.
        """
        with self._lock:
            return [dict(ev) for ev in self._events]

    def reset(self):
        """Drop all accumulated state (tests / a fresh training run)."""
        with self._lock:
            self._step_times.clear()
            self._step_counts.clear()
            self._events.clear()
            self._thread_names.clear()
            self._dropped_events = 0
            self._epoch = time.perf_counter()


#: Process-global tracer; subsystems open spans, the trainer configures/drains.
tracer = SpanTracer()


def span(name: str):
    """``with span("generate"):`` against the process-global tracer."""
    return tracer.span(name)
