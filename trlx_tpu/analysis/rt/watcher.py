"""CompileWatcher: runtime compile attribution for the zero-recompile gate.

The fixed-shape promise (docs/serving.md) says every registered entrypoint —
serving decode/verify/prefill steps, the PPO/GRPO train steps, the streamed
score fns — compiles a bounded number of times during *warmup* and exactly
**zero** times in *steady state*. This module measures that promise so the
budget gate (:mod:`trlx_tpu.analysis.rt.budget`) can enforce it.

Two complementary measurement channels, because neither alone is enough:

- ``track(name, jitted_fn)`` + ``poll()`` — reads the jitted callable's
  ``_cache_size()`` before/after; the diff is an exact compile count for that
  function. Authoritative where we hold the jitted object (the probes, the
  serving engine's step fns, bench's train step).
- ``jax.monitoring`` compile-duration events
  (``/jax/core/compile/backend_compile_duration``) — fire for *every* compile
  in the process but carry no function identity. The process's one dispatcher
  (:mod:`trlx_tpu.obs.compile_log`) attributes them to the innermost active
  :func:`attributed` scope on the current thread and forwards them to the
  active watcher, which accumulates their durations into
  ``compile_time_warmup_s``.

Each entry carries a *phase* (``warmup`` → ``steady``, flipped by
:meth:`mark_steady`); compiles land in the counter for the phase current at
poll/event time. The ledger exports as ``obs/compile/*`` gauges
(:func:`export_gauges`) and as the bench ``compile_ledger`` key.

Production call sites take :func:`attributed` from
:mod:`trlx_tpu.obs.compile_log`; it is re-exported here for the probes.
"""

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional

from trlx_tpu.obs import compile_log
from trlx_tpu.obs.compile_log import UNATTRIBUTED, attributed  # noqa: F401  (re-exported)

WARMUP = "warmup"
STEADY = "steady"


@dataclass
class EntryLedger:
    """Per-entrypoint compile accounting."""

    name: str
    phase: str = WARMUP
    warmup_compiles: int = 0
    steady_compiles: int = 0
    compile_time_warmup_s: float = 0.0
    compile_time_steady_s: float = 0.0
    #: compiles seen via monitoring events only (no tracked fn credited) —
    #: kept separate so tracked cache-size diffs are never double-counted
    event_compiles_warmup: int = 0
    event_compiles_steady: int = 0

    def record_compiles(self, n: int):
        if n <= 0:
            return
        if self.phase == WARMUP:
            self.warmup_compiles += n
        else:
            self.steady_compiles += n

    def record_event(self, duration_s: float):
        if self.phase == WARMUP:
            self.event_compiles_warmup += 1
            self.compile_time_warmup_s += duration_s
        else:
            self.event_compiles_steady += 1
            self.compile_time_steady_s += duration_s

    def as_dict(self) -> Dict[str, float]:
        return {
            "warmup_compiles": self.warmup_compiles,
            "steady_compiles": self.steady_compiles,
            "compile_time_warmup_s": round(self.compile_time_warmup_s, 6),
            "compile_time_steady_s": round(self.compile_time_steady_s, 6),
            "event_compiles_warmup": self.event_compiles_warmup,
            "event_compiles_steady": self.event_compiles_steady,
        }


class _TrackedFn:
    __slots__ = ("entry", "fn", "last_size")

    def __init__(self, entry: str, fn):
        self.entry = entry
        self.fn = fn
        self.last_size = _cache_size(fn)


def _cache_size(fn) -> int:
    """The jit cache size of a jitted callable; 0 when unavailable (not a
    jitted fn, or a jax without ``_cache_size``)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return 0


# The one ``jax.monitoring`` dispatcher lives in :mod:`trlx_tpu.obs.compile_log`
# (the trainer's always-on compile log uses it on every run); it forwards each
# compile, with its attributed entry, to whichever watcher is active.


class CompileWatcher:
    """Journal of per-entrypoint compiles, warmup vs steady state.

    Use as a context manager (``with CompileWatcher() as w:``) or via
    :meth:`install`/:meth:`uninstall`. Only one watcher is active at a time;
    nesting raises.
    """

    def __init__(self):
        self._entries: Dict[str, EntryLedger] = {}
        self._tracked: List[_TrackedFn] = []
        self._lock = threading.Lock()

    # -- lifecycle ------------------------------------------------------------

    def install(self) -> "CompileWatcher":
        compile_log.install()
        if not compile_log.set_watcher(self, expect=None):
            raise RuntimeError("another CompileWatcher is already active")
        return self

    def uninstall(self):
        self.poll()
        compile_log.set_watcher(None, expect=self)

    def __enter__(self) -> "CompileWatcher":
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()

    # -- registration ----------------------------------------------------------

    def entry(self, name: str) -> EntryLedger:
        with self._lock:
            led = self._entries.get(name)
            if led is None:
                led = self._entries[name] = EntryLedger(name)
            return led

    def track(self, name: str, fn) -> None:
        """Watch a jitted callable's cache size under entrypoint ``name``.
        Subsequent :meth:`poll` calls credit cache growth to ``name`` in its
        current phase."""
        self.entry(name)
        with self._lock:
            self._tracked.append(_TrackedFn(name, fn))

    def attributed(self, name: str):
        """Instance spelling of :func:`attributed`, creating the entry so the
        ledger shows the entrypoint even at zero compiles."""
        self.entry(name)
        return attributed(name)

    # -- phases ----------------------------------------------------------------

    def mark_steady(self, name: Optional[str] = None):
        """Flip ``name`` (or every entry) from warmup to steady state; polls
        first so pending warmup cache growth lands in warmup."""
        self.poll()
        with self._lock:
            entries = [self._entries[name]] if name else list(self._entries.values())
        for led in entries:
            led.phase = STEADY

    def mark_warmup(self, name: Optional[str] = None):
        """Return ``name`` (or every entry) to the warmup phase — bench legs
        reuse one watcher across several engine variants."""
        self.poll()
        with self._lock:
            entries = [self._entries[name]] if name else list(self._entries.values())
        for led in entries:
            led.phase = WARMUP

    # -- measurement -----------------------------------------------------------

    def poll(self):
        """Fold jit cache growth since the last poll into each tracked
        entrypoint's current phase."""
        with self._lock:
            tracked = list(self._tracked)
        for t in tracked:
            size = _cache_size(t.fn)
            grown = size - t.last_size
            if grown > 0:
                self.entry(t.entry).record_compiles(grown)
            t.last_size = size

    def _on_compile_event(self, entry: Optional[str], duration_s: float):
        self.entry(entry if entry is not None else UNATTRIBUTED).record_event(duration_s)

    # -- reporting -------------------------------------------------------------

    def ledger(self) -> Dict[str, Dict[str, float]]:
        self.poll()
        with self._lock:
            return {name: led.as_dict() for name, led in sorted(self._entries.items())}

    def steady_compiles(self, name: str) -> int:
        self.poll()
        with self._lock:
            led = self._entries.get(name)
        if led is None:
            return 0
        # tracked counts are authoritative when present; event counts cover
        # entrypoints observed only through attribution scopes
        return led.steady_compiles if led.steady_compiles else led.event_compiles_steady

    def export_gauges(self, registry=None):
        """Publish the ledger as ``obs/compile/<entry>/{warmup,steady,...}``
        gauges (docs/observability.md)."""
        if registry is None:
            from trlx_tpu.utils.metrics import gauges as registry  # type: ignore
        for name, led in self.ledger().items():
            base = f"obs/compile/{name}"
            registry.set(f"{base}/warmup_compiles", float(led["warmup_compiles"]))
            registry.set(f"{base}/steady_compiles", float(led["steady_compiles"]))
            registry.set(f"{base}/compile_time_warmup_s", led["compile_time_warmup_s"])
            registry.set(f"{base}/compile_time_steady_s", led["compile_time_steady_s"])
