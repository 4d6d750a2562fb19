"""The program's record of its own XLA compiles: which, when, how long.

jax reports every backend compile through ``jax.monitoring`` but carries no
function identity and offers no per-listener unregister. So the process has
exactly one dispatcher, installed at most once (:func:`install`; every trainer
calls it as it is built, before its first compile), that

- appends ``(time.monotonic(), seconds, entry)`` to the bounded,
  process-global :data:`log` for every
  ``/jax/core/compile/backend_compile_duration`` (a persistent-cache hit's
  retrieval is inside that event too), ``entry`` being the innermost
  :func:`attributed` scope open on the compiling thread, or None;
- counts ``/jax/compilation_cache/compile_requests_use_cache`` and
  ``/jax/compilation_cache/cache_hits``: their difference is the programs the
  persistent cache did not give back;
- forwards each compile to the active
  :class:`trlx_tpu.analysis.rt.watcher.CompileWatcher`, if one is installed
  (the zero-recompile gate keeps its own per-phase ledger).

Nothing here runs per step: the callbacks fire per compile, and a trainer in
steady state has none. ``_learn_loop`` warns, naming the step and the entries,
when :attr:`CompileLog.total` moves after the first full iteration. The log
outlives any trainer, so an after-the-fact reader (the benchmark's
``setup_compile_s`` / ``setup_compiled_anew``) can ask what happened before a
given time.

``jax.monitoring`` is imported inside :func:`install`, not with this module.
"""

import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

UNATTRIBUTED = "__unattributed__"

Compile = Tuple[float, float, Optional[str]]  # time.monotonic(), seconds, entry


class CompileLog:
    """Bounded record of compiles and persistent-cache look-ups. The newest
    ``capacity`` of each are kept with their time; ``total`` counts them all."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._compiles: Deque[Compile] = deque(maxlen=capacity)
        self._cache_events: Dict[str, Deque[float]] = {
            CACHE_REQUEST_EVENT: deque(maxlen=capacity), CACHE_HIT_EVENT: deque(maxlen=capacity)}
        self._total = 0

    def record_compile(self, seconds: float, entry: Optional[str], now: Optional[float] = None):
        with self._lock:
            self._compiles.append((time.monotonic() if now is None else now, float(seconds), entry))
            self._total += 1

    def record_cache_event(self, event: str, now: Optional[float] = None):
        with self._lock:
            self._cache_events[event].append(time.monotonic() if now is None else now)

    @property
    def total(self) -> int:
        """Compiles recorded since the process started (or :meth:`reset`)."""
        with self._lock:
            return self._total

    def compiles(self, before: Optional[float] = None) -> List[Compile]:
        """The kept compiles, oldest first; only those earlier than ``before``."""
        with self._lock:
            return [c for c in self._compiles if before is None or c[0] < before]

    def compile_seconds(self, before: Optional[float] = None) -> float:
        return sum(seconds for _, seconds, _ in self.compiles(before))

    def compiled_anew(self, before: Optional[float] = None) -> int:
        """Persistent-cache look-ups less hits: what the cache did not give
        back (a program too quick to be kept counts every time)."""
        with self._lock:
            requests, hits = (
                sum(1 for t in self._cache_events[event] if before is None or t < before)
                for event in (CACHE_REQUEST_EVENT, CACHE_HIT_EVENT))
        return requests - hits

    def by_entry(self, before: Optional[float] = None) -> Dict[str, Tuple[int, float]]:
        """entry -> (compiles, seconds) over the kept compiles (earlier than ``before``)."""
        out: Dict[str, Tuple[int, float]] = {}
        for _, seconds, entry in self.compiles(before):
            n, s = out.get(entry or UNATTRIBUTED, (0, 0.0))
            out[entry or UNATTRIBUTED] = (n + 1, s + seconds)
        return out

    def export_gauges(self, registry=None):
        """Publish ``obs/compile/<entry>/{compiles,compile_time_s}`` and
        ``obs/compile/compiled_anew`` as gauges (docs/observability.md)."""
        if registry is None:
            from trlx_tpu.utils.metrics import gauges as registry
        for entry, (n, seconds) in self.by_entry().items():
            registry.set(f"obs/compile/{entry}/compiles", float(n))
            registry.set(f"obs/compile/{entry}/compile_time_s", seconds)
        registry.set("obs/compile/compiled_anew", float(self.compiled_anew()))

    def reset(self):
        with self._lock:
            self._compiles.clear()
            for times in self._cache_events.values():
                times.clear()
            self._total = 0


#: Process-global log; the dispatcher below feeds it once installed.
log = CompileLog()

# -- the one dispatcher --------------------------------------------------------

_watcher = None  # the active CompileWatcher, if any
_installed = False
_install_lock = threading.Lock()
_tls = threading.local()


def _attribution_stack() -> List[str]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class attributed:
    """``with attributed(name):`` attributes this thread's compiles to ``name``
    while the scope is open. Call sites wrap their jitted calls in it
    unconditionally: it is a push and a pop on a thread-local list."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        _attribution_stack().append(self.name)

    def __exit__(self, *exc):
        _attribution_stack().pop()


def _on_duration(event: str, duration_s: float, **kwargs):
    if event != COMPILE_EVENT:
        return
    stack = _attribution_stack()
    entry = stack[-1] if stack else None
    log.record_compile(duration_s, entry)
    watcher = _watcher
    if watcher is not None:
        watcher._on_compile_event(entry, duration_s)


def _on_event(event: str, **kwargs):
    if event in (CACHE_REQUEST_EVENT, CACHE_HIT_EVENT):
        log.record_cache_event(event)


def install():
    """Register the dispatcher with ``jax.monitoring``; once per process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def set_watcher(watcher, expect=None) -> bool:
    """Make ``watcher`` the one the dispatcher forwards to, if the current one
    is ``expect``; says whether it did."""
    global _watcher
    with _install_lock:
        if _watcher is not expect:
            return False
        _watcher = watcher
        return True
