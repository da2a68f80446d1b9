"""Recompile / trace-cache watcher over ``jax.monitoring``.

A recompile inside the serving pump is a latency cliff: one shape drift
in the decode chunk and every request on the box stalls behind an XLA
compile. The IR lint tier bounds compile-key cardinality *statically*
(``ir-compile-key-cardinality``); this module watches the *dynamic*
counterpart — what actually compiled at runtime — and feeds it into the
PR 4 instrument registry:

- ``jit.compiles`` (Counter, ``fn`` label) + ``jit.compile_ms``
  (Histogram, ``fn`` label) — one increment/observation per XLA backend
  compile, keyed by the jitted function's name.
- ``jit.trace_cache_misses`` (Counter, ``fn`` label) — one increment per
  jaxpr re-trace (every trace-cache miss re-stages the program; most
  then also compile).

Mechanism: ``jax.monitoring.register_event_duration_secs_listener``
subscribes to jax's own ``/jax/core/compile/...`` duration events; each
carries the jitted function's name as its ``fun_name`` keyword, which
keys the instruments.

One process-wide watcher (:func:`watcher`) is installed lazily on first
use — the serving frontend snapshots its counters per run and raises a
``compile_storm`` warning event when one function name recompiles more
than ``DEFAULT_STORM_THRESHOLD`` times within a single frontend's
lifetime (docs/observability.md).

Attribution caveat: compiles are PROCESS-wide facts (jax has one trace
cache), so a frontend's ``stats()`` deltas and storm window see every
compile in the process during its lifetime — including another
concurrently live engine's. With the usual one-serving-engine-per-
process deployment the attribution is exact; with several, treat
``jit.compiles`` as a process number and ``compile_storm`` as a
process-level warning that happened to be noticed by this frontend.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

from jax import monitoring

from apex_tpu.utils import metrics

__all__ = ["CompileWatcher", "watcher", "DEFAULT_STORM_THRESHOLD"]

#: compiles of ONE function name within one frontend run that count as a
#: recompile storm (bucketed admission legitimately compiles once per
#: prompt bucket — the threshold sits above any sane bucket count)
DEFAULT_STORM_THRESHOLD = 8

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_UNKNOWN = "<unknown>"


class CompileWatcher:
    """Subscribes to jax compile/trace events; see the module docstring.

    Thread-safe: compiles happen on whichever thread first calls a
    jitted function (the pump, a submitter, an exporter warming up), so
    every mutation of the per-name tables takes ``self._lock``.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._compiles: Dict[str, int] = {}
        self._trace_misses: Dict[str, int] = {}
        self._installed = False

    # -- recording -----------------------------------------------------------

    # the listener runs synchronously inside jax's compile path on
    # arbitrary threads; it only updates host-side counters
    # tpu-lint: host-boundary -- monitoring callback, never traced
    def _on_duration(self, event, duration, *, fun_name=_UNKNOWN,
                     **kwargs) -> None:
        name = str(fun_name)
        if event == _COMPILE_EVENT:
            with self._lock:
                self._compiles[name] = self._compiles.get(name, 0) + 1
            metrics.counter("jit.compiles", labels={"fn": name}).inc()
            metrics.histogram("jit.compile_ms", labels={"fn": name}) \
                .observe(duration * 1e3)
        elif event == _TRACE_EVENT:
            with self._lock:
                self._trace_misses[name] = \
                    self._trace_misses.get(name, 0) + 1
            metrics.counter("jit.trace_cache_misses",
                            labels={"fn": name}).inc()

    # -- install / uninstall -------------------------------------------------

    def install(self) -> "CompileWatcher":
        """Idempotently subscribe to jax. Safe to call from any thread."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def uninstall(self) -> None:
        """Unsubscribe (tests); counts/instruments are kept."""
        with self._lock:
            if not self._installed:
                return
            self._installed = False
        monitoring.unregister_event_duration_listener(self._on_duration)

    # -- reads ---------------------------------------------------------------

    def counts(self) -> Dict[str, int]:
        """Per-function-name backend-compile counts (a copy)."""
        with self._lock:
            return dict(self._compiles)

    def trace_misses(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._trace_misses)

    def totals(self) -> Tuple[int, int]:
        """(total compiles, total trace-cache misses)."""
        with self._lock:
            return (sum(self._compiles.values()),
                    sum(self._trace_misses.values()))

    def storms(self, since: Optional[Dict[str, int]] = None,
               threshold: int = DEFAULT_STORM_THRESHOLD
               ) -> Dict[str, int]:
        """Function names whose compile count grew by >= ``threshold``
        since the ``since`` snapshot (``counts()`` at window start;
        None = process start). Returns {name: compiles_in_window}."""
        base = since or {}
        out = {}
        with self._lock:
            for name, n in self._compiles.items():
                delta = n - base.get(name, 0)
                if delta >= threshold:
                    out[name] = delta
        return out


_PROCESS_WATCHER: Optional[CompileWatcher] = None
_PROCESS_LOCK = threading.Lock()


def watcher() -> CompileWatcher:
    """The process-wide watcher, installed on first call (the serving
    frontend's constructor uses this — one set of hooks per process no
    matter how many engines run)."""
    global _PROCESS_WATCHER
    with _PROCESS_LOCK:
        if _PROCESS_WATCHER is None:
            _PROCESS_WATCHER = CompileWatcher().install()
        return _PROCESS_WATCHER
