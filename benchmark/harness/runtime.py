"""What every runner needs of the machine: the chip or nothing, its
memory peak, the compile counter and the traced sub-window."""

from __future__ import annotations

import os
import shutil
import threading
import time
from typing import Optional

from benchmark.harness import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The ``chips`` TPU devices the cell runs on.  There is no CPU mode."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); jax found {len(devices)} x "
            f"{devices[0].platform} ({devices[0].device_kind})")
    return devices[:chips]


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


class CompileCounter:
    """Programs compiled or fetched from the persistent cache, process-wide
    (jax reports both through the same event)."""

    def __init__(self):
        from jax import monitoring

        self._lock = threading.Lock()
        self.count = 0
        self.names = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.names.append(str(kwargs.get("fun_name", "?")))


def enable_compile_cache() -> str:
    """The program's own switch: ``JAX_COMPILATION_CACHE_DIR`` if the
    machine sets it, else ``<checkout>/.jax_cache``.  Small programs are
    cached too, so a second run of a cell compiles nothing."""
    import jax

    from apex_tpu.utils.compile_cache import enable_compile_cache as enable

    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class Phases(dict):
    """Seconds of a run's phases by the host's clock, for its notes:
    ``mark(name)`` closes the phase that began at the last mark."""

    def __init__(self):
        super().__init__()
        self._t = time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self[name], self._t = round(now - self._t, 3), now


def annotate(name: str):
    """A host span in the profiler's trace, ``bench:<name>``."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


class TracedWindow:
    """``with TracedWindow(dir) as t: ...`` traces the body; afterwards
    ``t.trace`` is the reduced-form trace and ``t.window_s`` its length on
    the host clock.  Python-level tracing is off: it slows the host."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.trace = None
        self.window_s = 0.0

    def __enter__(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        if exc[0] is None:
            self.trace = trace_reduce.load(
                trace_reduce.find_xplane(self.trace_dir))
        # the xplane file is tens of MB a run: keep none of it on disk
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return False


def trace_dir() -> str:
    """Inside the checkout, so two sides of a comparison share nothing."""
    return os.path.join(ROOT, ".bench_trace")
