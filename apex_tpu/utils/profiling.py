"""Profiling/tracing utilities.

Reference: none — apex removed its profiler (apex.pyprof, README points to
the archived repo); what remains is nvtx-friendly kernel naming and
``torch.cuda.synchronize()`` timing discipline in examples
(examples/imagenet/main_amp.py). SURVEY.md §5 prescribes jax.profiler
annotation + block_until_ready timing from day one as a gap to EXCEED.

- ``annotate(name)``: decorator adding a jax.profiler/XLA named scope, so
  kernels and modules show up as labeled spans in TensorBoard/xprof traces
  (the nvtx-range analog).
- ``trace(logdir)``: context manager around jax.profiler.trace.
- ``time_fn(fn, *args)``: wall-time with block_until_ready (the
  cuda-synchronize discipline).
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Callable

import jax


def annotate(name: str) -> Callable:
    """Decorator: run the function under a named profiler scope."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapped

    return deco


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    """Capture a profiler trace of the enclosed block to ``logdir``."""
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def time_fn(fn: Callable, *args, iters: int = 10, warmup: int = 2, **kwargs):
    """Mean wall-seconds per call, synchronized (block_until_ready).

    Returns (seconds_per_iter, last_output).
    """
    out = None
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out
