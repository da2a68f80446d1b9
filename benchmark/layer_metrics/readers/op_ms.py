"""Device milliseconds per step of the operations whose text on the trace's
``XLA Ops`` line matches ``pattern``, counted only while a program matching
``event_pattern`` runs (its events on ``XLA Modules``), per run of that
program x ``reading[event_steps]`` steps.  For work that carries no kernel
label: XLA's own Mosaic calls (``jax.lax.ragged_dot`` is ``%ragged-dot-*``
on the TPU), which also run inside other programs (the admit programs route
their prompts through the same experts) and must not be charged to the
decode step.  A program that has no such op has nothing to read."""

import bisect
import re

from benchmark.harness import trace_reduce


def seconds_per_step(reading, pattern, event_pattern, event_steps=None):
    trace = reading.get("trace")
    planes = trace_reduce.device_planes(trace) if trace else []
    if not planes:
        return None
    op_rx, event_rx = re.compile(pattern), re.compile(event_pattern)
    total, found, runs = 0, 0, 0
    for plane in planes:
        spans = sorted((s, s + d) for name, s, d in
                       trace[plane].get(trace_reduce.MODULES_LINE, [])
                       if event_rx.search(name))
        runs += len(spans)
        starts = [s for s, _ in spans]
        for name, start, dur in trace[plane].get(trace_reduce.OPS_LINE, []):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < spans[i][1] and op_rx.search(name):
                total += dur
                found += 1
    steps = runs * (reading[event_steps] if event_steps else 1)
    if not found or not steps:
        return None
    # planes cancel: both sums run over every chip
    return total / 1e9 / steps


def read(reading, pattern, event_pattern, event_steps=None):
    seconds = seconds_per_step(reading, pattern, event_pattern, event_steps)
    return None if seconds is None else seconds * 1e3
