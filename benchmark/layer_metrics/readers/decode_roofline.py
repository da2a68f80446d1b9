"""The decode step against streaming the weights once: bytes of the
engine's variables as held on the device / HBM bandwidth, over the device
time of a decode step.  Weights only: the K/V bytes a step reads need a
counter of tokens attended that the program lacks (tracing issue), so the
true least time is a little higher and this share a little low; it cannot
read over 100%."""

from benchmark.harness import trace_reduce


def read(reading, pattern):
    trace = reading.get("trace")
    if trace is None:
        return None
    seconds, events = trace_reduce.program_seconds(trace, pattern)
    steps = events * reading["sync_every"]      # a chunk is that many steps
    if not steps:
        return None
    least = reading["weight_bytes"] / reading["peak"].hbm_bytes_per_s
    return 100.0 * least / (seconds / steps)
