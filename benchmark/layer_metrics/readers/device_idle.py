"""1 - (union of the device's op intervals) / traced window."""

from benchmark.harness import trace_reduce


def read(reading):
    trace = reading.get("trace")
    if trace is None or not reading.get("window_s"):
        return None
    busy = trace_reduce.busy_seconds(trace)
    if not busy:
        return None
    return 100.0 * (1.0 - busy / reading["window_s"])
