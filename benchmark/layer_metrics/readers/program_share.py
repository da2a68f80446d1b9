"""Device time of the programs matching ``pattern`` as a share of the
device's busy time."""

from benchmark.harness import trace_reduce


def read(reading, pattern):
    trace = reading.get("trace")
    if trace is None:
        return None
    seconds, events = trace_reduce.program_seconds(trace, pattern)
    busy = trace_reduce.busy_seconds(trace)
    if not events or not busy:
        return None
    return 100.0 * seconds / busy
