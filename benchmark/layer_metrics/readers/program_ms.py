"""Device milliseconds of the compiled programs whose name matches
``pattern`` (the trace's ``XLA Modules`` line), per step.  ``per`` says what
a step is: ``"steps"`` is the runner's own count of the traced window;
``"event"`` counts the program's runs in the trace itself, each worth
``reading[event_steps]`` steps (a decode chunk is ``sync_every`` steps).  A
host-side counter will not do for the pump: it counts at dispatch, and the
device runs up to a chunk behind."""

from benchmark.harness import trace_reduce


def read(reading, pattern, per="steps", event_steps=None):
    trace = reading.get("trace")
    if trace is None:
        return None
    seconds, events = trace_reduce.program_seconds(trace, pattern)
    count = reading.get("steps") if per == "steps" \
        else events * (reading[event_steps] if event_steps else 1)
    if not events or not count:
        return None
    return seconds / count * 1e3
