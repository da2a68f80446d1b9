"""Device milliseconds per step of the kernels that carry one of ``labels``
(a regular expression over ``apex_tpu.ops._dispatch.KERNEL_LABELS``).  The
label is in the op's own text on the trace's ``XLA Ops`` line:
``frontend_attributes={kernel_metadata={"kernel":"<label>"}}``, whatever
flax module or Python closure the op's result is named after.  ``per`` says
what a step is, as ``program_ms`` does: ``"steps"`` is the runner's count of
the traced window; ``"event"`` counts the runs of the programs matching
``event_pattern`` in the trace itself, each worth ``reading[event_steps]``
steps (a decode chunk is ``sync_every`` steps).  A program without labels
(one from before they existed) has nothing to read."""

from benchmark.harness import trace_reduce


def pattern(labels):
    # the quotes may come escaped in a name; \b keeps flash_fwd off flash_fwd_x
    return r"kernel\W{1,8}(?:%s)\b" % labels


def seconds_per_step(reading, labels, per="steps", event_pattern=None,
                     event_steps=None):
    trace = reading.get("trace")
    if trace is None:
        return None
    seconds, events = trace_reduce.op_seconds(trace, pattern(labels))
    if per == "steps":
        count = reading.get("steps")
    else:
        _, runs = trace_reduce.program_seconds(trace, event_pattern)
        count = runs * (reading[event_steps] if event_steps else 1)
    if not events or not count:
        return None
    return seconds / count


def read(reading, labels, per="steps", event_pattern=None, event_steps=None):
    seconds = seconds_per_step(reading, labels, per, event_pattern,
                               event_steps)
    return None if seconds is None else seconds * 1e3
