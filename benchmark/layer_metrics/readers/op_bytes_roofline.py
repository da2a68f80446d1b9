"""``kernel_bytes_roofline`` for operations found by their text and the
program they run in (``op_ms``): the bytes the algorithm must read per step
(the program's counter ``bytes_counter`` over ``steps_counter``, deltas over
the traced window) / HBM bandwidth, over those operations' device time per
step.  The counter holds what the step's own choices made it read, so
nothing reads over 100%."""

from benchmark.layer_metrics.readers import op_ms


def read(reading, pattern, event_pattern, bytes_counter, steps_counter,
         event_steps=None):
    counters = reading.get("counters") or {}
    steps = counters.get(steps_counter)
    if not steps or bytes_counter not in counters:
        return None
    seconds = op_ms.seconds_per_step(reading, pattern, event_pattern,
                                     event_steps)
    if not seconds:
        return None
    least = counters[bytes_counter] / steps / reading["peak"].hbm_bytes_per_s
    return 100.0 * least / seconds
