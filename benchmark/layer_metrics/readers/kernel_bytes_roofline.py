"""A memory-bound kernel's share of its roofline: the bytes the algorithm
must read per step (the program's own counter ``bytes_counter`` over
``steps_counter``, both deltas over the traced window) / HBM bandwidth,
over the device time per step of the kernels labelled ``labels``
(``kernel_ms``).  Both sides are per step, so the host counter's lead of one
chunk over the device cancels.  The counter holds what the algorithm needs,
not what the kernel's grid touches, so a wasteful grid reads low and
nothing reads over 100%."""

from benchmark.layer_metrics.readers import kernel_ms


def read(reading, labels, bytes_counter, steps_counter, **per_step):
    counters = reading.get("counters") or {}
    steps = counters.get(steps_counter)
    if not steps or bytes_counter not in counters:
        return None
    seconds = kernel_ms.seconds_per_step(reading, labels, **per_step)
    if not seconds:
        return None
    least = counters[bytes_counter] / steps / reading["peak"].hbm_bytes_per_s
    return 100.0 * least / seconds
