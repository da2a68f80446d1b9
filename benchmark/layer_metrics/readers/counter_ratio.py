"""The delta of one of the frontend's counters over the traced window, over
the delta of another (``denominator``) or, without one, over the window's
seconds; times ``scale`` (1e3 for seconds -> ms, 100 for a share in %).
The counters read here hold seconds of a run, so where the traced window
saw no device (a rehearsal on the CPU) there is no number to give; nor from
a program that lacks the counter."""

from benchmark.harness import trace_reduce


def read(reading, numerator, denominator=None, scale=1.0):
    trace, counters = reading.get("trace"), reading.get("counters") or {}
    if trace is None or not trace_reduce.device_planes(trace) \
            or numerator not in counters:
        return None
    over = reading.get("window_s") if denominator is None \
        else counters.get(denominator)
    if not over:
        return None
    return scale * counters[numerator] / over
