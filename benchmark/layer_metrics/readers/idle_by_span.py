"""The first chip's idle gaps (5 us or more) whose covering host span
matches ``pattern``, as a share of the traced window in %.  The attribution
is ``trace_reduce.idle_gaps``' own: a gap goes to the span that covers most
of it, the innermost of equals, so a gap inside a nested child is the
child's and one that straddles children is their parent's.  ``idle_gaps``
ranks every name and cuts the list, so it is asked for more names than a
trace holds and the names are grouped here.  Part of ``device_idle.serve``:
the shares of disjoint patterns add up to its gap part.  No device plane in
the trace (a rehearsal on the CPU), no number."""

import re

from benchmark.harness import trace_reduce

EVERY_NAME = 1_000_000


def read(reading, pattern):
    trace = reading.get("trace")
    if trace is None or not reading.get("window_s") \
            or not trace_reduce.device_planes(trace):
        return None
    rx = re.compile(pattern)
    seconds = sum(s for name, s in trace_reduce.idle_gaps(trace, EVERY_NAME)
                  if rx.search(name))
    return 100.0 * seconds / reading["window_s"]
