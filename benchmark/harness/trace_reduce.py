"""From the profiler's ``.xplane.pb`` to numbers.

Two stages, so the arithmetic can be checked on a small recorded trace
(``tests/benchmark/data/``) without the profiler: :func:`load` reads the
file with ``jax.profiler.ProfileData`` into plain lists; everything else
works on those lists.

A trace is ``{plane: {line: [[name, start_ns, duration_ns], ...]}}``.  On
the v5e a chip is the plane ``/device:TPU:<n>``; its line ``XLA Modules``
has one event per run of a compiled program (``jit_<fn>(<fingerprint>)``),
``XLA Ops`` one per HLO operation, and the host's ``TraceAnnotation`` spans
sit on the thread lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Trace = Dict[str, Dict[str, List[list]]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, *, host_prefix=("bench:", "pump:")) -> Trace:
    """Device planes whole; of the host plane only the spans whose name
    starts with one of ``host_prefix``: the benchmark's own annotations and
    the phases of the serving pump."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Trace = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(host_prefix)]
            if events:
                lines.setdefault(line.name, []).extend(events)
    return out


def device_planes(trace: Trace) -> List[str]:
    return sorted(p for p in trace if DEVICE_PLANE.match(p))


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged (start, end) intervals."""
    merged: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _busy_intervals(trace: Trace, plane: str) -> List[Tuple[int, int]]:
    lines = trace[plane]
    events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
    return union([(s, s + d) for _, s, d in events])


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran on the device: the union of the
    op intervals, averaged over the chips in the trace."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    total = sum(e - s for p in planes for s, e in _busy_intervals(trace, p))
    return total / len(planes) / 1e9


def matching(trace: Trace, line: str, pattern: str) -> Tuple[float, int]:
    """(seconds, events) of the events on ``line`` whose name matches the
    regular expression, averaged over the chips."""
    planes = device_planes(trace)
    rx = re.compile(pattern)
    total, count = 0, 0
    for p in planes:
        for name, _, dur in trace[p].get(line, []):
            if rx.search(name):
                total += dur
                count += 1
    if not planes:
        return 0.0, 0
    return total / len(planes) / 1e9, count // len(planes)


def program_seconds(trace: Trace, pattern: str) -> Tuple[float, int]:
    return matching(trace, MODULES_LINE, pattern)


def op_seconds(trace: Trace, pattern: str) -> Tuple[float, int]:
    return matching(trace, OPS_LINE, pattern)


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
# ``kernel_metadata={"kernel":"flash_fwd"}`` in a labelled kernel's text (the
# quotes may come escaped); ``kernel_metadata`` itself does not match
_KERNEL_LABEL = re.compile(r"kernel\W{1,8}([A-Za-z0-9_]+)")


def short_name(op: str) -> str:
    """An op's name on the v5e is its whole HLO instruction, thousands of
    characters for a concatenate: keep the kernel's label where the text
    carries one (``flash_fwd``, whatever module the result is named after),
    else "<result name without its number> <opcode>", e.g. ``fusion
    fusion``, ``attention custom-call`` (a kernel from before the labels,
    or a name cut short of its label)."""
    label = _KERNEL_LABEL.search(op)
    if label:
        return label.group(1)
    head, sep, rest = op.partition(" = ")
    if not sep:
        return op[:80]
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    opcode = _OPCODE.search(rest)
    return f"{base} {opcode.group(1)}" if opcode else base


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The device operations that took most time, grouped under
    :func:`short_name`, [[name, seconds], ...]."""
    planes = device_planes(trace)
    totals: Dict[str, int] = {}
    for p in planes:
        for name, _, dur in trace[p].get(OPS_LINE, []):
            key = short_name(name)
            totals[key] = totals.get(key, 0) + dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / len(planes) / 1e9] for name, dur in ranked]


def idle_gaps(trace: Trace, n: int = 10, *, min_gap_ns: int = 5000,
              unattributed: str = "unattributed") -> List[list]:
    """The idle time of the first chip by what the host was doing.  A gap
    between busy intervals of ``min_gap_ns`` or more goes to the benchmark's
    host span that covers most of it (the shortest such span, where spans
    nest); shorter gaps are the device's own, "between ops".
    [[span name, seconds], ...], largest first."""
    import numpy as np

    planes = device_planes(trace)
    if not planes:
        return []
    busy = _busy_intervals(trace, planes[0])
    spans = [(s, s + d, name)
             for line in trace.get(HOST_PLANE, {}).values()
             for name, s, d in line]
    starts = np.array([s for s, _, _ in spans], np.int64)
    ends = np.array([e for _, e, _ in spans], np.int64)
    totals: Dict[str, int] = {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        gap = gap_end - gap_start
        if gap < min_gap_ns:
            name = "between ops"
        elif not spans:
            name = unattributed
        else:
            cover = np.minimum(ends, gap_end) - np.maximum(starts, gap_start)
            # most cover first; of equals the shortest (innermost) span
            best = int(np.lexsort((ends - starts, -cover))[0])
            name = spans[best][2] if cover[best] > 0 else unattributed
        totals[name] = totals.get(name, 0) + gap
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / 1e9] for name, dur in ranked]


def time_slice(trace: Trace, length_ns: int, name_chars: int = 100) -> Trace:
    """The events that lie wholly inside ``length_ns`` from the middle of
    the first chip's ops, names cut to ``name_chars``: small enough to check
    in as a recorded trace for the tests."""
    planes = device_planes(trace)
    ops = trace[planes[0]][OPS_LINE] if planes else []
    if not ops:
        return {}
    start = sorted(s for _, s, _ in ops)[len(ops) // 2]
    end = start + length_ns
    return {plane: {line: [[name[:name_chars], s, d] for name, s, d in events
                           if s >= start and s + d <= end]
                    for line, events in lines.items()}
            for plane, lines in trace.items()}


def summary(trace: Trace, n: int = 40) -> dict:
    """What a first look by hand needs: planes, lines, the commonest names."""
    out = {}
    for plane, lines in trace.items():
        out[plane] = {}
        for line, events in lines.items():
            names: Dict[str, list] = {}
            for name, _, dur in events:
                rec = names.setdefault(name, [0, 0])
                rec[0] += 1
                rec[1] += dur
            ranked = sorted(names.items(), key=lambda kv: -kv[1][1])[:n]
            out[plane][line] = {"events": len(events),
                                "top": [[k, c, d / 1e9]
                                        for k, (c, d) in ranked]}
    return out
