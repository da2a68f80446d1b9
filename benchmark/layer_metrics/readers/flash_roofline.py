"""Flash attention's share of its roofline over the traced training steps:
the least time the chip could take (the larger of FLOPs / peak and bytes /
bandwidth, from the static shapes) over the device time of the ops whose
name matches ``pattern``."""

from benchmark.harness import flops, trace_reduce


def read(reading, pattern):
    trace = reading.get("trace")
    if trace is None:
        return None
    seconds, events = trace_reduce.op_seconds(trace, pattern)
    if not events or not reading.get("steps"):
        return None
    shapes, peak = reading["shapes"], reading["peak"]
    least = max(
        flops.flash_train_flops(**shapes) / peak.bf16_flops_per_s,
        flops.flash_train_bytes(**shapes) / peak.hbm_bytes_per_s)
    return 100.0 * least * reading["steps"] / seconds
