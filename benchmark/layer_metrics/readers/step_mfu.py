"""The whole step's share of the chip's bf16 peak: FLOPs the algorithm
needs per token x tokens in the traced window / window / (chips x peak)."""


from benchmark.harness import trace_reduce


def read(reading, flops, counters=()):
    trace = reading.get("trace")
    if trace is None or not trace_reduce.device_planes(trace) \
            or not reading.get("window_s"):
        return None
    tokens = reading.get("tokens")
    if tokens is None:
        tokens = sum(reading["counters"][k] for k in counters)
    if not tokens:
        return None
    rate = reading[flops] * tokens / reading["window_s"]
    return 100.0 * rate / (reading["chips"]
                           * reading["peak"].bf16_flops_per_s)
