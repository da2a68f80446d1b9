"""Device milliseconds per traced step of the collective operations whose
HLO opcode is one of ``opcodes`` (a regular expression), averaged over the
chips: the time the exchange between chips holds a core.  An op's name on
the trace's ``XLA Ops`` line is its whole HLO instruction, ``%<result> =
<shape> <opcode>(<operands>), ...``; the result is named after the jax
primitive (``%psum.2423``) as often as after the opcode, so the opcode is
what is read.  On one chip there is no such op and nothing to read."""

from benchmark.harness import trace_reduce


def pattern(opcodes):
    # " all-reduce(" but neither " all-reduce-start(" nor an operand
    # "%all-reduce.7": an opcode follows a space and is followed by "("
    return r"\s(?:%s)\(" % opcodes


def read(reading, opcodes):
    trace = reading.get("trace")
    if trace is None or not reading.get("steps"):
        return None
    seconds, events = trace_reduce.op_seconds(trace, pattern(opcodes))
    if not events:
        return None
    return seconds / reading["steps"] * 1e3
