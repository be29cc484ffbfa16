"""Model: device time of one decode tick, in ms: the traced calls of the
engine's tick program, averaged per call on each chip, then over chips."""
from harness import trace


def read(m):
    per = [secs / calls
           for calls, secs in trace.program_calls(m["trace"], trace.TICK)]
    return 1e3 * sum(per) / len(per) if per else None
