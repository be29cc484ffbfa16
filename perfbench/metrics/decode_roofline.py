"""Kernels: the window's decode ticks' least time over their device time, in
%.  Least time = max(operations / peak, bytes / HBM bandwidth) of the work
the configuration requires for those ticks (``harness.work``): weights read
once per tick, keys and values over the live contexts, logits written
once.  Device time = the window's ticks x the mean device time of one call
of the tick program in the trace.  The work is counted low, never high, so
the share cannot pass 100."""
from harness import trace


def read(m):
    calls = trace.program_calls(m["trace"], trace.TICK)
    if not calls or not m.get("ticks") or not m.get("tick_events"):
        return None
    per_tick = sum(s for _, s in calls) / sum(c for c, _ in calls)
    c, p = m["counts"], m["peaks"]
    flops = c.decode_flops(m["tick_events"], m["contexts"])
    nbytes = c.decode_bytes(m["ticks"], m["tick_events"], m["contexts"])
    least = max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / (m["ticks"] * per_tick)
