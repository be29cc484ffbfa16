"""Tick: the engine thread's host time per decode tick, in ms.  Over the
traced window's ``engine.step`` spans, their time outside the
``engine.tick.wait`` spans they hold (the one packed fetch, which waits on
the device), over the number of those waits: one per tick.  A wait whose
step began before the trace started is left out with its step.  None where
the program has no such spans."""
import bisect

#: the program's span names this reader reads
SPANS = ("engine.step", "engine.tick.wait")


def read(m):
    rec = m.get("trace")
    if not rec:
        return None
    steps, waits = [], []
    for _, name, t, d in rec["host"]:
        if name == "engine.step":
            steps.append((t, t + d))
        elif name == "engine.tick.wait":
            waits.append((t, d))
    waits.sort()
    starts = [t for t, _ in waits]
    host = ticks = 0
    for s, e in steps:
        inside = 0
        i = bisect.bisect_left(starts, s)
        while i < len(waits) and sum(waits[i]) <= e:
            inside += waits[i][1]
            ticks += 1
            i += 1
        host += (e - s) - inside
    return 1e-6 * host / ticks if ticks else None
