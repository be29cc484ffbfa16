"""Admission: share of the traced window, in %, that the engine thread
spent admitting: its ``engine.admit`` (prefills of new requests),
``engine.fork`` (forked futures bootstrapped from a held parent) and
``engine.prefill_chunks`` spans.  None where the program has no
``engine.step`` span."""

#: the spans summed
ADMISSION = ("engine.admit", "engine.fork", "engine.prefill_chunks")
#: the program's span names this reader reads
SPANS = ("engine.step",) + ADMISSION


def read(m):
    rec = m.get("trace")
    if not rec or not any(h[1] == "engine.step" for h in rec["host"]):
        return None
    spent = sum(d for _, name, _, d in rec["host"] if name in ADMISSION)
    return 100.0 * spent * 1e-9 / rec["window_s"]
