"""Admission: share of the window's admissions that took cached prefix
blocks, whole-prompt hits and partial hits alike, over every admission
(prefix-index counters ``hits``, ``partial_hits``, ``misses``), in %."""


def read(m):
    n = m["prefix_hits"] + m["prefix_misses"]
    return 100.0 * (m["prefix_hits"] + m["prefix_partial"]) / n if n else None
