"""Tick: events decoded by the window's ticks over what its ticks could
have decoded (ticks x slots), in %."""


def read(m):
    n = m["ticks"] * m["slots"]
    return 100.0 * m["tick_events"] / n if n else None
