"""Device: share of the traced window in which no operation ran on the
chip, averaged over chips, in %: in the cells that report the event rate."""
from harness import trace


def read(m):
    return trace.idle_share(m["trace"])
