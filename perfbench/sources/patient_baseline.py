"""One synthetic history per request (``harness.patients``), cut at a
baseline age drawn uniformly in ``baseline_age``: the events up to it,
none of them Death, and at least one."""
import numpy as np

from harness import patients


def prompt(src: dict, spec, seed: int):
    toks, ages = patients.patient(spec.patient)
    lo, hi = src["baseline_age"]
    base = lo + (hi - lo) * spec.cut
    alive = toks != patients.DEATH
    k = max(1, int(np.sum((ages <= base) & alive)))
    return toks[:k].copy(), ages[:k].copy()


def span(src: dict):
    return 1, patients.MAX_EVENTS
