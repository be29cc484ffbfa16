"""One synthetic history per request (``harness.patients``), cut after an
event drawn uniformly among those before any Death."""
import numpy as np

from harness import patients


def cut_index(toks: np.ndarray, frac: float) -> int:
    """Number of leading events kept: uniform among those before Death."""
    dead = toks == patients.DEATH
    n = int(np.argmax(dead)) if dead.any() else len(toks)
    return 1 + min(int(frac * n), n - 1)


def prompt(src: dict, spec, seed: int):
    toks, ages = patients.patient(spec.patient)
    k = cut_index(toks, spec.cut)
    return toks[:k].copy(), ages[:k].copy()


def span(src: dict):
    return 1, patients.MAX_EVENTS
