"""Sessions of one synthetic history (``harness.patients``): ``visits``
(lo, hi) visits, their number drawn per patient; visit 1 cut as in
``patient_cut``, each later visit the last extended by the history's next
``extend`` events, while the events before any Death last."""
import numpy as np

from harness import byname, patients


def visits(src: dict, spec):
    """The session's prompts: [(tokens int32, ages float32)], one a visit."""
    toks, ages = patients.patient(spec.patient)
    k = byname.load("sources", "patient_cut").cut_index(toks, spec.cut)
    lo, hi = src["visits"]
    n = int(np.random.default_rng([patients.UNIVERSE_SEED, spec.patient,
                                   1]).integers(lo, hi + 1))
    dead = toks == patients.DEATH
    alive = int(np.argmax(dead)) if dead.any() else len(toks)
    out = [(toks[:k].copy(), ages[:k].copy())]
    for _ in range(n - 1):
        k += int(src["extend"])
        if k > alive:
            break
        out.append((toks[:k].copy(), ages[:k].copy()))
    return out


def prompt(src: dict, spec, seed: int):
    return visits(src, spec)[0]


def span(src: dict):
    return 1, patients.MAX_EVENTS
