"""Token ids uniform in ``token_range``, drawn from the run's seed and the
request's place in the stream; lengths log-normal (``median``, ``sigma``)
clipped to ``length_range``, drawn from the mix's pool
(``traffic.lengths``)."""
import numpy as np

from harness import traffic


def prompt(src: dict, spec, seed: int):
    lo, hi = src["token_range"]
    r = np.random.default_rng([int(seed), traffic.TAG_TOKENS, spec.index])
    return r.integers(lo, hi, spec.length).astype(np.int32), None


def span(src: dict):
    return tuple(src["length_range"])
