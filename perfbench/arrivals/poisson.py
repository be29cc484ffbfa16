"""Open loop at ``rate`` requests a second.

Exactly ``round(rate * warm_s)`` arrivals before the window and
``round(rate * seconds)`` inside it, one pool entry each.  Each stretch's
gaps are exponential draws from the pool, scaled to fill it and put in the
seed's order; the first request of a stretch arrives at its start.
"""
from typing import List

import numpy as np

from harness import traffic


def _arrivals(mix: dict, what: str, n: int, span: float,
              seed: int) -> np.ndarray:
    if n == 0:
        return np.zeros(0)
    g = traffic.pool_rng(mix, what).exponential(1.0, n)
    g *= span / g.sum()
    g = g[np.random.default_rng([int(seed), traffic.TAG_ORDER,
                                 sum(map(ord, what))]).permutation(n)]
    return np.cumsum(g) - g


def specs(mix: dict, seed: int, warm_s: float,
          seconds: float) -> List[traffic.Spec]:
    rate = float(mix["arrivals"]["rate"])
    n_warm, n_win = round(rate * warm_s), round(rate * seconds)
    t = np.concatenate([_arrivals(mix, "warm", n_warm, warm_s, seed),
                        warm_s + _arrivals(mix, "window", n_win, seconds,
                                           seed)])
    n = n_warm + n_win
    perm = traffic.order(seed, n)
    mx = traffic.max_new(mix, n)
    cut = traffic.pool_rng(mix, "cut").random(n)
    lens = traffic.lengths(mix, n)
    first = int(mix["source"].get("first_patient", 0))
    out = []
    for j in range(n):
        p = int(perm[j])
        out.append(traffic.Spec(index=j,
                                patient=-1 if lens[p] else first + p,
                                cut=float(cut[p]), length=int(lens[p]),
                                max_new=int(mx[p]), due=float(t[j])))
    return out
