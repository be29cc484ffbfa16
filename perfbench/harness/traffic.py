"""The one traffic generator: a mix file of parameters -> request specs.

A mix (``perfbench/traffic/<mix>.json``) names a prompt ``source``
(``perfbench/sources/<kind>.py``), an ``arrivals`` process
(``perfbench/arrivals/<kind>.py``), output lengths, the server the cell
runs and the ``driver`` that offers the load (``perfbench/drivers/``).
Sizes and arrival gaps are drawn once from the mix's ``pool_seed``; a run's
``--seed`` only permutes their order and draws the content (uniforms,
token ids), so every seed offers the same work in another order.

NumPy only: the load generator process imports this and never JAX.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from harness import byname

#: stream tags: keep the uniforms, token ids and permutations independent
TAG_ORDER = 7919
TAG_UNIFORMS = 104729
TAG_TOKENS = 15485863
#: closed and futures mixes: requests in a run's stream
STREAM = 4096


@dataclasses.dataclass
class Spec:
    """One request of a run, materialized lazily (``prompt``)."""
    index: int                  # position in the run's request stream
    patient: int                # history index, or -1 for random tokens
    cut: float                  # in [0, 1): which event / baseline age
    length: int                 # random-token prompt length (else 0)
    max_new: int
    due: float = 0.0            # open loop: seconds after the load starts


def specs(mix: dict, seed: int, warm_s: float, seconds: float) -> List[Spec]:
    """The run's request stream, from the mix's ``arrivals`` kind."""
    return byname.load("arrivals", mix["arrivals"]["kind"]).specs(
        mix, seed, warm_s, seconds)


def prompt(mix: dict, spec: Spec, seed: int):
    """(tokens int32, ages float32 or None) of one request, from the mix's
    ``source`` kind."""
    return byname.load("sources", mix["source"]["kind"]).prompt(
        mix["source"], spec, seed)


def length_span(mix: dict):
    """(shortest, longest) prompt the mix's source makes."""
    return byname.load("sources", mix["source"]["kind"]).span(mix["source"])


def pool_rng(mix: dict, what: str):
    return np.random.default_rng([int(mix["pool_seed"]), sum(map(ord, what))])


def order(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng([int(seed), TAG_ORDER]).permutation(n)


def lengths(mix: dict, n: int) -> np.ndarray:
    """n prompt lengths from the pool: log-normal (``median``, ``sigma``)
    clipped to ``length_range``; zeros for a source without them."""
    src = mix["source"]
    if "median" not in src:
        return np.zeros(n, int)
    lo, hi = src["length_range"]
    r = pool_rng(mix, "lengths")
    x = np.exp(np.log(src["median"]) + src["sigma"] * r.standard_normal(n))
    return np.clip(np.round(x), lo, hi).astype(int)


def max_new(mix: dict, n: int) -> np.ndarray:
    out = mix["output"]
    lo, hi = out["max_new"] if isinstance(out["max_new"], list) \
        else (out["max_new"], out["max_new"])
    return pool_rng(mix, "max_new").integers(lo, hi + 1, n)


def pool_stream(mix: dict, seed: int, warm_s: float,
                seconds: float) -> List[Spec]:
    """A stream the window cannot exhaust: the pool of ``arrivals.pool``
    entries in the seed's order, again and again, each round on fresh
    histories (so that no patient comes twice)."""
    src = mix["source"]
    pool = int(mix["arrivals"]["pool"])
    perm = order(seed, pool)
    mx = max_new(mix, pool)
    cut = pool_rng(mix, "cut").random(pool)
    lens = lengths(mix, pool)
    first = int(src.get("first_patient", 0))
    out = []
    for j in range(max(STREAM, pool)):
        rnd, p = divmod(j, pool)
        p = int(perm[p])
        # a prompt of drawn length is random tokens, else a history
        out.append(Spec(index=j,
                        patient=-1 if lens[p] else first + rnd * pool + p,
                        cut=float(cut[p]), length=int(lens[p]),
                        max_new=int(mx[p])))
    return out


def uniforms(seed: int, index: int, rows: int, vocab: int,
             n: Optional[int] = None) -> np.ndarray:
    """The injected U(0,1) draws of request ``index``: (rows, vocab), or
    (n, rows, vocab) for ``n`` futures."""
    r = np.random.default_rng([int(seed), TAG_UNIFORMS, int(index)])
    shape = (rows, vocab) if n is None else (n, rows, vocab)
    return r.random(shape, dtype=np.float32)
