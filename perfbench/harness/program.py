"""Everything the benchmark takes from the program, in one place.

The system under test (``repro``: its engine backend, HTTP server and
cohort risk aggregation), built from a configuration file and the
benchmark's own weights, warmed up through the engine's public calls, and
read through its counters.  Nothing here computes a metric or a reference.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.api import FuturesRequest
from repro.api.client import EngineBackend
from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.serve import Request

#: configuration-file keys that are not ``ModelConfig`` fields
_NOT_FIELDS = ("norm_eps",)
#: the program's norms use this epsilon, fixed in ``repro.models.layers``
PROGRAM_NORM_EPS = 1e-5


def model_config(cfg: dict, dtype: Optional[str] = None) -> ModelConfig:
    m = dict(cfg["model"])
    if float(m.pop("norm_eps")) != PROGRAM_NORM_EPS:
        raise ValueError(f"{cfg['name']}: the program's norms use epsilon "
                         f"{PROGRAM_NORM_EPS}")
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(m) - fields - set(_NOT_FIELDS))
    if unknown:
        raise ValueError(f"{cfg['name']}: not program settings: {unknown}")
    if dtype is not None:
        m["dtype"] = dtype
    return ModelConfig(name=cfg["name"], **m)


def check_tree(params, mcfg: ModelConfig) -> None:
    """The benchmark's weights must fill the program's parameter tree
    exactly: same paths, shapes and dtypes."""
    want = jax.eval_shape(lambda k: init_params(mcfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"weights do not fit the program's parameter tree:"
                         f"\n got  {got}\n want {want}")


def engine_backend(params, mcfg: ModelConfig, server: dict, served: dict,
                   seed: int) -> EngineBackend:
    """The engine as the configuration serves it (``served``), with the
    mix's ``server`` settings (``slots``, ``max_context`` and any other
    ``BatchedEngine`` keyword, such as ``prefill_chunk_tokens``) on top."""
    kw = dict(served, temperature=1.0, seed=seed)
    kw.update(server)
    kw["temperature"] = float(kw["temperature"])
    return EngineBackend.create(params, mcfg, **kw)


def http_server(backend):
    from repro.serve.server import InferenceServer
    return InferenceServer(backend, port=0).start()


def chapter_risk(trajectories, age0: float, horizon: float,
                 vocab: int) -> np.ndarray:
    """The cohort engine's per-patient host aggregation."""
    from repro.core.risk import futures_chapter_risk
    return futures_chapter_risk(trajectories, age0, horizon, vocab)


# -- counters -------------------------------------------------------------
def engine_counters(engines) -> Dict[str, int]:
    """Sums over ``engines`` of their tick and prefix-index counters."""
    out = {"ticks": 0, "preemptions": 0, "prefix_hits": 0,
           "prefix_partial": 0, "prefix_misses": 0}
    for e in engines:
        out["ticks"] += int(e.ticks)
        out["preemptions"] += int(e.preemptions)
        if e.prefix is not None:
            out["prefix_hits"] += int(e.prefix.hits)
            out["prefix_partial"] += int(e.prefix.partial_hits)
            out["prefix_misses"] += int(e.prefix.misses)
    return out


def engine_stack(engine, depth: int = 4) -> str:
    """Where the engine's thread is now: its innermost frames, innermost
    first."""
    import sys
    import traceback
    t = getattr(engine, "_thread", None)
    frame = sys._current_frames().get(t.ident) if t is not None else None
    if frame is None:
        return "no engine thread"
    return " < ".join(f"{f.name} ({f.filename.rsplit('/', 1)[-1]}:{f.lineno})"
                      for f in reversed(traceback.extract_stack(frame)[-depth:]))


def live_requests(engines) -> Dict[str, int]:
    """Request id -> events emitted so far, for every request in a slot,
    in the queue or finished (``engines`` started with
    ``retain_completed``): a racy but atomic-per-list read the engine
    thread never waits on.  A request that leaves its slot between the
    reads of ``slot_req`` and ``completed`` is missing."""
    out = {}
    for e in engines:
        for r in list(e.slot_req) + list(e.pending) + list(e.completed):
            if r is not None and r.request_id is not None:
                out[r.request_id] = len(r.out_tokens or ())
    return out


# -- warm-up through the engine's public calls ----------------------------
def _prompt(rng, length: int, vocab: int, ages: bool):
    toks = rng.integers(3, vocab, length).astype(np.int32)
    ags = (np.sort(rng.uniform(0.0, 60.0, length)).astype(np.float32)
           if ages else None)
    return toks, ags


def warm_admissions(engine, lengths: List[int], largest: int,
                    injected: bool, seed: int) -> None:
    """Admit groups of fresh prompts, two events each, so that every shape
    an admission of up to ``largest`` prompts takes is compiled and loaded
    before the window: the prefill at each (power-of-two batch, length
    bucket), and the block insert, commit and row slice, which take the
    group's own size, at every group size for each number of blocks."""
    rng = np.random.default_rng([seed, 31337])
    V = engine.cfg.vocab_size
    pow2 = [n for n in range(1, largest + 1) if n & (n - 1) == 0]
    blocks = set()
    for L in lengths:
        bucket = min(max(8, 1 << (L - 1).bit_length()), engine.max_context)
        nblk = -(-bucket // engine.block_size)
        sizes = pow2 if nblk in blocks else range(1, largest + 1)
        blocks.add(nblk)
        for n in sizes:
            for _ in range(n):
                toks, ags = _prompt(rng, L, V, engine.is_delphi)
                engine.submit(Request(
                    tokens=toks, ages=ags, max_new=2,
                    uniforms=(rng.random((2, V), dtype=np.float32)
                              if injected else None)))
            engine.run()


def warm_futures(backend, lengths: List[int], waves: List[int],
                 max_new: int, seed: int) -> None:
    """Held parents of each length and fork waves of every size, plus one
    fan-out wider than the free slots (children queue and admit by
    reference to the parent's cached prefix)."""
    rng = np.random.default_rng([seed, 27183])
    eng = backend.engine
    V = eng.cfg.vocab_size

    def futures(L, n):
        toks, ags = _prompt(rng, L, V, eng.is_delphi)
        backend.sample_futures(FuturesRequest(
            tokens=toks.tolist(), ages=ags.tolist(), n_futures=n,
            max_new=max_new,
            uniforms=rng.random((n, max_new, V), dtype=np.float32)))

    for L in lengths:
        futures(L, max(waves))
    for k in waves:
        futures(lengths[0], k)
    futures(lengths[-1], eng.slots + max(waves) // 4)
