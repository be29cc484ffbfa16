"""Whether what the timed path served is right: served tokens against the
plain float32 reference.

Each sample is one finished request: its prompt, the events it was served
and, where the request carried them, the uniforms it was sampled with.  The
reference runs once over prompt + served events (teacher forcing) and
scores every served event:

* ``gap``: how far the served event's score lies below the reference's
  best.  The score is the logit for greedy decoding, and ``logit -
  log(-log u)`` under injected uniforms: the competing-exponential sampler
  picks ``argmin -exp(-logit) log u``, the same event as the Gumbel-max
  ``argmax logit - log(-log u)``, so an exact sampler reads 0 and rounding
  reads a little above.
* ``dt_rel`` (time-to-event models): the served waiting time against the
  reference's ``-exp(-logit) log u`` for the served event, relative, with
  1e-3 years added below so that float32 ages do not swamp tiny waits.

The control puts the reference itself, computed in float8 (``mode="fp8"``),
in the program's place: at each position it takes the event its own scores
put first and reads that event's numbers against the float32 reference.
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

#: added to the reference waiting time (years) in ``dt_rel``'s denominator
DT_FLOOR = 1e-3
#: what a served event outside the vocabulary reads
UNBOUNDED = 1e9


def _gumbel(u: np.ndarray) -> np.ndarray:
    u = np.clip(u.astype(np.float64), 1e-12, 1.0 - 1e-12)
    return -np.log(-np.log(u))


def _rows(ref, cfg, w, batch, pad: int, mode: str) -> List[np.ndarray]:
    """Logits of each sample at the positions its served events were
    sampled from: (n_i, V) float64 per sample."""
    B = len(batch)
    tokens = np.zeros((B, pad), np.int32)
    ages = np.zeros((B, pad), np.float32)
    nmax = max(1, max(len(s["out"]) for s in batch))
    idx = np.zeros((B, nmax), np.int32)
    for b, s in enumerate(batch):
        seq = np.concatenate([s["tokens"], s["out"]]).astype(np.int32)
        tokens[b, :len(seq)] = seq
        if s.get("ages") is not None:
            a = np.concatenate([s["ages"], s["out_ages"]]).astype(np.float32)
            ages[b, :len(a)] = a
            ages[b, len(a):] = a[-1]
        S = len(s["tokens"])
        pos = S - 1 + np.arange(len(s["out"]))
        idx[b, :len(pos)] = pos
        idx[b, len(pos):] = S - 1
    lg = ref.logits(cfg, w, jnp.asarray(tokens), jnp.asarray(ages), mode=mode)
    got = np.asarray(jnp.take_along_axis(lg, jnp.asarray(idx)[:, :, None],
                                         axis=1), np.float64)
    return [got[b, :len(s["out"])] for b, s in enumerate(batch)]


def readings(ref, cfg, w, samples: List[dict], *, pad: int, batch: int,
             control: bool = False) -> Dict[str, float]:
    """Worst ``gap`` and ``dt_rel`` of the served events (and of the
    control's choices, as ``control_gap`` / ``control_dt_rel``), with the
    number of events compared."""
    out = {"gap": 0.0, "tokens": 0}
    timed = any(s.get("u") is not None for s in samples)
    if timed:
        out["dt_rel"] = 0.0
    if control:
        out["control_gap"] = 0.0
        if timed:
            out["control_dt_rel"] = 0.0
    V = int(cfg["model"]["vocab_size"])
    live = []
    for s in samples:
        if any(not 0 <= int(t) < V for t in s["out"]):
            for k in out:
                if k != "tokens" and not k.startswith("control_"):
                    out[k] = UNBOUNDED
            out["tokens"] += len(s["out"])
        elif len(s["out"]):
            live.append(s)
    for i in range(0, len(live), batch):
        chunk = live[i:i + batch]
        chunk = chunk + [chunk[0]] * (batch - len(chunk))
        f32 = _rows(ref, cfg, w, chunk, pad, "f32")
        f8 = _rows(ref, cfg, w, chunk, pad, "fp8") if control else None
        for b, s in enumerate(live[i:i + batch]):
            n = len(s["out"])
            ev = np.asarray(s["out"], np.int64)
            lg = f32[b]
            g = _gumbel(np.asarray(s["u"])[:n]) if s.get("u") is not None \
                else 0.0
            score = lg + g
            best = score.max(axis=1)
            out["gap"] = max(out["gap"], float(np.max(
                best - score[np.arange(n), ev])))
            out["tokens"] += n
            if s.get("u") is not None:
                u = np.clip(np.asarray(s["u"], np.float64)[:n], 1e-12,
                            1.0 - 1e-12)
                t_ref = -np.exp(-lg) * np.log(u)            # (n, V)
                prev = np.concatenate([[s["ages"][-1]],
                                       s["out_ages"][:-1]]).astype(np.float64)
                dt = np.asarray(s["out_ages"], np.float64) - prev
                tr = t_ref[np.arange(n), ev]
                out["dt_rel"] = max(out["dt_rel"], float(np.max(
                    np.abs(dt - tr) / (tr + DT_FLOOR))))
            if control:
                sc = f8[b] + g
                pick = sc.argmax(axis=1)
                out["control_gap"] = max(out["control_gap"], float(np.max(
                    best - score[np.arange(n), pick])))
                if s.get("u") is not None:
                    tc = (-np.exp(-f8[b]) * np.log(u))[np.arange(n), pick]
                    tr = t_ref[np.arange(n), pick]
                    out["control_dt_rel"] = max(
                        out["control_dt_rel"],
                        float(np.max(np.abs(tc - tr) / (tr + DT_FLOOR))))
    return out


def pick(rng: np.random.Generator, items: List, n: int, size) -> List:
    """``n`` of ``items`` drawn by ``rng``, the largest by ``size`` always
    among them."""
    if not items:
        return []
    longest = max(range(len(items)), key=lambda i: size(items[i]))
    rest = [i for i in range(len(items)) if i != longest]
    k = min(n - 1, len(rest))
    chosen = [longest] + list(rng.choice(rest, size=k, replace=False)) \
        if k > 0 else [longest]
    return [items[i] for i in chosen]
