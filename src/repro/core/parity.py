"""Parity against one float32 reference: logits, with written tolerances.

Separately compiled programs (a bfloat16 server on the chip, the CPU
backends, an exported artifact) never agree bit for bit, and a sampled event
flips wherever two candidates' waiting times lie closer together than that
noise.  So outputs are held to ONE reference instead of to each other: the
model's plain float32 forward pass under
``jax.default_matmul_precision("highest")``.

* :func:`check_logits` — logits a program computed, against the reference.
* :func:`check_trajectory` — a sampled trajectory, replayed along its own
  path (teacher forcing).  Each event must be the reference's
  competing-exponential argmin under the same uniforms, or within the
  tolerance's reach of it; each age step must be the reference's waiting
  time of that event, within the same tolerance; and a trajectory that ends
  early must end for a reason the reference gives.

A tolerance is an absolute error on logits.  An error of ``tol`` on a logit
scales its waiting time ``-exp(-logit) ln u`` by at most ``exp(tol)``, so an
event whose reference waiting time is within ``exp(2 tol)`` of the minimum
may win, and an age step may be off by a factor ``exp(tol)``.  Exact
equality stays the check only where both sides run the same executable.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import forward

#: Absolute logit tolerance by served dtype.  float32: the age encoding's
#: angles (age x up to 1,000 rad/year) carry float32's relative rounding,
#: 0.03 rad at age 270, so two programs that form them differently (the
#: engine's cached decode and a full forward pass) drift apart with age:
#: 0.0031 at most at ages up to 420 over 30 trajectories of the reduced
#: Delphi on the CPU; 0.01 leaves 3x margin.  bfloat16: activations round
#: at 2^-8 relative in every one of the 12 layers and the logits head rounds
#: its output to bfloat16 before the float32 bias.  Delphi-2M at published
#: widths, random
#: weights, logits up to |9.1|: the largest such error on the CPU was 0.013
#: over 64 synthetic histories of 14-60 events; 0.1 leaves about 8x margin
#: for the chip's own matmul rounding, and a real fault (a wrong mask,
#: position or cache block) moves logits by far more.
LOGIT_TOL = {"float32": 1e-2, "bfloat16": 0.1}

_F32_EPS = float(np.finfo(np.float32).eps)


def logit_tolerance(cfg: ModelConfig) -> float:
    return LOGIT_TOL[cfg.dtype]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _all_logits(params, cfg: ModelConfig, tokens, ages):
    batch = {"tokens": tokens}
    if cfg.age_encoding:
        batch["ages"] = ages
    return forward(params, cfg, batch, mode="train")["logits"]


def reference_logits(params, cfg: ModelConfig, tokens: Sequence[int],
                     ages: Optional[Sequence[float]] = None) -> np.ndarray:
    """(S,) history -> (S, V) float64 logits; row i predicts event i+1.

    The float32 forward pass of ``cfg`` whatever dtype it serves in, with
    every matmul at full float32 precision."""
    ref = cfg.replace(dtype="float32")
    t = jnp.asarray(np.asarray(tokens, np.int32)[None])
    a = (jnp.asarray(np.asarray(ages, np.float32)[None])
         if ages is not None else None)
    with jax.default_matmul_precision("highest"):
        lg = _all_logits(params, ref, t, a)
    return np.asarray(lg[0], np.float64)


def check_logits(got, ref, *, tol: float, what: str = "logits") -> float:
    """Raise unless ``max |got - ref| <= tol``; returns that error."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise AssertionError(f"{what}: shape {got.shape} != reference "
                             f"{ref.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{what}: non-finite values")
    err = float(np.max(np.abs(got - ref)))
    if not err <= tol:
        i = np.unravel_index(np.argmax(np.abs(got - ref)), got.shape)
        raise AssertionError(
            f"{what}: max |logit - fp32 reference| = {err:.3g} > tol {tol:g} "
            f"at {tuple(int(x) for x in i)} (got {got[i]:.6g}, reference "
            f"{ref[i]:.6g})")
    return err


def _waiting_times(logits, u) -> np.ndarray:
    u = np.clip(np.asarray(u, np.float64), 1e-12, 1.0 - 1e-12)
    return -np.exp(-np.asarray(logits, np.float64)) * np.log(u)


def check_trajectory(params, cfg: ModelConfig, prompt_tokens, prompt_ages,
                     tokens, ages, uniforms, *, tol: float, max_new: int,
                     max_age: Optional[float] = None,
                     death_token: Optional[int] = None) -> None:
    """Raise unless a sampled Delphi trajectory follows from the float32
    reference logits and its injected ``uniforms`` (max_new, V), within
    ``tol``.  ``max_age``/``death_token`` default to the config's."""
    if not cfg.age_encoding:
        raise ValueError("check_trajectory replays Delphi (age-encoded) "
                         "trajectories")
    max_age = cfg.max_age if max_age is None else max_age
    death = cfg.death_token if death_token is None else death_token
    tokens = [int(t) for t in tokens]
    ages = [float(a) for a in ages]
    S, n = len(prompt_tokens), len(tokens)
    if len(ages) != n:
        raise AssertionError(f"{n} events but {len(ages)} ages")
    if n > max_new:
        raise AssertionError(f"{n} events > max_new={max_new}")
    ref = reference_logits(params, cfg, list(prompt_tokens) + tokens,
                           list(prompt_ages) + ages)
    prev = float(prompt_ages[-1])
    for k, (e, a) in enumerate(zip(tokens, ages)):
        t = _waiting_times(ref[S - 1 + k], uniforms[k])
        best = int(np.argmin(t))
        if np.log(t[e]) - np.log(t[best]) > 2 * tol:
            raise AssertionError(
                f"event {k}: sampled {e} (reference waiting time {t[e]:.6g})"
                f" but the reference argmin is {best} ({t[best]:.6g}): "
                f"further apart than a logit error of {tol:g} explains")
        slack = t[e] * np.expm1(tol) + 4 * _F32_EPS * max(abs(a), abs(prev))
        if abs((a - prev) - t[e]) > slack:
            raise AssertionError(
                f"event {k}: age step {a - prev:.6g} vs reference waiting "
                f"time {t[e]:.6g} (slack {slack:.3g})")
        if e == death and k != n - 1:
            raise AssertionError(f"event {k}: trajectory continues past "
                                 f"the death token")
        prev = a
    if n == max_new or (n and tokens[-1] == death):
        return
    # ended early without death: the next event must cross max_age
    t = _waiting_times(ref[S - 1 + n], uniforms[n])
    reach = prev + t.min() * np.exp(2 * tol) \
        + 4 * _F32_EPS * max(abs(prev), abs(max_age))
    if not reach > max_age:
        raise AssertionError(
            f"trajectory stopped after {n} of {max_new} events at age "
            f"{prev:.6g}, but the reference's next event lands at "
            f"{prev + t.min():.6g} <= max_age {max_age:g}")
