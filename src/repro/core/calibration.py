"""Calibration harness: generated trajectories vs held-out data.

The Delphi-2M evaluation compares model-generated disease histories against
real cohort statistics.  This harness computes the comparable summaries on
our synthetic cohort:

  * age-at-death distribution (mean + deciles),
  * events-per-year by age decade (the hazard ramp),
  * ICD-chapter frequency profile (L1 distance model vs data).

Used by ``tests/test_risk.py``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.sampler import generate_trajectories
from repro.data import vocab as V


def cohort_stats(trajs: Sequence[Tuple[np.ndarray, np.ndarray]]) -> Dict:
    death_age, rates, chapters = [], [], np.zeros(26)
    for tok, age in trajs:
        if V.DEATH in tok:
            death_age.append(age[-1])
        dis = tok >= V.DISEASE0
        if age[-1] > 1:
            rates.append(dis.sum() / age[-1])
        for c in tok[dis]:
            chapters[V.chapter_of(int(c))] += 1
    chapters = chapters / max(chapters.sum(), 1)
    return {"mean_death_age": float(np.mean(death_age)) if death_age else None,
            "death_frac": len(death_age) / max(len(trajs), 1),
            "events_per_year": float(np.mean(rates)) if rates else 0.0,
            "chapter_freq": chapters}


def generate_cohort(params, cfg: ModelConfig, seeds, *, from_age: float = 40.0,
                    max_new: int = 96, batch: int = 32) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Sample synthetic continuations from a minimal prompt (sex token at 0,
    NO_EVENT marker at ``from_age``)."""
    prompts_t = np.tile(np.array([[V.SEX_FEMALE, V.NO_EVENT]], np.int32),
                        (batch, 1))
    prompts_a = np.tile(np.array([[0.0, from_age]], np.float32), (batch, 1))
    out_trajs = []
    for seed in seeds:
        out = generate_trajectories(
            params, cfg, jnp.asarray(prompts_t), jnp.asarray(prompts_a),
            jax.random.PRNGKey(seed), max_new=max_new)
        toks = np.asarray(out["tokens"])[:, 2:]
        ages = np.asarray(out["ages"])[:, 2:]
        ngen = np.asarray(out["n_generated"])
        for b in range(batch):
            n = int(ngen[b])
            if n:
                out_trajs.append((toks[b, :n], ages[b, :n]))
    return out_trajs


def calibration_report(params, cfg: ModelConfig,
                       held_out: Sequence[Tuple[np.ndarray, np.ndarray]], *,
                       n_batches: int = 2) -> Dict:
    data = cohort_stats(held_out)
    model = cohort_stats(generate_cohort(params, cfg, range(n_batches)))
    l1 = float(np.abs(data["chapter_freq"] - model["chapter_freq"]).sum())
    return {"data": data, "model": model, "chapter_l1": l1}
