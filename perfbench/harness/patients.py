"""Synthetic disease histories: the benchmark's own copy of the simulator.

A copy of the program's competing-risk simulator (``repro.data.synthetic``
with the event vocabulary of ``repro.data.vocab``), kept here so that the
traffic a cell sends cannot change when the program changes.  NumPy only:
the load generator, which never imports JAX, builds its prompts from it.

Per-code Gompertz hazards ``exp(a_i + b_i * age / 10)`` with comorbidity
boosts, an age- and burden-dependent death hazard, a "no event" marker after
every 5 event-free years, first-occurrence disease codes.  A history starts
with a sex token at age 0 and ends at Death or is censored at 85.
"""
from __future__ import annotations

import functools

import numpy as np

# event vocabulary (1,289 tokens)
DEATH = 1
NO_EVENT = 2
SEX_FEMALE = 3
SEX_MALE = 4
LIFESTYLE0 = 5
N_LIFESTYLE = 8
DISEASE0 = 13
N_DISEASE = 1276
VOCAB_SIZE = DISEASE0 + N_DISEASE

# simulator settings
UNIVERSE_SEED = 0
MAX_AGE = 85.0
NO_EVENT_INTERVAL = 5.0
MEAN_LOG_HAZARD = -10.4
SD_LOG_HAZARD = 1.0
MEAN_AGE_SLOPE = 0.35
SD_AGE_SLOPE = 0.15
N_PARTNERS = 5
PARTNER_BOOST = 0.4
DEATH_BASE = -10.3
DEATH_AGE_SLOPE = 0.9
DEATH_MORBIDITY_BOOST = 0.04
MAX_EVENTS = 120


@functools.lru_cache(maxsize=1)
def _universe():
    rng = np.random.default_rng(UNIVERSE_SEED)
    a = rng.normal(MEAN_LOG_HAZARD, SD_LOG_HAZARD, N_DISEASE)
    b = np.clip(rng.normal(MEAN_AGE_SLOPE, SD_AGE_SLOPE, N_DISEASE), 0.0, None)
    partners = rng.integers(0, N_DISEASE, (N_DISEASE, N_PARTNERS))
    boosts = rng.uniform(0.2, 0.2 + PARTNER_BOOST, (N_DISEASE, N_PARTNERS))
    return a, b, partners, boosts


def patient(index: int):
    """History ``index``: (tokens int32, ages float32 in years)."""
    a, b, partners, boosts = _universe()
    rng = np.random.default_rng([UNIVERSE_SEED, int(index)])
    tokens = [SEX_FEMALE if rng.random() < 0.5 else SEX_MALE]
    ages = [0.0]
    lifestyle_age = rng.uniform(18.0, 25.0)
    lifestyle_tok = LIFESTYLE0 + int(rng.integers(0, N_LIFESTYLE))
    age = 0.0
    occurred = np.zeros(N_DISEASE, bool)
    extra = np.zeros(N_DISEASE)
    emitted_lifestyle = False

    def maybe_emit_lifestyle(new_age):
        nonlocal emitted_lifestyle
        if not emitted_lifestyle and new_age >= lifestyle_age:
            tokens.append(lifestyle_tok)
            ages.append(lifestyle_age)
            emitted_lifestyle = True

    while len(tokens) < MAX_EVENTS:
        log_rates = a + b * (age / 10.0) + extra
        rates = np.where(occurred, 0.0, np.exp(log_rates))
        death_rate = np.exp(DEATH_BASE + DEATH_AGE_SLOPE * (age / 10.0)
                            + DEATH_MORBIDITY_BOOST * occurred.sum())
        total = rates.sum() + death_rate
        dt = rng.exponential(1.0 / total)
        if dt > NO_EVENT_INTERVAL:
            age += NO_EVENT_INTERVAL
            if age >= MAX_AGE:
                break
            maybe_emit_lifestyle(age)
            tokens.append(NO_EVENT)
            ages.append(age)
            continue
        age += dt
        if age >= MAX_AGE:
            break
        maybe_emit_lifestyle(age)
        if rng.random() < death_rate / total:
            tokens.append(DEATH)
            ages.append(age)
            break
        code = rng.choice(N_DISEASE, p=rates / rates.sum())
        occurred[code] = True
        extra[partners[code]] += boosts[code]
        tokens.append(DISEASE0 + code)
        ages.append(age)
    return np.asarray(tokens, np.int32), np.asarray(ages, np.float32)
