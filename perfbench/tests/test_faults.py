"""The check at a size the CPU holds: a sound run is correct, its control
is not, and a run whose timed path is broken underneath is not.

Each test drives a whole cell through ``run.measure`` (load, window,
reference) with the look for a chip skipped.  Faults are planted in the
program for the duration of one run: an event altered where the engine
hands it to the host, and a decode tick that leaves the key/value pool as
it was.
"""
import contextlib

import jax
import numpy as np
import pytest

import small

CELLS = ["delphi-2m.clinic", "delphi-2m.cohort", "h2o-danube-1.8b.decode"]


def verdict(workload, control=False):
    # Delphi-2M runs at its own widths: a CPU holds them, and its control
    # separates from the program only there
    runmod, _, run = small.small_run(workload,
                                     widths=workload.startswith("delphi"))
    outcome, readings = runmod.measure(run, control=control)
    checked = runmod.compared(run.cfg, run.mix, readings, outcome.failed)
    return (runmod.is_correct(checked), checked, readings,
            run.cfg["check"]["limits"])


@contextlib.contextmanager
def event_altered():
    from repro.serve import engine
    fetch = engine._to_host

    def altered(x):
        a = np.array(fetch(x))
        if a.ndim == 2 and a.shape[0] == 4:      # packed tick columns
            a[0] = np.where(a[2] > 0.5, a[0] + 3, a[0])
        return a
    engine._to_host = altered
    try:
        yield
    finally:
        engine._to_host = fetch


@contextlib.contextmanager
def pool_unwritten():
    from repro.models import attention
    write = attention.paged_write_stacked
    attention.paged_write_stacked = lambda caches, k, v, step: caches
    jax.clear_caches()
    try:
        yield
    finally:
        attention.paged_write_stacked = write
        jax.clear_caches()


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_its_control_is_not(workload):
    ok, checked, readings, limits = verdict(workload, control=True)
    assert ok, checked
    over = [readings["control_" + k] / v for k, v in limits.items()]
    assert max(over) > 1.0, readings


@pytest.mark.parametrize("fault", [event_altered, pool_unwritten])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_path_is_not_correct(workload, fault):
    with fault():
        ok, checked, _, _ = verdict(workload)
    assert not ok, checked
