"""``delphi-2m.router4`` at a size the CPU holds: the router over four
in-process replicas, all on the CPU's one device, at Delphi-2M's own
widths.  A sound run is correct, its float8 control is not, and a run
whose decode ticks leave the pool unwritten is not.
"""
import small
from test_faults import pool_unwritten

CELL = "delphi-2m.router4"
MIX = {"server": {"slots": 4},
       "arrivals": {"concurrency": 8, "ramp_s": 0.4},
       "output": {"max_new": 8},
       "warm": {"largest_group": 4}, "warm_s": 1,
       "check": {"sample": 8, "min_tokens": 5}}


def verdict(monkeypatch, control=False):
    monkeypatch.setitem(small.SMALL_MIX, "router4", MIX)
    runmod, _, run = small.small_run(CELL, widths=True)
    outcome, readings = runmod.measure(run, control=control)
    checked = runmod.compared(run.cfg, run.mix, readings, outcome.failed)
    return runmod.is_correct(checked), checked, readings, outcome


def test_sound_run_is_correct_and_its_control_is_not(monkeypatch):
    ok, checked, readings, outcome = verdict(monkeypatch, control=True)
    assert ok, checked
    assert readings["control_dt_rel"] > checked["dt_rel"]["limit"], readings
    # four engines' counters in one window; sessions came back to a
    # replica that held their prefix
    assert outcome.layer["ticks"] > 0 and outcome.e2e["events_per_s"] > 0
    assert any("affinity_routed" in n for n in outcome.notes)


def test_unwritten_pool_is_not_correct(monkeypatch):
    with pool_unwritten():
        ok, checked, _, _ = verdict(monkeypatch)
    assert not ok, checked
