"""The trace reduction on a small record cut from a v5e trace of the
``delphi-2m.clinic`` cell, and on hand-made records whose answers are
known."""
import json
import os

import pytest

import small  # noqa: F401  (puts the harness on the path)
from harness import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v5e_clinic.json")


def record(ops, modules=None, host=(), window_s=1.0):
    return {"window_s": window_s,
            "devices": {"/device:TPU:0": {"ops": ops,
                                          "modules": modules or ops}},
            "host": list(host)}


def test_busy_is_the_union_of_overlapping_operations():
    rec = record([["a", 0, 100], ["b", 50, 100], ["c", 400, 100]],
                 window_s=1e-6)
    assert trace.busy_s(rec) == pytest.approx(250e-9)
    assert trace.idle_share(rec) == pytest.approx(75.0)


def test_busy_is_averaged_over_devices_and_absent_without_any():
    rec = record([["a", 0, 100]])
    rec["devices"]["/device:TPU:1"] = {"ops": [["a", 0, 300]],
                                       "modules": []}
    assert trace.busy_s(rec) == pytest.approx(200e-9)
    assert trace.busy_s({"window_s": 1.0, "devices": {}, "host": []}) is None


def test_program_calls_and_idle_gaps_named_by_host_work():
    mods = [["jit__tick_u_jit(7)", 0, 1000], ["jit__prefill(3)", 1500, 500],
            ["jit__tick_u_jit(7)", 5000, 1000]]
    host = [["engine", "PjitFunction(_tick_u_jit)", 2100, 2800],
            ["engine", "whole run", 0, 10 ** 9]]
    rec = record(mods, mods, host)
    assert trace.program_calls(rec, trace.TICK) == [(2, pytest.approx(2e-6))]
    gaps = trace.idle_gaps(rec)
    assert gaps[0] == ["engine: PjitFunction(_tick_u_jit)",
                       pytest.approx(3e-6)]
    assert gaps[1][1] == pytest.approx(0.5e-6)


def test_recorded_v5e_trace():
    with open(FIXTURE) as f:
        rec = json.load(f)
    calls = trace.program_calls(rec, trace.TICK)
    assert calls and calls[0][0] > 0
    busy = trace.busy_s(rec)
    assert 0 < busy <= rec["window_s"]
    ops = trace.device_ops(rec)
    assert 0 < len(ops) <= 10 and all(s > 0 for _, s in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = trace.idle_gaps(rec)
    assert gaps and all(0 < s < rec["window_s"] for _, s in gaps)
