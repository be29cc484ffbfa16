"""The two readers of the program's spans, on hand-built records whose
answers are worked out by hand."""
import pytest

import small  # noqa: F401  (puts the harness on the path)
from harness import byname

MS = 1_000_000          # ns


def record(host, window_s=1.0):
    return {"trace": {"window_s": window_s, "devices": {},
                      "host": [["python3", n, t, d] for n, t, d in host]}}


def tick_host_ms(m):
    return byname.load("metrics", "tick_host_ms.thr").read(m)


def admission_share(m):
    return byname.load("metrics", "admission_share.thr").read(m)


HOST = [
    # a step that began before the trace: only its inner spans were
    # recorded, so its wait is not counted
    ("engine.tick.wait", 1 * MS, 4 * MS),
    # step 1: 20 ms, of which a 12 ms wait -> 8 ms of host time
    ("engine.step", 10 * MS, 20 * MS),
    ("engine.admit", 11 * MS, 3 * MS),
    ("engine.tick.wait", 16 * MS, 12 * MS),
    # a step with no tick (admission only): 5 ms of host time
    ("engine.step", 40 * MS, 5 * MS),
    ("engine.fork", 41 * MS, 2 * MS),
    # step 2: 30 ms, a 26 ms wait -> 4 ms of host time
    ("engine.step", 50 * MS, 30 * MS),
    ("engine.prefill_chunks", 51 * MS, 1 * MS),
    ("engine.tick.wait", 53 * MS, 26 * MS),
    # the loop's wait and a caller's span are no part of either
    ("engine.idle", 80 * MS, 9 * MS),
    ("api.sample_futures", 0, 90 * MS),
]


def test_host_time_per_tick_leaves_out_the_waits_and_an_unrecorded_step():
    # (20 - 12) + 5 + (30 - 26) ms over the 2 waits inside recorded steps
    assert tick_host_ms(record(HOST)) == pytest.approx(17 / 2)


def test_admission_share_sums_admit_fork_and_chunks_over_the_window():
    # 3 + 2 + 1 ms of a 0.1 s window
    assert admission_share(record(HOST, window_s=0.1)) == pytest.approx(6.0)


def test_admission_share_is_zero_when_the_engine_admitted_nothing():
    host = [("engine.step", 0, 5 * MS), ("engine.tick.wait", 1 * MS, 3 * MS)]
    assert admission_share(record(host)) == 0.0
    assert tick_host_ms(record(host)) == pytest.approx(2.0)


def test_none_without_the_programs_spans():
    """The parent program has no spans: the record's host events are JAX's
    own, and neither reader reports a number."""
    host = [("PjitFunction(_tick_u_jit)", 0, 5 * MS),
            ("_np.asarray(jax.Array)", 6 * MS, 3 * MS)]
    for m in (record(host), record([]), {"trace": None}):
        assert tick_host_ms(m) is None
        assert admission_share(m) is None


def test_no_tick_inside_a_recorded_step_reads_none():
    host = [("engine.step", 0, 5 * MS), ("engine.admit", 1 * MS, 2 * MS)]
    assert tick_host_ms(record(host)) is None
