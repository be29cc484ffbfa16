"""Events, decode-tick events and the keys those ticks read, from snapshots
of the engine's request lists; and the per-layer readers built on them,
against hand arithmetic."""
import json
import os

import pytest

import small
from harness import window, work


def snap(t, live):
    return {"t": t, "live": dict(live)}


def ledger():
    led = window.Ledger()
    led.add("a", 10, sub=0.0)               # in a slot at the start
    led.done("a", 8, ret=5.0)
    led.add("b", 4, sub=0.5)                # queued at the start
    led.add("c", 7, sub=20.0)               # after the window
    led.add("d", 5, sub=0.0)                # finished at the start, its
    led.done("d", 6, ret=1.1)               # caller not yet woken
    return led


def test_stretch_counts_events_ticks_and_contexts():
    led = ledger()
    start = snap(1.0, {"a": 3, "b": 0})
    end = snap(11.0, {"a": 8, "b": 4, "d": 6})
    ev, tick_ev, ctx, prefills = led.stretch(start, end)
    assert ev == 5 + 4
    # a: events 3..7 by ticks over 13..17 keys; b: its first event by its
    # prefill, events 1..3 by ticks over 5..7 keys
    assert tick_ev == 5 + 3
    assert ctx == sum(range(13, 18)) + sum(range(5, 8))
    assert prefills == [4]


def test_overlapping_requests():
    led = ledger()
    # a and b are in flight, d returns after 1.0, c comes after 11.0
    got = led.overlapping(snap(1.0, {}), snap(11.0, {}))
    assert sorted(r["S"] for r in got) == [4, 5, 10]


def test_request_in_no_list_counts_by_its_return():
    led = ledger()
    # d is in no list at 1.0 and returns at 1.1: it had finished
    assert led.emitted("d", snap(1.0, {})) == 6
    # b is in no list long before it returns: it was being admitted
    assert led.emitted("b", snap(1.0, {})) == 0
    assert led.emitted("c", snap(11.0, {})) == 0


def cfg(name):
    with open(os.path.join(small.ROOT, "configs", name + ".json")) as f:
        return json.load(f)


def reader(name):
    return small.load(os.path.join(small.ROOT, "metrics", name + ".py"),
                      "metric_" + name.replace(".", "_"))


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def measured(tick_s, ticks=100, events=3000, contexts=3000 * 400):
    mods = [["jit__tick_rng_jit(1)", i * 10 ** 8, int(tick_s * 1e9)]
            for i in range(5)]
    return {"trace": {"window_s": 1.0, "host": [],
                      "devices": {"/device:TPU:0": {"modules": mods,
                                                    "ops": mods}}},
            "ticks": ticks, "tick_events": events, "contexts": contexts,
            "counts": work.counts(cfg("h2o-danube-1.8b")), "peaks": PEAKS,
            "window_s": 10.0, "chips": 1, "flops": 0}


def test_decode_roofline_is_least_time_over_tick_time():
    m = measured(0.05)
    c = m["counts"]
    nbytes = (100 * c.weight_bytes + c.kv_bytes_per_token * 3000 * 400
              + 3000 * (2560 * 2 + 4 * 32000))
    least = nbytes / 819e9                   # bandwidth-bound at batch 30
    assert reader("decode_roofline").read(m) == pytest.approx(
        100.0 * least / (100 * 0.05))
    # a tick that takes exactly the least time reads 100
    assert reader("decode_roofline").read(measured(least / 100)) == \
        pytest.approx(100.0, rel=1e-6)


def test_readers_find_nothing_without_ticks():
    m = measured(0.05, ticks=0, events=0, contexts=0)
    assert reader("decode_roofline").read(m) is None
    assert reader("mfu.thr").read(m) is None
    assert reader("occupancy.thr").read(dict(m, slots=32)) is None


def test_watch_names_a_stretch_without_a_tick():
    import threading
    import time

    class Engine:
        ticks = 0

    eng, halt = Engine(), threading.Event()

    def loop():                     # ticks every 10 ms, then stands still
        while not halt.is_set():
            if not 0.3 < time.monotonic() - t0 < 1.0:
                eng.ticks += 1
            time.sleep(0.01)

    eng._thread = threading.Thread(target=loop)
    watch = window.Watch([eng])
    t0 = time.monotonic()
    eng._thread.start()
    watch.start()
    time.sleep(1.3)
    notes = watch.stop()
    halt.set()
    eng._thread.join()
    assert len(watch.stalls) == 1, watch.stalls
    at, secs, stack = watch.stalls[0]
    assert 0.55 < secs < 0.9 and 0.2 < at < 0.45
    assert "loop (test_ledger.py:" in stack
    assert "1 stretches without a tick" in notes[0]
