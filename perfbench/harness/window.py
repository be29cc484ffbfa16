"""The measured window and what a driver hands back from it.

A driver (``perfbench/drivers/<name>.py``, named by the mix) builds the
served path, offers the mix's load and measures with a :class:`Window`:
counter and request snapshots at its edges, a profiler trace of its middle
in a traced run, and a :class:`Watch` on where it lost time.  It returns an
:class:`Outcome`: the end-to-end numbers, what the per-layer readers read,
the device memory peak and the finished requests the check samples.
"""
from __future__ import annotations

import dataclasses
import gc
import resource
import threading
import time
from typing import Dict, List

import numpy as np

from harness import program, trace, traffic, work

#: a JSON-safe stand-in for an unbounded latency (a failed request)
UNBOUNDED_MS = 1e9
#: a request in none of the engine's lists that returns within this long
#: of a snapshot had finished at it; one that returns later was admitting
FINISHING_S = 0.25


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]
    attempted: int
    failed: int
    layer: dict
    memory_peak: int
    samples: List[dict]
    notes: List[str] = dataclasses.field(default_factory=list)


def p95(values) -> float:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, 95)) if v.size else UNBOUNDED_MS


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def sleep_until(t: float) -> None:
    while True:
        dt = t - time.monotonic()
        if dt <= 0:
            return
        time.sleep(min(dt, 0.5))


def prompt_lengths(mix: dict) -> List[int]:
    """One prompt length per power-of-two prefill bucket the mix reaches."""
    lo, hi = traffic.length_span(mix)
    out, b = [], 8
    while b < 2 * hi:
        if b >= lo:
            out.append(min(b, hi))
        b *= 2
    return sorted(set([max(lo, 1)] + out))


class Watch:
    """Where the window lost time, for the run's notes: the longest
    stretches in which no engine ticked (with the engine thread's stack
    half a second into each), the interpreter's garbage collections, and
    the process's CPU time and involuntary context switches.  A thread
    samples the tick counters every ``PERIOD`` seconds."""

    PERIOD = 0.02
    #: a stretch without a tick longer than this is counted
    STALL_S = 0.25

    def __init__(self, engines):
        self.engines = engines
        self.stalls: List[tuple] = []      # (offset, seconds, stack)
        self.collections: List[tuple] = []  # (offset, seconds, generation)
        self._halt = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)

    def _ticks(self) -> int:
        return sum(int(e.ticks) for e in self.engines)

    def _gc(self, phase, info):
        now = time.monotonic()
        if phase == "start":
            self._gc_t = now
        elif hasattr(self, "_gc_t"):
            self.collections.append((self._gc_t - self.t0, now - self._gc_t,
                                     info["generation"]))

    def _sample(self):
        last_n, last_t, stack = self._ticks(), time.monotonic(), None
        while True:
            halted = self._halt.wait(self.PERIOD)
            now, n = time.monotonic(), self._ticks()
            if n != last_n or halted:
                if now - last_t > self.STALL_S:
                    self.stalls.append((last_t - self.t0, now - last_t,
                                        stack))
                last_n, last_t, stack = n, now, None
            elif stack is None and now - last_t > 2 * self.STALL_S:
                stack = program.engine_stack(self.engines[0])
            if halted:
                return

    def start(self) -> None:
        self.t0 = time.monotonic()
        self._cpu0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._gc)
        self._thread.start()

    def stop(self) -> List[str]:
        self._halt.set()
        self._thread.join()
        gc.callbacks.remove(self._gc)
        wall = time.monotonic() - self.t0
        r0, r1 = self._cpu0, resource.getrusage(resource.RUSAGE_SELF)
        lost = sum(s for _, s, _ in self.stalls)
        top = sorted(self.stalls, key=lambda s: -s[1])[:3]
        gcs = sorted(self.collections, key=lambda c: -c[1])
        return [
            f"window {wall:.2f} s: {len(self.stalls)} stretches without a "
            f"tick over {self.STALL_S} s, {lost:.2f} s in all; longest "
            + "; ".join(f"{s:.2f} s at {t:.1f} s (engine thread in {k})"
                        for t, s, k in top),
            f"garbage collections {len(gcs)}, {sum(c[1] for c in gcs):.3f} "
            "s in all" + (f", longest {gcs[0][1]:.3f} s (generation "
                          f"{gcs[0][2]}) at {gcs[0][0]:.1f} s" if gcs else ""),
            f"process CPU {r1.ru_utime - r0.ru_utime:.1f} s user, "
            f"{r1.ru_stime - r0.ru_stime:.1f} s system; involuntary context "
            f"switches {r1.ru_nivcsw - r0.ru_nivcsw}"]


class Window:
    """The measured window: counter and request snapshots at its edges,
    a :class:`Watch` over it and, in a traced run, a profiler trace of its
    middle."""

    def __init__(self, run, engines):
        self.run, self.engines = run, engines
        self.snaps = {}
        self.record = None
        self.watched: List[str] = []

    def _snap(self, name):
        self.snaps[name] = {"t": time.monotonic(),
                            "counters": program.engine_counters(self.engines),
                            "live": program.live_requests(self.engines),
                            "compiles": self.run.compiles()}

    def measure(self, start: float) -> None:
        secs = self.run.seconds
        watch = Watch(self.engines)
        sleep_until(start)
        self._snap("start")
        watch.start()
        if self.run.traced:
            t_trace = min(self.run.trace_seconds, 0.6 * secs)
            sleep_until(start + (secs - t_trace) / 2)
            self.record = trace.capture(t_trace)
        sleep_until(start + secs)
        self._snap("end")
        self.watched = watch.stop()

    def delta(self, key: str, a="start", b="end") -> int:
        return self.snaps[b]["counters"][key] - self.snaps[a]["counters"][key]

    def layer(self) -> dict:
        d = {k: self.delta(k) for k in self.snaps["end"]["counters"]}
        d["window_s"] = self.snaps["end"]["t"] - self.snaps["start"]["t"]
        d["trace"] = self.record
        d["compiles_in_window"] = (self.snaps["end"]["compiles"]
                                   - self.snaps["start"]["compiles"])
        return d


class Ledger:
    """What each in-process request emitted by a given moment, from the
    snapshots of the engine's slots, queue and finished requests: the
    events inside a stretch of the window, with the context each was
    decoded at."""

    def __init__(self):
        self.reqs: Dict[str, dict] = {}

    def add(self, rid: str, prompt_len: int, sub: float, parent=None):
        self.reqs[rid] = {"S": prompt_len, "sub": sub, "ret": None,
                          "n": None, "parent": parent}

    def done(self, rid: str, n: int, ret: float) -> None:
        self.reqs[rid].update(n=n, ret=ret)

    def emitted(self, rid: str, snap: dict) -> int:
        r, live, t = self.reqs[rid], snap["live"], snap["t"]
        if rid in live:
            return live[rid]
        if r["sub"] > t or r["ret"] is None:
            return 0
        # in no list: not submitted yet, its admission (a prefill) under
        # way, or just out of its slot and not yet among the finished
        return r["n"] if r["ret"] - t < FINISHING_S else 0

    def overlapping(self, a: dict, b: dict) -> List[dict]:
        """The requests submitted before snapshot ``b`` and not returned
        by snapshot ``a``."""
        return [r for r in self.reqs.values() if r["sub"] < b["t"]
                and (r["ret"] is None or r["ret"] > a["t"])]

    def stretch(self, a: dict, b: dict):
        """(events, events decoded by a tick, sum of their contexts, the
        prompt lengths of the prefills whose first event fell inside)."""
        ev = tick_ev = ctx = 0
        prefills, seen = [], set()
        for rid, r in self.reqs.items():
            e0, e1 = self.emitted(rid, a), self.emitted(rid, b)
            if e1 <= e0:
                continue
            ev += e1 - e0
            j0 = max(e0, 1)
            if e1 > j0:
                tick_ev += e1 - j0
                ctx += (e1 - j0) * r["S"] + (e1 - 1 + j0) * (e1 - j0) // 2
            key = r["parent"] or rid
            if e0 == 0 and key not in seen:
                seen.add(key)
                prefills.append(r["S"])
        return ev, tick_ev, ctx, prefills


def engine_layer(win: Window, ledger: Ledger, counts: work.Counts,
                 slots: int) -> dict:
    """The window's counter deltas with the events it emitted, those its
    decode ticks emitted (a re-admission after preemption emits one by
    prefill), the keys those ticks read, and the operations of it all."""
    s = win.snaps
    ev, tick_ev, ctx, prefills = ledger.stretch(s["start"], s["end"])
    lay = win.layer()
    tick_ev = max(0, tick_ev - lay["preemptions"])
    lay.update(events=ev, tick_events=tick_ev, contexts=ctx, slots=slots,
               flops=(counts.decode_flops(tick_ev, ctx)
                      + sum(counts.prefill_flops(n) for n in prefills)))
    return lay
