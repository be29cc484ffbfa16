"""Profiler trace: capture a stretch of the window, reduce it to numbers.

``capture`` records JAX's profiler for a few seconds and flattens the
``.xplane.pb`` it writes into a plain record (``events``): per device plane
the program (``XLA Modules``) and operation (``XLA Ops``) events, and the
host threads' events, each as ``[name, start_ns, duration_ns]`` on one
clock.  Everything below works on that record alone, so the reduction is
checked on a small recorded one (``tests/fixtures``).
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:"
#: host events longer than this many times a gap say nothing about it
_ENCLOSING = 10.0
#: host events shorter than this are dropped from the record
HOST_MIN_NS = 10_000
#: the engine's decode-tick programs (``_tick_u_jit``, ``_tick_rng_jit``)
#: by their jitted functions' names, until the program names its spans
TICK = r"_tick_\w*jit"


def capture(seconds: float) -> dict:
    """Trace the next ``seconds`` of this process; -> the flat record."""
    import jax
    d = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        t0 = time.monotonic()
        time.sleep(seconds)
        window = time.monotonic() - t0
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return flatten(jax.profiler.ProfileData.from_file(paths[0]), window)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _short(hlo: str) -> str:
    """``%fusion.7 = bf16[32,8]{1,0:T(8,128)} fusion(...)`` -> ``fusion.7
    bf16[32,8]``: an operation's name and result shape."""
    lhs, _, rhs = hlo.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}".strip()


def flatten(profile, window_s: float) -> dict:
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = _short(ev.name) if key == "ops" else ev.name
                    dev[key].append([name, int(ev.start_ns),
                                     int(ev.duration_ns)])
            if not dev["modules"] and not dev["ops"]:
                del devices[plane.name]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns >= HOST_MIN_NS:
                        host.append([line.name, ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"window_s": window_s, "devices": devices, "host": host}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_intervals(dev: dict) -> List[Tuple[int, int]]:
    evs = dev["ops"] or dev["modules"]
    return _union([(t, t + d) for _, t, d in evs])


def busy_s(rec: dict) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the device planes;
    None where the trace holds no device operation."""
    per = [sum(e - s for s, e in busy_intervals(dev)) * 1e-9
           for dev in rec["devices"].values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else None


def idle_share(rec: dict) -> Optional[float]:
    """Share of the traced window, in %, in which no operation ran on a
    device, averaged over the devices; None without any operation."""
    busy = busy_s(rec)
    return None if busy is None else 100.0 * (1.0 - busy / rec["window_s"])


def program_calls(rec: dict, pattern: str) -> List[Tuple[int, float]]:
    """Per device plane with such programs: (calls, total device seconds)
    of the programs whose name matches ``pattern``."""
    rx = re.compile(pattern)
    out = []
    for dev in rec["devices"].values():
        hits = [d for name, _, d in dev["modules"] if rx.search(name)]
        if hits:
            out.append((len(hits), sum(hits) * 1e-9))
    return out


def device_ops(rec: dict, top: int = 10) -> List[list]:
    """The operations that took most device time, averaged over planes."""
    tot: Dict[str, float] = {}
    n = max(1, len(rec["devices"]))
    for dev in rec["devices"].values():
        for name, _, d in dev["ops"] or dev["modules"]:
            tot[name] = tot.get(name, 0.0) + d * 1e-9 / n
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:top]]


def idle_gaps(rec: dict, top: int = 10) -> List[list]:
    """The longest idle gaps of the first device plane, each named by the
    host event that overlaps it most (``thread: event``)."""
    if not rec["devices"]:
        return []
    dev = rec["devices"][sorted(rec["devices"])[0]]
    busy = busy_intervals(dev)
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)[:top]
    out = []
    for length, s, e in gaps:
        best, best_ov = "no host event", 0
        for thread, name, t, d in rec["host"]:
            if d > _ENCLOSING * length:
                continue
            ov = min(e, t + d) - max(s, t)
            if ov > best_ov:
                best, best_ov = f"{thread}: {name}", ov
        out.append([best, length * 1e-9])
    return out


def shrink(rec: dict, keep_ms: float = 20.0) -> dict:
    """The first ``keep_ms`` of a record: a small fixture for the tests."""
    def cut(evs, t0):
        return [e for e in evs if e[-2] < t0 + keep_ms * 1e6]
    starts = [e[1] for dev in rec["devices"].values()
              for e in dev["ops"] + dev["modules"]]
    t0 = min(starts) if starts else 0
    return {"window_s": keep_ms / 1e3,
            "devices": {k: {"modules": cut(v["modules"], t0),
                            "ops": cut(v["ops"], t0)}
                        for k, v in rec["devices"].items()},
            "host": [h for h in rec["host"]
                     if t0 <= h[2] < t0 + keep_ms * 1e6]}
