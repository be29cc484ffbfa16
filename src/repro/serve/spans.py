"""Phase spans on the profiler's clock, and what they add up to.

``Spans.span(name, **stats)`` marks one phase of the served path.  It
enters ``jax.profiler.TraceAnnotation(name, **stats)``, so a profiler trace
shows the phase in its host plane on the same clock as the device's
operations, and it adds the phase's ``time.perf_counter`` seconds to a
per-name aggregate (calls, total seconds, longest call) that
:meth:`Spans.snapshot` reads under the lock — the operator's view through
``/v1/healthz``.

Spans are always on.  With no profiler running one costs about 2 us on a
CPU core, so they mark phases (about a dozen per engine step), never a slot
or an event.

Span names (``engine.*`` on the engine's thread, ``api.*`` on the caller's):

- ``engine.step``: one ``BatchedEngine.step``; ``engine.idle``: the loop's
  wait for work, outside any step.
- inside a step: ``engine.control`` (cancels, deadlines, slot updates
  pushed to the device), ``engine.admit`` (pending requests selected and
  admitted), ``engine.fork`` (queued forks applied),
  ``engine.prefill_chunks``, ``engine.blocks`` (block growth,
  copy-on-write, preemption),
  ``engine.uniforms`` (the tick's injected uniforms to the device),
  ``engine.tick.dispatch`` (the tick program's call), ``engine.tick.wait``
  (its one packed fetch), ``engine.apply_host`` (events to requests).
- ``api.generate``, ``api.sample_futures`` and, inside the latter,
  ``api.futures.submit``, ``api.futures.wait``, ``api.futures.collect``.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

import jax

_Annotation = jax.profiler.TraceAnnotation


class Span:
    """One open span: ``with spans.span(name) as sp: ... sp.stat(k=v)``."""

    __slots__ = ("_owner", "_name", "_mark", "_t0")

    def __init__(self, owner: "Spans", name: str, stats: dict):
        self._owner = owner
        self._name = name
        self._mark = _Annotation(name, **stats) if stats else _Annotation(name)

    def stat(self, **stats) -> None:
        """Stats known only once the span has begun (a group's size)."""
        self._mark.set_metadata(**stats)

    def __enter__(self) -> "Span":
        self._mark.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self._mark.__exit__(*exc)
        self._owner.add(self._name, dt)
        return False


class Spans:
    """Per-name aggregates of the spans of one engine and its callers.

    Bumped on whichever thread closes the span (the engine's, or a
    caller's), read through a locked snapshot (RL001)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._agg: Dict[str, list] = {}        # guarded-by: _lock

    def span(self, name: str, **stats) -> Span:
        return Span(self, name, stats)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            a = self._agg.get(name)
            if a is None:
                self._agg[name] = [1, seconds, seconds]
                return
            a[0] += 1
            a[1] += seconds
            if seconds > a[2]:
                a[2] = seconds

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Span name -> ``{"calls", "total_s", "longest_s"}`` since the
        engine was built."""
        with self._lock:
            return {k: {"calls": c, "total_s": t, "longest_s": m}
                    for k, (c, t, m) in sorted(self._agg.items())}
