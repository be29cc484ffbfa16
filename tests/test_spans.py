"""Spans and counters inside the engine (``repro.serve.spans``): what the
aggregates and counters add up to on a small engine, and — on a real
profiler capture — that the span names the benchmark's readers read are the
names the program writes."""
import importlib.util
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.api import FuturesRequest, GenerateRequest
from repro.api.client import EngineBackend
from repro.configs import get_config
from repro.core import init_delphi
from repro.serve import BatchedEngine, Request
from repro.serve.spans import Spans

PERFBENCH = Path(__file__).resolve().parent.parent / "perfbench"

#: every span the engine's own thread writes
ENGINE_SPANS = ("engine.step", "engine.control", "engine.admit",
                "engine.fork", "engine.prefill_chunks", "engine.blocks",
                "engine.uniforms", "engine.tick.dispatch", "engine.tick.wait",
                "engine.apply_host", "engine.idle")
API_SPANS = ("api.generate", "api.sample_futures", "api.futures.submit",
             "api.futures.wait", "api.futures.collect")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=96, max_seq_len=64, max_age=1e9)
    params = init_delphi(cfg, jax.random.PRNGKey(3))
    return params, cfg


def _prompt(i, S):
    toks = ((np.arange(S) * 7 + i) % 90 + 3).astype(np.int32)
    ages = np.linspace(20.0, 50.0, S).astype(np.float32)
    return toks, ages


def _uniforms(i, shape):
    return np.random.default_rng(i).random(shape, dtype=np.float32)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _drive(backend, n: int, max_new: int = 6) -> None:
    """Futures for one patient and a plain generation, ``n`` times, through
    the API on this thread (the engine ticks on its own)."""
    V = backend.vocab_size
    for i in range(n):
        toks, ages = _prompt(i, 20 + i % 3)
        backend.sample_futures(FuturesRequest(
            tokens=toks.tolist(), ages=ages.tolist(), n_futures=3,
            max_new=max_new, uniforms=_uniforms(i, (3, max_new, V)),
            request_id=f"f{i}"))
        toks, ages = _prompt(100 + i, 12)
        backend.generate(GenerateRequest(
            tokens=toks.tolist(), ages=ages.tolist(), max_new=max_new,
            uniforms=_uniforms(100 + i, (max_new, V)), request_id=f"g{i}"))


def _engine(params, cfg, **kw):
    return BatchedEngine(params, cfg, slots=4, max_context=64, cache="paged",
                         block_size=16, prefix_cache=True, **kw)


def test_span_aggregate_counts_totals_and_longest():
    spans = Spans()
    for secs in (0.002, 0.006):
        with spans.span("phase", group=2) as sp:
            sp.stat(first="r1")
            time.sleep(secs)
    with pytest.raises(KeyError):
        with spans.span("phase"):
            raise KeyError("closed on the way out")
    agg = spans.snapshot()["phase"]
    assert agg["calls"] == 3
    assert 0.008 <= agg["total_s"] < 0.5
    assert 0.006 <= agg["longest_s"] <= agg["total_s"]


def test_span_aggregate_loses_no_update_across_threads():
    """The engine's thread and its callers' threads close spans into one
    aggregate: more threads than cores, switching as often as the
    interpreter allows, lose no call."""
    spans, n_threads, n_spans = Spans(), 16, 2000
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def close_spans():
            for _ in range(n_spans):
                with spans.span("shared"):
                    pass
        threads = [threading.Thread(target=close_spans)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert spans.snapshot()["shared"]["calls"] == n_threads * n_spans


def test_counters_and_spans_add_up(setup):
    params, cfg = setup
    eng = _engine(params, cfg, prefill_chunk_tokens=16)
    be = EngineBackend(eng)
    eng.start(retain_completed=True)
    try:
        _drive(be, 3)
    finally:
        eng.stop()
    h = eng.health_stats()
    spans = h["spans"]
    for name in ENGINE_SPANS + API_SPANS:
        assert name in spans, name
    ticks = h["ticks"]
    assert ticks > 0
    assert spans["engine.tick.wait"]["calls"] == ticks
    assert spans["engine.tick.dispatch"]["calls"] == ticks
    assert spans["engine.step"]["calls"] >= ticks
    finished = eng.completed
    assert h["events_tick"] + h["events_admit"] == sum(
        len(r.out_tokens) for r in finished)
    assert h["events_tick"] <= h["slot_ticks"] <= ticks * eng.slots
    # 3 held parents, 9 futures, 3 generations: each admitted once
    assert h["admitted"] == len(finished) == 15
    assert h["queue_wait_s"] >= 0.0
    for r in finished:
        assert r.t_submit <= r.t_admit <= r.t_done, r.request_id
        if r.out_tokens:
            assert r.t_admit <= r.t_first <= r.t_done, r.request_id
        else:
            assert r.t_first is None


def test_foreground_rng_ticks_count_their_events(setup):
    """No uniforms, the caller's thread driving ``run()``: the RNG tick
    path counts every event once, and no loop waits for work."""
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=64)
    reqs = []
    for i in range(3):
        toks, ages = _prompt(i, 8 + i)
        reqs.append(Request(tokens=toks, ages=ages, max_new=5))
        eng.submit(reqs[-1])
    eng.run()
    h = eng.health_stats()
    assert h["events_tick"] + h["events_admit"] == sum(
        len(r.out_tokens) for r in reqs)
    assert h["events_admit"] <= len(reqs)
    assert h["spans"]["engine.tick.wait"]["calls"] == h["ticks"]
    assert "engine.uniforms" not in h["spans"]
    assert "engine.idle" not in h["spans"]


def test_profiler_capture_holds_every_span_the_readers_read(setup):
    """A real ``jax.profiler`` capture around two ticking engines, flattened
    by the benchmark's own ``harness/trace.flatten``: every name the
    ``tick_host_ms.thr`` and ``admission_share.thr`` readers look for is in
    the record's host events, and both read a number from it.  One engine
    prefills whole prompts (``engine.admit``, ``engine.fork``), the other
    in chunks (``engine.prefill_chunks``)."""
    params, cfg = setup
    trace = _load(PERFBENCH / "harness" / "trace.py", "perfbench_trace")
    readers = [_load(PERFBENCH / "metrics" / f"{n}.py", n.replace(".", "_"))
               for n in ("tick_host_ms.thr", "admission_share.thr")]
    engines = [_engine(params, cfg), _engine(params, cfg,
                                             prefill_chunk_tokens=16)]
    backends = [EngineBackend(e) for e in engines]
    for e in engines:
        e.start()
    for b in backends:
        _drive(b, 1)                    # compile every shape before tracing
    stop = threading.Event()
    errors = []

    def feed(b):
        i = 0
        while not stop.is_set():
            try:
                _drive(b, 1, max_new=4 + i % 2)
            except Exception as e:      # noqa: BLE001 - reported below
                errors.append(e)
                return
            i += 1

    feeders = [threading.Thread(target=feed, args=(b,), daemon=True)
               for b in backends]
    for t in feeders:
        t.start()
    try:
        rec = trace.capture(1.0)
    finally:
        stop.set()
        for t in feeders:
            t.join(timeout=60)
        for e in engines:
            e.stop()
    assert not errors, errors
    names = {h[1] for h in rec["host"]}
    for r in readers:
        for name in r.SPANS:
            assert name in names, name
    host_ms, share = (r.read({"trace": rec}) for r in readers)
    assert host_ms is not None and host_ms > 0.0
    assert share is not None and 0.0 < share <= 100.0 * len(engines)
