"""Paged KV-cache engine: ring-parity (generate/stream/batch), free-block
admission, preemption on pool exhaustion, cancellation, timeouts, and the
zero-leaked-blocks invariant."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.api.errors import RequestCancelledError, RequestTimeoutError
from repro.configs import get_config
from repro.core import init_delphi
from repro.serve import BatchedEngine, BlockAllocator, Request


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=96, max_seq_len=48, max_age=1e9)
    params = init_delphi(cfg, jax.random.PRNGKey(7))
    return params, cfg


def _uniforms(max_new, V, seed=42):
    rng = np.random.default_rng(seed)
    return rng.uniform(size=(max_new, V)).astype(np.float32)


def _req(s, max_new=8, uniforms=None, request_id=None):
    S = 3 + (s % 4)
    return Request(tokens=(np.arange(3, 3 + S, dtype=np.int32) + s) % 90,
                   ages=np.linspace(0.0, 30.0, S).astype(np.float32),
                   max_new=max_new, uniforms=uniforms, request_id=request_id)


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------
def test_allocator_free_list():
    a = BlockAllocator(6)               # capacity 5, block 0 reserved
    assert (a.capacity, a.free, a.used) == (5, 5, 0)
    ids = a.alloc(3)
    assert len(ids) == 3 and 0 not in ids
    assert a.alloc(3) is None           # never partial
    assert a.used == 3 and a.peak_used == 3
    a.release(ids)
    assert a.used == 0 and a.free == 5
    with pytest.raises(ValueError):
        a.release([0])                  # trash block is not allocatable
    with pytest.raises(RuntimeError):
        a.release(ids + [1, 2])         # over-free detected


def test_admission_prefill_splits_at_its_row_budget(setup, monkeypatch):
    """An admission group whose prefill would return more ring rows
    (batch bucket x max_context) than ``PREFILL_ROWS`` splits: the rest
    prefill as further groups of the same step, and all are served."""
    from repro.serve import engine as engine_mod
    params, cfg = setup
    for rows, groups in ((None, 1), (2 * 32, 4)):
        if rows is not None:
            monkeypatch.setattr(engine_mod, "PREFILL_ROWS", rows)
        eng = BatchedEngine(params, cfg, slots=8, max_context=32,
                            cache="paged")
        reqs = [_req(s, uniforms=_uniforms(8, cfg.vocab_size, seed=s))
                for s in range(8)]
        for r in reqs:
            eng.submit(r)
        eng.step()                  # one step admits all eight
        assert all(r is not None for r in eng.slot_req)
        assert eng.admit_batches == groups
        assert eng.prefill_shapes == {(8 // groups, 8)}
        eng.run()
        assert all(r.done and r.error is None and r.out_tokens
                   for r in reqs)
        assert eng.allocator.used == 0


def test_engine_rejects_bad_paged_config(setup):
    params, cfg = setup
    with pytest.raises(ValueError, match="multiple"):
        BatchedEngine(params, cfg, max_context=50, cache="paged",
                      block_size=16)
    with pytest.raises(ValueError, match="one full slot"):
        BatchedEngine(params, cfg, max_context=64, cache="paged",
                      block_size=16, blocks=3)
    with pytest.raises(ValueError, match="'ring' or 'paged'"):
        BatchedEngine(params, cfg, cache="dense")


# ---------------------------------------------------------------------------
# Ring parity (the tentpole invariant)
# ---------------------------------------------------------------------------
def _run(params, cfg, kind, reqs, **kw):
    eng = BatchedEngine(params, cfg, slots=2, max_context=64, cache=kind,
                        **kw)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == len(reqs)
    return eng, [(r.out_tokens, r.out_ages) for r in done]


def test_paged_bit_identical_to_ring_generate(setup):
    """Same slots, same injected uniforms: the paged engine's trajectories
    (tokens AND fp32 ages) equal the ring engine's bit for bit — the paged
    read path reconstructs the exact ring view."""
    params, cfg = setup
    u = _uniforms(8, cfg.vocab_size)
    ring_reqs = [_req(s, uniforms=u) for s in range(5)]
    paged_reqs = [_req(s, uniforms=u) for s in range(5)]
    _, ring = _run(params, cfg, "ring", ring_reqs)
    eng, paged = _run(params, cfg, "paged", paged_reqs, block_size=16)
    assert ring == paged                # exact: tokens and ages
    assert eng.allocator.used == 0


def test_paged_bit_identical_over_width_prompt(setup):
    """S > max_context: the wrapped ring pack flows through the block copy
    identically (solo exact-shape admission in both engines)."""
    params, cfg = setup
    S, W = 33, 16
    toks = (np.arange(3, 3 + S) % 90).astype(np.int32)
    ages = np.linspace(0.0, 30.0, S).astype(np.float32)
    u = _uniforms(4, cfg.vocab_size, seed=13)

    def mk():
        return Request(tokens=toks, ages=ages, max_new=4, uniforms=u)
    r_ring = BatchedEngine(params, cfg, slots=1, max_context=W)
    r_ring.submit(mk())
    ring_done = r_ring.run()
    r_paged = BatchedEngine(params, cfg, slots=1, max_context=W,
                            cache="paged", block_size=8)
    r_paged.submit(mk())
    paged_done = r_paged.run()
    assert ring_done[0].out_tokens == paged_done[0].out_tokens
    assert ring_done[0].out_ages == paged_done[0].out_ages
    assert r_paged.allocator.used == 0


def test_paged_stream_and_batch_parity(setup):
    """EngineBackend generate/stream/batch over the paged engine == the
    ring engine, event for event, under injected uniforms."""
    from repro.api import GenerateRequest
    from repro.api.client import EngineBackend
    params, cfg = setup
    u = _uniforms(6, cfg.vocab_size, seed=5)
    toks, ages = [3, 10, 20], [0.0, 15.0, 28.0]

    def backend(kind):
        return EngineBackend.create(params, cfg, slots=2, max_context=64,
                                    cache=kind, block_size=16)
    ring_b, paged_b = backend("ring"), backend("paged")
    req = GenerateRequest(tokens=toks, ages=ages, max_new=6, uniforms=u)
    g_r = ring_b.generate(req)
    g_p = paged_b.generate(req)
    assert g_r.tokens == g_p.tokens and g_r.ages == g_p.ages
    ev_r = [e.token for e in ring_b.stream(req)]
    ev_p = [e.token for e in paged_b.stream(req)]
    assert ev_r == ev_p == g_r.tokens
    batch = [GenerateRequest(tokens=toks, ages=ages, max_new=6, uniforms=u)
             for _ in range(3)]
    b_r = ring_b.generate_batch(batch)
    b_p = paged_b.generate_batch(batch)
    assert [r.tokens for r in b_r] == [r.tokens for r in b_p]
    assert paged_b.engine.allocator.used == 0


# ---------------------------------------------------------------------------
# Scheduler: free-block admission, growth, preemption
# ---------------------------------------------------------------------------
def test_admission_budgeted_by_free_blocks(setup):
    """With a pool below slots x context the scheduler admits what fits and
    queues the rest; peak concurrency still exceeds what a dense ring of
    the same bytes could hold once requests are short."""
    params, cfg = setup
    # capacity 5 blocks of 8 tokens; 4 slots x 32 ctx would need 16
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=6)
    for s in range(6):
        eng.submit(_req(s, max_new=4))
    done = eng.run(max_ticks=2000)
    assert len(done) == 6
    assert eng.allocator.used == 0
    assert eng.allocator.peak_used <= 5
    assert eng.peak_active >= 2         # several short requests co-resident


def test_preemption_on_pool_exhaustion(setup):
    """Decode growth past the pool preempts the youngest request (requeued,
    recompute-resumed) instead of deadlocking; every request completes and
    no block leaks."""
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=6)
    for s in range(8):
        eng.submit(_req(s, max_new=10))
    done = eng.run(max_ticks=4000)
    assert len(done) == 8
    for r in done:
        assert r.error is None
        assert (len(r.out_tokens) == 10
                or r.out_tokens[-1] == cfg.death_token)
        assert len(r.out_ages) == len(r.out_tokens)
        assert all(b >= a - 1e-6
                   for a, b in zip(r.out_ages, r.out_ages[1:]))
    assert eng.preemptions > 0
    assert eng.allocator.used == 0


def test_preempted_injected_request_resumes_uniform_rows(setup):
    """A preempted uniforms-injected request consumes row i for event i
    across the preemption boundary (resume re-prefills, then continues
    from the next unconsumed row)."""
    params, cfg = setup
    u = _uniforms(10, cfg.vocab_size, seed=11)
    reqs = [_req(s, max_new=10, uniforms=u) for s in range(4)]
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=6)
    for r in reqs:
        eng.submit(r)
    done = eng.run(max_ticks=4000)
    assert len(done) == 4 and eng.preemptions > 0
    # sanity: every trajectory emitted events and respects max_new
    for r in done:
        assert 1 <= len(r.out_tokens) <= 10
    assert eng.allocator.used == 0


# ---------------------------------------------------------------------------
# Cancellation + timeout free blocks
# ---------------------------------------------------------------------------
def test_cancel_pending_and_inflight(setup):
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=32, cache="paged",
                        block_size=8)
    rs = [_req(s, max_new=28, request_id=f"r{s}") for s in range(4)]
    for r in rs:
        eng.submit(r)
    eng.step()                          # admit r0/r1; r2/r3 pending
    assert eng.cancel("r0")             # in flight
    assert eng.cancel("r3")             # pending
    assert not eng.cancel("unknown-id")
    eng.run(max_ticks=2000)
    assert isinstance(rs[0].error, RequestCancelledError)
    assert isinstance(rs[3].error, RequestCancelledError)
    assert rs[1].error is None and rs[2].error is None
    assert rs[0] not in eng.completed and rs[3] not in eng.completed
    assert eng.allocator.used == 0
    assert not eng.cancel("r0")         # already finished


def test_cancel_from_background_thread(setup):
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=1, max_context=512, cache="paged",
                        block_size=16).start()
    try:
        blocker = _req(0, max_new=480)
        target = _req(1, max_new=480, request_id="victim")
        evt = threading.Event()
        target.on_done = lambda _r: evt.set()
        eng.submit(blocker)
        eng.submit(target)              # queued behind the single slot
        assert eng.cancel("victim")
        assert evt.wait(30)
        assert isinstance(target.error, RequestCancelledError)
    finally:
        eng.stop()
    assert eng.allocator.used == 0


def test_request_timeout_frees_blocks(setup):
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=32, cache="paged",
                        block_size=8, request_timeout=0.0)
    r = _req(0, max_new=20)
    eng.submit(r)
    time.sleep(0.01)
    eng.run(max_ticks=100)
    assert r.done and isinstance(r.error, RequestTimeoutError)
    assert eng.allocator.used == 0


def test_ring_engine_cancel_also_supported(setup):
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=32)
    r = _req(1, max_new=28)
    eng.submit(r)
    eng.step()
    assert eng.cancel(r.request_id)
    eng.run(max_ticks=500)
    assert r.done and isinstance(r.error, RequestCancelledError)


def test_paged_keeps_one_host_sync_per_tick(setup, monkeypatch):
    """The paged scheduler's host-side bookkeeping (tables, allocator,
    slot positions) must not add device->host transfers: still exactly ONE
    packed sync per tick plus one per admission batch."""
    from repro.serve import engine as engine_mod
    params, cfg = setup
    calls = []
    orig = engine_mod._to_host

    def counting(x):
        calls.append(x.shape)
        return orig(x)
    monkeypatch.setattr(engine_mod, "_to_host", counting)
    eng = BatchedEngine(params, cfg, slots=2, max_context=64, cache="paged",
                        block_size=16)
    for s in range(5):
        eng.submit(_req(s, max_new=4))
    done = eng.run()
    assert len(done) == 5
    assert len(calls) == eng.host_syncs == eng.ticks + eng.admit_batches
    assert all(s[0] == 4 for s in calls)


def test_admission_crash_releases_blocks_and_fails_waiters(setup, monkeypatch):
    """A device error mid-admission (after blocks were allocated, before
    the cohort landed in slots) must return the blocks to the pool and
    surface the failure to the cohort's waiters instead of stranding
    them."""
    from repro.serve import engine as engine_mod
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=32, cache="paged",
                        block_size=8)

    def boom(*a, **k):
        raise RuntimeError("injected insert failure")
    monkeypatch.setattr(engine_mod, "_insert_blocks_jit", boom)
    rs = [_req(s, max_new=4) for s in range(2)]
    for r in rs:
        eng.submit(r)
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()                       # foreground: the error propagates
    # blocks allocated for the crashed cohort are back in the pool and the
    # requests are back on the queue (a background loop would now fail them
    # via _fail_inflight)
    assert eng.allocator.used == 0
    assert len(eng.pending) == 2
    eng._fail_inflight(RuntimeError("injected insert failure"))
    assert all(r.done and r.error is not None for r in rs)
    assert eng.allocator.used == 0


def test_duplicate_request_id_rejected(setup):
    from repro.api.errors import InvalidRequestError
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=32, cache="paged",
                        block_size=8)
    eng.submit(_req(0, request_id="dup"))
    with pytest.raises(InvalidRequestError, match="already in flight"):
        eng.submit(_req(1, request_id="dup"))
    eng.run(max_ticks=500)
    eng.submit(_req(2, request_id="dup"))   # id free again after completion
    eng.run(max_ticks=500)
    assert eng.allocator.used == 0


def test_pool_stats_shape(setup):
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=32, cache="paged",
                        block_size=8)
    st = eng.pool_stats()
    assert st["cache"] == "paged" and st["blocks"] == 9
    assert st["cache_bytes"] == eng.cache_bytes > 0
    ring = BatchedEngine(params, cfg, slots=2, max_context=32)
    assert ring.pool_stats()["cache"] == "ring"
    # dense-equivalent default pool: paged k/v bytes == ring k/v bytes
    dflt = BatchedEngine(params, cfg, slots=2, max_context=32, cache="paged",
                        block_size=8)
    assert dflt.allocator.capacity == 2 * (32 // 8)
