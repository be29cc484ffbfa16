"""Prefix-sharing subsystem: refcounted block pool, COW, prefix index,
engine fork / sample_futures (bit-parity vs the vectorized oracle), the
futures wire endpoint, and the zero-leak invariant extended to refcounts."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.api import FuturesRequest, RequestCancelledError
from repro.api.client import EngineBackend, LocalBackend
from repro.configs import get_config
from repro.core import (engine_oracle_trajectories, futures_risk_items,
                        init_delphi, monte_carlo_risk)
from repro.core.parity import check_trajectory, logit_tolerance
from repro.serve import (BatchedEngine, BlockAllocator, PrefixIndex, Request,
                         SharedBlockPool, chunked_reference_trajectory,
                         ring_reference_futures)

W, BS, K = 64, 16, 4          # shared geometry -> shared jit cache


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=96, max_seq_len=48, max_age=1e9)
    params = init_delphi(cfg, jax.random.PRNGKey(7))
    return params, cfg


TOKS = np.asarray([3, 10, 20, 30, 41], np.int32)
AGES = np.linspace(0.0, 30.0, 5).astype(np.float32)


def _uniforms(n, max_new, V, seed=42):
    rng = np.random.default_rng(seed)
    return rng.uniform(size=(n, max_new, V)).astype(np.float32)


def _trajs(kids):
    return [(list(k.out_tokens), [np.float32(a) for a in k.out_ages])
            for k in kids]


# ---------------------------------------------------------------------------
# SharedBlockPool
# ---------------------------------------------------------------------------
def test_shared_pool_refcounts():
    pool = SharedBlockPool(BlockAllocator(8))        # capacity 7
    ids = pool.alloc(3)
    assert pool.used == 3 and all(pool.refcount(i) == 1 for i in ids)
    pool.share(ids)
    assert pool.shared_blocks == 3 and pool.peak_shared == 3
    pool.release(ids)                                # drop one of two refs
    assert pool.used == 3, "a still-referenced block must not free"
    assert pool.shared_blocks == 0
    pool.release(ids)
    assert pool.used == 0 and pool.total_refs == 0
    with pytest.raises(ValueError):
        pool.release(ids)                            # refcount underflow
    with pytest.raises(ValueError):
        pool.share([99])                             # share of unallocated
    assert pool.alloc(8) is None                     # never partial


def test_shared_pool_available_counts_shared_once():
    pool = SharedBlockPool(BlockAllocator(8))
    ids = pool.alloc(4)
    pool.share(ids)                                  # 2 owners, 4 blocks
    assert pool.used == 4                            # counted ONCE
    assert pool.available() == 3                     # free only — no index


# ---------------------------------------------------------------------------
# PrefixIndex
# ---------------------------------------------------------------------------
def test_prefix_index_chain_and_eviction():
    pool = SharedBlockPool(BlockAllocator(12))
    idx = PrefixIndex(pool, block_size=4, max_entries=8)
    toks = np.arange(10)
    ages = np.linspace(0, 9, 10).astype(np.float32)
    blocks = pool.alloc(3)                           # 2 full + tail
    idx.register(toks, ages, blocks, S=10, age0=9.0, logits=np.zeros(5))
    assert idx.entries == 1 and idx.cached_blocks == 3
    # chain matches full blocks only, in order, longest-prefix
    assert idx.match_prefix(toks, ages) == blocks[:2]
    assert idx.match_prefix(toks[:8], ages[:8]) == blocks[:2]
    assert idx.match_prefix(toks[:4], ages[:4]) == blocks[:1]
    other = toks.copy()
    other[1] = 77
    assert idx.match_prefix(other, ages) == []
    # exact-prompt complete lookup; age perturbation breaks it
    assert idx.lookup(toks, ages) is not None
    assert idx.lookup(toks, ages + 1.0) is None
    assert idx.lookup(toks[:9], ages[:9]) is None
    # eviction releases the index refs; owner drops at 0 -> blocks free
    pool.release([blocks[0]])        # simulate: request released its refs
    pool.release([blocks[1]])
    pool.release([blocks[2]])
    assert pool.used == 3            # index still holds all three
    assert idx.evictable() == 3
    freed = idx.evict(2)
    assert freed == 3 and idx.entries == 0 and pool.used == 0
    assert idx.match_prefix(toks, ages) == []


def test_prefix_index_lru_cap():
    pool = SharedBlockPool(BlockAllocator(32))
    idx = PrefixIndex(pool, block_size=4, max_entries=2)
    for s in range(3):
        toks = np.arange(8) + 10 * s
        b = pool.alloc(2)
        idx.register(toks, None, b, S=8, age0=0.0)
        pool.release(b)              # only the index holds them
    assert idx.entries == 2          # LRU-capped
    assert idx.evictions == 1
    assert pool.used == 4


# ---------------------------------------------------------------------------
# Fork parity: engine (ring + paged + prefix-cached) == vectorized oracle
# ---------------------------------------------------------------------------
def test_fork_bit_identical_to_oracle(setup):
    """sample_futures through hold/fork/COW must reproduce the scheduler-
    free oracle bit for bit (tokens AND fp32 ages) — on the ring engine
    (row-copy fork), the paged engine (refcounted block sharing), and the
    prefix-cached paged engine twice (2nd run admits by reference)."""
    params, cfg = setup
    n, max_new = 4, 6
    u = _uniforms(n, max_new, cfg.vocab_size)
    ora = [(list(t), [np.float32(a) for a in a_])
           for t, a_ in ring_reference_futures(
               params, cfg, TOKS, AGES, n=n, max_new=max_new, uniforms=u,
               slots=K, max_context=W)]
    ring = BatchedEngine(params, cfg, slots=K, max_context=W)
    assert _trajs(ring.sample_futures(TOKS, AGES, n=n, max_new=max_new,
                                      uniforms=u)) == ora
    paged = BatchedEngine(params, cfg, slots=K, max_context=W,
                          cache="paged", block_size=BS)
    assert _trajs(paged.sample_futures(TOKS, AGES, n=n, max_new=max_new,
                                       uniforms=u)) == ora
    assert paged.allocator.used == 0 and not paged.pool._refs
    pfx = BatchedEngine(params, cfg, slots=K, max_context=W, cache="paged",
                        block_size=BS, prefix_cache=True)
    assert _trajs(pfx.sample_futures(TOKS, AGES, n=n, max_new=max_new,
                                     uniforms=u)) == ora
    assert _trajs(pfx.sample_futures(TOKS, AGES, n=n, max_new=max_new,
                                     uniforms=u)) == ora
    assert pfx.pool_stats()["prefix_cache"]["hits"] >= 1
    pfx.drop_prefix_cache()
    assert pfx.allocator.used == 0 and not pfx.pool._refs


def test_backend_futures_match_monte_carlo_oracle(setup):
    """EngineBackend.sample_futures == monte_carlo_risk configured with the
    engine-parity trajectory source, bit for bit — trajectories AND the
    aggregated risk values (acceptance criterion)."""
    params, cfg = setup
    n, max_new, horizon = 4, 6, 100.0
    u = _uniforms(n, max_new, cfg.vocab_size, seed=3)
    req = FuturesRequest(tokens=TOKS.tolist(), ages=AGES.tolist(),
                         n_futures=n, max_new=max_new, uniforms=u,
                         horizon=horizon, top=8)
    tr = engine_oracle_trajectories(params, cfg, TOKS, AGES, n_samples=n,
                                    max_new=max_new, uniforms=u, slots=K,
                                    max_context=W)
    mc = monte_carlo_risk(params, cfg, TOKS, AGES, horizon=horizon,
                          trajectories=tr)
    code_risk = np.asarray(mc["code_risk"])
    S = len(TOKS)
    n_gen = np.asarray(tr["n_generated"])
    ora = [(np.asarray(tr["tokens"][j])[S:S + n_gen[j]].tolist(),
            [np.float32(x)
             for x in np.asarray(tr["ages"][j])[S:S + n_gen[j]]])
           for j in range(n)]
    for kind, kw in (("ring", {}), ("paged", {"block_size": BS,
                                              "prefix_cache": True})):
        b = EngineBackend.create(params, cfg, slots=K, max_context=W,
                                 cache=kind, **kw)
        out = b.sample_futures(req)
        assert [(t.tokens, [np.float32(a) for a in t.ages])
                for t in out.trajectories] == ora
        for item in out.risk.items:
            assert item.risk == pytest.approx(code_risk[item.token],
                                              abs=0.0)
        if kind == "paged":
            assert out.sharing["forks"] == 1
            assert out.sharing["cow_copies"] >= 1


def test_local_backend_futures_vectorized(setup):
    """LocalBackend fans N futures through ONE jitted call; its risk report
    aggregates through the same host-side path as the engine's."""
    params, cfg = setup
    n, max_new = 3, 5
    u = _uniforms(n, max_new, cfg.vocab_size, seed=9)
    req = FuturesRequest(tokens=TOKS.tolist(), ages=AGES.tolist(),
                         n_futures=n, max_new=max_new, uniforms=u,
                         horizon=50.0)
    out = LocalBackend(params, cfg, seq_len=48).sample_futures(req)
    assert len(out.trajectories) == n and out.backend == "local"
    items = futures_risk_items(
        [(t.tokens, t.ages) for t in out.trajectories],
        float(AGES[-1]), 50.0, cfg.vocab_size, top=10)
    assert [(i.token, i.risk) for i in out.risk.items] == items


def test_monte_carlo_risk_vectorized_uniforms(setup):
    """The vectorized monte_carlo_risk draws every sample through one
    generate_trajectories_jit call; injected uniforms make it exact."""
    params, cfg = setup
    u = _uniforms(4, 5, cfg.vocab_size, seed=11)
    r1 = monte_carlo_risk(params, cfg, TOKS, AGES, n_samples=4, max_new=5,
                          horizon=100.0, uniforms=u)
    r2 = monte_carlo_risk(params, cfg, TOKS, AGES, n_samples=4, max_new=5,
                          horizon=100.0, uniforms=u)
    assert np.array_equal(np.asarray(r1["code_risk"]),
                          np.asarray(r2["code_risk"]))
    assert float(np.max(r1["code_risk"])) > 0.0


# ---------------------------------------------------------------------------
# Scheduler edge cases
# ---------------------------------------------------------------------------
def test_cancel_one_of_n_forks_midstream(setup):
    """Cancelling one forked future mid-decode frees only ITS references;
    the siblings finish and every refcount drains."""
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=K, max_context=512, cache="paged",
                        block_size=BS, prefix_cache=True).start()
    try:
        parent = Request(tokens=TOKS, ages=AGES, max_new=400, hold=True,
                         request_id="mc")
        eng.submit(parent)
        kids = eng.fork("mc", 3)
        time.sleep(0.2)                  # let the forks decode a while
        assert eng.cancel("mc/fork-1")
        deadline = time.monotonic() + 120
        while not all(k.done for k in kids) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert all(k.done for k in kids)
    finally:
        eng.stop()
    assert isinstance(kids[1].error, RequestCancelledError)
    assert kids[0].error is None and kids[2].error is None
    assert len(kids[0].out_tokens) > 0
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_preempt_lands_on_fork_and_reacquires_prefix(setup):
    """Pool exhaustion preempts the youngest — a forked future — whose
    recompute resume must RE-ACQUIRE the shared prefix blocks through the
    index instead of duplicating them."""
    params, cfg = setup
    # block-aligned prompt: the whole prefix is shareable full blocks, so
    # the index entry stays pinned (refcount > 1) while any fork lives and
    # pool pressure must preempt a fork rather than evict the entry
    S = 16                               # exactly 2 full blocks at BS=8
    toks = (np.arange(3, 3 + S) % 90).astype(np.int32)
    ages = np.linspace(0.0, 30.0, S).astype(np.float32)
    # capacity 6: prefix 2 + three forks' growth blocks exhaust it mid-run.
    # Suppress the death token (u -> 1e-12 makes its waiting time huge) so
    # every future runs all 12 events and the crunch is deterministic.
    u = _uniforms(3, 12, cfg.vocab_size, seed=7)
    u[:, :, cfg.death_token] = 1e-12
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=7, prefix_cache=True)
    kids = eng.sample_futures(toks, ages, n=3, max_new=12, uniforms=u)
    assert all(k.done and k.error is None for k in kids)
    assert [len(k.out_tokens) for k in kids] == [12, 12, 12]
    assert eng.preemptions > 0
    st = eng.pool_stats()["prefix_cache"]
    assert st["partial_hits"] > 0, \
        "resumed fork must re-acquire its prefix by reference"
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_over_width_prompt_bypasses_prefix_index(setup):
    """S > max_context histories wrap the ring: they must neither register
    in nor borrow from the prefix index, and forking them still matches
    the ring engine."""
    params, cfg = setup
    S, Wn = 40, 32
    toks = (np.arange(3, 3 + S) % 90).astype(np.int32)
    ages = np.linspace(0.0, 30.0, S).astype(np.float32)
    u = _uniforms(2, 4, cfg.vocab_size, seed=17)
    eng = BatchedEngine(params, cfg, slots=2, max_context=Wn, cache="paged",
                        block_size=8, prefix_cache=True)
    kids = eng.sample_futures(toks, ages, n=2, max_new=4, uniforms=u)
    assert eng.prefix.entries == 0       # bypassed, not registered
    assert eng.prefix.hits == 0
    ring = BatchedEngine(params, cfg, slots=2, max_context=Wn)
    rkids = ring.sample_futures(toks, ages, n=2, max_new=4, uniforms=u)
    assert _trajs(kids) == _trajs(rkids)
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_shared_admission_budget_counts_block_once(setup):
    """N futures co-reside in a pool far smaller than N unshared copies
    would need: the admission budget charges a shared block once."""
    params, cfg = setup
    S = 17                               # 3 blocks at BS=8 (2 full + tail)
    toks = (np.arange(3, 3 + S) % 90).astype(np.int32)
    ages = np.linspace(0.0, 30.0, S).astype(np.float32)
    # capacity 6 < 3 unshared copies (9 blocks); shared: 3 + 3 tails = 6
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=7, prefix_cache=True)
    kids = eng.sample_futures(toks, ages, n=3, max_new=3)
    assert all(k.done and k.error is None for k in kids)
    assert eng.peak_active == 3          # all three futures co-resident
    assert eng.preemptions == 0
    assert eng.allocator.peak_used <= 6
    assert eng.pool.peak_shared >= 2


def test_pinned_hits_budget_is_honest(setup):
    """Prefix hits must not double as eviction headroom: requests whose
    hits are the pool's cached blocks admit on free blocks alone (waiting
    their turn under pressure) instead of crashing admission or
    livelocking — and the shared entry survives to serve every one."""
    params, cfg = setup
    S1, S2 = 16, 24
    toks1 = (np.arange(3, 3 + S1) % 90).astype(np.int32)
    ages1 = np.linspace(0.0, 30.0, S1).astype(np.float32)
    toks2 = np.concatenate([toks1, np.arange(50, 58) % 90]).astype(np.int32)
    ages2 = np.concatenate([ages1,
                            np.linspace(31, 40, 8)]).astype(np.float32)
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=5, prefix_cache=True)
    r1 = Request(tokens=toks1, ages=ages1, max_new=2)
    eng.submit(r1)
    eng.run()
    assert eng.prefix.entries == 1       # 2 cached full blocks, free = 2
    # each S=24 request: 2-block hit + 1 fresh + 1 aligned-growth = 2 fresh
    # against 2 free blocks -> they admit one at a time, sharing the SAME
    # pinned entry, which must never be evicted out from under them
    rs = [Request(tokens=toks2.copy(), ages=ages2.copy(), max_new=4)
          for _ in range(3)]
    for r in rs:
        eng.submit(r)
    done = eng.run(max_ticks=2000)
    assert len(done) >= 4
    assert all(r.done and r.error is None for r in rs)
    assert all(len(r.out_tokens) == 4 for r in rs)
    st = eng.pool_stats()["prefix_cache"]
    # every admission (including preempt-resumes) shared the prefix, and
    # the pinned entry was never evicted out from under a live sharer
    assert st["partial_hits"] >= 3
    assert st["evictions"] == 0
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_hold_survives_ticks_with_other_traffic(setup):
    """A parent parked across several ticks of unrelated decode traffic
    must fork the SAME bits as an immediate fork — the held slot's parked
    writes must never corrupt the shared prefix."""
    params, cfg = setup
    n, max_new = 2, 5
    u = _uniforms(n, max_new, cfg.vocab_size, seed=29)
    for kind, kw in (("ring", {}), ("paged", {"block_size": BS})):
        ref_eng = BatchedEngine(params, cfg, slots=K, max_context=W,
                                cache=kind, **kw)
        ref = _trajs(ref_eng.sample_futures(TOKS, AGES, n=n,
                                            max_new=max_new, uniforms=u))
        eng = BatchedEngine(params, cfg, slots=K, max_context=W,
                            cache=kind, **kw)
        parent = Request(tokens=TOKS, ages=AGES, max_new=max_new, hold=True)
        eng.submit(parent)
        other = Request(tokens=TOKS[:3], ages=AGES[:3], max_new=8,
                        uniforms=_uniforms(1, 8, cfg.vocab_size, 31)[0])
        eng.submit(other)
        for _ in range(4):               # parent parked while other decodes
            eng.step()
        kids = eng.fork(parent.request_id, n, uniforms=u, max_new=max_new)
        eng.run()
        assert _trajs(kids) == ref, f"held-parent fork diverged ({kind})"


def test_fork_validation_and_ring_refuses_prefix(setup):
    params, cfg = setup
    from repro.api.errors import InvalidRequestError
    with pytest.raises(ValueError, match="prefix_cache requires"):
        BatchedEngine(params, cfg, cache="ring", prefix_cache=True)
    eng = BatchedEngine(params, cfg, slots=2, max_context=W, cache="paged",
                        block_size=BS)
    with pytest.raises(InvalidRequestError, match="unknown or finished"):
        eng.fork("nope", 2)
    r = Request(tokens=TOKS, ages=AGES, max_new=4)
    eng.submit(r)
    with pytest.raises(InvalidRequestError, match="hold=True parent"):
        eng.fork(r.request_id, 2)
    eng.run()
    assert eng.allocator.used == 0


def test_cancelled_parent_fails_children(setup):
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=W, cache="paged",
                        block_size=BS)
    parent = Request(tokens=TOKS, ages=AGES, max_new=4, hold=True,
                     request_id="doomed")
    eng.submit(parent)
    kids = eng.fork("doomed", 2)
    assert eng.cancel("doomed")
    eng.run(max_ticks=200)
    assert parent.done and isinstance(parent.error, RequestCancelledError)
    assert all(k.done and isinstance(k.error, RequestCancelledError)
               for k in kids)
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_pool_stats_sharing_fields(setup):
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=W, cache="paged",
                        block_size=BS, prefix_cache=True)
    st = eng.pool_stats()
    for key in ("shared_blocks", "shared_blocks_peak", "cow_copies",
                "forks", "prefix_cache"):
        assert key in st
    assert st["prefix_cache"]["entries"] == 0
    ring = BatchedEngine(params, cfg, slots=2, max_context=W)
    assert "shared_blocks" not in ring.pool_stats()
    assert ring.pool_stats()["forks"] == 0


# ---------------------------------------------------------------------------
# Resource-leak regressions (bugs found by repro-lint RL005): a raising
# path between acquire and transfer must never strand block references
# ---------------------------------------------------------------------------
def test_register_failure_takes_no_refs(monkeypatch):
    """PrefixIndex.register must build its entry BEFORE sharing the blocks:
    a failure mid-registration may not leave unowned index refs behind."""
    import repro.serve.prefix as prefix_mod
    pool = SharedBlockPool(BlockAllocator(8))
    idx = PrefixIndex(pool, block_size=4, max_entries=8)
    blocks = pool.alloc(2)

    def boom(*a, **k):
        raise RuntimeError("entry construction failed")
    monkeypatch.setattr(prefix_mod, "_Entry", boom)
    toks = np.arange(8)
    ages = np.linspace(0.0, 7.0, 8).astype(np.float32)
    with pytest.raises(RuntimeError, match="entry construction failed"):
        idx.register(toks, ages, blocks, S=8, age0=7.0)
    assert idx.entries == 0
    assert pool.total_refs == len(blocks)    # only the caller's own refs
    pool.release(blocks)
    assert pool.used == 0 and not pool._refs


def test_admission_alloc_crash_releases_shared_hits(setup, monkeypatch):
    """Prefix hits are shared BEFORE the suffix alloc; if the alloc raises,
    the admission cleanup must drop those shares (they are parked on the
    slot immediately), and the engine must recover and serve the retry."""
    params, cfg = setup
    S1 = 16                              # exactly 2 full blocks at BS=8
    toks1 = (np.arange(3, 3 + S1) % 90).astype(np.int32)
    ages1 = np.linspace(0.0, 30.0, S1).astype(np.float32)
    toks2 = np.concatenate([toks1, np.arange(50, 58) % 90]).astype(np.int32)
    ages2 = np.concatenate([ages1,
                            np.linspace(31, 40, 8)]).astype(np.float32)
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=7, prefix_cache=True)
    r1 = Request(tokens=toks1, ages=ages1, max_new=2)
    eng.submit(r1)
    eng.run()
    assert eng.prefix.entries == 1       # 2 cached blocks to hit on

    real_alloc = eng.pool.alloc
    armed = {"on": True}

    def flaky_alloc(n):
        if armed["on"]:
            armed["on"] = False
            raise RuntimeError("injected alloc failure")
        return real_alloc(n)
    monkeypatch.setattr(eng.pool, "alloc", flaky_alloc)
    r2 = Request(tokens=toks2, ages=ages2, max_new=2)
    eng.submit(r2)
    with pytest.raises(RuntimeError, match="injected alloc failure"):
        eng.run()
    # the crashed admission's shares are gone: only the index holds refs
    assert eng.pool.used == 2 and eng.pool.total_refs == 2
    # the request went back on the queue and the next run serves it
    done = eng.run()
    assert r2 in done and r2.error is None and len(r2.out_tokens) == 2
    assert eng.pool_stats()["prefix_cache"]["partial_hits"] >= 1
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_cow_failure_mid_fork_leaks_no_blocks(setup, monkeypatch):
    """A COW copy that crashes after its destination block was allocated
    must release that block on the way out; the loop thread fails the
    in-flight forks and the pool drains to zero."""
    import repro.serve.engine as engine_mod
    params, cfg = setup
    real = engine_mod._cow_block_jit
    fired = {"on": False}

    def flaky(*a, **k):
        if not fired["on"]:
            fired["on"] = True
            raise RuntimeError("injected COW failure")
        return real(*a, **k)
    monkeypatch.setattr(engine_mod, "_cow_block_jit", flaky)
    eng = BatchedEngine(params, cfg, slots=K, max_context=W, cache="paged",
                        block_size=BS).start()
    try:
        parent = Request(tokens=TOKS, ages=AGES, max_new=5, hold=True,
                         request_id="cow")
        eng.submit(parent)
        kids = eng.fork("cow", 2, uniforms=_uniforms(2, 5, cfg.vocab_size))
        deadline = time.monotonic() + 120
        while not all(k.done for k in kids) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert all(k.done for k in kids)
    finally:
        eng.stop()
    assert fired["on"], "fork decode must have attempted a COW"
    assert any(isinstance(k.error, RuntimeError)
               and "injected COW failure" in str(k.error) for k in kids)
    assert eng.allocator.used == 0 and not eng.pool._refs


# ---------------------------------------------------------------------------
# Chunked prefill (prefill/decode interleaving) + partial-prefix suffix
# ---------------------------------------------------------------------------
LONG_TOKS = (np.arange(3, 24) % 90).astype(np.int32)     # S=21: full + tail
LONG_AGES = np.linspace(0.0, 30.0, 21).astype(np.float32)


def _one(eng, toks, ages, max_new, u):
    r = Request(tokens=toks, ages=ages, max_new=max_new, uniforms=u)
    eng.submit(r)
    eng.run()
    assert r.done and r.error is None
    return list(r.out_tokens), [np.float32(a) for a in r.out_ages]


def test_chunked_prefill_bit_identical_to_monolithic(setup):
    """Chunked prefill is a scheduling change: a budget covering any prompt
    (= monolithic cadence), a one-block budget and the unchunked engine all
    follow the fp32 reference logits within tolerance under injected
    uniforms.  They run different executables (chunk widths change the fp
    reductions), so they agree within tolerance, not bit for bit; the
    one-block engine matches the straight-line chunked oracle, which runs
    its executables, exactly."""
    params, cfg = setup
    max_new = 6
    u = _uniforms(1, max_new, cfg.vocab_size, seed=23)[0]
    u[:, cfg.death_token] = 1e-12        # run all max_new events

    def run(**kw):
        eng = BatchedEngine(params, cfg, slots=K, max_context=W,
                            cache="paged", block_size=BS, **kw)
        out = _one(eng, LONG_TOKS, LONG_AGES, max_new, u)
        assert eng.allocator.used == 0 and not eng.pool._refs
        return out, eng

    base, _ = run()
    inf, _ = run(prefill_chunk_tokens=W)
    chunked, eng16 = run(prefill_chunk_tokens=BS)
    for toks, ages in (base, inf, chunked):
        check_trajectory(params, cfg, LONG_TOKS, LONG_AGES, toks, ages, u,
                         tol=logit_tolerance(cfg), max_new=max_new)
    st = eng16.pool_stats()
    assert st["prefill_chunk_tokens"] == BS
    assert st["chunked_prefills"] == 1 and st["prefill_chunks"] == 2
    assert st["suffix_tokens_saved"] == 0 and st["prefill_in_progress"] == 0
    ot, oa = chunked_reference_trajectory(
        params, cfg, LONG_TOKS, LONG_AGES, max_new=max_new, uniforms=u,
        chunk_tokens=BS, slots=K, max_context=W, block_size=BS)
    assert chunked == (ot, [np.float32(a) for a in oa]), \
        "chunked engine diverged from the chunked oracle"


def test_partial_prefix_hit_prefills_only_suffix(setup):
    """A partial index hit acquires the matched blocks by reference and
    chunk-prefills ONLY the unmatched suffix: suffix_tokens_saved counts
    the skipped prefix, one extra chunk covers the 5-token tail, and the
    trajectory matches the matched-boundary oracle bit for bit."""
    params, cfg = setup
    max_new = 4
    eng = BatchedEngine(params, cfg, slots=K, max_context=W, cache="paged",
                        block_size=BS, prefix_cache=True,
                        prefill_chunk_tokens=BS)
    ua = _uniforms(1, max_new, cfg.vocab_size, seed=5)[0]
    ua[:, cfg.death_token] = 1e-12
    # registrant: block-aligned prompt -> one full shareable block
    _one(eng, LONG_TOKS[:BS], LONG_AGES[:BS], max_new, ua)
    assert eng.prefix.entries >= 1
    chunks0 = eng.pool_stats()["prefill_chunks"]
    ub = _uniforms(1, max_new, cfg.vocab_size, seed=6)[0]
    ub[:, cfg.death_token] = 1e-12
    got = _one(eng, LONG_TOKS, LONG_AGES, max_new, ub)
    st = eng.pool_stats()
    assert st["suffix_tokens_saved"] == BS
    assert st["prefix_cache"]["partial_hits"] == 1
    assert st["prefill_chunks"] == chunks0 + 1      # suffix = one chunk
    ot, oa = chunked_reference_trajectory(
        params, cfg, LONG_TOKS, LONG_AGES, max_new=max_new, uniforms=ub,
        chunk_tokens=BS, slots=K, max_context=W, block_size=BS,
        matched_tokens=BS)
    assert got == (ot, [np.float32(a) for a in oa]), \
        "suffix prefill diverged from the matched-boundary oracle"
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_preempted_chunked_resume_reacquires_prefix(setup):
    """The chunked twin of test_preempt_lands_on_fork_and_reacquires_prefix:
    pool exhaustion preempts a forked future, and its recompute-resume goes
    back through chunked admission — re-acquiring the shared prefix by
    reference and re-prefilling ONLY the unmatched suffix (counted by
    suffix_tokens_saved)."""
    params, cfg = setup
    S = 16                               # exactly 2 full blocks at BS=8
    toks = (np.arange(3, 3 + S) % 90).astype(np.int32)
    ages = np.linspace(0.0, 30.0, S).astype(np.float32)
    u = _uniforms(3, 12, cfg.vocab_size, seed=7)
    u[:, :, cfg.death_token] = 1e-12
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=8, blocks=7, prefix_cache=True,
                        prefill_chunk_tokens=8)
    kids = eng.sample_futures(toks, ages, n=3, max_new=12, uniforms=u)
    assert all(k.done and k.error is None for k in kids)
    assert [len(k.out_tokens) for k in kids] == [12, 12, 12]
    assert eng.preemptions > 0
    st = eng.pool_stats()
    assert st["prefix_cache"]["partial_hits"] > 0, \
        "resumed fork must re-acquire its prefix by reference"
    assert st["suffix_tokens_saved"] > 0, \
        "resume must skip the matched prefix and prefill only the suffix"
    # bit-parity with the unchunked engine through the same preemption dance
    ref_eng = BatchedEngine(params, cfg, slots=4, max_context=32,
                            cache="paged", block_size=8, blocks=7,
                            prefix_cache=True)
    assert _trajs(kids) == _trajs(ref_eng.sample_futures(
        toks, ages, n=3, max_new=12, uniforms=u))
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_cancel_mid_prefill_releases_partial_blocks(setup):
    """Cancelling a slot whose prompt is still chunking must release its
    partially-written blocks AND its shared prefix refs — the zero-leak
    invariant extended to prefill-in-progress state."""
    params, cfg = setup
    bs = 8
    eng = BatchedEngine(params, cfg, slots=4, max_context=32, cache="paged",
                        block_size=bs, blocks=8, prefix_cache=True,
                        prefill_chunk_tokens=bs)
    toks_a = (np.arange(3, 3 + bs) % 90).astype(np.int32)
    ages_a = np.linspace(0.0, 10.0, bs).astype(np.float32)
    ua = _uniforms(1, 2, cfg.vocab_size, seed=31)[0]
    ua[:, cfg.death_token] = 1e-12
    _one(eng, toks_a, ages_a, 2, ua)     # registers one shareable block
    assert eng.prefix.entries == 1
    toks_b = np.concatenate([toks_a,
                             np.arange(60, 76) % 90]).astype(np.int32)
    ages_b = np.concatenate([ages_a,
                             np.linspace(11.0, 30.0, 16)]).astype(np.float32)
    rb = Request(tokens=toks_b, ages=ages_b, max_new=4, request_id="mid")
    eng.submit(rb)
    eng.step()                           # admit + first suffix chunk only
    st = eng.pool_stats()
    assert st["prefill_in_progress"] == 1
    assert st["suffix_tokens_saved"] == bs
    assert eng.cancel("mid")
    eng.run(max_ticks=50)
    assert rb.done and isinstance(rb.error, RequestCancelledError)
    assert eng.pool_stats()["prefill_in_progress"] == 0
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_fork_from_chunk_prefilled_parent(setup):
    """hold=True parents park their bootstrap logits at the end of chunked
    prefill exactly as monolithic admission does: sample_futures through a
    chunked engine == the unchunked fork run, bit for bit."""
    params, cfg = setup
    n, max_new = 3, 5
    u = _uniforms(n, max_new, cfg.vocab_size, seed=13)
    ora = _trajs(BatchedEngine(
        params, cfg, slots=K, max_context=W, cache="paged",
        block_size=BS).sample_futures(TOKS, AGES, n=n, max_new=max_new,
                                      uniforms=u))
    eng = BatchedEngine(params, cfg, slots=K, max_context=W, cache="paged",
                        block_size=BS, prefill_chunk_tokens=BS)
    assert _trajs(eng.sample_futures(TOKS, AGES, n=n, max_new=max_new,
                                     uniforms=u)) == ora
    assert eng.pool_stats()["chunked_prefills"] == 1
    assert eng.allocator.used == 0 and not eng.pool._refs


def test_chunked_knob_validation(setup):
    params, cfg = setup
    with pytest.raises(ValueError, match="requires the paged KV cache"):
        BatchedEngine(params, cfg, cache="ring", prefill_chunk_tokens=16)
    with pytest.raises(ValueError, match="positive multiple"):
        BatchedEngine(params, cfg, cache="paged", block_size=BS,
                      prefill_chunk_tokens=BS + 1)
    with pytest.raises(ValueError, match="positive multiple"):
        BatchedEngine(params, cfg, cache="paged", block_size=BS,
                      prefill_chunk_tokens=0)


def test_healthz_exposes_chunked_prefill(setup):
    from repro.api.remote import RemoteBackend
    from repro.serve.server import InferenceServer
    params, cfg = setup
    server = InferenceServer(
        EngineBackend.create(params, cfg, slots=2, max_context=W,
                             cache="paged", block_size=BS, prefix_cache=True,
                             prefill_chunk_tokens=2 * BS), port=0).start()
    try:
        rb = RemoteBackend(server.address)
        mem = rb.healthz()["engine"]["memory"]
        assert mem["prefill_chunk_tokens"] == 2 * BS
        for key in ("chunked_prefills", "prefill_chunks",
                    "prefill_in_progress", "suffix_tokens_saved"):
            assert mem[key] == 0
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Wire: schemas + /v1/futures + RemoteBackend
# ---------------------------------------------------------------------------
def test_futures_wire_roundtrip():
    from repro.api import FuturesResult, RiskItem, RiskReport
    from repro.api.schemas import TrajectoryResult
    u = np.random.default_rng(0).uniform(size=(2, 3, 4)).astype(np.float32)
    req = FuturesRequest(tokens=[1, 2], ages=[0.0, 1.5], n_futures=2,
                         max_new=3, horizon=2.5, top=4, uniforms=u,
                         request_id="abc")
    back = FuturesRequest.from_json(req.to_json())
    assert back.tokens == [1, 2] and back.n_futures == 2
    assert back.horizon == 2.5 and back.request_id == "abc"
    assert np.array_equal(back.uniforms, u)          # bit-exact b64 bytes
    res = FuturesResult(
        risk=RiskReport(horizon=2.5, items=[RiskItem(token=7, risk=0.5)],
                        backend="engine"),
        trajectories=[TrajectoryResult(tokens=[7], ages=[1.0],
                                       prompt_tokens=[1, 2],
                                       prompt_ages=[0.0, 1.5],
                                       backend="engine")],
        n_futures=2, backend="engine", sharing={"forks": 1})
    rb = FuturesResult.from_json(res.to_json())
    assert rb.risk.items[0].token == 7 and rb.n_futures == 2
    assert rb.trajectories[0].tokens == [7] and rb.sharing == {"forks": 1}


def test_futures_validation_errors(setup):
    from repro.api.errors import (AgesRequiredError, EmptyTrajectoryError,
                                  InvalidRequestError)
    params, cfg = setup
    b = EngineBackend.create(params, cfg, slots=2, max_context=W,
                             cache="paged", block_size=BS)
    with pytest.raises(EmptyTrajectoryError):
        b.sample_futures(FuturesRequest(tokens=[]))
    with pytest.raises(AgesRequiredError):
        b.sample_futures(FuturesRequest(tokens=[1, 2]))
    with pytest.raises(InvalidRequestError, match="n_futures"):
        b.sample_futures(FuturesRequest(tokens=[1], ages=[0.0],
                                        n_futures=0))
    with pytest.raises(InvalidRequestError, match="futures uniforms"):
        b.sample_futures(FuturesRequest(
            tokens=[1], ages=[0.0], n_futures=2, max_new=4,
            uniforms=np.zeros((2, 4, 7), np.float32)))


def test_remote_futures_bit_identical(setup):
    """POST /v1/futures through RemoteBackend == in-process EngineBackend,
    trajectories and risks, under injected uniforms (acceptance: remote
    parity for both ring and paged servers)."""
    from repro.api import Client
    from repro.serve.server import InferenceServer
    params, cfg = setup
    n, max_new = 3, 5
    u = _uniforms(n, max_new, cfg.vocab_size, seed=41)
    req = FuturesRequest(tokens=TOKS.tolist(), ages=AGES.tolist(),
                         n_futures=n, max_new=max_new, uniforms=u,
                         horizon=100.0, top=6)
    for kind, kw in (("ring", {}), ("paged", {"block_size": BS,
                                              "prefix_cache": True})):
        local = EngineBackend.create(params, cfg, slots=K, max_context=W,
                                     cache=kind, **kw)
        ref = local.sample_futures(req)
        server = InferenceServer(
            EngineBackend.create(params, cfg, slots=K, max_context=W,
                                 cache=kind, **kw), port=0).start()
        try:
            out = Client.connect(server.address).sample_futures(req)
        finally:
            server.stop()
        assert out.backend == "remote[engine]"
        assert [(t.tokens, [np.float32(a) for a in t.ages])
                for t in out.trajectories] == \
               [(t.tokens, [np.float32(a) for a in t.ages])
                for t in ref.trajectories], f"remote diverged ({kind})"
        assert [(i.token, i.risk) for i in out.risk.items] == \
               [(i.token, i.risk) for i in ref.risk.items]
        if kind == "paged":
            assert out.sharing.get("forks") == 1


def test_healthz_exposes_sharing(setup):
    from repro.api.remote import RemoteBackend
    from repro.serve.server import InferenceServer
    params, cfg = setup
    server = InferenceServer(
        EngineBackend.create(params, cfg, slots=2, max_context=W,
                             cache="paged", block_size=BS,
                             prefix_cache=True), port=0).start()
    try:
        rb = RemoteBackend(server.address)
        mem = rb.healthz()["engine"]["memory"]
        assert "shared_blocks" in mem and "cow_copies" in mem
        assert mem["prefix_cache"]["entries"] == 0
    finally:
        server.stop()


def test_background_sample_futures_concurrent(setup):
    """Handler-thread orchestration: concurrent sample_futures against one
    background-ticking engine all complete, share, and drain."""
    params, cfg = setup
    eng = BatchedEngine(params, cfg, slots=K, max_context=W, cache="paged",
                        block_size=BS, prefix_cache=True).start()
    results = {}
    try:
        def worker(i):
            kids = eng.sample_futures(TOKS, AGES, n=2, max_new=4,
                                      request_id=f"bg-{i}",
                                      wait_timeout=120.0)
            results[i] = kids
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads)
    finally:
        eng.stop()
    assert sorted(results) == [0, 1, 2]
    for kids in results.values():
        assert all(k.done and k.error is None for k in kids)
        assert all(len(k.out_tokens) >= 1 for k in kids)
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0 and not eng.pool._refs
