"""Copy-on-write prefix sharing for the paged KV cache.

The paper's headline workload — individual morbidity risk — is a
*many-futures-from-one-history* problem: Delphi-style risk estimates sample N
stochastic continuations of a single patient trajectory.  PR 4's paged cache
already reads through per-slot block tables, so N requests whose histories
share a prefix can share the underlying *blocks*; this module supplies the
ownership layer that makes that safe:

* :class:`SharedBlockPool` — per-block **refcounts** layered on the engine's
  ``BlockAllocator``.  ``alloc`` hands out exclusively-owned blocks
  (refcount 1), ``share`` adds references, ``release`` drops one and returns
  the block to the free list only at refcount 0.  The engine copy-on-writes
  a block the first time a slot writes into one it does not exclusively own
  (refcount > 1), so shared prefixes are immutable while referenced.

* :class:`PrefixIndex` — a hash-keyed index over **full blocks** of admitted
  prompts: each ``block_size`` chunk of (token, age) history hashes into a
  chain (chunk ``i``'s digest folds in chunk ``i-1``'s), so a lookup walks
  the new prompt's chunks and returns the longest run of already-resident
  blocks.  Matched blocks are acquired by *reference* at admission instead
  of re-inserted, and a **complete** entry (full blocks + partial tail +
  bootstrap logits, registered by ``hold`` admissions) lets an identical
  prompt admit with **no prefill at all**.  Entries hold their own block
  references and are LRU-evicted — only blocks whose refcount drops to 0
  actually free, so eviction never rips a prefix out from under a live
  request.

* :func:`ring_reference_futures` — the scheduler-free **bit-parity oracle**
  for the engine's ``fork`` primitive: a straight-line dense-ring N-futures
  generator built from the engine's *own* module-level jitted functions
  (solo bucketed prefill → fork-row bootstrap → shared decode tick), so the
  paged/forked/COW engine path must reproduce it bit for bit under injected
  uniforms.  ``core.risk.monte_carlo_risk`` accepts its trajectories as the
  engine-parity sampling backend.

Zero-leak invariant (extends PR 4): after the engine drains *and* the index
is dropped (``BatchedEngine.drop_prefix_cache``), ``allocator.used == 0`` and
no refcounts remain — ``scripts/paged_parity.py`` storms this with
fork/cancel/preempt/timeout traffic.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["SharedBlockPool", "PrefixIndex", "prompt_digests",
           "ring_reference_futures", "chunked_reference_trajectory"]


class SharedBlockPool:
    """Ref-counted block ownership over a ``BlockAllocator``.

    Every block handed out by :meth:`alloc` starts at refcount 1; additional
    owners (forked requests, the prefix index) attach with :meth:`share`.
    :meth:`release` drops ONE reference — the underlying allocator sees the
    free only when the last reference goes, so ``allocator.used`` keeps
    counting each physical block exactly once no matter how many requests
    reference it (the admission-budget and ``pool_stats`` contract).
    """

    def __init__(self, allocator):
        self.allocator = allocator
        self._refs: Dict[int, int] = {}                    # guarded-by: engine-thread
        #: jitted block copies triggered by a write into a shared block
        self.cow_copies = 0
        #: high-water mark of concurrently shared (refcount >= 2) blocks
        self.peak_shared = 0
        #: set by the engine when the prefix index is enabled — alloc()
        #: evicts LRU index entries before giving up on pool pressure
        self.index: Optional["PrefixIndex"] = None

    # -- allocator passthrough (once-counted accounting) ---------------------
    @property
    def capacity(self) -> int:
        return self.allocator.capacity

    @property
    def free(self) -> int:
        return self.allocator.free

    @property
    def used(self) -> int:
        return self.allocator.used

    @property
    def num_blocks(self) -> int:
        return self.allocator.num_blocks

    @property
    def peak_used(self) -> int:
        return self.allocator.peak_used

    def available(self, exclude=None) -> int:
        """Admission budget: free blocks plus blocks an index eviction could
        free right now.  A block shared by a live request counts ZERO times
        (it is neither free nor evictable), and ``exclude`` removes blocks
        the caller is about to PIN by sharing them — they must not be
        double-counted as both lent-by-reference and evictable."""
        n = self.allocator.free
        if self.index is not None:
            n += self.index.evictable(exclude)
        return n

    # -- ownership ------------------------------------------------------------
    def alloc(self, n: int, *, evict: bool = True) -> Optional[List[int]]:  # repro-lint: engine-thread-only
        """n exclusively-owned blocks (refcount 1), or None — after trying
        to make room by LRU-evicting prefix-index entries."""
        if evict and self.index is not None and n > self.allocator.free:
            self.index.evict(n - self.allocator.free)
        ids = self.allocator.alloc(n)
        if ids is not None:
            for i in ids:
                self._refs[i] = 1
        return ids

    def share(self, ids: List[int]) -> None:  # repro-lint: engine-thread-only
        """Attach one more reference to each block (fork / prefix admit /
        index registration)."""
        for i in ids:
            r = self._refs.get(i)
            if r is None:
                raise ValueError(f"share of unallocated block {i}")
            self._refs[i] = r + 1
        self.peak_shared = max(self.peak_shared, self.shared_blocks)

    def release(self, ids: List[int]) -> None:  # repro-lint: engine-thread-only
        """Drop one reference per block; frees into the allocator at 0."""
        for i in ids:
            r = self._refs.get(i)
            if r is None:
                raise ValueError(f"release of unowned block {i}")
            if r == 1:
                del self._refs[i]
                self.allocator.release([i])
            else:
                self._refs[i] = r - 1

    def refcount(self, block_id: int) -> int:  # repro-lint: engine-thread-only
        return self._refs.get(block_id, 0)

    @property
    def shared_blocks(self) -> int:
        """Physical blocks currently referenced by more than one owner."""
        # repro-lint: disable=RL001 GIL-atomic counter scan; the only
        # cross-thread caller is engine.pool_stats, holding the engine lock
        return sum(1 for r in self._refs.values() if r > 1)

    @property
    def total_refs(self) -> int:
        # repro-lint: disable=RL001 GIL-atomic counter scan; the only
        # cross-thread caller is engine.pool_stats, holding the engine lock
        return sum(self._refs.values())


# ---------------------------------------------------------------------------
# Prefix index
# ---------------------------------------------------------------------------
def _chunk_digest(prev: bytes, toks: np.ndarray,
                  ages: Optional[np.ndarray]) -> bytes:
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(np.ascontiguousarray(toks, np.int64).tobytes())
    if ages is not None:
        h.update(np.ascontiguousarray(ages, np.float32).tobytes())
    return h.digest()


def prompt_digests(tokens, ages, block_size: int
                   ) -> Tuple[List[bytes], bytes]:
    """Chained blake2b digests of a prompt's (token, age) history.

    Returns ``(chain, key)``: one digest per FULL ``block_size`` chunk
    (chunk ``i`` folds in chunk ``i-1``'s digest, so digest ``i`` names the
    whole prefix through block ``i``) plus a whole-prompt key that also
    folds in the partial tail and the exact length.

    This is the shared vocabulary between a replica's :class:`PrefixIndex`
    (which blocks are resident) and the multi-replica router's
    prefix-affinity scheduler (``repro.serve.router``): both sides hash the
    same history to the same chain, so the router can route a request to
    the replica whose pool already holds its prefix blocks without ever
    seeing that pool.
    """
    toks = np.asarray(tokens, np.int64)
    ags = None if ages is None else np.asarray(ages, np.float32)
    bs = block_size
    S = len(toks)
    full, prev = [], b"prefix-v1"
    for i in range(S // bs):
        prev = _chunk_digest(prev, toks[i * bs:(i + 1) * bs],
                             None if ags is None
                             else ags[i * bs:(i + 1) * bs])
        full.append(prev)
    key = prev
    if S % bs:
        key = _chunk_digest(prev, toks[-(S % bs):],
                            None if ags is None else ags[-(S % bs):])
    # fold the exact length in so "aligned prompt" vs "same prompt plus
    # an empty tail" cannot collide
    key = hashlib.blake2b(key + S.to_bytes(8, "little"),
                          digest_size=16).digest()
    return full, key


class _Entry:
    __slots__ = ("key", "chain", "blocks", "complete", "S", "age0", "logits",
                 "hits")

    def __init__(self, key, chain, blocks, complete, S, age0, logits):
        self.key = key
        self.chain = chain          # per-full-block chain digests
        self.blocks = blocks        # table-order block ids (full [+ tail])
        self.complete = complete    # tail + bootstrap logits present
        self.S = S
        self.age0 = age0
        self.logits = logits        # (V,) device array (complete entries)
        self.hits = 0


class PrefixIndex:
    """Hash-keyed LRU index over admitted prompts' KV blocks.

    Two lookup grains:

    * :meth:`match_prefix` — longest run of FULL blocks whose (token, age)
      chunk-chain digests are resident: admission shares these by reference
      and prefills only the unmatched suffix (memory saved, compute kept) —
      also how a preempted forked request *re-acquires* its shared prefix on
      recompute resume.
    * :meth:`lookup` — exact whole-prompt match against a **complete** entry
      (registered by ``hold`` admissions: full blocks, partial tail block,
      and the prompt's bootstrap logits): admission by pure reference, no
      prefill at all — the Monte-Carlo N-futures fast path.

    The index owns one reference per block of each entry; eviction releases
    them, and a block frees only when no live request still shares it.
    """

    def __init__(self, pool: SharedBlockPool, block_size: int,
                 max_entries: int = 256):
        self.pool = pool
        self.block_size = block_size
        self.max_entries = max_entries
        self._entries: "OrderedDict[bytes, _Entry]" = \
            OrderedDict()                                  # guarded-by: engine-thread
        self._chain: Dict[bytes, Tuple[int, bytes]] = {}   # guarded-by: engine-thread
        pool.index = self
        self.hits = 0           # complete-entry (no-prefill) admissions
        self.partial_hits = 0   # admissions that shared >= 1 full block
        self.misses = 0
        self.evictions = 0

    # -- hashing --------------------------------------------------------------
    def _digests(self, tokens, ages) -> Tuple[List[bytes], bytes]:
        return prompt_digests(tokens, ages, self.block_size)

    # -- queries (side-effect-free: admission probes them repeatedly; the
    #    engine calls touch() only when an admission actually lands) ---------
    def digests(self, tokens, ages) -> Tuple[List[bytes], bytes]:
        """(per-full-block chain digests, whole-prompt key) — computed once
        per request and memoized by the engine (hashing a long Delphi
        history is O(S) and admission probes run under the engine lock)."""
        return self._digests(tokens, ages)

    def match_run(self, full_digests: List[bytes]) -> List[int]:  # repro-lint: engine-thread-only
        """Longest resident run of full-block ids for a digest chain."""
        out: List[int] = []
        for d in full_digests:
            hit = self._chain.get(d)
            if hit is None:
                break
            out.append(hit[0])
        return out

    def match_prefix(self, tokens, ages) -> List[int]:
        """Longest resident run of full-block ids for this history."""
        return self.match_run(self._digests(tokens, ages)[0])

    def lookup_key(self, key: bytes) -> Optional[_Entry]:  # repro-lint: engine-thread-only
        """Complete entry exactly matching a whole-prompt key."""
        e = self._entries.get(key)
        return e if e is not None and e.complete else None

    def lookup(self, tokens, ages) -> Optional[_Entry]:
        """Exact whole-prompt match against a complete entry."""
        return self.lookup_key(self._digests(tokens, ages)[1])

    def touch(self, entry: _Entry) -> None:  # repro-lint: engine-thread-only
        """An admission actually used this entry: bump MRU + hit count."""
        self._entries.move_to_end(entry.key)
        entry.hits += 1

    # -- registration / eviction ----------------------------------------------
    def aligned_key(self, chain: List[bytes], n_blocks: int) -> bytes:
        """Whole-prompt key of the block-aligned truncation covering the
        first ``n_blocks`` full blocks — derived from an existing chain in
        O(1) instead of re-hashing the history."""
        prev = chain[n_blocks - 1] if n_blocks else b"prefix-v1"
        S = n_blocks * self.block_size
        return hashlib.blake2b(prev + S.to_bytes(8, "little"),
                               digest_size=16).digest()

    def register(self, tokens, ages, blocks: List[int], *, S: int,  # repro-lint: engine-thread-only
                 age0: float, logits=None,
                 digests: Optional[Tuple[List[bytes], bytes]] = None
                 ) -> None:
        """Index an admitted prompt's blocks (the index takes one reference
        per block).  ``logits`` marks the entry complete: ``blocks`` then
        also carries the partial tail block and :meth:`lookup` can admit the
        exact prompt with no prefill.  ``digests`` passes the prompt's
        already-computed (chain, key) — the engine memoizes them per
        request, and re-hashing a long history here would serialize the
        engine thread for nothing."""
        chain, key = (digests if digests is not None
                      else self._digests(tokens, ages))
        if key in self._entries:
            self._entries.move_to_end(key)
            return
        # build the entry BEFORE taking the shares: _Entry / np.float32 can
        # raise, and shares taken first would have no owner to release them
        e = _Entry(key, chain[:len(blocks)], list(blocks),
                   logits is not None, S, np.float32(age0), logits)
        self.pool.share(blocks)
        self._entries[key] = e
        for d, b in zip(e.chain, e.blocks):
            self._chain.setdefault(d, (b, key))
        while len(self._entries) > self.max_entries:
            # trim the cap preferring entries whose eviction frees blocks;
            # pinned entries (live owners) go only when nothing else is
            # left — evicting them strands a preempted fork's re-acquire
            victim = self._freeing_victim() or next(iter(self._entries))
            self._evict_entry(victim)

    def _evict_entry(self, key: bytes) -> int:  # repro-lint: engine-thread-only
        e = self._entries.pop(key)
        for d in e.chain:
            owner = self._chain.get(d)
            if owner is not None and owner[1] == key:
                del self._chain[d]
        before = self.pool.free
        self.pool.release(e.blocks)
        self.evictions += 1
        return self.pool.free - before

    def _evict_one(self) -> int:  # repro-lint: engine-thread-only
        return self._evict_entry(next(iter(self._entries)))    # LRU head

    def _index_block_refs(self) -> Dict[int, int]:  # repro-lint: engine-thread-only
        """block id -> how many index entries hold a reference to it."""
        counts: Dict[int, int] = {}
        for e in self._entries.values():
            for b in e.blocks:
                counts[b] = counts.get(b, 0) + 1
        return counts

    def _freeing_victim(self) -> Optional[bytes]:  # repro-lint: engine-thread-only
        """LRU-most entry whose eviction makes progress toward freeing
        memory: some of its blocks are held ONLY by index entries (a block
        shared between two cached entries frees once both go — picking
        such entries repeatedly reaches the fixpoint).  Entries whose
        every block is still referenced by a live request are *pinned* —
        evicting them frees nothing and would only strand an in-flight
        fork's resume from re-acquiring its prefix."""
        counts = self._index_block_refs()
        for key, e in self._entries.items():                   # LRU order
            if any(self.pool.refcount(b) == counts.get(b, 0)
                   for b in e.blocks):
                return key
        return None

    def evict(self, need_blocks: Optional[int] = None) -> int:  # repro-lint: engine-thread-only
        """Make room: LRU-evict entries until ``need_blocks`` blocks have
        actually freed, skipping pinned entries (see
        :meth:`_freeing_victim`).  Loops to a fixpoint, so blocks shared
        only between cached entries free once their last holder goes.
        ``need_blocks=None`` clears unconditionally (``drop_prefix_cache``
        / the zero-leak drain)."""
        freed = 0
        if need_blocks is None:
            while self._entries:
                freed += self._evict_one()
            return freed
        while freed < need_blocks:
            victim = self._freeing_victim()
            if victim is None:
                break
            freed += self._evict_entry(victim)
        return freed

    def clear(self) -> int:
        return self.evict(None)

    def evictable(self, exclude=None) -> int:
        """Blocks a pressure eviction could free right now: cached blocks
        whose every reference is an index entry (the fixpoint
        :meth:`evict` reaches).  ``exclude`` drops blocks the caller is
        about to pin by sharing them."""
        counts = self._index_block_refs()
        return sum(1 for b, c in counts.items()
                   if self.pool.refcount(b) == c
                   and (exclude is None or b not in exclude))

    # -- stats ---------------------------------------------------------------
    @property
    def entries(self) -> int:
        # repro-lint: disable=RL001 GIL-atomic counter scan; the only
        # cross-thread caller is engine.pool_stats, holding the engine lock
        return len(self._entries)

    @property
    def cached_blocks(self) -> int:
        # repro-lint: disable=RL001 GIL-atomic counter scan; the only
        # cross-thread caller is engine.pool_stats, holding the engine lock
        return len({b for e in self._entries.values() for b in e.blocks})

    def stats(self) -> Dict[str, float]:
        n = self.hits + self.misses
        return {
            "entries": self.entries,
            "cached_blocks": self.cached_blocks,
            "hits": self.hits,
            "partial_hits": self.partial_hits,
            "misses": self.misses,
            "hit_rate": self.hits / n if n else 0.0,
            "evictions": self.evictions,
        }


# ---------------------------------------------------------------------------
# Bit-parity oracle for engine fork
# ---------------------------------------------------------------------------
def ring_reference_futures(params, cfg, tokens, ages=None, *, n: int,
                           max_new: int = 48, uniforms=None,
                           slots: Optional[int] = None,
                           max_context: int = 512, temperature: float = 1.0,
                           sampler: str = "jnp", min_seq_bucket: int = 8
                           ) -> List[Tuple[List[int], List[float]]]:
    """Scheduler-free N-futures generation on a dense ring — the oracle the
    forked/COW/paged engine must match bit for bit.

    Mirrors the engine's ``sample_futures`` data path while bypassing every
    piece of new machinery under test (allocator, refcounts, prefix index,
    fork ops, preemption): ONE solo bucketed prefill of the history (the
    same ``_prefill_u_jit`` executable a ``hold`` admission dispatches),
    a fork-row bootstrap sampling each future's first event from the shared
    prefill logits (``_fork_rows_jit``), then the engine's own decode tick
    (``_tick_u_jit``) until every future terminates.  Because the jitted
    functions are the engine's module-level ones with an identical knob
    tuple, both sides run the *same compiled executables* — divergence is a
    real bug, never fp noise.

    Bit-parity contract: ``uniforms`` (n, max_new, V) must be injected, the
    engine must run with the same ``slots``/``max_context``/``temperature``/
    ``sampler``/``min_seq_bucket``, and all n forks must land in one wave
    (``slots >= n``, no preemption) — recompute resume re-prefills at new
    shapes and is only *semantically*, not bit-wise, aligned.

    Returns ``[(tokens, fp32 ages), ...]`` per future.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import make_decode_cache
    from repro.serve.engine import (_Knobs, _commit_jit, _fork_copy_rows_jit,
                                    _fork_rows_jit, _insert_rows_jit,
                                    _next_pow2, _prefill_u_jit, _tick_u_jit)
    if uniforms is None:
        raise ValueError("ring_reference_futures is the injected-uniforms "
                         "parity oracle: pass uniforms (n, max_new, V)")
    uniforms = np.asarray(uniforms, np.float32)
    if uniforms.shape[:2] != (n, max_new) \
            or uniforms.shape[2] != cfg.vocab_size:
        raise ValueError(f"uniforms must be (n={n}, max_new={max_new}, "
                         f"V={cfg.vocab_size}); got {uniforms.shape}")
    K = n if slots is None else slots
    if K < n:
        raise ValueError(f"slots={K} cannot hold n={n} futures in one wave")
    W = max_context
    V = cfg.vocab_size
    is_delphi = cfg.age_encoding
    kn = _Knobs(slots=K, max_context=W, is_delphi=is_delphi,
                use_pallas=sampler == "pallas",
                inv_temp=1.0 / max(temperature, 1e-6),
                max_age=cfg.max_age, death_token=cfg.death_token, vocab=V)

    toks = np.asarray(tokens, np.int64)
    ags = None if ages is None else np.asarray(ages, np.float64)
    S = len(toks)
    if S > W:
        sb = S                                   # over-width: exact shape
    else:
        sb = min(max(_next_pow2(S), min_seq_bucket), W)
    t = np.zeros((1, sb), np.int32)
    t[0, :S] = toks
    a = np.zeros((1, sb), np.float32)
    age0 = 0.0
    if ags is not None:
        a[0, :S] = ags
        a[0, S:] = ags[-1]
        age0 = float(ags[-1])

    cache = make_decode_cache(params, cfg, K, W)
    state = {
        "last": jnp.zeros((K,), jnp.int32),
        "age": jnp.zeros((K,), jnp.float32),
        "step": jnp.zeros((K,), jnp.int32),
        "n_emitted": jnp.zeros((K,), jnp.int32),
        "max_new": jnp.ones((K,), jnp.int32),
        "active": jnp.zeros((K,), bool),
    }
    # solo hold-style prefill: filler uniforms, sampled row discarded
    filler = np.full((1, V), 0.5, np.float32)
    cache_rows, _rows, _packed, lg = _prefill_u_jit(
        params, jnp.asarray(t), jnp.asarray(a),
        jnp.asarray([S - 1], jnp.int32), jnp.asarray([age0], jnp.float32),
        jnp.asarray([S], jnp.int32), jnp.asarray([max_new], jnp.int32),
        jnp.asarray(filler), cfg=cfg, kn=kn)
    cache = _insert_rows_jit(
        cache, jax.tree_util.tree_map(lambda x: x[:, :1], cache_rows),
        jnp.asarray([0], np.int32))

    # fork the prefilled row into n child slots (0..n-1), masking any
    # position >= S exactly as the engine's fork copy does
    kb = _next_pow2(n)
    dst = np.zeros((kb,), np.int32)              # padded with src (slot 0)
    dst[:n] = np.arange(n)
    cache = _fork_copy_rows_jit(cache, jnp.int32(0), jnp.asarray(dst),
                                jnp.int32(S - 1))
    u0 = np.full((kb, V), 0.5, np.float32)
    u0[:n] = uniforms[:, 0]
    lg_b = jnp.broadcast_to(lg[0][None], (kb, V))
    rows, packed = _fork_rows_jit(
        lg_b, jnp.asarray(u0),
        jnp.full((kb,), age0, jnp.float32), jnp.full((kb,), S, jnp.int32),
        jnp.full((kb,), max_new, jnp.int32), kn=kn)
    state = _commit_jit(state, jnp.asarray(np.arange(n, dtype=np.int32)),
                        jax.tree_util.tree_map(lambda x: x[:n], rows))

    out_t: List[List[int]] = [[] for _ in range(n)]
    out_a: List[List[float]] = [[] for _ in range(n)]
    live = [True] * n

    def apply(j, col):
        evt, age, emit, finished = col
        if emit >= 0.5:
            out_t[j].append(int(evt))
            if is_delphi:
                out_a[j].append(float(age))
        if finished >= 0.5:
            live[j] = False

    arr = np.asarray(packed)
    for j in range(n):
        apply(j, arr[:, j])
    while any(live):
        u = np.full((K, V), 0.5, np.float32)
        for j in range(n):
            if live[j]:
                u[j] = uniforms[j, len(out_t[j])]
        cache, state, packed, _ = _tick_u_jit(params, cache, state,
                                              jnp.asarray(u), cfg=cfg, kn=kn)
        arr = np.asarray(packed)
        for j in range(n):
            if live[j]:
                apply(j, arr[:, j])
    return [(out_t[j], out_a[j]) for j in range(n)]


# ---------------------------------------------------------------------------
# Bit-parity oracle for chunked / suffix prefill
# ---------------------------------------------------------------------------
def chunked_reference_trajectory(params, cfg, tokens, ages=None, *,
                                 max_new: int, uniforms,
                                 chunk_tokens: int, slots: int = 4,
                                 max_context: int = 512,
                                 block_size: int = 16,
                                 matched_tokens: int = 0,
                                 blocks: Optional[int] = None,
                                 temperature: float = 1.0,
                                 sampler: str = "jnp"
                                 ) -> Tuple[List[int], List[float]]:
    """Scheduler-free single-request trajectory on a paged pool via chunked
    suffix prefill — the oracle the interleaved engine path must match bit
    for bit.

    Mirrors ``BatchedEngine(prefill_chunk_tokens=chunk_tokens)`` serving one
    request while bypassing the scheduler under test (admission budgeting,
    the per-tick budget walk, preemption, the prefix index): the prompt's
    suffix is driven through the engine's OWN module-level jits — one
    ``_suffix_chunk_jit`` per ``_chunk_len``-sized chunk, a
    ``_fork_rows_jit`` bootstrap from the final chunk's logits, then
    ``_tick_u_jit`` decode ticks with block growth + position resets in the
    engine's exact flush order.  Chunk geometry comes from the shared
    ``_chunk_arrays`` helper, so both sides compile and run the *same*
    executables per shape.

    ``matched_tokens`` models a partial prefix-index hit: a warm pass
    chunk-prefills ``tokens[:matched_tokens]`` (block-aligned, < S) into its
    own blocks — standing in for the indexed registrant's blocks, which the
    engine-side request acquires by reference — and the request's cursor
    starts at that boundary, prefilling ONLY the unmatched suffix.  The
    engine registrant must have served that aligned prefix with the same
    ``chunk_tokens`` so the lent block bytes agree.

    Bit-parity contract: injected ``uniforms`` (max_new, V) — row 0 is the
    bootstrap event; the engine must run the request solo on a fresh engine
    with the same ``slots``/``max_context``/``block_size``/``temperature``/
    ``sampler``; and ``S + max_new <= max_context`` (no ring wrap: the
    oracle never copy-on-writes).  Returns ``(tokens, fp32 ages)``.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import make_paged_decode_cache
    from repro.serve.engine import (_Knobs, _chunk_arrays, _chunk_len,
                                    _commit_jit, _fork_rows_jit, _next_pow2,
                                    _reset_pos_jit, _suffix_chunk_jit,
                                    _tick_u_jit)
    uniforms = np.asarray(uniforms, np.float32)
    toks = np.asarray(tokens, np.int64)
    ags = None if ages is None else np.asarray(ages)
    S = len(toks)
    bs = block_size
    W = max_context
    V = cfg.vocab_size
    if uniforms.shape != (max_new, V):
        raise ValueError(f"uniforms must be (max_new={max_new}, V={V}); "
                         f"got {uniforms.shape}")
    if S + max_new > W:
        raise ValueError(
            f"S + max_new = {S + max_new} > max_context={W}: the oracle "
            f"forbids ring wrap (a wrapped slot copy-on-writes, which this "
            f"straight line does not model)")
    if matched_tokens % bs or not 0 <= matched_tokens < S:
        raise ValueError(f"matched_tokens={matched_tokens} must be a "
                         f"block-aligned length in [0, S)")
    if chunk_tokens < bs:
        raise ValueError(f"chunk_tokens={chunk_tokens} must be >= "
                         f"block_size={bs}")
    kn = _Knobs(slots=slots, max_context=W, is_delphi=cfg.age_encoding,
                use_pallas=sampler == "pallas",
                inv_temp=1.0 / max(temperature, 1e-6),
                max_age=cfg.max_age, death_token=cfg.death_token, vocab=V)
    nb = -(-S // bs)
    nb_warm = matched_tokens // bs
    if blocks is None:
        blocks = nb_warm + -(-(S + max_new) // bs) + 2
    cache = make_paged_decode_cache(cfg, slots, W, num_blocks=blocks,
                                    block_size=bs)
    nbs = W // bs
    next_id = 1

    def take(k: int) -> List[int]:
        nonlocal next_id
        ids = list(range(next_id, next_id + k))
        next_id += k
        if next_id > blocks:
            raise ValueError(f"oracle pool of {blocks} blocks exhausted")
        return ids

    def run_chunks(row, start: int, end: int):
        nonlocal cache
        lg = None
        cur = start
        while cur < end:
            n = _chunk_len(end, cur, chunk_tokens, bs)
            t_, a_, p_, c_, d_, li_ = _chunk_arrays(toks, ags, cur, n, bs,
                                                    row)
            cache, lg = _suffix_chunk_jit(
                params, cache, jnp.asarray(t_), jnp.asarray(a_),
                jnp.asarray(p_), jnp.asarray(c_), jnp.asarray(d_),
                jnp.asarray(li_), cfg=cfg)
            cur += n
        return lg

    # warm pass: the indexed registrant's aligned prefix, in its own blocks
    warm = take(nb_warm)
    if nb_warm:
        wrow = np.full((nbs,), -1, np.int32)
        wrow[:nb_warm] = warm
        run_chunks(wrow, 0, matched_tokens)

    # request pass: lent blocks + fresh suffix blocks, cursor at the match
    row = np.full((nbs,), -1, np.int32)
    row[:nb] = warm + take(nb - nb_warm)
    lg = run_chunks(row, matched_tokens, S)

    age0 = float(ags[-1]) if ags is not None else 0.0
    state = {
        "last": jnp.zeros((slots,), jnp.int32),
        "age": jnp.zeros((slots,), jnp.float32),
        "step": jnp.zeros((slots,), jnp.int32),
        "n_emitted": jnp.zeros((slots,), jnp.int32),
        "max_new": jnp.ones((slots,), jnp.int32),
        "active": jnp.zeros((slots,), bool),
    }
    lg_b = jnp.broadcast_to(lg[0][None], (1, V))
    rows, packed = _fork_rows_jit(
        lg_b, jnp.asarray(uniforms[0][None]),
        jnp.full((1,), age0, jnp.float32), jnp.full((1,), S, jnp.int32),
        jnp.full((1,), max_new, jnp.int32), kn=kn)
    state = _commit_jit(state, jnp.asarray([0], np.int32), rows)

    out_t: List[int] = []
    out_a: List[float] = []
    live = [True]

    def apply(col):
        evt, age, emit, finished = col
        if emit >= 0.5:
            out_t.append(int(evt))
            if cfg.age_encoding:
                out_a.append(float(age))
        if finished >= 0.5:
            live[0] = False

    apply(np.asarray(packed)[:, 0])
    pos = S
    tab = np.full((slots, nbs), -1, np.int32)
    table_dirty = True
    npad = _next_pow2(max(1, slots))       # the engine's fresh-id padding
    while live[0]:
        jb = (pos % W) // bs
        if row[jb] < 0:                    # decode growth, engine order:
            row[jb] = take(1)[0]           # reset positions, then the table
            ids = np.zeros(npad, np.int32)
            ids[0] = row[jb]
            cache = _reset_pos_jit(cache, jnp.asarray(ids))
            table_dirty = True
        if table_dirty:
            tab[0] = row
            pc = cache["self"]
            cache = {"self": pc._replace(table=jnp.asarray(tab))}
            table_dirty = False
        u = np.full((slots, V), 0.5, np.float32)
        u[0] = uniforms[len(out_t)]
        cache, state, packed, _ = _tick_u_jit(params, cache, state,
                                              jnp.asarray(u), cfg=cfg, kn=kn)
        apply(np.asarray(packed)[:, 0])
        pos += 1
    return out_t, out_a
