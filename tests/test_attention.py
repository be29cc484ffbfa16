"""Attention: chunked-flash vs naive, ring caches, GQA, sliding window,
and the paged (block pool + block table) twin of the ring cache."""
import importlib.util
import os
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.models.attention import (KVRows, LayerCache, PagedCache,
                                    PagedLayerView, cache_from_prefill,
                                    cache_write, cache_write_stacked,
                                    chunked_attention, decode_attention,
                                    empty_cache, empty_paged_cache,
                                    paged_gather_layer, paged_ring_index)


def _mk(key, B, Hq, Hkv, S, hd):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    return q, k, v


@pytest.mark.parametrize("S,window,qb,kb", [
    (64, None, 512, 512),       # direct small path
    (700, None, 128, 128),      # chunked path with padding
    (700, 100, 128, 128),       # sliding window chunked
    (256, 32, 512, 512),        # sliding window direct
])
def test_chunked_vs_ref(key, S, window, qb, kb):
    B, Hq, Hkv, hd = 2, 4, 2, 32
    q, k, v = _mk(key, B, Hq, Hkv, S, hd)
    pos = jnp.arange(S, dtype=jnp.int32)
    out = chunked_attention(q, k, v, pos, pos, causal=True, window=window,
                            q_block=qb, kv_block=kb, q_per_kv=2)
    r = ref.flash_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True, window=window)
    np.testing.assert_allclose(out, r.transpose(0, 2, 1, 3), atol=2e-5)


def test_bidirectional(key):
    B, H, S, hd = 2, 2, 256, 16
    q, k, v = _mk(key, B, H, H, S, hd)
    pos = jnp.arange(S, dtype=jnp.int32)
    out = chunked_attention(q, k, v, pos, pos, causal=False, window=None,
                            q_block=128, kv_block=128)
    r = ref.flash_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=False)
    np.testing.assert_allclose(out, r.transpose(0, 2, 1, 3), atol=2e-5)


def test_ring_cache_prefill_layout(key):
    B, Hkv, hd, S, W = 1, 2, 8, 10, 4
    k = jnp.arange(B * S * Hkv * hd, dtype=jnp.float32).reshape(B, S, Hkv, hd)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    c = cache_from_prefill(k, k, pos, W)
    # slot j holds the latest token with position % W == j
    assert c.pos[0].tolist() == [8, 9, 6, 7]
    np.testing.assert_array_equal(c.k[0, :, 0], k[0, 8])


def test_ring_cache_write_and_evict(key):
    B, Hkv, hd, W = 1, 1, 4, 3
    c = empty_cache_like(B, Hkv, W, hd)
    for step in range(5):
        kv = jnp.full((B, 1, Hkv, hd), float(step))
        c = cache_write(c, kv, kv, jnp.int32(step))
    assert sorted(c.pos[0].tolist()) == [2, 3, 4]


def empty_cache_like(B, Hkv, W, hd):
    return LayerCache(k=jnp.zeros((B, Hkv, W, hd)),
                      v=jnp.zeros((B, Hkv, W, hd)),
                      pos=jnp.full((B, W), -1, jnp.int32))


def test_swa_ring_equals_full_window(key):
    """Decoding with an SWA ring of width W must equal full attention
    restricted to the last W tokens."""
    B, Hkv, hd, S, W = 2, 2, 16, 29, 8
    ks = jax.random.split(key, 4)
    k_all = jax.random.normal(ks[0], (B, S + 1, Hkv, hd))
    v_all = jax.random.normal(ks[1], (B, S + 1, Hkv, hd))
    q = jax.random.normal(ks[2], (B, 1, Hkv, hd))

    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    ring = cache_from_prefill(k_all[:, :S], v_all[:, :S], pos, W)
    ring = cache_write(ring, k_all[:, S:], v_all[:, S:], jnp.int32(S))
    o_ring = decode_attention(q, ring, jnp.int32(S), window=W, q_per_kv=1)

    # reference: naive attention of q over the last W tokens (all visible to
    # the newest query, so no causal mask on the 1-token query)
    ctx_k = k_all[:, S - W + 1:].transpose(0, 2, 1, 3)
    ctx_v = v_all[:, S - W + 1:].transpose(0, 2, 1, 3)
    r = ref.flash_attention_ref(q.transpose(0, 2, 1, 3), ctx_k, ctx_v, causal=False)
    np.testing.assert_allclose(o_ring[:, 0], r[:, :, 0], atol=2e-5)


@pytest.mark.parametrize("form", ["ring", "rows"])
def test_decode_reads_match_reference(key, form):
    """Decode over a per-head ``(B, Hkv, W, hd)`` cache and over the same
    keys and values as side-by-side rows (:class:`KVRows`, a paged pool's
    gathered form) equals a plain float64 softmax over the valid slots, GQA
    groups and empty slots included."""
    B, Hkv, G, hd, W = 2, 3, 2, 10, 12
    ks = jax.random.split(key, 3)
    k = jax.random.normal(ks[0], (B, Hkv, W, hd))
    v = jax.random.normal(ks[1], (B, Hkv, W, hd))
    q = jax.random.normal(ks[2], (B, 1, Hkv * G, hd))
    pos = np.tile(np.arange(W, dtype=np.int32), (B, 1))
    pos[0, [2, 7]] = -1
    pos[1, 11] = -1
    cache = LayerCache(k, v, jnp.asarray(pos))
    if form == "rows":
        cache = KVRows(*(a.transpose(0, 2, 1, 3).reshape(B, W, Hkv * hd)
                         for a in (k, v)), cache.pos)
    got = decode_attention(q, cache, jnp.int32(2**30), window=None,
                           q_per_kv=G)
    qn, kn, vn = (np.asarray(a, np.float64) for a in (q, k, v))
    want = np.zeros((B, Hkv * G, hd))
    for b in range(B):
        keep = pos[b] >= 0
        for h in range(Hkv * G):
            s = kn[b, h // G, keep] @ qn[b, 0, h] * hd ** -0.5
            p = np.exp(s - s.max())
            want[b, h] = p @ vn[b, h // G, keep] / p.sum()
    np.testing.assert_allclose(np.asarray(got)[:, 0], want, atol=2e-5)


def test_unrolled_attention_matches_scanned(key):
    """The straight-line cost-accounting twin is numerically identical."""
    B, Hq, Hkv, S, hd = 2, 4, 2, 300, 32
    q, k, v = _mk(key, B, Hq, Hkv, S, hd)
    pos = jnp.arange(S, dtype=jnp.int32)
    a = chunked_attention(q, k, v, pos, pos, causal=True, window=None,
                          q_block=128, kv_block=128, q_per_kv=2)
    b = chunked_attention(q, k, v, pos, pos, causal=True, window=None,
                          q_block=128, kv_block=128, q_per_kv=2, unroll=True)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_seq_shard_attention_flag_noop_on_host(key):
    """cfg.seq_shard_attn only adds sharding constraints — outputs equal."""
    from repro.configs import get_config
    from repro.models import forward, init_params
    from repro.launch.mesh import make_host_mesh
    cfg = get_config("tinyllama-1.1b", reduced=True).replace(dtype="float32")
    p = init_params(cfg, key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    y0 = forward(p, cfg, {"tokens": tokens}, mode="train")["logits"]
    with make_host_mesh():
        y1 = forward(p, cfg.replace(seq_shard_attn=True),
                     {"tokens": tokens}, mode="train")["logits"]
    np.testing.assert_allclose(y0, y1, atol=1e-5)


def test_cache_from_prefill_wrap_equals_sequential_writes(key):
    """Ring-wrap edge (S > W): packing a long prefill must equal writing the
    same tokens one at a time through the ring — slot j holds the LAST token
    with position % W == j, and evicted positions are gone."""
    B, Hkv, hd, S, W = 2, 2, 8, 23, 8
    ks = jax.random.split(key, 2)
    k = jax.random.normal(ks[0], (B, S, Hkv, hd))
    v = jax.random.normal(ks[1], (B, S, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    packed = cache_from_prefill(k, v, pos, W)
    seq = empty_cache_like(B, Hkv, W, hd)
    for p in range(S):
        seq = cache_write(seq, k[:, p:p + 1], v[:, p:p + 1], jnp.int32(p))
    np.testing.assert_array_equal(packed.pos, seq.pos)
    np.testing.assert_allclose(packed.k, seq.k, atol=0)
    np.testing.assert_allclose(packed.v, seq.v, atol=0)
    # only the last W positions survive
    assert sorted(np.asarray(packed.pos[0]).tolist()) == list(range(S - W, S))


def test_mask_padded_positions_under_wrap(key):
    """Bucketed prefill pads past the true prompt; when the padded length
    wraps the ring (S_pad > W) the mask must invalidate every slot holding a
    padded position WITHOUT touching surviving real ones."""
    from repro.models.model import mask_padded_positions
    B, Hkv, hd, W = 1, 1, 4, 8
    S_real, S_pad = 10, 23                 # both wrap the 8-wide ring
    k = jnp.arange(B * S_pad * Hkv * hd, dtype=jnp.float32).reshape(
        B, S_pad, Hkv, hd)
    pos = jnp.broadcast_to(jnp.arange(S_pad, dtype=jnp.int32)[None],
                           (B, S_pad))
    c = cache_from_prefill(k, k, pos, W)
    st = jax.tree_util.tree_map(lambda a: a[None], c)   # stack L=1
    masked = mask_padded_positions({"self": st}, np.asarray([S_real - 1]))
    got = np.asarray(masked["self"].pos[0, 0])
    # padded positions 10..22 overwrote the whole ring except slots still
    # holding positions <= 9: after the wrap the ring holds 15..22, so ALL
    # slots must be invalidated
    assert (got == -1).all(), got

    # shorter pad: S_pad=12 keeps positions 4..11; slots holding 4..9 stay
    c2 = cache_from_prefill(k[:, :12], k[:, :12], pos[:, :12], W)
    st2 = jax.tree_util.tree_map(lambda a: a[None], c2)
    m2 = mask_padded_positions({"self": st2}, np.asarray([S_real - 1]))
    got2 = np.asarray(m2["self"].pos[0, 0])
    kept = sorted(p for p in got2.tolist() if p >= 0)
    assert kept == [4, 5, 6, 7, 8, 9], got2


# ---------------------------------------------------------------------------
# Paged cache: pool + block-table twin of the ring
# ---------------------------------------------------------------------------
class _Pool(NamedTuple):
    """One layer's paged cache in the per-head block layout the oracles
    address: pool planes, pool positions, block table."""
    k: jax.Array          # (num_blocks, Hkv, block_size, hd)
    v: jax.Array
    pos: jax.Array        # (num_blocks, block_size)
    table: jax.Array      # (B, blocks_per_slot)


def _layout_check():
    """``scripts/pool_layout_check.py``, which holds the per-head layout's
    functions: the oracle the row pool is held to, here and on the chip."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "pool_layout_check.py")
    spec = importlib.util.spec_from_file_location("pool_layout_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_oracle = _layout_check()
_rows = _oracle.head_rows      # (L, NB, Hkv, bs, hd) -> the held rows


def _view(k, v, pos, table, *, layer=0) -> PagedLayerView:
    """The layer view the decode scan builds: per-head pools (stacked on a
    leading layer axis, or one layer) in the held row layout."""
    k, v = (a if a.ndim == 5 else a[None] for a in (k, v))
    return PagedLayerView(_rows(k), _rows(v), layer,
                          *paged_ring_index(pos, table), heads=k.shape[2])


def _per_position_gather(k, v, pos, table) -> LayerCache:
    """Test oracle: the ring view gathered one position at a time from one
    layer's ``(NB, Hkv, bs, hd)`` pool.

    Ring slot ``j`` of slot ``b`` is ``pool[table[b, j // bs], j % bs]``;
    unallocated table entries gather from the trash block 0 and carry
    ``pos = -1``.
    """
    B, nbs = table.shape
    bs = k.shape[2]
    W = nbs * bs
    j = jnp.arange(W)
    blk = table[:, j // bs]                       # (B, W) pool ids
    off = jnp.broadcast_to(j % bs, (B, W))
    safe = jnp.maximum(blk, 0)
    rk = k[safe, :, off, :].transpose(0, 2, 1, 3)  # (B, Hkv, W, hd)
    rv = v[safe, :, off, :].transpose(0, 2, 1, 3)
    rpos = jnp.where(blk >= 0, pos[safe, off], -1).astype(jnp.int32)
    return LayerCache(k=rk, v=rv, pos=rpos)


def _bits(a):
    """Array -> integers of its bytes (bfloat16 as uint16)."""
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype.itemsize == 2 else a


def _assert_bits_equal(got, want):
    got, want = _bits(got), _bits(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def _ring_to_paged(ring: LayerCache, bs: int) -> _Pool:
    """Pack a ring LayerCache into an equivalent single-layer paged pool."""
    B, Hkv, W, hd = ring.k.shape
    nbs = W // bs
    NB = 1 + B * nbs
    table = np.full((B, nbs), -1, np.int32)
    pool_k = np.zeros((NB, Hkv, bs, hd), np.float32)
    pool_v = np.zeros((NB, Hkv, bs, hd), np.float32)
    pool_pos = np.full((NB, bs), -1, np.int32)
    nxt = 1
    rk, rv, rp = (np.asarray(x) for x in (ring.k, ring.v, ring.pos))
    for b in range(B):
        for jb in range(nbs):
            if (rp[b, jb * bs:(jb + 1) * bs] < 0).all():
                continue
            table[b, jb] = nxt
            pool_k[nxt] = rk[b, :, jb * bs:(jb + 1) * bs]
            pool_v[nxt] = rv[b, :, jb * bs:(jb + 1) * bs]
            pool_pos[nxt] = rp[b, jb * bs:(jb + 1) * bs]
            nxt += 1
    return _Pool(jnp.asarray(pool_k), jnp.asarray(pool_v),
                 jnp.asarray(pool_pos), jnp.asarray(table))


def test_paged_gather_reconstructs_ring_bitwise(key):
    B, Hkv, hd, S, W, bs = 2, 2, 8, 13, 16, 4
    ks = jax.random.split(key, 2)
    k = jax.random.normal(ks[0], (B, S, Hkv, hd))
    v = jax.random.normal(ks[1], (B, S, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    ring = cache_from_prefill(k, v, pos, W)
    g = paged_gather_layer(_view(*_ring_to_paged(ring, bs)))
    np.testing.assert_array_equal(g.pos, ring.pos)
    valid = np.asarray(ring.pos) >= 0
    np.testing.assert_array_equal(
        np.asarray(g.k).transpose(0, 2, 1, 3)[valid],
        np.asarray(ring.k).transpose(0, 2, 1, 3)[valid])


def test_paged_decode_bit_identical_to_ring(key):
    """decode_attention over a PagedLayerView == over the ring it factors —
    bit-identical, including the deferred-write new-token merge (the paged
    engine's parity claim at the layer level)."""
    B, Hkv, hd, S, W, bs = 2, 2, 16, 21, 16, 4     # S > W: wrapped ring
    ks = jax.random.split(key, 5)
    k = jax.random.normal(ks[0], (B, S, Hkv, hd))
    v = jax.random.normal(ks[1], (B, S, Hkv, hd))
    q = jax.random.normal(ks[2], (B, 1, Hkv * 2, hd))
    kn = jax.random.normal(ks[3], (B, 1, Hkv, hd))
    vn = jax.random.normal(ks[4], (B, 1, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    ring = cache_from_prefill(k, v, pos, W)
    view = _view(*_ring_to_paged(ring, bs))
    step = jnp.full((B,), S, jnp.int32)
    for window in (None, 6):
        o_r = decode_attention(q, ring, step, window=window, q_per_kv=2,
                               k_new=kn, v_new=vn)
        o_p = decode_attention(q, view, step, window=window, q_per_kv=2,
                               k_new=kn, v_new=vn)
        np.testing.assert_array_equal(np.asarray(o_r), np.asarray(o_p))


def test_paged_write_stacked_matches_ring_write(key):
    """cache_write_stacked dispatches on cache kind; the paged write lands
    in the table-mapped block and unallocated slots write to trash."""
    B, Hkv, hd, W, bs, L = 2, 1, 8, 8, 4, 2
    ks = jax.random.split(key, 3)
    k = jax.random.normal(ks[0], (B, 6, Hkv, hd))
    v = jax.random.normal(ks[1], (B, 6, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32)[None], (B, 6))
    ring = cache_from_prefill(k, v, pos, W)
    pool = _ring_to_paged(ring, bs)
    pc = PagedCache(k=_rows(jnp.stack([pool.k] * L)),
                    v=_rows(jnp.stack([pool.v] * L)),
                    pos=pool.pos, table=pool.table)
    ring_st = jax.tree_util.tree_map(lambda a: jnp.stack([a] * L), ring)
    kn = jax.random.normal(ks[2], (L, B, 1, Hkv, hd))
    step = jnp.asarray([6, 7], jnp.int32)
    r2 = cache_write_stacked(ring_st, kn, kn, step)
    p2 = cache_write_stacked(pc, kn, kn, step)
    assert isinstance(p2, PagedCache)
    g = paged_gather_layer(PagedLayerView(
        p2.k, p2.v, L - 1, *paged_ring_index(p2.pos, p2.table), heads=Hkv))
    np.testing.assert_array_equal(g.pos, r2.pos[-1])
    valid = np.asarray(r2.pos[-1]) >= 0
    np.testing.assert_array_equal(
        np.asarray(g.k).transpose(0, 2, 1, 3)[valid],
        np.asarray(r2.k[-1]).transpose(0, 2, 1, 3)[valid])


_ROWS = {                 # one token's row: Hkv, key width, value width
    "delphi_heads": (12, 10, 10),
    "gqa_danube_heads": (8, 80, 80),
    "latent_row": (1, 576, 0),          # Moonlight's [c | k_pe], no values
}

_GATHER_CASES = {         # row shape, num_blocks, slots, blocks per slot
    "delphi_heads": ("delphi_heads", 65, 8, 4),
    "gqa_danube_heads": ("gqa_danube_heads", 5, 2, 2),
    "unallocated": ("delphi_heads", 65, 8, 4),
    "fork_shared": ("delphi_heads", 65, 8, 4),
    "latent_row": ("latent_row", 17, 4, 4),
}


def _gather_case(case: str, key) -> _Pool:
    bs, bf16 = 16, jnp.bfloat16
    ks = jax.random.split(key, 2)
    if case == "wrapped_ring":
        B, Hkv, hd, S, W = 3, 8, 80, 45, 32          # S > W: the ring wrapped
        k = jax.random.normal(ks[0], (B, S, Hkv, hd))
        v = jax.random.normal(ks[1], (B, S, Hkv, hd))
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        pool = _ring_to_paged(cache_from_prefill(k, v, pos, W), bs)
        return pool._replace(k=pool.k.astype(bf16), v=pool.v.astype(bf16))
    rows, NB, B, nbs = _GATHER_CASES[case]
    Hkv, dk, dv = _ROWS[rows]
    rng = np.random.default_rng(7)
    table = rng.permutation(np.arange(1, NB))[:B * nbs].reshape(B, nbs)
    if case == "unallocated":
        table[rng.random((B, nbs)) < 0.4] = -1
        table[B - 1] = -1                            # an idle slot
    if case == "fork_shared":
        table[1, :2] = table[0, :2]        # a fork shares its parent's prefix
        table[2] = table[0]
    pos = rng.integers(0, 4 * NB * bs, (NB, bs))
    pos[rng.random((NB, bs)) < 0.2] = -1             # empty positions
    return _Pool(jax.random.normal(ks[0], (NB, Hkv, bs, dk), bf16),
                 jax.random.normal(ks[1], (NB, Hkv, bs, dv), bf16),
                 jnp.asarray(pos, jnp.int32), jnp.asarray(table, jnp.int32))


@pytest.mark.parametrize("case", [*_GATHER_CASES, "wrapped_ring"])
def test_block_gather_matches_per_position_oracle(key, case):
    """The served whole-row gather equals the per-position oracle bit for
    bit in k, v and pos: trash-block reads of unallocated entries, shared
    (forked) blocks, wrapped rings and the latent row (one head, an empty
    value plane) included.  The pool holds another layer first, so the
    gather's layer index is exercised too."""
    pool = _gather_case(case, key)
    other = jax.tree_util.tree_map(lambda a: -a, (pool.k, pool.v))
    view = _view(jnp.stack([other[0], pool.k]), jnp.stack([other[1], pool.v]),
                 pool.pos, pool.table, layer=1)
    got = paged_gather_layer(view)
    want = _per_position_gather(*pool)
    for g, w in zip(got, want):
        _assert_bits_equal(g, w)


def _write_case(rows: str, key):
    """A 3-layer bfloat16 pool of 33 blocks of 16 behind 8 slots of 4
    blocks, the edges of the tick's write in one tick: offsets 0 and 15,
    block id NB - 1, a fork writing its own block past its parent's shared
    ones, a wrapped ring, a slot whose destination block is unallocated and
    three idle slots, all four writing the trash block 0."""
    L, NB, bs = 3, 33, 16
    Hkv, dk, dv = _ROWS[rows]
    ks = jax.random.split(key, 4)
    rng = np.random.default_rng(11)
    pos = rng.integers(0, 2048, (NB, bs)).astype(np.int32)
    pos[rng.random((NB, bs)) < 0.2] = -1
    table = np.full((8, 4), -1, np.int32)
    table[0] = [1, 2, 3, NB - 1]
    table[1] = [1, 2, 4, -1]           # fork of slot 0: shares blocks 1, 2
    table[2] = [5, 6, 7, 8]
    table[6] = [9, 10, -1, -1]
    table[7] = [11, 12, 13, 14]
    #      NB-1, off 15   own block 4, off 0   off 7   idle x3   unallocated
    step = [3 * bs + 15, 2 * bs, bs + 7, 0, 17, 40, 2 * bs + 3,
            4 * bs + 5]                # slot 7: wrapped, column 0, off 5
    bf16 = jnp.bfloat16
    k = jax.random.normal(ks[0], (L, NB, Hkv, bs, dk), bf16)
    v = jax.random.normal(ks[1], (L, NB, Hkv, bs, dv), bf16)
    kn = jax.random.normal(ks[2], (L, 8, 1, Hkv, dk), bf16)
    vn = jax.random.normal(ks[3], (L, 8, 1, Hkv, dv), bf16)
    return (k, v, jnp.asarray(pos), jnp.asarray(table), kn, vn,
            jnp.asarray(step, jnp.int32))


def _paged(k, v, pos, table) -> PagedCache:
    return PagedCache(k=_rows(k), v=_rows(v), pos=pos, table=table)


@pytest.mark.parametrize("rows", list(_ROWS))
def test_paged_write_matches_per_head_oracle(key, rows):
    """The tick's in-place row write equals the per-head scatter it
    replaced, byte for byte in every block (the trash block included: both
    keep the last idle slot's write) and in the position plane."""
    k, v, pos, table, kn, vn, step = _write_case(rows, key)
    wk, wv, wpos = _oracle.head_write(k, v, pos, table, kn, vn, step)
    got = cache_write_stacked(_paged(k, v, pos, table), kn, vn, step)
    _assert_bits_equal(got.k, _rows(wk))
    _assert_bits_equal(got.v, _rows(wv))
    _assert_bits_equal(got.pos, wpos)
    # the fork's write left its parent's shared blocks alone
    _assert_bits_equal(got.k[:, 1:3], _rows(k)[:, 1:3])


@pytest.mark.parametrize("rows", list(_ROWS))
def test_cow_block_matches_per_head_oracle(key, rows):
    """Copy-on-write of a forked slot's shared block into the last pool
    block, in place: every layer's row and the position row, bytes equal
    to the per-head copy; nothing else moves."""
    from repro.serve.engine import _cow_block_jit
    k, v, pos, table, *_ = _write_case(rows, key)
    src, dst = 2, k.shape[1] - 1
    want = _oracle.head_cow(k, v, pos, src, dst)
    got = _cow_block_jit({"self": _paged(k, v, pos, table)}, jnp.int32(src),
                         jnp.int32(dst))["self"]
    _assert_bits_equal(got.k, _rows(want[0]))
    _assert_bits_equal(got.v, _rows(want[1]))
    _assert_bits_equal(got.pos, want[2])


@pytest.mark.parametrize("rows", list(_ROWS))
def test_insert_blocks_matches_per_head_oracle(key, rows):
    """Admission's copy of ring prefill rows into pool blocks: block id
    NB - 1, a shorter prompt whose tail blocks pad into the trash block,
    bytes equal to the per-head scatter."""
    from repro.serve.engine import _insert_blocks_jit
    k, v, pos, table, *_ = _write_case(rows, key)
    L, NB, Hkv, bs, dk = k.shape
    dv = v.shape[-1]
    W, nblk = 4 * bs, 3
    ks = jax.random.split(key, 2)
    ring = LayerCache(
        k=jax.random.normal(ks[0], (L, 2, Hkv, W, dk), jnp.bfloat16),
        v=jax.random.normal(ks[1], (L, 2, Hkv, W, dv), jnp.bfloat16),
        pos=jnp.broadcast_to(jnp.where(jnp.arange(W) < 40, jnp.arange(W), -1),
                             (L, 2, W)).astype(jnp.int32))
    dst = jnp.asarray([[NB - 1, 20, 21], [22, 0, 0]], jnp.int32)
    want = _oracle.head_insert(k, v, pos, ring, dst, nblk=nblk)
    got = _insert_blocks_jit({"self": _paged(k, v, pos, table)},
                             {"self": ring}, dst, nblk=nblk)["self"]
    for g, w in zip(got[:2], want[:2]):
        _assert_bits_equal(g, _rows(w))
    _assert_bits_equal(got.pos, want[2])


@pytest.mark.parametrize("rows", list(_ROWS))
def test_reset_pos_leaves_rows(key, rows):
    """Resetting freshly allocated blocks' positions (block NB - 1 and the
    trash padding) touches the position plane only."""
    from repro.serve.engine import _reset_pos_jit
    k, v, pos, table, *_ = _write_case(rows, key)
    ids = jnp.asarray([k.shape[1] - 1, 7, 0, 0], jnp.int32)
    want = _oracle.head_reset(pos, ids)
    got = _reset_pos_jit({"self": _paged(k, v, pos, table)}, ids)["self"]
    _assert_bits_equal(got.k, _rows(k))
    _assert_bits_equal(got.v, _rows(v))
    _assert_bits_equal(got.pos, want)


def _hlo_computations(text: str):
    """HLO text -> {computation: {instruction: (shape, opcode, operands,
    attributes)}}."""
    comps, cur = {}, None
    inst = re.compile(r"(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)"
                      r"\((.*?)\)(.*)")
    for line in text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            cur = comps.setdefault(line.split()[-2].lstrip("%").split("(")[0],
                                   {})
        elif line == "}":
            cur = None
        elif cur is not None and (m := inst.match(line.strip())):
            name, shape, op, args, attrs = m.groups()
            cur[name] = (shape, op, [a.lstrip("%") for a in args.split(", ")],
                         attrs)
    return comps


def _called(comps, roots):
    """Every computation reachable from ``roots`` through calls."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for _, _, _, attrs in comps[c].values():
            todo += re.findall(r"(?:to_apply|calls|body|condition)=%?"
                               r"([\w.\-]+)", attrs)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", attrs):
                todo += [g.strip().lstrip("%") for g in group.split(",")]
    return seen


def test_paged_decode_scan_gathers_whole_blocks():
    """Structural guard on the lowered paged decode_step: inside the layer
    scan no gather reads the pool's (num_blocks, block_size) position plane
    or the (slots, blocks_per_slot) table, and every K/V gather reads the
    whole held (L, num_blocks, bs * Hkv * hd) pool at (layer, block) — one
    whole row per table entry, no per-layer slice of the pool."""
    from repro.configs import get_config
    from repro.models import decode_step, init_params, make_paged_decode_cache
    cfg = get_config("delphi-2m", reduced=True)
    B, ctx, bs = 4, 64, 16
    NB, nbs = B * ctx // bs + 1, ctx // bs
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_paged_decode_cache(
        cfg, B, ctx, num_blocks=NB, block_size=bs))
    batch = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
             "ages": jax.ShapeDtypeStruct((B, 1), jnp.float32)}
    step = jax.ShapeDtypeStruct((B,), jnp.int32)
    text = jax.jit(lambda p, c, b, s: decode_step(p, cfg, c, b, s)).lower(
        params, cache, batch, step).compiler_ir("hlo").as_hlo_text()
    plane = {"bfloat16": "bf16", "float32": "f32"}[
        str(cache["self"].k.dtype)]
    comps = _hlo_computations(text)
    bodies = [re.search(r"body=%?([\w.\-]+)", i[3]).group(1)
              for c in comps.values() for i in c.values() if i[1] == "while"]
    assert bodies, "no layer scan in the paged decode program"
    kv_gathers = 0
    for c in _called(comps, bodies):
        for _, op, args, attrs in comps[c].values():
            if op != "gather":
                continue
            operand = comps[c][args[0]][0]
            dims = operand[operand.index("[") + 1:operand.index("]")]
            assert dims not in (f"{NB},{bs}", f"{B},{nbs}"), (
                f"per-position index gather in the layer scan: {operand}")
            assert dims != f"{NB},{bs * Hkv * hd}", (
                f"gather from a per-layer slice of the pool: {operand}")
            if dims == f"{L},{NB},{bs * Hkv * hd}" and operand.startswith(
                    plane):
                # a K/V plane read at (layer, block): whole rows
                kv_gathers += 1
                assert "collapsed_slice_dims={0,1}," in attrs, attrs
                assert f"slice_sizes={{1,1,{bs * Hkv * hd}}}" in attrs, attrs
    assert kv_gathers == 2, kv_gathers       # K and V, once in the body


def test_empty_paged_cache_shapes_and_validation():
    from repro.configs import get_config
    cfg = get_config("delphi-2m", reduced=True)
    pc = empty_paged_cache(cfg, 3, 9, 4, 32, 8, jnp.float32)
    assert pc.k.shape == (3, 9, 8 * cfg.n_kv_heads * cfg.head_dim)
    assert pc.v.shape == (3, 9, 8 * cfg.n_kv_heads * cfg.head_dim)
    assert pc.table.shape == (4, 4) and (np.asarray(pc.table) == -1).all()
    assert (np.asarray(pc.pos) == -1).all()
    with pytest.raises(ValueError, match="multiple"):
        empty_paged_cache(cfg, 3, 9, 4, 30, 8, jnp.float32)


def test_deferred_write_matches_inline(key):
    B, Hkv, hd, S, W = 2, 2, 16, 12, 16
    ks = jax.random.split(key, 4)
    k_all = jax.random.normal(ks[0], (B, S + 1, Hkv, hd))
    v_all = jax.random.normal(ks[1], (B, S + 1, Hkv, hd))
    q = jax.random.normal(ks[2], (B, 1, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cache = cache_from_prefill(k_all[:, :S], v_all[:, :S], pos, W)

    inline = cache_write(cache, k_all[:, S:], v_all[:, S:], jnp.int32(S))
    o_inline = decode_attention(q, inline, jnp.int32(S), window=None)
    o_defer = decode_attention(q, cache, jnp.int32(S), window=None,
                               k_new=k_all[:, S:], v_new=v_all[:, S:])
    np.testing.assert_allclose(o_inline, o_defer, atol=1e-5)
