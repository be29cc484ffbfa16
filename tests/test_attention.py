"""Attention: chunked-flash vs naive, ring caches, GQA, sliding window,
and the paged (block pool + block table) twin of the ring cache."""
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.models.attention import (LayerCache, PagedCache, PagedLayerView,
                                    cache_from_prefill, cache_write,
                                    cache_write_stacked, chunked_attention,
                                    decode_attention, empty_cache,
                                    empty_paged_cache, paged_gather_layer,
                                    paged_ring_index)


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
    """One layer's paged cache: pool planes, pool positions, block table."""
    k: jax.Array          # (num_blocks, Hkv, block_size, hd)
    v: jax.Array
    pos: jax.Array        # (num_blocks, block_size)
    table: jax.Array      # (B, blocks_per_slot)


def _view(k, v, pos, table) -> PagedLayerView:
    """The layer view the decode scan builds from one layer's pool."""
    return PagedLayerView(k, v, *paged_ring_index(pos, table))


def _per_position_gather(k, v, pos, table) -> LayerCache:
    """Test oracle: the ring view gathered one position at a time.

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
    pc = PagedCache(k=jnp.stack([pool.k] * L), v=jnp.stack([pool.v] * L),
                    pos=pool.pos, table=pool.table)
    ring_st = jax.tree_util.tree_map(lambda a: jnp.stack([a] * L), ring)
    kn = jax.random.normal(ks[2], (L, B, 1, Hkv, hd))
    step = jnp.asarray([6, 7], jnp.int32)
    r2 = cache_write_stacked(ring_st, kn, kn, step)
    p2 = cache_write_stacked(pc, kn, kn, step)
    assert isinstance(p2, PagedCache)
    g = paged_gather_layer(_view(p2.k[0], p2.v[0], p2.pos, p2.table))
    np.testing.assert_array_equal(g.pos, r2.pos[0])
    valid = np.asarray(r2.pos[0]) >= 0
    np.testing.assert_array_equal(
        np.asarray(g.k).transpose(0, 2, 1, 3)[valid],
        np.asarray(r2.k[0]).transpose(0, 2, 1, 3)[valid])


_GATHER_CASES = {             # Hkv, hd, num_blocks, slots, blocks per slot
    "delphi_heads": (12, 10, 65, 8, 4),
    "gqa_danube_heads": (8, 80, 5, 2, 2),
    "unallocated": (12, 10, 65, 8, 4),
    "fork_shared": (12, 10, 65, 8, 4),
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
    Hkv, hd, NB, B, nbs = _GATHER_CASES[case]
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
    return _Pool(jax.random.normal(ks[0], (NB, Hkv, bs, hd), bf16),
                 jax.random.normal(ks[1], (NB, Hkv, bs, hd), bf16),
                 jnp.asarray(pos, jnp.int32), jnp.asarray(table, jnp.int32))


@pytest.mark.parametrize("case", [*_GATHER_CASES, "wrapped_ring"])
def test_block_gather_matches_per_position_oracle(key, case):
    """The served whole-block gather equals the per-position oracle bit for
    bit in k, v and pos: trash-block reads of unallocated entries, shared
    (forked) blocks and wrapped rings included."""
    pool = _gather_case(case, key)
    got = paged_gather_layer(_view(*pool))
    want = _per_position_gather(*pool)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g), np.asarray(w)
        if g.dtype != np.int32:
            g, w = g.view(np.uint16), w.view(np.uint16)
        np.testing.assert_array_equal(g, w)


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
    or the (slots, blocks_per_slot) table, and every K/V gather collapses
    the pool's block axis only — one whole block per table entry."""
    from repro.configs import get_config
    from repro.models import decode_step, init_params, make_paged_decode_cache
    cfg = get_config("delphi-2m", reduced=True)
    B, ctx, bs = 4, 64, 16
    NB, nbs = B * ctx // bs + 1, ctx // bs
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
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
            lead, *rest = dims.split(",")
            if int(lead) == NB and operand.startswith(plane):
                # a K/V plane, however its block is viewed: whole blocks
                kv_gathers += 1
                assert np.prod([int(d) for d in rest]) == Hkv * bs * hd
                assert "collapsed_slice_dims={0}," in attrs, attrs
                assert f"slice_sizes={{1,{','.join(rest)}}}" in attrs, attrs
    assert kv_gathers == 2, kv_gathers       # K and V, once in the body


def test_empty_paged_cache_shapes_and_validation():
    from repro.configs import get_config
    cfg = get_config("delphi-2m", reduced=True)
    pc = empty_paged_cache(cfg, 3, 9, 4, 32, 8, jnp.float32)
    assert pc.k.shape == (3, 9, cfg.n_kv_heads, 8, cfg.head_dim)
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
