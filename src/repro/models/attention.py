"""Attention: GQA / MHA, sliding-window, cross-attention, and KV caches.

Design notes
------------
* The jnp path implements **online-softmax chunked attention** (the same
  algorithm as the Pallas flash kernel in ``repro.kernels``) so that the
  lowered HLO never materializes an (S, T) score matrix — mandatory for the
  32k prefill dry-runs to fit on-device memory.  The inner KV-block body is
  rematerialized (``jax.checkpoint``) so the backward pass is flash-like too.
* One **unified ring cache** covers full-cache decode and sliding-window
  decode: a cache of width ``W`` with per-slot absolute positions.  Writing
  slot ``step % W`` makes a full cache (``W >= context``) and an SWA ring
  (``W == window``) the same code path.  Keys are stored *post-RoPE* (RoPE is
  applied on absolute positions, so relative offsets remain exact at any
  context depth — this is what makes long_500k ring decoding valid).
* GQA: queries are grouped ``(n_kv_heads, q_per_kv)``; KV is never repeated
  in memory.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_norm, apply_rope, param_dtype

NEG_INF = -1e30


class LayerCache(NamedTuple):
    """Per-layer decode cache (stacked on a leading layer axis by the model)."""
    k: jax.Array          # (B, Hkv, W, hd)  roped keys
    v: jax.Array          # (B, Hkv, W, hd)
    pos: jax.Array        # (B, W) int32 absolute position per slot, -1 = empty


class PagedCache(NamedTuple):
    """Paged decode cache: one shared block pool + per-slot block tables.

    Instead of a dense per-slot ring (``LayerCache`` stacked to
    ``(L, B, Hkv, W, hd)``), K/V live in a pool of fixed-size blocks that a
    host-side allocator hands out on demand, so resident cache memory scales
    with *tokens actually held*, not ``slots x max_context`` worst case —
    the serving lever for Delphi's short-median/long-tail trajectories.

    Leaves:
      k, v  : (L, num_blocks, block_size * Hkv * hd) — the shared pool, one
              row per (layer, block) holding the block's tokens in order,
              each token's ``Hkv * hd`` values together (token-major).
              Block 0 is the **trash block**: writes of slots with no
              allocated destination land there and are never read back.
              Latent attention keeps one ``[c | k_pe]`` row a token in the
              k plane (Hkv 1) and an empty v plane (width 0): the same
              blocks, gather and writes (:func:`cache_rows`).
      pos   : (num_blocks, block_size) int32 absolute positions, -1 = empty.
              Layer-independent (every layer writes the same positions).
      table : (B, blocks_per_slot) int32 pool block ids, -1 = unallocated.

    Why rows: the TPU tiles an array's two minor axes, and a
    ``(block_size, hd)`` minor pair such as ``(16, 10)`` would pad, so it
    holds a ``(L, NB, Hkv, bs, hd)`` pool with the block axis minor and
    every scatter into it lays the whole pool out afresh.  Rows of
    ``bs * Hkv * hd`` (1,920 for Delphi-2M, 10,240 for Danube, 9,216 for
    Moonlight's latent rows) are whole lane tiles, so the pool is held as
    written: the tick's write (:func:`paged_write_stacked`), the per-layer
    gather (:func:`paged_gather_rows`) and the engine's block copies
    (:func:`paged_put_rows`) all address it in place.

    The logical layout is *exactly* the ring cache factored through one
    indirection: with ``W = blocks_per_slot * block_size``, the token at
    absolute position ``p`` of slot ``b`` lives in row
    ``table[b, (p % W) // block_size]`` at token ``p % block_size`` — the
    same ``p % W`` ring slot the dense cache uses: ring positions
    ``[jb * block_size, (jb + 1) * block_size)`` of slot ``b`` are exactly
    pool block ``table[b, jb]``.  The gather therefore reads exactly the
    ring's values (``paged_gather_layer`` reconstructs the bit-identical
    ``LayerCache`` view), and :func:`decode_attention` reads both in one
    formulation, which is what makes the paged engine's trajectories
    bit-equal to the ring engine's under injected uniforms.
    """
    k: jax.Array
    v: jax.Array
    pos: jax.Array
    table: jax.Array


class PagedLayerView(NamedTuple):
    """One layer of the pool plus the tick's layer-independent ring index
    (:func:`paged_ring_index`, built once outside the layer scan) — what the
    decode layer scan hands to :func:`decode_attention`.  The planes are
    the whole stacked pool, read at ``layer`` by the gather itself: a
    per-layer slice of the pool would be a copy of it."""
    k: jax.Array          # (L, num_blocks, block_size * heads * hd)
    v: jax.Array
    layer: jax.Array      # () int32 layer index into the planes
    blocks: jax.Array     # (B, blocks_per_slot) pool ids, -1 -> trash block 0
    pos: jax.Array        # (B, W) int32 absolute position per ring slot
    heads: int            # Hkv: how a token's values split into heads


class KVRows(NamedTuple):
    """One layer's keys and values with each token's heads side by side:
    the paged pool's own token-major order (:func:`paged_gather_rows`), and
    the form :func:`decode_attention` reads every cache in."""
    k: jax.Array          # (B, W, heads * hd)
    v: jax.Array          # (B, W, heads * dv)
    pos: jax.Array        # (B, W) int32 absolute position per ring slot


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, cross: bool = False):
    if cfg.is_mla and not cross:
        return init_mla(key, cfg)
    pdt = param_dtype(cfg)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, hq, hd)) * s).astype(pdt),
        "wk": (jax.random.normal(k2, (d, hkv, hd)) * s).astype(pdt),
        "wv": (jax.random.normal(k3, (d, hkv, hd)) * s).astype(pdt),
        "wo": (jax.random.normal(k4, (hq, hd, d)) * (hq * hd) ** -0.5).astype(pdt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), pdt)
        p["bk"] = jnp.zeros((hkv, hd), pdt)
        p["bv"] = jnp.zeros((hkv, hd), pdt)
    return p


def init_mla(key, cfg: ModelConfig):
    """Latent attention without query compression (DeepSeek-V2/V3 with
    ``q_lora_rank`` null): ``wq`` gives each head [q_nope | q_pe];
    ``wkv_a`` gives the latent c (normed by ``kv_norm``) and the shared
    k_pe; ``wkv_b`` expands c into each head's [k_nope | v]."""
    pdt = param_dtype(cfg)
    d, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(k1, (d, H, dn + dr)) * s).astype(pdt),
        "wkv_a": (jax.random.normal(k2, (d, r + dr)) * s).astype(pdt),
        "kv_norm": {"scale": jnp.ones((r,), pdt)},
        "wkv_b": (jax.random.normal(k3, (r, H, dn + dv))
                  * r ** -0.5).astype(pdt),
        "wo": (jax.random.normal(k4, (H, dv, d)) * (H * dv) ** -0.5).astype(pdt),
    }


def _project_qkv(params, xq, xkv, cfg: ModelConfig):
    dt = xq.dtype
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", xkv, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", xkv, params["wv"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    return q, k, v


# ---------------------------------------------------------------------------
# Online-softmax chunked attention (jnp flash)
# ---------------------------------------------------------------------------
def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def chunked_attention(q, k, v, q_pos, k_pos, *, causal: bool,
                      window: Optional[int], q_block: int = 512,
                      kv_block: int = 512, q_per_kv: int = 1,
                      unroll: bool = False):
    """q, k: (B, S*, H*, hd); v: (B, Skv, Hkv, dv); *_pos int32 (B, S*) or
    (S*,).

    Invalid KV slots are marked with k_pos < 0.  Returns (B, Sq, Hq, dv):
    values may be narrower than queries and keys (latent attention).
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = q_per_kv
    assert Hq == Hkv * G
    scale = hd ** -0.5

    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (B, Sq))
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None], (B, Skv))

    if Sq * Skv <= q_block * kv_block:
        # small problem: one dense masked block (cheaper than scan machinery)
        qg = q.reshape(B, Sq, Hkv, G, hd)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
        valid = k_pos[:, None, None, None, :] >= 0
        if causal:
            rel = q_pos[:, None, None, :, None] - k_pos[:, None, None, None, :]
            valid = valid & (rel >= 0)
            if window is not None:
                valid = valid & (rel < window)
        s = jnp.where(valid, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
        return o.reshape(B, Sq, Hq, dv)

    q, _ = _pad_to(q, 1, q_block)
    q_pos_p, _ = _pad_to(q_pos, 1, q_block)
    k, _ = _pad_to(k, 1, kv_block)
    v, _ = _pad_to(v, 1, kv_block)
    # padded KV slots must be invalid
    k_pos_p = jnp.pad(k_pos, ((0, 0), (0, (-Skv) % kv_block)), constant_values=-1)
    Sq_p, Skv_p = q.shape[1], k.shape[1]
    nq, nk = Sq_p // q_block, Skv_p // kv_block

    # (nq, B, Hkv, G, q_block, hd)
    qb = q.reshape(B, nq, q_block, Hkv, G, hd).transpose(1, 0, 3, 4, 2, 5)
    qpb = q_pos_p.reshape(B, nq, q_block).transpose(1, 0, 2)
    kb = k.reshape(B, nk, kv_block, Hkv, hd).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nk, kv_block, Hkv, dv).transpose(1, 0, 3, 2, 4)
    kpb = k_pos_p.reshape(B, nk, kv_block).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def kv_step(carry, blk, q_i, qp_i):
        o, m, l = carry
        k_i, v_i, kp_i = blk
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_i, k_i).astype(jnp.float32) * scale
        valid = kp_i[:, None, None, None, :] >= 0
        if causal:
            rel = qp_i[:, None, None, :, None] - kp_i[:, None, None, None, :]
            valid = valid & (rel >= 0)
            if window is not None:
                valid = valid & (rel < window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(v_i.dtype), v_i).astype(jnp.float32)
        return (o_new, m_new, l_new), None

    def q_step(q_i, qp_i):
        o0 = jnp.zeros((B, Hkv, G, q_block, dv), jnp.float32)
        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        if unroll:
            # straight-line twin (dry-run cost accounting: XLA's CPU cost
            # analysis counts loop bodies once, so loops are peeled here)
            c = (o0, m0, l0)
            for ik in range(nk):
                c, _ = kv_step(c, (kb[ik], vb[ik], kpb[ik]), q_i, qp_i)
            o, m, l = c
        else:
            (o, m, l), _ = jax.lax.scan(
                lambda c, b: kv_step(c, b, q_i, qp_i), (o0, m0, l0),
                (kb, vb, kpb))
        return o / jnp.maximum(l, 1e-30)[..., None]

    if unroll:
        out = jnp.stack([q_step(qb[iq], qpb[iq]) for iq in range(nq)])
    else:
        out = jax.lax.map(lambda args: q_step(*args), (qb, qpb))   # (nq, ...)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq_p, Hq, dv)
    return out[:, :Sq].astype(q.dtype)


@jax.named_scope("paged_gather")
def paged_ring_index(pos, table):
    """The layer-independent half of the paged ring view, built once a tick.

    pos: the pool's ``(num_blocks, block_size)`` positions; table:
    ``(B, blocks_per_slot)``.  Returns ``(blocks, ring_pos)``: the table
    with unallocated entries (-1) pointed at the trash block 0, and the
    ``(B, W)`` absolute position of every ring slot, -1 where the table
    entry is unallocated — exactly like an empty ring slot.
    """
    B = table.shape[0]
    blocks = jnp.maximum(table, 0)
    ring_pos = jnp.where(table[:, :, None] >= 0, pos[blocks], -1)
    return blocks, ring_pos.reshape(B, -1).astype(jnp.int32)


def paged_rows(a, bs: int):
    """Token-major ``(..., n * bs, Hkv, hd)`` -> the pool's rows ``(..., n,
    bs * Hkv * hd)``: block ``i`` holds tokens ``[i * bs, (i + 1) * bs)``,
    each token's heads side by side.  Inverse of :func:`paged_tokens`."""
    *lead, T, H, hd = a.shape
    # explicit widths: a latent value plane is zero-wide, and a reshape
    # cannot infer a -1 axis of a zero-size array
    return a.reshape(*lead, T // bs, bs * H * hd)


def paged_tokens(rows, bs: int, heads: int):
    """The pool's rows ``(..., n, bs * heads * hd)`` -> token-major ``(...,
    n * bs, heads, hd)``.  Inverse of :func:`paged_rows`."""
    *lead, n, R = rows.shape
    return rows.reshape(*lead, n * bs, heads, R // (bs * heads))


@jax.named_scope("paged_gather")
def paged_gather_rows(view: PagedLayerView) -> KVRows:
    """Gather one layer's paged context in the form decode reads it.

    Ring slots ``[jb * bs, (jb + 1) * bs)`` of slot ``b`` are the whole pool
    block ``blocks[b, jb]``, so each plane is gathered one row per table
    entry straight from the held ``(L, NB, bs * Hkv * hd)`` pool (layer and
    block in one index, no per-layer slice) and read on as ``(B, W, Hkv *
    hd)``, the rows' own order: only the gathered transient is laid out,
    never the pool.  Unallocated entries read (masked) garbage from the
    trash block and carry ``pos = -1``.  (The fused no-materialization read
    lives in ``repro.kernels.paged_decode_attention``.)
    """
    B, nbs = view.blocks.shape
    W = view.pos.shape[1]

    def rows(pool):          # (L, NB, bs * H * hd) -> (B, W, H * hd)
        return pool[view.layer, view.blocks].reshape(
            B, W, pool.shape[-1] * nbs // W)

    return KVRows(k=rows(view.k), v=rows(view.v), pos=view.pos)


def paged_gather_layer(view: PagedLayerView) -> LayerCache:
    """The dense ring view ``(B, Hkv, W, hd)`` of one layer's paged cache:
    the ring cache's slots, bit for bit, wherever the table is allocated.
    The tests and ``scripts/pool_layout_check.py`` hold the pool to the ring
    it factors through it; decode reads :func:`paged_gather_rows`."""
    r = paged_gather_rows(view)
    B, nbs = view.blocks.shape
    bs = view.pos.shape[1] // nbs

    def ring(a):             # (B, W, H * hd) -> (B, H, W, hd)
        return paged_tokens(a.reshape(B, nbs, bs * a.shape[-1]), bs,
                            view.heads).transpose(0, 2, 1, 3)

    return LayerCache(k=ring(r.k), v=ring(r.v), pos=r.pos)


def paged_put_rows(caches: PagedCache, dst, k_rows, v_rows, pos_rows,
                   cols=None) -> PagedCache:
    """Write into pool blocks ``dst`` (n,), in order, in place.

    ``k_rows``/``v_rows`` (L, n, w) and ``pos_rows`` (n, p): entry ``i``
    lands in block ``dst[i]`` at values ``[cols[i] * w, (cols[i] + 1) * w)``
    of every layer's row and positions ``[cols[i] * p, (cols[i] + 1) * p)``
    — one token (``w = Hkv * hd``, ``p = 1``) or, with ``cols`` None, a
    whole block.  One dynamic update per entry keeps the pool in its held
    layout: a scatter of windows narrower than a lane tile makes XLA lay
    the pool out afresh.  Repeated ids keep the last write (idle slots'
    trash writes).
    """
    if cols is None:
        cols = jnp.zeros_like(dst)
    wk, wv, p = k_rows.shape[-1], v_rows.shape[-1], pos_rows.shape[-1]

    def body(i, c):
        k, v, pos = c
        k = jax.lax.dynamic_update_slice(
            k, k_rows[:, i, None].astype(k.dtype), (0, dst[i], cols[i] * wk))
        v = jax.lax.dynamic_update_slice(
            v, v_rows[:, i, None].astype(v.dtype), (0, dst[i], cols[i] * wv))
        pos = jax.lax.dynamic_update_slice(
            pos, pos_rows[i, None].astype(pos.dtype), (dst[i], cols[i] * p))
        return k, v, pos

    k, v, pos = jax.lax.fori_loop(0, dst.shape[0], body,
                                  (caches.k, caches.v, caches.pos))
    return caches._replace(k=k, v=v, pos=pos)


def paged_write_stacked(caches: PagedCache, k_news, v_news,
                        step) -> PagedCache:
    """The tick's one deferred write: every slot's new token into its pool
    block, every layer at once, in place in the held row layout.

    k_news/v_news: (L, B, 1, Hkv, hd); ``step`` scalar or (B,) per-slot
    absolute positions.  Slot ``b``'s token lands in block ``dst`` of its
    table at token ``off = step % bs``: values ``[off * Hkv * hd, (off + 1)
    * Hkv * hd)`` of each layer's row.  A slot whose destination block is
    unallocated (``table`` entry -1: an idle engine slot) writes to the
    trash block 0, which no table references — the paged twin of the ring
    engine's harmless inactive-row writes.
    """
    bs = caches.pos.shape[1]
    B, nbs = caches.table.shape
    W = nbs * bs
    step = jnp.asarray(step)
    if step.ndim == 0:
        step = jnp.broadcast_to(step, (B,))
    step = step.astype(jnp.int32)
    jb = jnp.mod(step, W) // bs                         # (B,) table column
    blk = jnp.take_along_axis(caches.table, jb[:, None], axis=1)[:, 0]
    dst = jnp.where(blk >= 0, blk, 0)
    off = jnp.mod(step, bs)
    return paged_put_rows(caches, dst, paged_rows(k_news, 1)[:, :, 0],
                          paged_rows(v_news, 1)[:, :, 0], step[:, None],
                          cols=off)


def decode_attention(q, cache, step, *, window: Optional[int],
                     q_per_kv: int = 1, k_new=None, v_new=None,
                     scale: Optional[float] = None, per_head: bool = False):
    """Single-token attention against a ring cache (or paged view of one).

    q: (B, 1, Hq, hd) roped; cache.k/v: (B, Hkv, W, hd), or (B, W, Hkv *
    hd) as :class:`KVRows`; step: scalar int32 (absolute position of the
    query token) or (B,) per-example positions — the batched serving engine
    decodes slots at different depths in one call.  A
    :class:`PagedLayerView` cache is gathered first (:func:`paged_gather_rows`).

    Every cache is read through one sequence of operations: as rows with
    each token's heads side by side, ``(B, W, Hkv * hd)`` (the paged pool's
    own order, as gathered), then per head with the ring axis minor, ``(B,
    Hkv, hd, W)``, for the matmuls.  That is the layout the TPU holds a ring
    cache in, so a ring cache is read as held and only a paged cache's
    gathered transient is laid out (with ``hd`` minor, Delphi's 10-wide
    heads would pad to 128 lanes); and one sequence keeps the paged decode
    bit-identical to the ring decode it factors.  ``per_head`` reads a
    ``(B, Hkv, W, hd)`` cache per head as it stands instead: cross-attention's
    memory, which no paged cache mirrors (the TPU holds it ``hd`` minor).

    When ``k_new``/``v_new`` (B, 1, Hkv, hd) are given, the cache is treated
    as *read-only* and the new token is attended via an appended logit — the
    actual cache write is deferred to one post-scan scatter (keeps XLA from
    round-tripping the full cache through scan temporaries).  Ring semantics
    are preserved by masking positions <= step - W.  ``scale`` defaults to
    ``hd ** -0.5``; values may be narrower than keys (the absorbed latent
    read), and the result takes their width.
    """
    if isinstance(cache, PagedLayerView):
        cache = paged_gather_rows(cache)
    B, _, Hq, hd = q.shape
    W = cache.pos.shape[1]
    G = q_per_kv
    Hkv = Hq // G
    if per_head:
        k, v = cache.k.swapaxes(2, 3), cache.v.swapaxes(2, 3)
    else:
        if not isinstance(cache, KVRows):    # (B, Hkv, W, d) -> side by side
            cache = KVRows(*(a.transpose(0, 2, 1, 3).reshape(
                B, W, Hkv * a.shape[-1]) for a in (cache.k, cache.v)),
                cache.pos)
        # (B, W, Hkv * d) -> (B, Hkv, d, W); explicit widths: a latent value
        # plane may be zero-wide
        k, v = (a.transpose(0, 2, 1).reshape(B, Hkv, a.shape[-1] // Hkv, W)
                for a in (cache.k, cache.v))
    if scale is None:
        scale = hd ** -0.5
    step = jnp.asarray(step)
    if step.ndim == 1:
        step = step.reshape(B, 1, 1, 1)   # broadcast against pos (B,1,1,W)
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bhgd,bhdw->bhgw", qg, k).astype(jnp.float32) * scale
    pos = cache.pos[:, None, None, :]
    valid = (pos >= 0) & (pos <= step)
    if k_new is not None:
        valid = valid & (pos > step - W)          # ring eviction of oldest
    if window is not None:
        valid = valid & (pos > step - window)
    s = jnp.where(valid, s, NEG_INF)
    if k_new is not None:
        # merge the new token by online-softmax combination rather than a
        # concat along W: every W-dim op stays a pure reduction, so GSPMD can
        # keep a window-sharded cache sharded (a concat forces an all-gather
        # of the whole score tensor — EXPERIMENTS.md §Perf H4)
        s_new = jnp.einsum("bhgd,bhd->bhg", qg,
                           k_new[:, 0]).astype(jnp.float32) * scale
        m_c = jnp.max(s, axis=-1)                              # (b,h,g)
        m = jnp.maximum(m_c, s_new)
        p_c = jnp.exp(s - m[..., None])
        l = jnp.sum(p_c, axis=-1) + jnp.exp(s_new - m)
        o = jnp.einsum("bhgw,bhdw->bhgd", p_c.astype(v.dtype), v)
        o = o + (jnp.exp(s_new - m)[..., None].astype(v_new.dtype)
                 * v_new[:, 0][:, :, None, :])
        o = o / l[..., None].astype(o.dtype)
    else:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgw,bhdw->bhgd", p.astype(v.dtype), v)
    return o.reshape(B, 1, Hq, v.shape[2])


# ---------------------------------------------------------------------------
# Cache construction / update
# ---------------------------------------------------------------------------
def cache_rows(cfg: ModelConfig):
    """(heads, key width, value width) of what the cache holds per token:
    K and V per KV head, or for latent attention one ``[c | k_pe]`` row
    in the key plane and an empty value plane (the row holds both)."""
    if cfg.is_mla:
        return 1, cfg.kv_lora_rank + cfg.qk_rope_head_dim, 0
    return cfg.n_kv_heads, cfg.head_dim, cfg.head_dim


def empty_cache(cfg: ModelConfig, batch: int, width: int, dtype) -> LayerCache:
    h, dk, dv = cache_rows(cfg)
    return LayerCache(
        k=jnp.zeros((batch, h, width, dk), dtype),
        v=jnp.zeros((batch, h, width, dv), dtype),
        pos=jnp.full((batch, width), -1, jnp.int32),
    )


def empty_paged_cache(cfg: ModelConfig, n_layers: int, num_blocks: int,
                      slots: int, width: int, block_size: int,
                      dtype) -> PagedCache:
    """Zeroed block pool + all-unallocated tables for ``slots`` decode rows.

    ``width`` is the logical ring width each slot's table spans; it must be
    a block multiple so ``p % W`` and ``p % block_size`` agree blockwise.
    """
    if width % block_size != 0:
        raise ValueError(f"paged cache width {width} must be a multiple of "
                         f"block_size {block_size}")
    h, dk, dv = cache_rows(cfg)
    return PagedCache(
        k=jnp.zeros((n_layers, num_blocks, block_size * h * dk), dtype),
        v=jnp.zeros((n_layers, num_blocks, block_size * h * dv), dtype),
        pos=jnp.full((num_blocks, block_size), -1, jnp.int32),
        table=jnp.full((slots, width // block_size), -1, jnp.int32),
    )


def cache_from_prefill(k, v, positions, width: int) -> LayerCache:
    """Pack the (roped) prefill K/V of length S into a ring cache of width W.

    Slot j holds the most recent token with position % W == j.
    k, v: (B, S, Hkv, hd); positions: (B, S) absolute (assumed 0..S-1 order).
    """
    B, S, Hkv, hd = k.shape
    W = width
    j = jnp.arange(W)
    if S <= W:
        tok = jnp.minimum(j, S - 1)
        pos_slot = jnp.where(j < S, j, -1)
    else:
        tok = S - W + ((j - (S - W)) % W)
        pos_slot = tok
    kc = jnp.take(k, tok, axis=1).transpose(0, 2, 1, 3)       # (B, Hkv, W, hd)
    vc = jnp.take(v, tok, axis=1).transpose(0, 2, 1, 3)
    base = positions[:, :1] if S <= W else positions[:, :1]
    pos = jnp.where(pos_slot[None, :] >= 0,
                    pos_slot[None, :] + base, -1).astype(jnp.int32)
    return LayerCache(k=kc, v=vc, pos=pos)


def cache_write(cache: LayerCache, k_new, v_new, step) -> LayerCache:
    """Write one token (B, 1, Hkv, hd) at absolute position ``step``.

    ``step`` may be a scalar (all examples at the same depth) or (B,)
    per-example positions (the serving engine's continuous-batching slots)."""
    step = jnp.asarray(step)
    k_t = k_new.transpose(0, 2, 1, 3)   # (B, Hkv, 1, hd)
    v_t = v_new.transpose(0, 2, 1, 3)
    if step.ndim == 1:
        def one(k, v, p, kt, vt, s):
            # k: (Hkv, W, hd); p: (W,); kt/vt: (Hkv, 1, hd)
            slot = jnp.mod(s, p.shape[0])
            k = jax.lax.dynamic_update_slice_in_dim(
                k, kt.astype(k.dtype), slot, axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(
                v, vt.astype(v.dtype), slot, axis=1)
            p = jax.lax.dynamic_update_slice(
                p, s.astype(jnp.int32).reshape(1), (slot,))
            return k, v, p
        k, v, pos = jax.vmap(one)(cache.k, cache.v, cache.pos, k_t, v_t, step)
        return LayerCache(k=k, v=v, pos=pos)
    W = cache.k.shape[2]
    slot = jnp.mod(step, W)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_t.astype(cache.k.dtype), slot, axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_t.astype(cache.v.dtype), slot, axis=2)
    pos = jax.lax.dynamic_update_slice_in_dim(
        cache.pos, jnp.broadcast_to(jnp.int32(step), (cache.pos.shape[0], 1)), slot, axis=1)
    return LayerCache(k=k, v=v, pos=pos)


@jax.named_scope("cache_write")
def cache_write_stacked(caches, k_news, v_news, step):
    """One scatter for the whole layer stack (the deferred decode write).

    caches: (L, B, Hkv, W, hd) leaves; k_news/v_news: (L, B, 1, Hkv, hd).
    ``step`` scalar, or (B,) per-example positions (per-slot engine decode —
    each example's write lands in its own ring slot).  A :class:`PagedCache`
    dispatches to :func:`paged_write_stacked` (same semantics, one
    indirection through the block table).
    """
    if isinstance(caches, PagedCache):
        return paged_write_stacked(caches, k_news, v_news, step)
    step = jnp.asarray(step)
    k_t = k_news.transpose(0, 1, 3, 2, 4)    # (L, B, Hkv, 1, hd)
    v_t = v_news.transpose(0, 1, 3, 2, 4)
    if step.ndim == 1:
        def one(k, v, p, kt, vt, s):
            # k: (L, Hkv, W, hd); p: (L, W); kt/vt: (L, Hkv, 1, hd)
            slot = jnp.mod(s, p.shape[1])
            k = jax.lax.dynamic_update_slice_in_dim(
                k, kt.astype(k.dtype), slot, axis=2)
            v = jax.lax.dynamic_update_slice_in_dim(
                v, vt.astype(v.dtype), slot, axis=2)
            p = jax.lax.dynamic_update_slice_in_dim(
                p, jnp.broadcast_to(s.astype(jnp.int32), (p.shape[0], 1)),
                slot, axis=1)
            return k, v, p
        k, v, pos = jax.vmap(one, in_axes=(1, 1, 1, 1, 1, 0),
                             out_axes=(1, 1, 1))(
            caches.k, caches.v, caches.pos, k_t, v_t, step)
        return LayerCache(k=k, v=v, pos=pos)
    W = caches.k.shape[3]
    slot = jnp.mod(step, W)
    k = jax.lax.dynamic_update_slice_in_dim(caches.k, k_t.astype(caches.k.dtype),
                                            slot, axis=3)
    v = jax.lax.dynamic_update_slice_in_dim(caches.v, v_t.astype(caches.v.dtype),
                                            slot, axis=3)
    pos = jax.lax.dynamic_update_slice_in_dim(
        caches.pos,
        jnp.broadcast_to(jnp.int32(step), caches.pos.shape[:2] + (1,)),
        slot, axis=2)
    return LayerCache(k=k, v=v, pos=pos)


# ---------------------------------------------------------------------------
# Full attention layer (self or cross), all modes
# ---------------------------------------------------------------------------
@jax.named_scope("attention")
def attention(params, x, positions, cfg: ModelConfig, *, mode: str,
              cache: Optional[LayerCache] = None, step=None,
              memory=None, memory_pos=None, cross: bool = False,
              causal: bool = True, window: Optional[int] = None,
              use_rope: bool = True, cache_width: Optional[int] = None,
              defer_write: bool = False, ctx_k=None, ctx_v=None,
              ctx_pos=None):
    """Run one attention layer.

    mode: "dense"   — full-sequence self/cross attention (train / encoder)
          "prefill" — like dense, but also returns a ring cache
          "decode"  — one-token step against ``cache`` at position ``step``
          "suffix"  — chunked-prefill step: the tokens are a prompt *suffix*
                      attending over pre-existing (roped) context K/V
                      ``ctx_k``/``ctx_v`` (B, C, Hkv, hd) at absolute
                      positions ``ctx_pos`` (B, C) plus themselves; returns
                      the raw suffix (k, v) for the caller's cache write
    For cross-attention pass ``memory`` (B, M, d) in dense/prefill modes, or
    ``cross=True`` in decode mode (the cache then holds the projected memory
    K/V, written at prefill).
    """
    dt = x.dtype
    G = cfg.q_per_kv
    win = window if window is not None else cfg.sliding_window

    if mode == "suffix":
        q, k, v = _project_qkv(params, x, x, cfg)
        if use_rope:
            # keys are stored post-RoPE: rotating at absolute positions
            # keeps suffix K byte-compatible with the cached context K
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        from repro.kernels.ops import suffix_prefill_attention
        o = suffix_prefill_attention(q, k, v, ctx_k, ctx_v, positions,
                                     ctx_pos, causal=causal, window=win,
                                     q_per_kv=G)
        out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
        return out, (k, v)

    if mode == "decode":
        if cross:
            # cross-attention at decode: cache holds projected memory K/V
            q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
            if "bq" in params:
                q = q + params["bq"].astype(dt)
            o = decode_attention(q, cache, jnp.int32(2**30), window=None,
                                 q_per_kv=G, per_head=True)
            out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
            return out, cache
        q, k, v = _project_qkv(params, x, x, cfg)
        if use_rope:
            st_arr = jnp.asarray(step)
            # (1, 1) shared position, or (B, 1) per-example engine positions
            pos1 = (st_arr.reshape(-1, 1) if st_arr.ndim == 1
                    else jnp.reshape(st_arr, (1, 1)))
            q = apply_rope(q, pos1, cfg.rope_theta)
            k = apply_rope(k, pos1, cfg.rope_theta)
        if defer_write:
            o = decode_attention(q, cache, step, window=win, q_per_kv=G,
                                 k_new=k, v_new=v)
            out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
            return out, (k, v)
        cache = cache_write(cache, k, v, step)
        o = decode_attention(q, cache, step, window=win, q_per_kv=G)
        out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
        return out, cache

    if memory is not None:  # dense/prefill cross-attention
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
        if "bq" in params:
            q = q + params["bq"].astype(dt)
        k = jnp.einsum("bsd,dhk->bshk", memory, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", memory, params["wv"].astype(dt))
        if "bk" in params:
            k = k + params["bk"].astype(dt)
            v = v + params["bv"].astype(dt)
        mpos = (memory_pos if memory_pos is not None
                else jnp.arange(memory.shape[1], dtype=jnp.int32))
        qb = kb = 512
        if cfg.attn_direct:
            qb = -(-max(-(-x.shape[1] // 4), 512) // 128) * 128
            kb = -(-max(-(-memory.shape[1] // 4), 512) // 128) * 128
        o = chunked_attention(q, k, v, positions, mpos, causal=False,
                              window=None, q_per_kv=G, q_block=qb,
                              kv_block=kb, unroll=cfg.attn_direct)
        out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
        if mode == "prefill":
            M = memory.shape[1]
            mpos2 = jnp.broadcast_to(mpos[None], (x.shape[0], M)) if mpos.ndim == 1 else mpos
            new_cache = cache_from_prefill(k, v, mpos2, M)
            return out, new_cache
        return out, None

    q, k, v = _project_qkv(params, x, x, cfg)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    S = x.shape[1]
    if cfg.seq_shard_attn:
        # context parallelism: shard queries over the model axis (KV layout
        # is left to GSPMD — explicitly replicating it forced per-layer
        # all-gathers, see EXPERIMENTS.md §Perf iteration 2)
        from jax.sharding import PartitionSpec as P
        q = jax.lax.with_sharding_constraint(q, P(None, "model", None, None))
    # cost-accounting mode uses big straight-line blocks (nq*nk <= 16)
    qb = max(-(-S // 4), 512) if cfg.attn_direct else 512
    qb = -(-qb // 128) * 128
    o = chunked_attention(q, k, v, positions, positions, causal=causal,
                          window=win, q_per_kv=G, q_block=qb, kv_block=qb,
                          unroll=cfg.attn_direct)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
    if mode == "prefill":
        W = cache_width or (win if win is not None else x.shape[1])
        pos2 = (jnp.broadcast_to(positions[None], x.shape[:2])
                if positions.ndim == 1 else positions)
        new_cache = cache_from_prefill(k, v, pos2, W)
        return out, new_cache
    return out, None


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2/V3)
# ---------------------------------------------------------------------------
@jax.named_scope("latent")
def _mla_rows(params, x, positions, cfg: ModelConfig):
    """x (B, S, d) -> the cached rows (B, S, r + dr): the normed latent
    ``c = RMSNorm(x wkv_a[:, :r])`` and ``k_pe = RoPE(x wkv_a[:, r:])``,
    one rotated key part shared by every head."""
    r = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(x.dtype))
    c = apply_norm(params["kv_norm"], ckv[..., :r], cfg)
    k_pe = apply_rope(ckv[..., None, r:], positions, cfg.rope_theta)
    return jnp.concatenate([c, k_pe[..., 0, :]], axis=-1)


@jax.named_scope("latent")
def _mla_expand(params, rows, cfg: ModelConfig):
    """Rows (B, T, r + dr) -> each head's keys [c wkv_b[:, :dn] | k_pe]
    (B, T, H, dn + dr) and values c wkv_b[:, dn:] (B, T, H, dv)."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = jnp.einsum("btr,rhk->bthk", rows[..., :r],
                    params["wkv_b"].astype(rows.dtype))
    k_pe = jnp.broadcast_to(rows[:, :, None, r:],
                            kv.shape[:3] + (cfg.qk_rope_head_dim,))
    return jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]


@jax.named_scope("mla")
def mla_attention(params, x, positions, cfg: ModelConfig, *, mode: str,
                  cache=None, step=None, causal: bool = True,
                  window: Optional[int] = None,
                  cache_width: Optional[int] = None,
                  defer_write: bool = False, ctx_k=None, ctx_pos=None):
    """One latent-attention layer, in the modes of :func:`attention`.

    The cache holds one row ``[c | k_pe]`` per token (:func:`cache_rows`:
    the key plane of a one-head cache; its value plane is empty).  Prefill,
    dense and suffix modes expand rows through ``wkv_b`` into per-head keys
    and values (suffix: the cached context rows ``ctx_k`` (B, C, 1, r + dr)
    too).  Decode absorbs ``wkv_b``: ``q_nope wkv_b[:, :dn]^T`` joins q_pe
    as a query over the rows, so ``n_heads`` query heads attend over one
    head of width r + dr for scores and r for values, and ``wkv_b[:, dn:]``
    takes the attended latent to each head's value.  Scores are scaled by
    ``(dn + dr) ** -0.5`` in every mode.  Returns (out, what
    :func:`attention` returns there), the new rows standing for (k, v).
    """
    dt = x.dtype
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    win = window if window is not None else cfg.sliding_window
    if mode == "decode":
        st = jnp.asarray(step)
        # (1, 1) shared position, or (B, 1) per-example engine positions
        positions = (st.reshape(-1, 1) if st.ndim == 1
                     else jnp.reshape(st, (1, 1)))
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    rows = _mla_rows(params, x, positions, cfg)
    new = (rows[:, :, None], rows[:, :, None, :0])     # the cache's planes
    wo = params["wo"].astype(dt)

    if mode == "decode":
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :dn],
                               params["wkv_b"][:, :, :dn].astype(dt))
            q_lat = jnp.concatenate([q_lat, q_pe], axis=-1)
        if isinstance(cache, PagedLayerView):
            cache = paged_gather_rows(cache)
        kw = {}
        if defer_write:
            kw = dict(k_new=new[0], v_new=new[0])
        else:
            cache = cache_write(cache, *new, step)
        # the rows serve as values too, whole: the attended latent is their
        # first r columns (one layout of the rows for both matmuls)
        o = decode_attention(
            q_lat, cache._replace(v=cache.k), step, window=win,
            q_per_kv=cfg.n_heads, scale=(dn + cfg.qk_rope_head_dim) ** -0.5,
            **kw)[..., :r]
        with jax.named_scope("absorb"):
            o = jnp.einsum("bshr,rhv->bshv", o,
                           params["wkv_b"][:, :, dn:].astype(dt))
        out = jnp.einsum("bshv,hvd->bsd", o, wo)
        return out, (new if defer_write else cache)

    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k, v = _mla_expand(params, rows, cfg)
    if mode == "suffix":
        from repro.kernels.ops import suffix_prefill_attention
        ck, cv = _mla_expand(params, ctx_k[:, :, 0], cfg)
        o = suffix_prefill_attention(q, k, v, ck, cv, positions, ctx_pos,
                                     causal=causal, window=win)
        return jnp.einsum("bshv,hvd->bsd", o, wo), new
    qb = max(-(-x.shape[1] // 4), 512) if cfg.attn_direct else 512
    qb = -(-qb // 128) * 128
    o = chunked_attention(q, k, v, positions, positions, causal=causal,
                          window=win, q_block=qb, kv_block=qb,
                          unroll=cfg.attn_direct)
    out = jnp.einsum("bshv,hvd->bsd", o, wo)
    if mode == "prefill":
        W = cache_width or (win if win is not None else x.shape[1])
        pos2 = (jnp.broadcast_to(positions[None], x.shape[:2])
                if positions.ndim == 1 else positions)
        return out, cache_from_prefill(*new, pos2, W)
    return out, None
