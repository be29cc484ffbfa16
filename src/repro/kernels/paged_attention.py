"""Pallas TPU kernel for paged single-token decode attention.

The serving engine's paged KV cache stores K/V in a shared pool of
fixed-size blocks with per-slot block tables (``repro.models.attention.
PagedCache``).  The jnp read path reconstructs a dense ring view per layer
(a gather that materializes ``(B, Hkv, W, hd)`` transiently); this kernel is
the fused twin: the block table is **scalar-prefetched**, so each grid step
DMAs exactly one pool block straight from its table-indexed HBM location
into VMEM and folds it into an online softmax — gather and attention in one
pass, no dense intermediate.  At pool scale the resident win is the paged
cache itself; this kernel removes the read path's transient so decode
bandwidth is ``tokens held``, not ``slots x max_context``.

Grid: ``(B, Hkv, blocks_per_slot)``; the innermost dimension walks one
slot's table sequentially, carrying fp32 ``(acc, m, l)`` in VMEM scratch
(same online-softmax scheme as ``flash_attention``).  Unallocated table
entries (id -1) are clamped to block 0 in the index map and skipped with
``pl.when`` — no MXU work, no contribution.

Masking matches ``decode_attention`` on the gathered ring view exactly:
``pos >= 0 & pos <= step & pos > step - W`` (+ sliding window), with
``W = blocks_per_slot * block_size`` the logical ring width.  Slots whose
blocks are all invalid return zeros (the engine never decodes an empty
slot).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tbl_ref, stp_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  acc, m_s, l_s, *, W: int, scale: float,
                  window: Optional[int]):
    b = pl.program_id(0)
    i = pl.program_id(2)
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    blk = tbl_ref[b, i]
    step = stp_ref[b]

    @pl.when(blk >= 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        p = pos_ref[0]                                # (1, bs) int32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = (p >= 0) & (p <= step) & (p > step - W)
        if window is not None:
            valid = jnp.logical_and(valid, p > step - window)
        s = jnp.where(valid, s, NEG_INF)              # (G, bs) via broadcast
        m_new = jnp.maximum(m_s[...], jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_s[...] - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(i == ni - 1)
    def _finish():
        o_ref[0, 0] = (acc[...] /
                       jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, table, pos, step, *,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, hd); k/v_pool: (NB, Hkv, bs, hd); table: (B, nbs)
    int32 pool ids (-1 = unallocated); pos: (NB, bs) int32 absolute
    positions (-1 = empty); step: (B,) int32 query positions.
    Returns (B, Hkv, G, hd)."""
    B, Hkv, G, hd = q.shape
    bs = k_pool.shape[2]
    nbs = table.shape[1]
    kern = functools.partial(_paged_kernel, W=nbs * bs, scale=hd ** -0.5,
                             window=window)

    def _blk(b, h, i, tbl, stp):
        return (jnp.maximum(tbl[b, i], 0), h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nbs),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, i, tbl, stp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), _blk),
            pl.BlockSpec((1, 1, bs, hd), _blk),
            # (1, 1, bs) block over pos as (NB, 1, bs): its last two dims
            # equal the array's, which the TPU tiling rule accepts
            pl.BlockSpec((1, 1, bs),
                         lambda b, h, i, tbl, stp: (jnp.maximum(tbl[b, i], 0),
                                                    0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, i, tbl, stp: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), step.astype(jnp.int32), q, k_pool, v_pool,
      pos.astype(jnp.int32).reshape(pos.shape[0], 1, bs))
