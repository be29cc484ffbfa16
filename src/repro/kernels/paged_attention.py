"""Pallas TPU kernel for paged single-token decode attention.

The serving engine's paged KV cache stores K/V in a shared pool of
fixed-size blocks with per-slot block tables (``repro.models.attention.
PagedCache``), one lane-dense row of ``block_size * Hkv * hd`` values per
(layer, block), token-major.  The jnp read path reconstructs a dense ring
view per layer (a gather that materializes ``(B, Hkv, W, hd)``
transiently); this kernel is the fused twin: the block table is
**scalar-prefetched**, so each grid step DMAs exactly one pool block, all
its heads, straight from its table-indexed HBM location into VMEM and folds
it into an online softmax — gather and attention in one pass, no dense
intermediate.  At pool scale the resident win is the paged cache itself;
this kernel removes the read path's transient so decode bandwidth is
``tokens held``, not ``slots x max_context``.

Grid: ``(B, blocks_per_slot)``; the inner dimension walks one slot's table
sequentially, carrying fp32 ``(acc, m, l)`` in VMEM scratch (same
online-softmax scheme as ``flash_attention``).  A block arrives as
``(block_size, Hkv * hd)``: every head's keys side by side.  So the query
arrives block-diagonal, ``(Hkv * G, Hkv * hd)`` with head ``h``'s ``G``
query rows nonzero only in head ``h``'s columns: one matmul scores every
head against its own keys (the other columns add exact zeros), and the
caller keeps each head's own columns of the result.  Unallocated table
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
    i = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    blk = tbl_ref[b, i]
    step = stp_ref[b]

    @pl.when(blk >= 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (Hkv*G, Hkv*hd)
        k = k_ref[0].astype(jnp.float32)              # (bs, Hkv*hd)
        v = v_ref[0].astype(jnp.float32)
        p = pos_ref[0]                                # (1, bs) int32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = (p >= 0) & (p <= step) & (p > step - W)
        if window is not None:
            valid = jnp.logical_and(valid, p > step - window)
        s = jnp.where(valid, s, NEG_INF)              # (Hkv*G, bs)
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
        o_ref[0] = (acc[...] /
                    jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, table, pos, step, *,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, hd); k/v_pool: (NB, bs * Hkv * hd) one layer's held
    token-major rows; table: (B, nbs) int32 pool ids (-1 = unallocated);
    pos: (NB, bs) int32 absolute positions (-1 = empty); step: (B,) int32
    query positions.  Returns (B, Hkv, G, hd).  The blocks are read as
    ``(bs, Hkv * hd)``; on the TPU that view of the rows is a relayout of
    the plane, paid per call (no served path calls this kernel)."""
    B, Hkv, G, hd = q.shape
    NB, bs = pos.shape
    nbs = table.shape[1]
    HG, C = Hkv * G, Hkv * hd
    kern = functools.partial(_paged_kernel, W=nbs * bs, scale=hd ** -0.5,
                             window=window)
    # block-diagonal queries: head h's rows carry q in head h's columns
    eye = jnp.eye(Hkv, dtype=q.dtype)
    qd = jnp.einsum("bhgd,hk->bhgkd", q, eye).reshape(B, HG, C)

    def _blk(b, i, tbl, stp):
        return (jnp.maximum(tbl[b, i], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nbs),
        in_specs=[
            pl.BlockSpec((1, HG, C), lambda b, i, tbl, stp: (b, 0, 0)),
            # a block's row as (bs, Hkv * hd): the last two dims equal the
            # array's, which the TPU tiling rule accepts
            pl.BlockSpec((1, bs, C), _blk),
            pl.BlockSpec((1, bs, C), _blk),
            pl.BlockSpec((1, 1, bs), _blk),
        ],
        out_specs=pl.BlockSpec((1, HG, C), lambda b, i, tbl, stp: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((HG, C), jnp.float32),
            pltpu.VMEM((HG, 1), jnp.float32),
            pltpu.VMEM((HG, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HG, C), q.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), step.astype(jnp.int32), qd,
      k_pool.reshape(NB, bs, C), v_pool.reshape(NB, bs, C),
      pos.astype(jnp.int32).reshape(NB, 1, bs))
    # each head's own columns: (B, Hkv, G, Hkv, hd) -> (B, Hkv, G, hd)
    out = out.reshape(B, Hkv, G, Hkv, hd)
    return jnp.diagonal(out, axis1=1, axis2=3).transpose(0, 3, 1, 2)
