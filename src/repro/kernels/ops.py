"""Jit'd public wrappers around the Pallas kernels (padding, dtype policy).

Every wrapper takes ``interpret: Optional[bool] = None``.  ``None`` decides
by the platform the call is lowered for (``lax.platform_dependent``): the
Pallas interpreter on the CPU, the Mosaic kernel everywhere else.  The
choice follows the program's target device, not ``jax.default_backend()``,
so a program compiled for a TPU from a CPU host (a compile rehearsal) gets
the real kernel.  ``True``/``False`` force one side.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_decode_attention as _paged
from repro.kernels.ssd_scan import ssd_intra as _ssd_intra
from repro.kernels.tte_sample import tte_sample as _tte


def _call(kernel, *args, interpret: Optional[bool], **kw):
    """Run ``kernel(*args, interpret=..., **kw)``: interpret mode only where
    the lowering platform is the CPU, unless the caller forces a mode."""
    if interpret is not None:
        return kernel(*args, interpret=interpret, **kw)
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(kernel, interpret=True, **kw),
        default=functools.partial(kernel, interpret=False, **kw))


def _pad_axis(x, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None
                    ) -> jax.Array:
    """q: (B, Hq, S, hd); k, v: (B, Hkv, T, hd) -> (B, Hq, S, hd).

    Pads S/T to block multiples; padded KV rows are masked out by the causal
    predicate (they sit at positions > any query) and padded Q rows are
    sliced off.
    """
    S, T = q.shape[2], k.shape[2]
    qp = _pad_axis(q, 2, bq)
    kp = _pad_axis(k, 2, bk)
    vp = _pad_axis(v, 2, bk)
    if not causal and kp.shape[2] != T:
        raise ValueError("non-causal flash requires T % bk == 0 "
                         "(padding would attend to garbage)")
    out = _call(_flash, qp, kp, vp, causal=causal, window=window, bq=bq,
                bk=bk, interpret=interpret)
    return out[:, :, :S]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra(xdt, Bm, Cm, cum, *, interpret: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Intra-chunk SSD: see kernels/ssd_scan.py.  Shapes (BH, C, Q, ·)."""
    return _call(_ssd_intra, xdt, Bm, Cm, cum, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, table, pos, step, *,
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged decode: block-table gather + online softmax in one pass.

    q: (B, Hq, hd) one roped query token per slot; k/v_pool:
    (NB, bs * Hkv * hd) one layer of the held pool (token-major rows,
    ``PagedCache``); table: (B, nbs) pool ids (-1 = unallocated); pos:
    (NB, bs) absolute positions (-1 = empty); step: (B,) per-slot query
    positions.  GQA by the same (Hkv, G) grouping as ``decode_attention``.
    Returns (B, Hq, hd).
    """
    B, Hq, hd = q.shape
    Hkv = k_pool.shape[1] // (pos.shape[1] * hd)
    q4 = q.reshape(B, Hkv, Hq // Hkv, hd)
    out = _call(_paged, q4, k_pool, v_pool, table, pos, step, window=window,
                interpret=interpret)
    return out.reshape(B, Hq, hd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_per_kv"))
def suffix_prefill_attention(q, k, v, ctx_k, ctx_v, q_pos, ctx_pos, *,
                             causal: bool = True,
                             window: Optional[int] = None,
                             q_per_kv: int = 1) -> jax.Array:
    """Suffix prefill: chunk queries attend over cached context K/V plus the
    chunk itself, masked by absolute position.

    q/k/v: (B, Sc, Hq|Hkv, hd) the suffix chunk's projected (roped) heads;
    ctx_k/ctx_v: (B, C, Hkv, hd) pre-existing cache context (earlier chunks
    or prefix-cache hits); q_pos: (B, Sc) and ctx_pos: (B, C) absolute
    positions, -1 = invalid (right padding / trash-block slots).  Causality
    and sliding windows are decided by position difference, so a chunk
    starting mid-prompt composes exactly with the context before it.
    Dispatches through the online-softmax chunked path (the jnp flash twin
    of ``kernels.flash_attention`` — a concat along KV would break its
    index-based causal predicate, positions are the ground truth here).
    Returns (B, Sc, Hq, hd).
    """
    from repro.models.attention import chunked_attention
    kc = jnp.concatenate([ctx_k, k], axis=1)
    vc = jnp.concatenate([ctx_v, v], axis=1)
    kp = jnp.concatenate([ctx_pos, q_pos], axis=1)
    return chunked_attention(q, kc, vc, q_pos, kp, causal=causal,
                             window=window, q_per_kv=q_per_kv)


@functools.partial(jax.jit, static_argnames=("bv", "interpret"))
def tte_sample(logits, u, *, bv: int = 2048,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Fused competing-exponential sampler: (B, V) -> (event, t_min).

    Pads the vocab axis with neutral entries (rate ~ e^-100: never wins).
    """
    V = logits.shape[1]
    b = min(bv, max(256, 1 << (V - 1).bit_length()))
    lp = _pad_axis(logits.astype(jnp.float32), 1, b, value=-100.0)
    up = _pad_axis(u.astype(jnp.float32), 1, b, value=0.5)
    return _call(_tte, lp, up, bv=b, interpret=interpret)
