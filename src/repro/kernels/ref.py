"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are deliberately naive (quadratic attention, sequential SSD recurrence,
full-materialization sampling) — small-shape exact references the kernel
sweeps assert against.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None) -> jax.Array:
    """q: (B, Hq, S, hd); k, v: (B, Hkv, T, hd).  GQA by head repetition."""
    B, Hq, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = Hq // Hkv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * hd ** -0.5
    if causal:
        rel = jnp.arange(S)[:, None] - jnp.arange(T)[None, :]
        valid = rel >= 0
        if window is not None:
            valid &= rel < window
        s = jnp.where(valid[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def ssd_ref(x, dt, A, Bm, Cm) -> Tuple[jax.Array, jax.Array]:
    """Sequential SSD recurrence (exact).

    x: (B, S, H, P); dt: (B, S, H); A: (H,) negative; Bm, Cm: (B, S, N).
    Returns (y (B, S, H, P), final state (B, H, N, P)), all fp32 math.
    """
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    x, dt, Bm, Cm = (t.astype(f32) for t in (x, dt, Bm, Cm))
    A = A.astype(f32)

    def step(h, inp):
        xt, dtt, bt, ct = inp           # (B,H,P), (B,H), (B,N), (B,N)
        a = jnp.exp(dtt * A[None])      # (B,H)
        h = a[..., None, None] * h + jnp.einsum("bn,bh,bhp->bhnp", bt, dtt, xt)
        y = jnp.einsum("bn,bhnp->bhp", ct, h)
        return h, y

    h0 = jnp.zeros((B_, H, N, P), f32)
    hT, ys = jax.lax.scan(step, h0,
                          (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
                           Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2, 3), hT


def ssd_intra_ref(xdt, Bm, Cm, cum) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the intra-chunk kernel (one (head, chunk) tile).

    xdt: (Q, P) dt-scaled inputs; Bm, Cm: (Q, N); cum: (Q,) cumulative dt*A.
    Returns (y_diag (Q, P), state (N, P)).
    """
    f32 = jnp.float32
    xdt, Bm, Cm, cum = (t.astype(f32) for t in (xdt, Bm, Cm, cum))
    Q = xdt.shape[0]
    seg = cum[:, None] - cum[None, :]
    L = jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), jnp.exp(seg), 0.0)
    scores = Cm @ Bm.T                       # (Q, Q)
    y = (scores * L) @ xdt                   # (Q, P)
    decay_to_end = jnp.exp(cum[-1] - cum)    # (Q,)
    state = Bm.T @ (decay_to_end[:, None] * xdt)   # (N, P)
    return y, state


def paged_decode_attention_ref(q, k_pool, v_pool, table, pos, step,
                               window: Optional[int] = None) -> jax.Array:
    """Paged single-token decode oracle: dense gather + masked softmax.

    q: (B, Hkv, G, hd); k/v_pool: (NB, bs * Hkv * hd) token-major block
    rows; table: (B, nbs) int32 pool ids (-1 = unallocated); pos: (NB, bs)
    int32 absolute positions (-1 = empty); step: (B,) query positions.
    Each slot attends its valid ring window ``(step - W, step]`` where
    ``W = nbs * bs``; requires at least one valid position per slot.
    Returns (B, Hkv, G, hd) fp32.
    """
    B, Hkv, G, hd = q.shape
    NB, bs = pos.shape
    nbs = table.shape[1]
    W = nbs * bs
    j = jnp.arange(W)
    blk = table[:, j // bs]                            # (B, W)
    off = jnp.broadcast_to(j % bs, (B, W))
    safe = jnp.maximum(blk, 0)
    k = k_pool.reshape(NB, bs, Hkv, hd)[safe, off].astype(jnp.float32)
    v = v_pool.reshape(NB, bs, Hkv, hd)[safe, off].astype(jnp.float32)
    p = jnp.where(blk >= 0, pos[safe, off], -1)
    s = jnp.einsum("bhgd,bwhd->bhgw", q.astype(jnp.float32), k) * hd ** -0.5
    stp = step.reshape(B, 1, 1, 1)
    pv = p[:, None, None, :]
    valid = (pv >= 0) & (pv <= stp) & (pv > stp - W)
    if window is not None:
        valid &= pv > stp - window
    s = jnp.where(valid, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgw,bwhd->bhgd", w, v)


def suffix_prefill_attention_ref(q, k, v, ctx_k, ctx_v, q_pos, ctx_pos,
                                 causal: bool = True,
                                 window: Optional[int] = None,
                                 q_per_kv: int = 1) -> jax.Array:
    """Suffix-prefill oracle: dense masked softmax over context + chunk.

    q/k/v: (B, Sc, Hq|Hkv, hd) suffix chunk heads; ctx_k/ctx_v:
    (B, C, Hkv, hd) cached context; q_pos (B, Sc) / ctx_pos (B, C) absolute
    positions, -1 = invalid.  GQA by head repetition, fp32 softmax.
    Returns (B, Sc, Hq, hd) fp32.
    """
    B, Sq, Hq, hd = q.shape
    G = q_per_kv
    kc = jnp.concatenate([ctx_k, k], axis=1).astype(jnp.float32)
    vc = jnp.concatenate([ctx_v, v], axis=1).astype(jnp.float32)
    kp = jnp.concatenate([ctx_pos, q_pos], axis=1)
    kc = jnp.repeat(kc, G, axis=2)                     # (B, T, Hq, hd)
    vc = jnp.repeat(vc, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kc) * hd ** -0.5
    valid = kp[:, None, None, :] >= 0
    if causal:
        rel = q_pos[:, None, :, None] - kp[:, None, None, :]
        valid = valid & (rel >= 0)
        if window is not None:
            valid = valid & (rel < window)
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vc)


def tte_sample_ref(logits, u) -> Tuple[jax.Array, jax.Array]:
    """Competing-exponential sampler oracle.

    logits, u: (B, V) fp32.  Returns (event (B,) int32, t_min (B,) f32).
    t_i = -exp(-logit_i) * ln(u_i).
    """
    u = jnp.clip(u, 1e-12, 1.0 - 1e-12)
    t = -jnp.exp(-logits.astype(jnp.float32)) * jnp.log(u)
    idx = jnp.argmin(t, axis=-1).astype(jnp.int32)
    tmin = jnp.take_along_axis(t, idx[..., None], axis=-1)[..., 0]
    return idx, tmin
