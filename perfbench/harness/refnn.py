"""Plain building blocks of the reference forward passes.

Straightforward ``jax.numpy``: every matrix product is float32 at
``Precision.HIGHEST`` (``mode="f32"``), or, for the control, both operands
rounded to float8 e4m3 first with one scale per row of activations and per
output column of weights (``mode="fp8"``, the W8A8 step below the
configurations' bfloat16).  Norms, softmax and activations stay float32 in
both modes.  Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(x, w, mode: str):
    """x (..., k) @ w (k, n)."""
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def layernorm(x, scale, bias, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rmsnorm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta: float):
    """Rotary embedding, halves layout: x (S, H, hd), positions (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, mode: str, window=None):
    """Causal attention of one sequence: q (S, H, hd), k/v (S, Hkv, hd);
    query heads grouped over KV heads; ``window`` keeps keys with
    ``q_pos - k_pos < window``."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    if mode == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(hd))
    rel = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    ok = rel >= 0
    if window is not None:
        ok = ok & (rel < window)
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    if mode == "fp8":
        p = _q8(p, -1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def project(x, w, mode: str):
    """x (S, d) @ w (d, H, hd) -> (S, H, hd)."""
    d, H, hd = w.shape
    return mm(x, w.reshape(d, H * hd), mode).reshape(-1, H, hd)


def unproject(o, w, mode: str):
    """o (S, H, hd) @ w (H, hd, d) -> (S, d)."""
    H, hd, d = w.shape
    return mm(o.reshape(-1, H * hd), w.reshape(H * hd, d), mode)
