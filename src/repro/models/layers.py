"""Shared neural-net layers: norms, RoPE, MLPs, embeddings.

Parameters are plain pytrees (nested dicts of ``jnp.ndarray``).  Every layer is
a pair of functions ``init_*(key, cfg, ...) -> params`` and a pure apply
function.  Compute dtype follows ``cfg.dtype``; parameters are kept in
``cfg.param_dtype`` and cast at use (the TPU-standard mixed-precision recipe).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def act_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((d,), param_dtype(cfg))}
    return {"scale": jnp.ones((d,), param_dtype(cfg)),
            "bias": jnp.zeros((d,), param_dtype(cfg))}


def apply_norm(params, x, cfg: ModelConfig, eps: float = 1e-5):
    """RMSNorm / LayerNorm computed in fp32, cast back to the activation dtype."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + eps)
        y = y * params["scale"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding (applied on absolute positions so ring-buffer
# caches stay correct at any context offset).
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = rope_frequencies(head_dim, theta)                     # (half,)
    angles = positions.astype(jnp.float32)[..., None] * freqs     # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]                           # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Continuous age encoding (Delphi-2M): sinusoidal features of patient age at
# each event, replacing discrete positional encodings.  Ages are in years;
# frequencies span ~days to ~centuries.
# ---------------------------------------------------------------------------
def age_encoding(ages, d_model: int, min_scale: float = 1e-3, max_scale: float = 200.0):
    """ages: (..., seq) float years -> (..., seq, d_model)."""
    half = d_model // 2
    log_inc = jnp.log(max_scale / min_scale) / max(half - 1, 1)
    inv_scales = (1.0 / min_scale) * jnp.exp(-log_inc * jnp.arange(half, dtype=jnp.float32))
    angles = ages.astype(jnp.float32)[..., None] * inv_scales     # (..., seq, half)
    enc = jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)
    if enc.shape[-1] < d_model:  # odd d_model
        enc = jnp.pad(enc, [(0, 0)] * (enc.ndim - 1) + [(0, d_model - enc.shape[-1])])
    return enc


# ---------------------------------------------------------------------------
# MLP (SwiGLU for llama-family, GELU for GPT/nanoGPT/seamless family)
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d: int, d_ff: int):
    pdt = param_dtype(cfg)
    s_in = d ** -0.5
    s_ff = d_ff ** -0.5
    if cfg.activation == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": (jax.random.normal(k1, (d, d_ff)) * s_in).astype(pdt),
            "w_up": (jax.random.normal(k2, (d, d_ff)) * s_in).astype(pdt),
            "w_down": (jax.random.normal(k3, (d_ff, d)) * s_ff).astype(pdt),
        }
    k1, k2 = jax.random.split(key)
    return {
        "w_fc": (jax.random.normal(k1, (d, d_ff)) * s_in).astype(pdt),
        "b_fc": jnp.zeros((d_ff,), pdt),
        "w_proj": (jax.random.normal(k2, (d_ff, d)) * s_ff).astype(pdt),
        "b_proj": jnp.zeros((d,), pdt),
    }


@jax.named_scope("mlp")
def apply_mlp(params, x, cfg: ModelConfig):
    dt = x.dtype
    if "w_gate" in params:
        g = x @ params["w_gate"].astype(dt)
        u = x @ params["w_up"].astype(dt)
        h = jax.nn.silu(g) * u
        return h @ params["w_down"].astype(dt)
    h = x @ params["w_fc"].astype(dt) + params["b_fc"].astype(dt)
    h = jax.nn.gelu(h)
    return h @ params["w_proj"].astype(dt) + params["b_proj"].astype(dt)


# ---------------------------------------------------------------------------
# Embeddings / output head
# ---------------------------------------------------------------------------
def init_embed(key, cfg: ModelConfig):
    pdt = param_dtype(cfg)
    p = {"embed": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(pdt)}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["lm_head"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
                        * cfg.d_model ** -0.5).astype(pdt)
    if cfg.dual_head:
        # logits are log-hazards (1/years); start rates low so the initial
        # total rate Lambda = sum e^{logit} is O(0.1/yr), not O(vocab)
        p["out_bias"] = jnp.full((cfg.vocab_size,), -8.0, pdt)
    return p


def embed_tokens(params, tokens, cfg: ModelConfig):
    return params["embed"].astype(act_dtype(cfg))[tokens]


@jax.named_scope("head")
def logits_head(params, h, cfg: ModelConfig):
    if cfg.tie_embeddings:
        w = params["embed"].astype(h.dtype).T
    else:
        w = params["lm_head"].astype(h.dtype)
    # logits in fp32 for numerically stable losses / sampling
    logits = (h @ w).astype(jnp.float32)
    if "out_bias" in params:
        logits = logits + params["out_bias"].astype(jnp.float32)
    return logits
