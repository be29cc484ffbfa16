"""Delphi-2M: seeded weights and the plain float32 reference forward pass.

GPT over event tokens (Shmatko et al., Nature 2025; gerstung-lab/Delphi):
token embedding plus a sinusoidal encoding of the age at each event in
place of positions, pre-LayerNorm blocks of causal multi-head attention
and a GELU MLP (both with biases on the MLP), a final LayerNorm and logits
through the tied embedding plus a per-token bias: the log-hazards of the
competing-exponential time-to-event head.  Departures from the published
code, each stated in ``delphi-2m.json``: GELU in its tanh form and the age
encoding's scales, as the configuration runs them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import refnn as nn


def _dims(cfg):
    m = cfg["model"]
    return (m["n_layers"], m["d_model"], m["n_heads"], m["head_dim"],
            m["d_ff"], m["vocab_size"])


@functools.partial(jax.jit, static_argnums=0)
def _make(shape_key, key):
    L, d, H, hd, F, V, bias = shape_key
    ks = iter(jax.random.split(key, 16))

    def n(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    return {
        "tok_embed": n((V, d), 0.02),
        "out_bias": jnp.full((V,), bias, jnp.float32),
        "ln1_scale": 1.0 + n((L, d), 0.1), "ln1_bias": n((L, d), 0.02),
        "wq": n((L, d, H, hd), d ** -0.5), "wk": n((L, d, H, hd), d ** -0.5),
        "wv": n((L, d, H, hd), d ** -0.5),
        "wo": n((L, H, hd, d), (H * hd) ** -0.5),
        "ln2_scale": 1.0 + n((L, d), 0.1), "ln2_bias": n((L, d), 0.02),
        "w_fc": n((L, d, F), d ** -0.5), "b_fc": n((L, F), 0.02),
        "w_proj": n((L, F, d), F ** -0.5), "b_proj": n((L, d), 0.02),
        "lnf_scale": 1.0 + n((d,), 0.1), "lnf_bias": n((d,), 0.02),
    }


def make_weights(cfg, key):
    """Every weight from ``key``, float32 on the default device, in one
    jitted call."""
    return _make(_dims(cfg) + (float(cfg["weights"]["out_bias"]),), key)


def to_program(w):
    """The same arrays in the program's parameter tree."""
    return {
        "embed": {"embed": w["tok_embed"], "out_bias": w["out_bias"]},
        "final_norm": {"scale": w["lnf_scale"], "bias": w["lnf_bias"]},
        "layers": {
            "attn_norm": {"scale": w["ln1_scale"], "bias": w["ln1_bias"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "mlp_norm": {"scale": w["ln2_scale"], "bias": w["ln2_bias"]},
            "mlp": {"w_fc": w["w_fc"], "b_fc": w["b_fc"],
                    "w_proj": w["w_proj"], "b_proj": w["b_proj"]},
        },
    }


def _age_encoding(ages, d, lo, hi):
    half = d // 2
    inv = (1.0 / lo) * jnp.exp(-jnp.log(hi / lo) / max(half - 1, 1)
                               * jnp.arange(half, dtype=jnp.float32))
    ang = ages[:, None] * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _forward(cfg_key, w, tokens, ages, mode):
    eps, lo, hi = cfg_key
    d = w["tok_embed"].shape[1]
    x = w["tok_embed"][tokens] + _age_encoding(ages, d, lo, hi)
    layers = {k: w[k] for k in ("ln1_scale", "ln1_bias", "wq", "wk", "wv",
                                "wo", "ln2_scale", "ln2_bias", "w_fc",
                                "b_fc", "w_proj", "b_proj")}

    def layer(x, p):
        h = nn.layernorm(x, p["ln1_scale"], p["ln1_bias"], eps)
        o = nn.attention(nn.project(h, p["wq"], mode),
                         nn.project(h, p["wk"], mode),
                         nn.project(h, p["wv"], mode), mode)
        x = x + nn.unproject(o, p["wo"], mode)
        h = nn.layernorm(x, p["ln2_scale"], p["ln2_bias"], eps)
        h = nn.gelu_tanh(nn.mm(h, p["w_fc"], mode) + p["b_fc"])
        return x + nn.mm(h, p["w_proj"], mode) + p["b_proj"], None

    x, _ = jax.lax.scan(layer, x, layers)
    h = nn.layernorm(x, w["lnf_scale"], w["lnf_bias"], eps)
    return nn.mm(h, w["tok_embed"].T, mode) + w["out_bias"]


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(cfg_key, w, tokens, ages, mode):
    return jax.vmap(lambda t, a: _forward(cfg_key, w, t, a, mode))(
        tokens, ages)


def logits(cfg, w, tokens, ages, mode="f32"):
    """(B, S, V) float32 logits of right-padded (B, S) tokens and ages."""
    m = cfg["model"]
    enc = cfg["age_encoding"]
    return _logits((float(m["norm_eps"]), float(enc["min_scale"]),
                    float(enc["max_scale"])), w, tokens, ages, mode)
