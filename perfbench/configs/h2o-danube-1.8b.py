"""H2O-Danube-1.8B: seeded weights and the plain float32 reference forward.

Llama/Mistral-style decoder (arXiv:2401.16818; h2oai/h2o-danube-1.8b-base
``config.json``): token embedding, pre-RMSNorm blocks of grouped-query
attention (32 query heads over 8 key/value heads of width 80, rotary
embedding in the halves layout, sliding window of 4,096) and a SwiGLU MLP
without biases, a final RMSNorm and an untied output head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import refnn as nn


@functools.partial(jax.jit, static_argnums=0)
def _make(shape_key, key):
    L, d, H, Hkv, hd, F, V = shape_key
    ks = iter(jax.random.split(key, 16))

    def n(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    return {
        "tok_embed": n((V, d), 0.02), "lm_head": n((d, V), d ** -0.5),
        "attn_norm": 1.0 + n((L, d), 0.1),
        "wq": n((L, d, H, hd), d ** -0.5), "wk": n((L, d, Hkv, hd), d ** -0.5),
        "wv": n((L, d, Hkv, hd), d ** -0.5),
        "wo": n((L, H, hd, d), (H * hd) ** -0.5),
        "mlp_norm": 1.0 + n((L, d), 0.1),
        "w_gate": n((L, d, F), d ** -0.5), "w_up": n((L, d, F), d ** -0.5),
        "w_down": n((L, F, d), F ** -0.5),
        "final_norm": 1.0 + n((d,), 0.1),
    }


def make_weights(cfg, key):
    """Every weight from ``key``, float32 on the default device, in one
    jitted call."""
    m = cfg["model"]
    return _make((m["n_layers"], m["d_model"], m["n_heads"],
                  m["n_kv_heads"], m["head_dim"], m["d_ff"],
                  m["vocab_size"]), key)


def to_program(w):
    """The same arrays in the program's parameter tree."""
    return {
        "embed": {"embed": w["tok_embed"], "lm_head": w["lm_head"]},
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "attn_norm": {"scale": w["attn_norm"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "mlp_norm": {"scale": w["mlp_norm"]},
            "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]},
        },
    }


def _forward(cfg_key, w, tokens, mode):
    eps, theta, window = cfg_key
    x = w["tok_embed"][tokens]
    pos = jnp.arange(tokens.shape[0])
    layers = {k: w[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                "mlp_norm", "w_gate", "w_up", "w_down")}

    def layer(x, p):
        h = nn.rmsnorm(x, p["attn_norm"], eps)
        q = nn.rope(nn.project(h, p["wq"], mode), pos, theta)
        k = nn.rope(nn.project(h, p["wk"], mode), pos, theta)
        o = nn.attention(q, k, nn.project(h, p["wv"], mode), mode,
                         window=window)
        x = x + nn.unproject(o, p["wo"], mode)
        h = nn.rmsnorm(x, p["mlp_norm"], eps)
        g = jax.nn.silu(nn.mm(h, p["w_gate"], mode)) * nn.mm(h, p["w_up"],
                                                             mode)
        return x + nn.mm(g, p["w_down"], mode), None

    x, _ = jax.lax.scan(layer, x, layers)
    return nn.mm(nn.rmsnorm(x, w["final_norm"], eps), w["lm_head"], mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(cfg_key, w, tokens, mode):
    return jax.vmap(lambda t: _forward(cfg_key, w, t, mode))(tokens)


def logits(cfg, w, tokens, ages=None, mode="f32"):
    """(B, S, V) float32 logits of right-padded (B, S) tokens."""
    m = cfg["model"]
    return _logits((float(m["norm_eps"]), float(m["rope_theta"]),
                    int(m["sliding_window"])), w, tokens, mode)
