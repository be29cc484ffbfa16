"""Moonlight-16B-A3B: seeded weights and the plain float32 reference forward.

DeepSeek-V3 decoder (moonshotai/Moonlight-16B-A3B ``config.json``; the
equations of the DeepSeek-V2 and V3 reports): token embedding, 27 pre-RMSNorm
blocks, a final RMSNorm and an untied head.  Attention is latent (MLA) with
no query compression: ``q = h wq`` per head split into 128 + 64, the 64
rotated; ``c = RMSNorm(h wkv_a[:, :512])`` and one rotated ``k_pe = RoPE(h
wkv_a[:, 512:])`` shared by all heads; ``[k_nope | v] = c wkv_b``; scores of
``[q_nope | q_pe] . [k_nope | k_pe]`` scaled by 192 ** -0.5; ``o wo``.  Here
attention is computed expanded, never absorbed.  Block 0 has a SwiGLU MLP of
width 11,264; blocks 1-26 a mixture of experts: sigmoid scores over all 64
experts, the top 6 chosen by score plus a correction bias, weighted by their
scores without it, normalised and times 2.446, plus 2 shared experts (one
SwiGLU of width 2,816).  Of the routed experts only this chip's share runs
(``n_experts`` from ``expert_offset``), as in the program: what the absent
experts add is left out.  Rotary embedding in the halves layout.

The weights are made in bfloat16, the program's parameter dtype, and shared
with it; each layer's are upcast to float32 inside the scan.  Imports nothing
of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import refnn as nn

F32 = jnp.float32


def _dims(cfg):
    m = cfg["model"]
    return (m["n_layers"], m["first_dense_layers"], m["d_model"],
            m["n_heads"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["d_ff"], m["moe_d_ff"],
            m["n_experts"], m["n_router_experts"],
            m["n_shared_experts"] * m["moe_d_ff"], m["vocab_size"])


@functools.partial(jax.jit, static_argnums=0)
def _make(shape_key, key):
    L, nd, d, H, r, dn, dr, dv, F, f, E, Er, Fs, V = shape_key
    ks = iter(jax.random.split(key, 32))

    def n(shape, std, mean=0.0):
        x = mean + jax.random.normal(next(ks), shape, F32) * std
        return x.astype(jnp.bfloat16)

    def attn(n_l):
        return {"attn_norm": n((n_l, d), 0.1, 1.0),
                "wq": n((n_l, d, H, dn + dr), d ** -0.5),
                "wkv_a": n((n_l, d, r + dr), d ** -0.5),
                "kv_norm": n((n_l, r), 0.1, 1.0),
                "wkv_b": n((n_l, r, H, dn + dv), r ** -0.5),
                "wo": n((n_l, H, dv, d), (H * dv) ** -0.5),
                "mlp_norm": n((n_l, d), 0.1, 1.0)}

    M = L - nd
    return {
        "tok_embed": n((V, d), 0.02), "lm_head": n((d, V), d ** -0.5),
        "final_norm": n((d,), 0.1, 1.0),
        "dense": dict(attn(nd), w_gate=n((nd, d, F), d ** -0.5),
                      w_up=n((nd, d, F), d ** -0.5),
                      w_down=n((nd, F, d), F ** -0.5)),
        "moe": dict(attn(M), router=n((M, d, Er), d ** -0.5),
                    router_bias=n((M, Er), 0.02),
                    w_gate=n((M, E, d, f), d ** -0.5),
                    w_up=n((M, E, d, f), d ** -0.5),
                    w_down=n((M, E, f, d), f ** -0.5),
                    s_gate=n((M, d, Fs), d ** -0.5),
                    s_up=n((M, d, Fs), d ** -0.5),
                    s_down=n((M, Fs, d), Fs ** -0.5)),
    }


def make_weights(cfg, key):
    """Every weight from ``key``, bfloat16 on the default device, in one
    jitted call."""
    return _make(_dims(cfg), key)


def to_program(w):
    """The same arrays in the program's parameter tree."""
    def block(p):
        return {"attn_norm": {"scale": p["attn_norm"]},
                "attn": {"wq": p["wq"], "wkv_a": p["wkv_a"],
                         "kv_norm": {"scale": p["kv_norm"]},
                         "wkv_b": p["wkv_b"], "wo": p["wo"]},
                "mlp_norm": {"scale": p["mlp_norm"]}}
    dense, moe = w["dense"], w["moe"]
    return {
        "embed": {"embed": w["tok_embed"], "lm_head": w["lm_head"]},
        "final_norm": {"scale": w["final_norm"]},
        "dense_layers": dict(block(dense), mlp={
            "w_gate": dense["w_gate"], "w_up": dense["w_up"],
            "w_down": dense["w_down"]}),
        "layers": dict(block(moe), moe={
            "router": moe["router"], "router_bias": moe["router_bias"],
            "w_gate": moe["w_gate"], "w_up": moe["w_up"],
            "w_down": moe["w_down"],
            "shared": {"w_gate": moe["s_gate"], "w_up": moe["s_up"],
                       "w_down": moe["s_down"]}}),
    }


def _swiglu(x, wg, wu, wd, mode):
    return nn.mm(jax.nn.silu(nn.mm(x, wg, mode)) * nn.mm(x, wu, mode), wd,
                 mode)


def _attention(x, p, pos, k_, mode):
    eps, theta, r, dn = k_["eps"], k_["theta"], k_["r"], k_["dn"]
    h = nn.rmsnorm(x, p["attn_norm"], eps)
    q = nn.project(h, p["wq"], mode)                        # (S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], nn.rope(q[..., dn:], pos, theta)], -1)
    ckv = nn.mm(h, p["wkv_a"], mode)                         # (S, r + dr)
    c = nn.rmsnorm(ckv[:, :r], p["kv_norm"], eps)
    k_pe = nn.rope(ckv[:, None, r:], pos, theta)             # (S, 1, dr)
    kv = nn.project(c, p["wkv_b"], mode)                     # (S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, kv.shape[:2] + k_pe.shape[2:])],
        -1)
    o = nn.attention(q, k, kv[..., dn:], mode)               # (S, H, dv)
    return x + nn.unproject(o, p["wo"], mode)


def _experts(h, p, k_, mode):
    """This chip's share of the routed experts, plus the shared ones."""
    scores = jax.nn.sigmoid(nn.mm(h, p["router"], mode))    # (S, Er)
    _, top = jax.lax.top_k(scores + p["router_bias"], k_["top_k"])
    wt = jnp.take_along_axis(scores, top, axis=-1)
    wt = wt / (jnp.sum(wt, -1, keepdims=True) + 1e-20) * k_["scale"]
    y = _swiglu(h, p["s_gate"], p["s_up"], p["s_down"], mode)
    for e in range(p["w_gate"].shape[0]):                   # held experts
        gate = jnp.sum(jnp.where(top == k_["e0"] + e, wt, 0.0), axis=-1)
        y = y + gate[:, None] * _swiglu(h, p["w_gate"][e], p["w_up"][e],
                                        p["w_down"][e], mode)
    return y


def _forward(cfg_key, w, tokens, mode):
    k_ = dict(cfg_key)
    x = w["tok_embed"][tokens].astype(F32)
    pos = jnp.arange(tokens.shape[0])
    up = functools.partial(jax.tree_util.tree_map, lambda a: a.astype(F32))

    def dense(x, p):
        p = up(p)
        x = _attention(x, p, pos, k_, mode)
        h = nn.rmsnorm(x, p["mlp_norm"], k_["eps"])
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mode), None

    def moe(x, p):
        p = up(p)
        x = _attention(x, p, pos, k_, mode)
        return x + _experts(nn.rmsnorm(x, p["mlp_norm"], k_["eps"]), p, k_,
                            mode), None

    x, _ = jax.lax.scan(dense, x, w["dense"])
    x, _ = jax.lax.scan(moe, x, w["moe"])
    x = nn.rmsnorm(x, w["final_norm"].astype(F32), k_["eps"])
    return nn.mm(x, w["lm_head"].astype(F32), mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(cfg_key, w, tokens, mode):
    return jax.vmap(lambda t: _forward(cfg_key, w, t, mode))(tokens)


def logits(cfg, w, tokens, ages=None, mode="f32"):
    """(B, S, V) float32 logits of right-padded (B, S) tokens."""
    m = cfg["model"]
    return _logits((("eps", float(m["norm_eps"])),
                    ("theta", float(m["rope_theta"])),
                    ("r", int(m["kv_lora_rank"])),
                    ("dn", int(m["qk_nope_head_dim"])),
                    ("top_k", int(m["top_k"])),
                    ("scale", float(m["routed_scaling"])),
                    ("e0", int(m["expert_offset"]))), w, tokens, mode)
