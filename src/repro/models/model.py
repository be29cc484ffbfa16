"""Composable model definitions for every assigned architecture family.

Entry points (all pure functions over parameter pytrees):

* ``init_params(cfg, key)`` — parameter pytree.  Layer stacks are vmapped so
  they carry a leading layer axis and are ``lax.scan``-ed; compile time is
  O(1) in depth (mandatory for the 64-layer × 512-device CPU dry-run).
* ``forward(params, cfg, batch, mode)`` — ``mode="train"`` returns
  ``{"logits", "aux_loss"}``; ``mode="prefill"`` additionally returns the
  decode ``cache``.
* ``decode_step(params, cfg, cache, batch, step)`` — one-token serving step
  (the object lowered by decode dry-run shapes).

Batch dict keys by family:
  dense/moe/ssm/hybrid: tokens (B,S) int32 [+ ages (B,S) f32 for Delphi cfgs]
  vlm:   tokens (B,S) + patches (B, n_frontend_tokens, d_model)   [stub]
  audio: tokens (B,S) + frames (B, M, d_model)                    [stub]

Cache pytrees (leading axis = layer / application):
  dense/moe/vlm: {"self": LayerCache[L]}
  ssm:           {"ssm": SSMCache[L]}
  hybrid:        {"ssm": SSMCache[L], "attn": LayerCache[n_apps]}
  audio:         {"self": LayerCache[L], "cross": LayerCache[L]}
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import base as cb
from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import LayerCache
from repro.models.layers import (act_dtype, age_encoding, apply_mlp, apply_norm,
                                 embed_tokens, init_embed, init_mlp, init_norm,
                                 logits_head)
from repro.models.ssm import SSMCache


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_transformer_layer(key, cfg: ModelConfig, *, cross: bool = False,
                           moe: bool = False):
    ks = jax.random.split(key, 3)
    p = {
        "attn_norm": init_norm(cfg, cfg.d_model),
        "attn": attn_lib.init_attention(ks[0], cfg),
        "mlp_norm": init_norm(cfg, cfg.d_model),
    }
    if moe:
        p["moe"] = moe_lib.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg, cfg.d_model, cfg.d_ff)
    if cross:
        p["cross_norm"] = init_norm(cfg, cfg.d_model)
        p["cross_attn"] = attn_lib.init_attention(ks[2], cfg, cross=True)
    return p


def init_mamba_layer(key, cfg: ModelConfig):
    return {"norm": init_norm(cfg, cfg.d_model),
            "ssm": ssm_lib.init_ssm(key, cfg)}


def _stacked(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def n_attn_apps(cfg: ModelConfig) -> int:
    """Hybrid: number of shared-attention applications over the layer stack."""
    return -(-cfg.n_layers // cfg.attn_every)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"embed": init_embed(ks[0], cfg),
                         "final_norm": init_norm(cfg, cfg.d_model)}
    t = cfg.arch_type
    if t in (cb.DENSE, cb.VLM):
        p["layers"] = _stacked(lambda k: init_transformer_layer(k, cfg),
                               ks[1], cfg.n_layers)
    elif t == cb.MOE:
        nd = cfg.first_dense_layers
        p["layers"] = _stacked(lambda k: init_transformer_layer(k, cfg, moe=True),
                               ks[1], cfg.n_layers - nd)
        if nd:
            p["dense_layers"] = _stacked(
                lambda k: init_transformer_layer(k, cfg), ks[2], nd)
    elif t == cb.SSM:
        p["layers"] = _stacked(lambda k: init_mamba_layer(k, cfg), ks[1], cfg.n_layers)
    elif t == cb.HYBRID:
        p["layers"] = _stacked(lambda k: init_mamba_layer(k, cfg), ks[1], cfg.n_layers)
        p["shared_attn"] = init_transformer_layer(ks[2], cfg)
    elif t in (cb.AUDIO, cb.ENC_DEC):
        p["encoder"] = _stacked(lambda k: init_transformer_layer(k, cfg),
                                ks[1], cfg.n_encoder_layers)
        p["enc_norm"] = init_norm(cfg, cfg.d_model)
        p["layers"] = _stacked(lambda k: init_transformer_layer(k, cfg, cross=True),
                               ks[2], cfg.n_layers)
    else:
        raise ValueError(t)
    return p


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Single blocks
# ---------------------------------------------------------------------------
def transformer_layer(lp, x, positions, cfg: ModelConfig, *, mode: str,
                      cache: Optional[LayerCache] = None, step=None,
                      cross_cache: Optional[LayerCache] = None,
                      memory=None, causal: bool = True,
                      cache_width: Optional[int] = None,
                      moe_impl: str = "dense_scan",
                      defer_write: bool = False, ctx_k=None, ctx_v=None,
                      ctx_pos=None):
    """Pre-norm transformer block.  Returns (x, cache, cross_cache, aux):
    aux is the MoE load-balance loss, except in decode, where an MoE block
    gives each token's number of assignments to its held experts (B,).

    In decode mode with ``defer_write``, the second return is the (k, v) pair
    of the new token instead of an updated cache (one post-scan scatter).
    In suffix mode the second return is the (k, v) pair of the chunk tokens
    (same deferred-write contract), attending over ``ctx_k``/``ctx_v``/
    ``ctx_pos`` — the already-cached prompt context.  Latent attention
    (``cfg.is_mla``) caches one row per token in the k plane."""
    h = apply_norm(lp["attn_norm"], x, cfg)
    if cfg.is_mla:
        a, new_cache = attn_lib.mla_attention(
            lp["attn"], h, positions, cfg, mode=mode, cache=cache,
            step=step, causal=causal, cache_width=cache_width,
            defer_write=defer_write, ctx_k=ctx_k, ctx_pos=ctx_pos)
    else:
        a, new_cache = attn_lib.attention(
            lp["attn"], h, positions, cfg,
            mode=mode, cache=cache, step=step, causal=causal,
            use_rope=not cfg.age_encoding, cache_width=cache_width,
            defer_write=defer_write, ctx_k=ctx_k, ctx_v=ctx_v,
            ctx_pos=ctx_pos)
    x = x + a
    new_cross = cross_cache
    if "cross_attn" in lp:
        h = apply_norm(lp["cross_norm"], x, cfg)
        if mode == "decode":
            c, new_cross = attn_lib.attention(
                lp["cross_attn"], h, positions, cfg, mode="decode",
                cache=cross_cache, step=step, cross=True)
        else:
            c, new_cross = attn_lib.attention(
                lp["cross_attn"], h, positions, cfg, mode=mode, memory=memory)
        x = x + c
    h = apply_norm(lp["mlp_norm"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in lp and mode == "decode":
        y, _, held = moe_lib.apply_moe(lp["moe"], h, cfg, impl=moe_impl,
                                       count=True)
        aux = held[:, 0]
    elif "moe" in lp:
        y, aux = moe_lib.apply_moe(lp["moe"], h, cfg, impl=moe_impl)
    else:
        y = apply_mlp(lp["mlp"], h, cfg)
    return x + y, new_cache, new_cross, aux


def mamba_layer(lp, x, cfg: ModelConfig, *, mode: str,
                cache: Optional[SSMCache] = None):
    h = apply_norm(lp["norm"], x, cfg)
    if mode == "decode":
        y, new_cache = ssm_lib.ssm_decode_step(lp["ssm"], h, cache, cfg)
        return x + y, new_cache
    if mode == "prefill":
        y, new_cache = ssm_lib.ssm_forward(lp["ssm"], h, cfg, return_state=True)
        return x + y, new_cache
    return x + ssm_lib.ssm_forward(lp["ssm"], h, cfg), None


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


# ---------------------------------------------------------------------------
# Embedding frontends
# ---------------------------------------------------------------------------
def _embed_input(params, cfg: ModelConfig, batch, *, positions=None):
    """Returns (x (B, S', d), positions (S'? or (B,S')), text_offset)."""
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.age_encoding:
        x = x + age_encoding(batch["ages"], cfg.d_model).astype(x.dtype)
    offset = 0
    if cfg.frontend == "vision_patches":
        patches = batch["patches"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        offset = patches.shape[1]
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    return x, pos, offset


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------
def _slice_layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _cat_layers(a, b):
    """Two layer-stacked trees (caches, K/V rows) as one, ``a``'s first;
    None stays None (train mode has no caches)."""
    if a is None:
        return b
    return jax.tree_util.tree_map(lambda x, y: jnp.concatenate([x, y]), a, b)


def _transformer_stack_unrolled(layers, x, positions, cfg, *, mode,
                                memory=None, causal=True, caches=None,
                                cross_caches=None, step=None, cache_width=None,
                                moe_impl="dense_scan", has_cross=False,
                                head=None):
    """Python-loop twin of _transformer_stack (cfg.unroll_layers cost mode)."""
    if isinstance(caches, attn_lib.PagedCache):
        raise ValueError("paged KV cache requires the scanned stack "
                         "(cfg.unroll_layers is a cost-accounting mode)")
    stacks = [layers] if head is None else [head, layers]
    lps = [_slice_layer(t, i) for t in stacks
           for i in range(jax.tree_util.tree_leaves(t)[0].shape[0])]
    aux = jnp.zeros((), jnp.float32)
    out_caches, out_cross, kvs = [], [], []
    for i, lp in enumerate(lps):
        if mode == "decode":
            x, kv, _, a = transformer_layer(
                lp, x, positions, cfg, mode="decode",
                cache=_slice_layer(caches, i),
                cross_cache=(_slice_layer(cross_caches, i) if has_cross
                             else None),
                step=step, moe_impl=moe_impl, defer_write=True)
            aux = aux + a
            kvs.append(kv)
        else:
            def call(lp_, h_):
                return transformer_layer(
                    lp_, h_, positions, cfg, mode=mode, memory=memory,
                    causal=causal, cache_width=cache_width,
                    moe_impl=moe_impl)
            x, nc, nx, a = _maybe_remat(call, cfg)(lp, x)
            aux = aux + a
            if mode == "prefill":
                out_caches.append(nc)
                out_cross.append(nx)
    if mode == "decode":
        k_news, v_news = _stack_trees(kvs)
        caches = attn_lib.cache_write_stacked(caches, k_news, v_news, step)
        return x, caches, cross_caches, aux
    if mode == "prefill":
        return (x, _stack_trees(out_caches),
                (_stack_trees(out_cross) if has_cross else None), aux)
    return x, None, None, aux


def _transformer_stack(layers, x, positions, cfg, *, mode, memory=None,
                       causal=True, caches=None, cross_caches=None, step=None,
                       cache_width=None, moe_impl="dense_scan", has_cross=False,
                       head=None):
    """Scan a stacked transformer.  In decode mode caches are scan xs (read
    by layer index where a ``head`` shares them); in prefill they are scan
    ys; in train they don't exist.

    ``head``: leading layers of another kind (an MoE stack's dense layers),
    run first; their caches come first on the cache's layer axis.  The
    fourth return is the summed ``aux`` of :func:`transformer_layer`: the
    load-balance loss, or in decode each token's assignments to held
    experts (B,)."""
    if cfg.unroll_layers:
        return _transformer_stack_unrolled(
            layers, x, positions, cfg, mode=mode, memory=memory,
            causal=causal, caches=caches, cross_caches=cross_caches,
            step=step, cache_width=cache_width, moe_impl=moe_impl,
            has_cross=has_cross, head=head)
    if head is not None and mode != "decode":
        x, hc, _, aux = _transformer_stack(head, x, positions, cfg, mode=mode,
                                           cache_width=cache_width)
        x, c, _, aux_l = _transformer_stack(layers, x, positions, cfg,
                                            mode=mode, cache_width=cache_width,
                                            moe_impl=moe_impl)
        return x, _cat_layers(hc, c), None, aux + aux_l
    if mode == "train":
        def body(h, lp):
            h, _, _, aux = transformer_layer(
                lp, h, positions, cfg, mode="train", memory=memory,
                causal=causal, moe_impl=moe_impl)
            return h, aux
        x, auxes = jax.lax.scan(_maybe_remat(body, cfg), x, layers)
        return x, None, None, jnp.sum(auxes)

    if mode == "prefill":
        def body(h, lp):
            h, nc, nx, aux = transformer_layer(
                lp, h, positions, cfg, mode="prefill", memory=memory,
                causal=causal, cache_width=cache_width, moe_impl=moe_impl)
            if not has_cross:
                nx = jnp.zeros((0,))
            return h, (nc, nx, aux)
        x, (caches, cross_caches, auxes) = jax.lax.scan(
            _maybe_remat(body, cfg), x, layers)
        return x, caches, (cross_caches if has_cross else None), jnp.sum(auxes)

    # decode: caches are read-only inside the scan; new-token K/V are
    # collected and written with ONE stacked scatter afterwards (avoids
    # round-tripping the full cache through scan temporaries)
    def scan(x, stack, per_layer, read, xcs):
        def body(h, xs):
            lp, c, xc = xs
            h, kv, _, aux = transformer_layer(
                lp, h, positions, cfg, mode="decode", cache=read(c),
                cross_cache=xc, step=step, moe_impl=moe_impl,
                defer_write=True)
            return h, (kv, aux)
        x, (kv, aux) = jax.lax.scan(body, x, (stack, per_layer, xcs))
        return x, kv, jnp.sum(aux, axis=0)

    paged = isinstance(caches, attn_lib.PagedCache)
    if paged and has_cross:
        raise ValueError("paged KV cache does not support cross-"
                         "attention stacks")
    if head is None and not paged:
        x, kv, aux = scan(x, layers, caches, lambda c: c, cross_caches)
    else:
        # each layer reads the cache by index: a head's layers come first
        # on its layer axis, and the paged pool is gathered at (layer,
        # block) from the whole held pool (slicing it per layer or per
        # stack would copy it).  The pool's block ids and ring positions
        # have no layer axis, so they are built once here.
        if paged:
            blocks, ring_pos = attn_lib.paged_ring_index(caches.pos,
                                                         caches.table)
            heads = attn_lib.cache_rows(cfg)[0]

            def at(i):
                return attn_lib.PagedLayerView(caches.k, caches.v, i, blocks,
                                               ring_pos, heads)
        else:
            def at(i):
                return jax.tree_util.tree_map(lambda a: a[i], caches)
        n = jax.tree_util.tree_leaves(layers)[0].shape[0]
        nd = 0
        if head is not None:
            nd = jax.tree_util.tree_leaves(head)[0].shape[0]
            x, kv_head, _ = scan(x, head, jnp.arange(nd), at, None)
        x, kv, aux = scan(x, layers, jnp.arange(nd, nd + n), at,
                          cross_caches)
        if head is not None:
            kv = _cat_layers(kv_head, kv)
    caches = attn_lib.cache_write_stacked(caches, kv[0], kv[1], step)
    return x, caches, cross_caches, aux


def _suffix_stack(layers, x, positions, cfg, *, ctx_k, ctx_v, ctx_pos,
                  moe_impl="dense_scan"):
    """Scan a stacked transformer over a prompt *suffix* (chunked prefill).

    ``ctx_k``/``ctx_v`` (L, B, C, Hkv, hd) are the already-cached context
    K/V per layer (scan xs, like the paged decode scan); ``ctx_pos`` (B, C)
    their absolute positions (-1 = invalid, shared across layers).  Returns
    (x, k_news, v_news) where k_news/v_news (L, B, Sc, Hkv, hd) are the
    suffix K/V for the caller's one stacked block write."""
    def body(h, xs):
        lp, ck, cv = xs
        h, kv, _, _ = transformer_layer(
            lp, h, positions, cfg, mode="suffix", ctx_k=ck, ctx_v=cv,
            ctx_pos=ctx_pos, moe_impl=moe_impl)
        return h, kv
    x, (k_news, v_news) = jax.lax.scan(body, x, (layers, ctx_k, ctx_v))
    return x, k_news, v_news


def forward_suffix(params, cfg: ModelConfig, batch: Dict[str, Any], ctx,
                   *, last_index, moe_impl: str = "dense_scan") -> Dict[str, Any]:
    """Chunked-prefill forward over a prompt *suffix*.

    The suffix tokens attend over pre-existing cache context (gathered from
    the paged pool by the caller) plus themselves, by absolute position —
    the incremental half of a prefill whose earlier chunks (or prefix-cache
    hits) already wrote their K/V.

    batch: tokens (B, Sc) int32 [+ ages (B, Sc) for Delphi cfgs], positions
    (B, Sc) int32 absolute positions (-1 = right padding).  ctx: dict with
    "k"/"v" (L, B, C, Hkv, hd) roped context K/V and "pos" (B, C) absolute
    positions (-1 = invalid).  ``last_index``: (B,) index of each example's
    last valid suffix token (the bootstrap logits read there).

    Returns {"logits": (B, 1, V), "k"/"v": (L, B, Sc, Hkv, hd)} — the
    suffix K/V for the caller's paged block write.  Attention-cache
    architectures only (same constraint as :func:`make_paged_decode_cache`).
    """
    t = cfg.arch_type
    if t not in (cb.DENSE, cb.VLM, cb.MOE):
        raise ValueError(f"suffix prefill supports attention-cache "
                         f"architectures (dense/moe/vlm), not {t}")
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.age_encoding:
        x = x + age_encoding(batch["ages"], cfg.d_model).astype(x.dtype)
    positions = batch["positions"]
    ck, cv = ctx["k"], ctx["v"]
    head = params.get("dense_layers")
    if head is not None:
        nd = cfg.first_dense_layers
        x, hk, hv = _suffix_stack(head, x, positions, cfg, ctx_k=ck[:nd],
                                  ctx_v=cv[:nd], ctx_pos=ctx["pos"])
        ck, cv = ck[nd:], cv[nd:]
    x, k_news, v_news = _suffix_stack(
        params["layers"], x, positions, cfg, ctx_k=ck, ctx_v=cv,
        ctx_pos=ctx["pos"], moe_impl=moe_impl)
    if head is not None:
        k_news, v_news = _cat_layers((hk, hv), (k_news, v_news))
    idx = jnp.asarray(last_index, jnp.int32)
    x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    x = apply_norm(params["final_norm"], x, cfg)
    return {"logits": logits_head(params["embed"], x, cfg),
            "k": k_news, "v": v_news}


def _ssm_stack(layers, x, cfg, *, mode, caches=None):
    if cfg.unroll_layers:   # cost-accounting mode (python loop, exact FLOPs)
        L = jax.tree_util.tree_leaves(layers)[0].shape[0]
        outs = []
        for i in range(L):
            lp = _slice_layer(layers, i)
            c = _slice_layer(caches, i) if caches is not None else None
            def call(lp_, h_):
                return mamba_layer(lp_, h_, cfg, mode=mode, cache=c)
            if mode == "train":
                x, _ = _maybe_remat(call, cfg)(lp, x)
            else:
                x, nc = call(lp, x)
                outs.append(nc)
        return x, (_stack_trees(outs) if outs else None)
    if mode == "train":
        def body(h, lp):
            h, _ = mamba_layer(lp, h, cfg, mode="train")
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, layers)
        return x, None
    if mode == "prefill":
        def body(h, lp):
            h, nc = mamba_layer(lp, h, cfg, mode="prefill")
            return h, nc
        x, caches = jax.lax.scan(_maybe_remat(body, cfg), x, layers)
        return x, caches
    def body(h, xs):
        lp, c = xs
        h, nc = mamba_layer(lp, h, cfg, mode="decode", cache=c)
        return h, nc
    x, caches = jax.lax.scan(body, x, (layers, caches))
    return x, caches


def _hybrid_stack(params, x, positions, cfg, *, mode, ssm_caches=None,
                  attn_caches=None, step=None, cache_width=None):
    """Zamba2-style: scan Mamba layers; apply the weight-shared attention
    block before every ``cfg.attn_every``-th layer.  Attention caches are
    stacked per *application* and carried through the scan."""
    L = cfg.n_layers
    k = cfg.attn_every
    shared = params["shared_attn"]
    idxs = jnp.arange(L, dtype=jnp.int32)

    if cfg.unroll_layers:   # cost-accounting mode: static periodic structure
        ssm_outs = []
        attn_list = ([None] * n_attn_apps(cfg) if mode != "train" else None)
        for i in range(L):
            if i % k == 0:
                app = i // k
                if mode == "train":
                    x, _, _, _ = transformer_layer(shared, x, positions, cfg,
                                                   mode="train")
                elif mode == "prefill":
                    x, nc, _, _ = transformer_layer(
                        shared, x, positions, cfg, mode="prefill",
                        cache_width=cache_width)
                    attn_list[app] = nc
                else:
                    c = _slice_layer(attn_caches, app)
                    x, nc, _, _ = transformer_layer(
                        shared, x, positions, cfg, mode="decode", cache=c,
                        step=step)
                    attn_list[app] = nc
            lp = _slice_layer(params["layers"], i)
            c = _slice_layer(ssm_caches, i) if ssm_caches is not None else None
            x, nc = mamba_layer(lp, x, cfg, mode=mode, cache=c)
            if mode != "train":
                ssm_outs.append(nc)
        if mode == "train":
            return x, None, None
        return x, _stack_trees(ssm_outs), _stack_trees(attn_list)

    def apply_shared(h, app_idx, ac_all):
        if mode == "train":
            h2, _, _, _ = transformer_layer(shared, h, positions, cfg,
                                            mode="train")
            return h2, ac_all
        if mode == "prefill":
            h2, nc, _, _ = transformer_layer(shared, h, positions, cfg,
                                             mode="prefill",
                                             cache_width=cache_width)
            ac_all = jax.tree_util.tree_map(
                lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                    buf, new.astype(buf.dtype), app_idx, 0), ac_all, nc)
            return h2, ac_all
        c = jax.tree_util.tree_map(
            lambda buf: jax.lax.dynamic_index_in_dim(buf, app_idx, 0,
                                                     keepdims=False), ac_all)
        h2, nc, _, _ = transformer_layer(shared, h, positions, cfg,
                                         mode="decode", cache=c, step=step)
        ac_all = jax.tree_util.tree_map(
            lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                buf, new.astype(buf.dtype), app_idx, 0), ac_all, nc)
        return h2, ac_all

    def body(carry, xs):
        h, ac_all = carry
        if mode == "decode":
            lp, c, i = xs
        else:
            lp, i = xs
            c = None
        h, ac_all = jax.lax.cond(
            i % k == 0,
            lambda hh, aa: apply_shared(hh, i // k, aa),
            lambda hh, aa: (hh, aa),
            h, ac_all)
        h, nc = mamba_layer(lp, h, cfg, mode=mode, cache=c)
        return (h, ac_all), nc

    body = _maybe_remat(body, cfg) if mode != "decode" else body
    if mode == "decode":
        (x, attn_caches), ssm_caches = jax.lax.scan(
            body, (x, attn_caches), (params["layers"], ssm_caches, idxs))
        return x, ssm_caches, attn_caches
    if mode == "prefill":
        (x, attn_caches), ssm_caches = jax.lax.scan(
            body, (x, attn_caches), (params["layers"], idxs))
        return x, ssm_caches, attn_caches
    dummy = _empty_hybrid_attn_cache(cfg, x.shape[0], 1, x.dtype)
    (x, _), _ = jax.lax.scan(body, (x, dummy), (params["layers"], idxs))
    return x, None, None


def _empty_hybrid_attn_cache(cfg: ModelConfig, batch: int, width: int, dtype):
    one = attn_lib.empty_cache(cfg, batch, width, dtype)
    n = n_attn_apps(cfg)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), one)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            mode: str = "train", cache_width: Optional[int] = None,
            moe_impl: str = "dense_scan",
            last_index: Optional[Any] = None) -> Dict[str, Any]:
    """mode in {"train", "prefill"}.

    ``last_index`` (prefill only): (B,) int32 index of each example's last
    *valid* token.  Right-padded batched prefill (the serving engine's
    bucketed admission) reads its bootstrap logits there instead of at the
    fixed position -1; padded tail positions never reach the logits head.
    """
    assert mode in ("train", "prefill")
    t = cfg.arch_type
    x, pos, offset = _embed_input(params, cfg, batch)
    B = x.shape[0]
    out: Dict[str, Any] = {"text_offset": offset}
    aux = jnp.zeros((), jnp.float32)

    if t in (cb.DENSE, cb.VLM, cb.MOE):
        x, caches, _, aux = _transformer_stack(
            params["layers"], x, pos, cfg, mode=mode,
            cache_width=cache_width, moe_impl=moe_impl,
            head=params.get("dense_layers"))
        if mode == "prefill":
            out["cache"] = {"self": caches}
    elif t == cb.SSM:
        x, caches = _ssm_stack(params["layers"], x, cfg, mode=mode)
        if mode == "prefill":
            out["cache"] = {"ssm": caches}
    elif t == cb.HYBRID:
        attn_c = None
        if mode == "prefill":
            W = cache_width or (cfg.sliding_window or x.shape[1])
            attn_c = _empty_hybrid_attn_cache(cfg, B, W, act_dtype(cfg))
        x, ssm_c, attn_c = _hybrid_stack(
            params, x, pos, cfg, mode=mode, attn_caches=attn_c,
            cache_width=cache_width)
        if mode == "prefill":
            out["cache"] = {"ssm": ssm_c, "attn": attn_c}
    elif t in (cb.AUDIO, cb.ENC_DEC):
        frames = batch["frames"].astype(act_dtype(cfg))
        fpos = jnp.arange(frames.shape[1], dtype=jnp.int32)
        mem, _, _, _ = _transformer_stack(
            params["encoder"], frames, fpos, cfg, mode="train", causal=False)
        mem = apply_norm(params["enc_norm"], mem, cfg)
        x, caches, cross, _ = _transformer_stack(
            params["layers"], x, pos, cfg, mode=mode, memory=mem,
            cache_width=cache_width, has_cross=True)
        if mode == "prefill":
            out["cache"] = {"self": caches, "cross": cross}
    else:
        raise ValueError(t)

    if mode == "prefill":
        # decode bootstrap only needs the last position; slicing before the
        # head keeps the (B, S, V) fp32 logits out of the live set
        if last_index is not None:
            idx = jnp.asarray(last_index, jnp.int32) + offset
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            x = x[:, -1:]
    x = apply_norm(params["final_norm"], x, cfg)
    out["logits"] = logits_head(params["embed"], x, cfg)
    out["aux_loss"] = aux
    return out


def decode_step(params, cfg: ModelConfig, cache, batch: Dict[str, Any], step,
                *, moe_impl: str = "dense_scan") -> Dict[str, Any]:
    """One-token decode.  batch["tokens"]: (B, 1); step: scalar int32 absolute
    position of the new token, or (B,) per-example positions — the serving
    engine advances its continuous-batching slots, each at a different depth,
    in one batched call.  Returns {"logits": (B, 1, V), "cache": ...}, and
    for MoE stacks "expert_tokens": (B,) int32, each token's assignments to
    held experts summed over layers."""
    t = cfg.arch_type
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.age_encoding:
        x = x + age_encoding(batch["ages"], cfg.d_model).astype(x.dtype)
    step = jnp.asarray(step, jnp.int32)
    pos = step if step.ndim == 1 else jnp.reshape(step, (1,))

    out: Dict[str, Any] = {}
    if t in (cb.DENSE, cb.VLM, cb.MOE):
        x, caches, _, held = _transformer_stack(
            params["layers"], x, pos, cfg, mode="decode",
            caches=cache["self"], step=step, moe_impl=moe_impl,
            head=params.get("dense_layers"))
        new_cache = {"self": caches}
        if t == cb.MOE:
            out["expert_tokens"] = held.astype(jnp.int32)
    elif t == cb.SSM:
        x, caches = _ssm_stack(params["layers"], x, cfg, mode="decode",
                               caches=cache["ssm"])
        new_cache = {"ssm": caches}
    elif t == cb.HYBRID:
        x, ssm_c, attn_c = _hybrid_stack(
            params, x, pos, cfg, mode="decode", ssm_caches=cache["ssm"],
            attn_caches=cache["attn"], step=step)
        new_cache = {"ssm": ssm_c, "attn": attn_c}
    elif t in (cb.AUDIO, cb.ENC_DEC):
        x, caches, cross, _ = _transformer_stack(
            params["layers"], x, pos, cfg, mode="decode", caches=cache["self"],
            cross_caches=cache["cross"], step=step, has_cross=True)
        new_cache = {"self": caches, "cross": cross}
    else:
        raise ValueError(t)

    x = apply_norm(params["final_norm"], x, cfg)
    return {"logits": logits_head(params["embed"], x, cfg), "cache": new_cache,
            **out}


def mask_padded_positions(cache, last_idx):
    """Invalidate ring-cache positions past each example's true last token.

    Right-padded batched prefill (the serving engine's bucketed admission,
    the exported spec-v2 prefill graph) writes garbage K/V at positions
    ``len..S-1``; setting their ``pos`` to -1 makes ``decode_attention`` mask
    them until real decode writes reclaim the slots one position at a time.
    Non-attention cache components (SSM state) pass through — callers only
    right-pad pure-attention architectures.  ``last_idx``: (B,) int32.
    """
    li = jnp.asarray(last_idx).reshape((1, -1, 1))

    def fix(v):
        if isinstance(v, LayerCache):
            return v._replace(
                pos=jnp.where((v.pos >= 0) & (v.pos <= li), v.pos, -1))
        return v
    return {k: fix(v) for k, v in cache.items()}


def make_paged_decode_cache(cfg: ModelConfig, batch: int, context_len: int,
                            *, num_blocks: int, block_size: int):
    """Paged twin of :func:`make_decode_cache`: a shared block pool sized by
    ``num_blocks`` (block 0 reserved as trash) instead of a dense
    ``batch x context_len`` ring per slot.  Attention-cache architectures
    only — recurrent SSM/hybrid state has nothing to page."""
    t = cfg.arch_type
    if t not in (cb.DENSE, cb.VLM, cb.MOE):
        raise ValueError(f"paged KV cache supports attention-cache "
                         f"architectures (dense/moe/vlm), not {t}")
    return {"self": attn_lib.empty_paged_cache(
        cfg, cfg.n_layers, num_blocks, batch, context_len, block_size,
        act_dtype(cfg))}


def make_decode_cache(params, cfg: ModelConfig, batch: int, context_len: int):
    """Build an empty decode cache shaped as if ``context_len`` tokens had been
    processed (what the decode dry-run shapes lower against)."""
    dtype = act_dtype(cfg)
    W = min(cfg.sliding_window or context_len, context_len)
    L = cfg.n_layers

    def stack(c, n):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), c)

    t = cfg.arch_type
    if t in (cb.DENSE, cb.VLM, cb.MOE):
        return {"self": stack(attn_lib.empty_cache(cfg, batch, W, dtype), L)}
    if t == cb.SSM:
        return {"ssm": stack(ssm_lib.empty_ssm_cache(cfg, batch, dtype), L)}
    if t == cb.HYBRID:
        return {"ssm": stack(ssm_lib.empty_ssm_cache(cfg, batch, dtype), L),
                "attn": _empty_hybrid_attn_cache(cfg, batch, W, dtype)}
    if t in (cb.AUDIO, cb.ENC_DEC):
        M = cfg.dec_enc_len
        return {"self": stack(attn_lib.empty_cache(cfg, batch, W, dtype), L),
                "cross": stack(attn_lib.empty_cache(cfg, batch, M, dtype), L)}
    raise ValueError(t)
