"""Mixture-of-Experts layer: top-k router, shared experts, load-balance loss.

Two dispatch implementations with identical semantics (equivalence is
property-tested):

* ``dense_scan`` (baseline): ``lax.scan`` over experts, each expert computes
  over all tokens and results are combined with the (mostly-zero) router
  weights.  Always compiles, memory-light, but does E/top_k times the active
  FLOPs — the roofline MODEL_FLOPS/HLO_FLOPs ratio exposes this and the §Perf
  hillclimb replaces it.
* ``ragged`` (optimized): tokens are sorted by expert id and run through
  ``lax.ragged_dot`` grouped matmuls — active-FLOPs-only compute.  On TPU this
  maps to the native grouped-matmul; token sort/gather stays shard-local when
  wrapped in shard_map by the launcher.

Routing is a configuration field (``router_score``): the qwen/olmoe
recipe (softmax over router logits, top-k, renormalized combine weights) or
DeepSeek-V3's (sigmoid scores, top-k of the scores plus a selection-only
bias, the chosen scores normalized and scaled); the auxiliary load-balance
loss (Switch-style ``E * sum_e f_e * p_e``) is returned to the caller.

Every dispatch computes one chip's share under expert parallelism: the
router scores all ``cfg.router_experts`` experts, the layer holds experts
``[expert_offset, expert_offset + n_experts)`` and computes only the
assignments that land on them; the shared experts are added once.  On one
chip nothing is exchanged.  With every expert held this is the whole layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import init_mlp, apply_mlp, param_dtype


def init_moe(key, cfg: ModelConfig):
    pdt = param_dtype(cfg)
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    k_r, k_e, k_s = jax.random.split(key, 3)
    s_in, s_ff = d ** -0.5, f ** -0.5
    ke1, ke2, ke3 = jax.random.split(k_e, 3)
    p = {
        "router": (jax.random.normal(k_r, (d, cfg.router_experts))
                   * s_in).astype(pdt),
        "w_gate": (jax.random.normal(ke1, (E, d, f)) * s_in).astype(pdt),
        "w_up": (jax.random.normal(ke2, (E, d, f)) * s_in).astype(pdt),
        "w_down": (jax.random.normal(ke3, (E, f, d)) * s_ff).astype(pdt),
    }
    if cfg.n_shared_experts:
        # shared experts fused into one always-on MLP of combined width
        p["shared"] = init_mlp(k_s, cfg.replace(activation="swiglu"),
                               d, cfg.n_shared_experts * f)
    if cfg.router_score == "sigmoid":
        # DeepSeek-V3's score correction: it steers selection, never weights
        p["router_bias"] = jnp.zeros((cfg.router_experts,), pdt)
    return p


def route(params, x, cfg: ModelConfig):
    """x: (T, d) -> (weights (T, k), experts (T, k) int32 among all the
    router's experts, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    if cfg.router_score == "sigmoid":
        # noaux_tc with one group: pick by score + bias, weigh by score
        probs = jax.nn.sigmoid(logits)                          # (T, E)
        _, experts = jax.lax.top_k(
            probs + params["router_bias"].astype(jnp.float32), cfg.top_k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
        weights = (weights / (jnp.sum(weights, axis=-1, keepdims=True)
                              + 1e-20) * cfg.routed_scaling)
    else:
        probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
        weights, experts = jax.lax.top_k(probs, cfg.top_k)      # (T, k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss
    E = cfg.router_experts
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)      # (T, k, E)
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)     # f_e
    frac_probs = jnp.mean(probs, axis=0)                        # p_e
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return weights, experts, aux


@jax.named_scope("route")
def _routed(params, x, cfg: ModelConfig):
    """-> (weights, local expert ids, held mask (T, k), aux_loss): each
    assignment's expert among those held here, or ``n_experts`` (one past
    the last) where it is not held."""
    weights, experts, aux = route(params, x, cfg)
    local = experts - cfg.expert_offset
    held = (local >= 0) & (local < cfg.n_experts)
    return weights, jnp.where(held, local, cfg.n_experts), held, aux


def _expert_mlp(w_gate, w_up, w_down, x):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def _combine(weights, local, T: int, E: int, dt):
    """Combine weight of held expert e for token t: (T, E), mostly zero
    (assignments to experts not held fall past the last column, dropped)."""
    return jnp.zeros((T, E), dt).at[
        jnp.arange(T)[:, None], local].set(weights.astype(dt))


def moe_dense_scan(params, x, cfg: ModelConfig):
    """Baseline dispatch: scan over the held experts, weighted combine."""
    T, d = x.shape
    dt = x.dtype
    weights, local, held, aux = _routed(params, x, cfg)
    combine = _combine(weights, local, T, cfg.n_experts, dt)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(acc, wexp):
        wg, wu, wd, ce = wexp
        y = _expert_mlp(wg.astype(dt), wu.astype(dt), wd.astype(dt), x)
        return acc + y * ce[:, None], None

    acc0 = jnp.zeros_like(x)
    if cfg.unroll_layers:   # cost-accounting mode: exact FLOP counts
        acc = acc0
        for e in range(cfg.n_experts):
            acc, _ = body(acc, (params["w_gate"][e], params["w_up"][e],
                                params["w_down"][e], combine.T[e]))
        return acc, aux, held
    with jax.named_scope("experts"):
        out, _ = jax.lax.scan(
            body, acc0,
            (params["w_gate"], params["w_up"], params["w_down"], combine.T))
    return out, aux, held


def moe_ragged(params, x, cfg: ModelConfig):
    """Optimized dispatch: sort by expert + grouped (ragged) matmuls.

    Assignments to experts not held sort last, past every group, and the
    grouped matmuls leave them out.  Token order within an expert group
    follows the stable argsort, so the scatter-add back is exact.  Designed
    to sit inside shard_map so the sort is shard-local on TPU.
    """
    T, d = x.shape
    dt = x.dtype
    E, k = cfg.n_experts, cfg.top_k
    weights, local, held, aux = _routed(params, x, cfg)

    flat_expert = local.reshape(-1)                     # (T*k,)
    flat_weight = weights.reshape(-1)                   # (T*k,)
    flat_token = jnp.repeat(jnp.arange(T), k)           # (T*k,)
    with jax.named_scope("experts"):
        order = jnp.argsort(flat_expert, stable=True)
        sorted_expert = flat_expert[order]
        sorted_token = flat_token[order]
        group_sizes = jnp.bincount(sorted_expert, length=E).astype(jnp.int32)

        xs = x[sorted_token]                            # (T*k, d)
        h = (jax.nn.silu(jax.lax.ragged_dot(
                xs, params["w_gate"].astype(dt), group_sizes))
             * jax.lax.ragged_dot(xs, params["w_up"].astype(dt), group_sizes))
        ys = jax.lax.ragged_dot(h, params["w_down"].astype(dt),
                                group_sizes)            # (T*k, d)
        # rows past the groups (experts not held) carry no defined value
        ys = jnp.where((sorted_expert < E)[:, None],
                       ys * flat_weight[order][:, None].astype(dt), 0)
        out = jnp.zeros((T, d), dt).at[sorted_token].add(ys)
    return out, aux, held


def moe_dense_einsum(params, x, cfg: ModelConfig):
    """Decode-path dispatch: all experts via one einsum, combine contracting
    the (model-sharded) expert dim.

    Expert weights stay sharded on E; outputs are reduced across the model
    axis (an all-reduce of (T, d) — KBs at decode) instead of the weight
    all-gather that slicing a sharded expert stack forces (GBs).  Memory is
    O(T * E * f), so this is for small-T (decode) only.
    """
    T, d = x.shape
    dt = x.dtype
    weights, local, held, aux = _routed(params, x, cfg)
    combine = _combine(weights, local, T, cfg.n_experts, dt)
    h = (jax.nn.silu(jnp.einsum("td,edf->tef", x, params["w_gate"].astype(dt)))
         * jnp.einsum("td,edf->tef", x, params["w_up"].astype(dt)))
    y = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(dt))
    out = jnp.einsum("ted,te->td", y, combine)
    return out, aux, held


def _ambient_mesh():
    try:
        from jax._src.mesh import thread_resources
        mesh = thread_resources.env.physical_mesh
        return mesh if mesh.devices.size > 1 else None
    except Exception:  # noqa: BLE001
        return None


def moe_ragged_local(params, x, cfg: ModelConfig):
    """Shard-local ragged dispatch (the §Perf fix for the global-sort blowup).

    shard_map pins the token dim to the data axes, so argsort / gather /
    scatter stay device-local; expert weights remain on the auto "model" axis
    (f-dim or expert-dim sharded) and the grouped matmuls partition over it.
    Falls back to the plain ragged path off-mesh (CPU tests).
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return moe_ragged(params, x, cfg)
    from jax.sharding import PartitionSpec as P
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(xs, p):
        y, aux, held = moe_ragged(p, xs, cfg)
        return y, aux[None], held

    y, aux, held = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(da, None), P()),
        out_specs=(P(da, None), P(da), P(da, None)),
        check_vma=False, axis_names=set(da))(x, params)
    return y, jnp.mean(aux), held


@jax.named_scope("moe")
def apply_moe(params, x, cfg: ModelConfig, impl: str = "dense_scan", *,
              count: bool = False):
    """x: (B, S, d) -> (y (B, S, d), aux loss scalar), and with ``count``
    also each token's number of assignments to the held experts, (B, S)
    int32."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    if impl == "ragged":
        y, aux, held = moe_ragged(params, flat, cfg)
    elif impl == "ragged_local":
        y, aux, held = moe_ragged_local(params, flat, cfg)
    elif impl == "dense_einsum":
        y, aux, held = moe_dense_einsum(params, flat, cfg)
    else:
        y, aux, held = moe_dense_scan(params, flat, cfg)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            y = y + apply_mlp(params["shared"], flat, cfg)
    y = y.reshape(B, S, d)
    if count:
        return y, aux, jnp.sum(held, axis=-1).reshape(B, S).astype(jnp.int32)
    return y, aux
