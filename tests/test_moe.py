"""MoE: dispatch-implementation equivalence, routing invariants, sharding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import apply_mlp
from repro.models.moe import apply_moe, init_moe, route


@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_config("olmoe-1b-7b", reduced=True).replace(dtype="float32")
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    return cfg, params, x


def test_impl_equivalence(moe_setup):
    """All four dispatch implementations agree (the §Perf variants are
    semantics-preserving)."""
    cfg, params, x = moe_setup
    y1, a1 = apply_moe(params, x, cfg, impl="dense_scan")
    for impl in ("ragged", "dense_einsum", "ragged_local"):
        y2, a2 = apply_moe(params, x, cfg, impl=impl)
        np.testing.assert_allclose(y1, y2, atol=1e-5, err_msg=impl)
        np.testing.assert_allclose(a1, a2, rtol=1e-6, err_msg=impl)


def test_router_normalized(moe_setup):
    cfg, params, x = moe_setup
    w, e, aux = route(params, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-5)
    assert int(jnp.min(e)) >= 0 and int(jnp.max(e)) < cfg.n_experts
    # top-k experts are distinct per token
    assert bool((jnp.sort(e, -1)[:, 1:] != jnp.sort(e, -1)[:, :-1]).all())
    assert float(aux) > 0


def test_aux_loss_balanced_lower_bound(moe_setup):
    """Aux loss is minimized (== top_k) under perfectly uniform routing."""
    cfg, params, x = moe_setup
    # uniform router: zero weights
    params2 = dict(params)
    params2["router"] = jnp.zeros_like(params["router"])
    _, _, aux = route(params2, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(float(aux), cfg.top_k, rtol=0.2)


def test_shared_experts_contribute(moe_setup):
    cfg, params, x = moe_setup
    cfg_shared = get_config("qwen2-moe-a2.7b", reduced=True).replace(
        dtype="float32")
    p = init_moe(jax.random.PRNGKey(3), cfg_shared)
    assert "shared" in p
    y, _ = apply_moe(p, x[..., :cfg_shared.d_model], cfg_shared)
    p0 = dict(p)
    p0["shared"] = jax.tree_util.tree_map(jnp.zeros_like, p["shared"])
    y0, _ = apply_moe(p0, x[..., :cfg_shared.d_model], cfg_shared)
    assert float(jnp.max(jnp.abs(y - y0))) > 1e-4


def test_expert_gradients_flow(moe_setup):
    cfg, params, x = moe_setup
    def loss(p):
        y, aux = apply_moe(p, x, cfg)
        return jnp.sum(y ** 2) + 0.01 * aux
    g = jax.grad(loss)(params)
    gnorm = float(sum(jnp.sum(jnp.abs(v))
                      for v in jax.tree_util.tree_leaves(g)))
    assert np.isfinite(gnorm) and gnorm > 0
    # router must receive gradient (load-balance + combine weights)
    assert float(jnp.sum(jnp.abs(g["router"]))) > 0


@pytest.fixture(scope="module")
def sigmoid_setup():
    """Moonlight-style routing at a small size: 8 routed experts, top-3 by
    sigmoid score plus a correction bias, 1 shared expert."""
    cfg = get_config("moonlight-16b-a3b", reduced=True).replace(
        dtype="float32", n_experts=8, top_k=3)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    params["router_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), params["router_bias"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    return cfg, params, x


def test_sigmoid_routing_picks_by_biased_score_weighs_by_score(sigmoid_setup):
    cfg, params, x = sigmoid_setup
    flat = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
    w, e, _ = route(params, jnp.asarray(flat, jnp.float32), cfg)
    scores = 1 / (1 + np.exp(-flat @ np.asarray(params["router"], np.float64)))
    biased = scores + np.asarray(params["router_bias"], np.float64)
    want = np.argsort(-biased, axis=-1)[:, :cfg.top_k]
    np.testing.assert_array_equal(np.sort(np.asarray(e), -1),
                                  np.sort(want, -1))
    # the bias changes the choice somewhere, so selection did not ignore it
    assert (np.sort(np.argsort(-scores, -1)[:, :cfg.top_k], -1)
            != np.sort(want, -1)).any()
    chosen = np.take_along_axis(scores, np.asarray(e), -1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scaling,
        rtol=1e-5)


@pytest.mark.parametrize("impl", ["dense_scan", "ragged", "dense_einsum"])
def test_expert_shares_sum_to_the_uncut_layer(sigmoid_setup, impl):
    """Two chips' shares (experts 0-3 and 4-7 of 8), with the shared
    expert every chip computes counted once, add up to the uncut layer."""
    cfg, params, x = sigmoid_setup
    whole, _ = apply_moe(params, x, cfg, impl=impl)
    shares, held = [], 0
    for e0 in (0, 4):
        c = cfg.replace(n_experts=4, n_router_experts=8, expert_offset=e0)
        p = dict(params, **{k: params[k][e0:e0 + 4]
                            for k in ("w_gate", "w_up", "w_down")})
        y, _, n = apply_moe(p, x, c, impl=impl, count=True)
        shares.append(y)
        held = held + n
    shared = apply_mlp(params["shared"], x, cfg)
    np.testing.assert_allclose(shares[0] + shares[1] - shared, whole,
                               atol=1e-5)
    # every assignment landed on exactly one share
    np.testing.assert_array_equal(held, cfg.top_k)
