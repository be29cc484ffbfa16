"""Latent attention (MLA) and the expert share of Moonlight-16B-A3B at a
size the CPU holds: the absorbed decode equals expanded attention, and the
paged engine's prefill and decode logits (monolithic, chunked, after a
prefix hit) match the benchmark's plain float32 reference forward.

The cut keeps every mechanism: one dense layer and two MoE layers, 4 heads,
latent rank 32 with nope/rope/value widths 16/8/16 (all different, so a
slice taken at the wrong width shows), 8 routed experts of which experts
2-5 are held, top-3 sigmoid routing with a correction bias.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as A
from repro.models import decode_step, forward
from repro.serve import BatchedEngine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(n_layers=3, d_model=128, n_heads=4, n_kv_heads=4, head_dim=16,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, d_ff=256, moe_d_ff=64, n_experts=4,
             n_router_experts=8, expert_offset=2, top_k=3, vocab_size=512)
#: float32 program against the float32 (HIGHEST) reference on the same
#: weights: only the order of reductions differs (about 1e-5 measured);
#: a wrong slice, scale or routing moves logits by 0.1 or more
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference (imports nothing of the program),
    its seeded weights and the program's configuration at the cut."""
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    from harness import program
    spec = importlib.util.spec_from_file_location(
        "moonlight_reference",
        os.path.join(ROOT, "perfbench", "configs", "moonlight-16b-a3b.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "moonlight-16b-a3b.json")) as f:
        cfg = json.load(f)
    cfg["model"].update(SMALL)
    w = ref.make_weights(cfg, jax.random.PRNGKey(1))
    mcfg = program.model_config(cfg, dtype="float32")
    params = ref.to_program(w)
    program.check_tree(params, mcfg)
    return ref, cfg, w, mcfg, params


@pytest.mark.parametrize("paged", [False, True], ids=["ring", "paged"])
def test_absorbed_decode_equals_expanded_attention(paged):
    cfg = get_config("moonlight-16b-a3b", reduced=True).replace(
        dtype="float32", qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16)
    p = A.init_mla(jax.random.PRNGKey(0), cfg)
    p["kv_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), p["kv_norm"]["scale"].shape)
    S, W, bs = 37, 64, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    pos = jnp.arange(S)
    full, _ = A.mla_attention(p, x, pos, cfg, mode="dense")
    _, ring = A.mla_attention(p, x[:, :S - 1], pos[:S - 1], cfg,
                              mode="prefill", cache_width=W)
    rw = cfg.kv_lora_rank + cfg.qk_rope_head_dim       # one latent row
    assert ring.k.shape == (2, 1, W, rw) and ring.v.shape[-1] == 0
    cache = ring
    if paged:       # the same rows behind a block table, out of order
        nb = W // bs
        ids = np.arange(1, 2 * nb + 1)[::-1].reshape(2, nb)
        k = jnp.zeros((1, 2 * nb + 1, bs * rw)).at[0, ids].set(
            ring.k.reshape(2, nb, bs * rw))          # one row a block
        ppos = jnp.full((2 * nb + 1, bs), -1, jnp.int32).at[ids].set(
            ring.pos.reshape(2, nb, bs))
        table = jnp.asarray(ids, jnp.int32)
        blocks, ring_pos = A.paged_ring_index(ppos, table)
        cache = A.PagedLayerView(k, k[..., :0], 0, blocks, ring_pos, heads=1)
    step = jnp.full((2,), S - 1, jnp.int32)
    out, new = A.mla_attention(p, x[:, S - 1:], None, cfg, mode="decode",
                               cache=cache, step=step, defer_write=True)
    np.testing.assert_allclose(out[:, 0], full[:, S - 1], atol=1e-5)
    rows = A._mla_rows(p, x, pos, cfg)
    np.testing.assert_allclose(new[0][:, 0, 0], rows[:, S - 1], atol=1e-6)


def _serve(eng, reqs, steps=None):
    """Drive the engine in the foreground; -> {request index: [(n, logits
    row)]}: the tick's (V,) logits of each decoded position, ``n`` the
    request's events after it."""
    seen = {i: [] for i in range(len(reqs))}
    while eng.pending or any(r is not None for r in eng.slot_req):
        before = {id(r): len(r.out_tokens or ()) for r in eng.slot_req
                  if r is not None}
        eng.step()
        lg = np.asarray(eng.last_logits)
        for s, r in enumerate(eng.slot_req):
            if r is None or id(r) not in before:
                continue
            n = len(r.out_tokens)
            if n > before[id(r)] and before[id(r)] > 0:
                seen[reqs.index(r)].append((n, lg[s]))
    return seen


def _check(ref, cfg, w, eng, reqs, seen):
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.tokens, r.out_tokens]).astype(np.int32)
        lg = np.asarray(ref.logits(cfg, w, jnp.asarray(seq[None])))[0]
        S = len(r.tokens)
        np.testing.assert_allclose(eng.prefill_logits(r.tokens), lg[S - 1],
                                   atol=LOGIT_TOL)
        assert seen[i], "no decoded position"
        for n, row in seen[i]:
            # the tick that emitted event n-1 read event n-2 at S + n - 2
            np.testing.assert_allclose(row, lg[S + n - 2], atol=LOGIT_TOL,
                                       err_msg=f"request {i} event {n - 1}")


def test_paged_engine_matches_reference_forward(reference):
    ref, cfg, w, mcfg, params = reference
    eng = BatchedEngine(params, mcfg, slots=4, max_context=64,
                        temperature=0.0, cache="paged")
    assert eng.cache["self"].k.shape[2:] == (16 * 40,)    # one row a token
    assert eng.cache["self"].v.shape[2:] == (0,)
    rng = np.random.default_rng(0)
    reqs = [Request(tokens=rng.integers(3, 512, n).astype(np.int32),
                    max_new=6) for n in (5, 17, 33)]
    for r in reqs:
        eng.submit(r)
    seen = _serve(eng, reqs)
    _check(ref, cfg, w, eng, reqs, seen)
    # every tick's assignments to the 4 held experts, over 2 MoE layers
    assert 0 < eng.health_stats()["expert_tokens"] <= eng.slot_ticks * 2 * 3


def test_prefix_hit_and_chunked_admissions_match_reference(reference):
    ref, cfg, w, mcfg, params = reference
    eng = BatchedEngine(params, mcfg, slots=2, max_context=64,
                        temperature=0.0, cache="paged", prefix_cache=True,
                        prefill_chunk_tokens=16)
    rng = np.random.default_rng(1)
    head = rng.integers(3, 512, 34).astype(np.int32)
    first = Request(tokens=head, max_new=4)
    eng.submit(first)
    _serve(eng, [first])
    # the second prompt extends the first: its two full blocks come from
    # the prefix index and only the suffix is prefilled, in chunks
    second = Request(tokens=np.concatenate(
        [head, rng.integers(3, 512, 9).astype(np.int32)]), max_new=5)
    eng.submit(second)
    seen = _serve(eng, [second])
    assert eng.prefix.partial_hits >= 1 and eng.suffix_tokens_saved >= 32
    assert eng.chunked_prefills == 2
    _check(ref, cfg, w, eng, [second], seen)


@pytest.mark.parametrize("unroll", [False, True], ids=["scanned", "unrolled"])
def test_ring_decode_after_dense_head_matches_reference(reference, unroll):
    """Decode over a ring cache whose layer axis holds the dense layer and
    then the MoE layers, scanned or unrolled (the cost-accounting mode),
    matches the reference forward and counts each token's held
    assignments: at most top-3 in each of the two MoE layers."""
    ref, cfg, w, mcfg, params = reference
    mcfg = mcfg.replace(unroll_layers=unroll)
    toks = jnp.asarray(np.random.default_rng(2).integers(3, 512, (2, 12)),
                       jnp.int32)
    pre = forward(params, mcfg, {"tokens": toks[:, :11]}, mode="prefill",
                  cache_width=16)
    d = decode_step(params, mcfg, pre["cache"], {"tokens": toks[:, 11:]}, 11)
    lg = np.asarray(ref.logits(cfg, w, toks))
    np.testing.assert_allclose(d["logits"][:, 0], lg[:, 11], atol=LOGIT_TOL)
    held = np.asarray(d["expert_tokens"])
    assert held.shape == (2,) and held.dtype == np.int32
    assert np.all((held >= 0) & (held <= 2 * 3)), held
