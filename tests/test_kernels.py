"""Pallas kernel sweeps vs the ref.py oracles (interpret mode on CPU).

Each kernel is swept over shapes and dtypes per the mandate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.kernels import (flash_attention, paged_decode_attention, ssd_intra,
                           suffix_prefill_attention, tte_sample)
from repro.kernels import ref

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # (B, Hq, Hkv, S, hd, window, dtype)
    (1, 1, 1, 128, 64, None, jnp.float32),
    (2, 4, 2, 256, 64, None, jnp.float32),
    (2, 4, 1, 256, 32, None, jnp.float32),      # strong GQA
    (1, 2, 2, 384, 128, 100, jnp.float32),      # sliding window
    (1, 2, 2, 200, 64, None, jnp.float32),      # ragged -> padding path
    (2, 2, 2, 256, 64, None, jnp.bfloat16),     # bf16 in/out
    (1, 8, 2, 128, 16, 40, jnp.float32),
]


@pytest.mark.parametrize("B,Hq,Hkv,S,hd,window,dtype", FLASH_CASES)
def test_flash_vs_ref(key, B, Hq, Hkv, S, hd, window, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, window=window, bq=128, bk=128)
    r = ref.flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32), r, atol=atol)


def test_flash_bidirectional(key):
    B, H, S, hd = 1, 2, 256, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    out = flash_attention(q, k, v, causal=False)
    r = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, r, atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention (block-table gather + softmax in one pass)
# ---------------------------------------------------------------------------
PAGED_CASES = [
    # (B, Hkv, G, hd, bs, nbs, window, dtype)
    (1, 1, 1, 32, 4, 2, None, jnp.float32),
    (2, 2, 2, 16, 4, 4, None, jnp.float32),
    (3, 1, 4, 64, 8, 2, None, jnp.float32),     # strong GQA
    (2, 2, 1, 16, 4, 4, 6, jnp.float32),        # sliding window
    (2, 2, 2, 32, 8, 4, None, jnp.bfloat16),    # bf16 pool
]


def _paged_inputs(key, B, Hkv, G, hd, bs, nbs, dtype, *, wrap=False):
    """A consistent pool: slot b holds n_tok sequential tokens, blockwise."""
    rng = np.random.default_rng(
        int(jax.random.randint(key, (), 0, 2**31 - 1)))
    NB = 1 + B * nbs
    W = nbs * bs
    # one layer of the held pool: a token-major row per block
    k_pool = jnp.asarray(rng.normal(size=(NB, bs * Hkv * hd))).astype(dtype)
    v_pool = jnp.asarray(rng.normal(size=(NB, bs * Hkv * hd))).astype(dtype)
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, hd))).astype(dtype)
    table = np.full((B, nbs), -1, np.int32)
    pos = np.full((NB, bs), -1, np.int32)
    step = np.zeros((B,), np.int32)
    nxt = 1
    for b in range(B):
        n_tok = int(rng.integers(1, W))
        step[b] = n_tok + (W if wrap else 0)
        nalloc = -(-n_tok // bs)
        for jb in range(nalloc):
            table[b, jb] = nxt
            for o in range(bs):
                p = jb * bs + o
                if p < n_tok:
                    pos[nxt, o] = p + (W if wrap else 0)
            nxt += 1
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(pos), \
        jnp.asarray(step)


@pytest.mark.parametrize("B,Hkv,G,hd,bs,nbs,window,dtype", PAGED_CASES)
def test_paged_decode_vs_ref(key, B, Hkv, G, hd, bs, nbs, window, dtype):
    q, k_pool, v_pool, table, pos, step = _paged_inputs(
        key, B, Hkv, G, hd, bs, nbs, dtype)
    out = paged_decode_attention(q, k_pool, v_pool, table, pos, step,
                                 window=window)
    r = ref.paged_decode_attention_ref(
        q.reshape(B, Hkv, G, hd).astype(jnp.float32),
        k_pool.astype(jnp.float32), v_pool.astype(jnp.float32),
        table, pos, step, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, Hkv, G, hd), np.float32), r, atol=atol)


def test_paged_decode_wrapped_ring_eviction(key):
    """step >= W with stale pre-wrap positions still in the pool: the
    kernel's `p > step - W` eviction mask must agree with the oracle (the
    one clause plain causal masking doesn't cover), and evicted entries
    must not contribute at all."""
    B, Hkv, G, hd, bs, nbs = 2, 2, 2, 16, 4, 4
    W = nbs * bs
    q, k_pool, v_pool, table, pos, step = _paged_inputs(
        key, B, Hkv, G, hd, bs, nbs, jnp.float32, wrap=True)
    # plant stale entries: every other valid position falls back a full
    # ring width, landing at or below step - W (evicted)
    pos_np = np.asarray(pos).copy()
    valid = pos_np >= 0
    stale = valid & (np.arange(pos_np.shape[1])[None, :] % 2 == 0)
    pos_np[stale] -= W
    pos = jnp.asarray(pos_np)
    assert (pos_np[stale] <= int(step.max()) - W).all()
    out = paged_decode_attention(q, k_pool, v_pool, table, pos, step)
    r = ref.paged_decode_attention_ref(
        q.reshape(B, Hkv, G, hd), k_pool, v_pool, table, pos, step)
    np.testing.assert_allclose(np.asarray(out.reshape(B, Hkv, G, hd)), r,
                               atol=2e-5)
    # pushing evicted entries further into the past changes nothing
    pos2 = jnp.asarray(np.where(stale, pos_np - 5 * W, pos_np))
    out2 = paged_decode_attention(q, k_pool, v_pool, table, pos2, step)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_paged_decode_skips_unallocated_blocks(key):
    """Unallocated table entries are index-clamped to the trash block; its
    contents must not leak into the output (pl.when skip)."""
    B, Hkv, G, hd, bs, nbs = 1, 1, 1, 16, 4, 4
    q, k_pool, v_pool, table, pos, step = _paged_inputs(
        key, B, Hkv, G, hd, bs, nbs, jnp.float32)
    out = paged_decode_attention(q, k_pool, v_pool, table, pos, step)
    # poison the trash block: output must be unchanged
    k2 = k_pool.at[0].set(1e9)
    v2 = v_pool.at[0].set(1e9)
    out2 = paged_decode_attention(q, k2, v2, table, pos, step)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ---------------------------------------------------------------------------
# suffix prefill attention (chunked prefill over cached context)
# ---------------------------------------------------------------------------
SUFFIX_CASES = [
    # (B, Sc, C, Hkv, G, hd, window, dtype)
    (1, 16, 0, 1, 1, 32, None, jnp.float32),     # chunk at the prompt head
    (2, 16, 32, 2, 2, 32, None, jnp.float32),    # GQA mid-prompt chunk
    (1, 8, 24, 1, 4, 64, None, jnp.float32),     # strong GQA
    (2, 16, 16, 2, 1, 16, 12, jnp.float32),      # sliding window
    (1, 16, 32, 2, 2, 32, None, jnp.bfloat16),   # bf16 cache
]


@pytest.mark.parametrize("B,Sc,C,Hkv,G,hd,window,dtype", SUFFIX_CASES)
def test_suffix_prefill_vs_ref(key, B, Sc, C, Hkv, G, hd, window, dtype):
    """suffix_prefill_attention vs ref.suffix_prefill_attention_ref over
    right-padded contexts (trash slots = pos -1) and padded chunk tails."""
    ks = jax.random.split(key, 5)
    Hq = Hkv * G
    q = jax.random.normal(ks[0], (B, Sc, Hq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sc, Hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sc, Hkv, hd)).astype(dtype)
    ctx_k = jax.random.normal(ks[3], (B, C, Hkv, hd)).astype(dtype)
    ctx_v = jax.random.normal(ks[4], (B, C, Hkv, hd)).astype(dtype)
    n_ctx = max(C - 3, 0)
    n_q = Sc - 2
    ctx_pos = np.full((B, C), -1, np.int32)
    ctx_pos[:, :n_ctx] = np.arange(n_ctx)
    q_pos = np.full((B, Sc), -1, np.int32)
    q_pos[:, :n_q] = n_ctx + np.arange(n_q)
    out = suffix_prefill_attention(q, k, v, ctx_k, ctx_v,
                                   jnp.asarray(q_pos), jnp.asarray(ctx_pos),
                                   window=window, q_per_kv=G)
    r = ref.suffix_prefill_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        ctx_k.astype(jnp.float32), ctx_v.astype(jnp.float32),
        jnp.asarray(q_pos), jnp.asarray(ctx_pos), window=window, q_per_kv=G)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out[:, :n_q], np.float32),
                               np.asarray(r[:, :n_q]), atol=atol)


def test_suffix_prefill_composes_with_flash(key):
    """A mid-prompt suffix chunk attending over its prefix-as-context must
    equal the same rows of ONE full flash_attention pass over the whole
    prompt — the invariant that makes chunked prefill a pure scheduling
    change."""
    B, S, C, H, hd = 1, 48, 32, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    full = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), causal=True)
    pos = jnp.arange(S)[None]
    out = suffix_prefill_attention(q[:, C:], k[:, C:], v[:, C:],
                                   k[:, :C], v[:, :C],
                                   pos[:, C:], pos[:, :C])
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(full.transpose(0, 2, 1, 3)[:, C:]), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD intra-chunk
# ---------------------------------------------------------------------------
SSD_CASES = [
    # (BH, C, Q, P, N, dtype)
    (1, 1, 16, 8, 8, jnp.float32),
    (4, 3, 32, 16, 32, jnp.float32),
    (2, 2, 128, 64, 128, jnp.float32),   # production tile (mamba2-780m)
    (2, 2, 64, 32, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("BH,C,Q,P,N,dtype", SSD_CASES)
def test_ssd_intra_vs_ref(key, BH, C, Q, P, N, dtype):
    ks = jax.random.split(key, 4)
    xdt = jax.random.normal(ks[0], (BH, C, Q, P)).astype(dtype)
    Bm = jax.random.normal(ks[1], (BH, C, Q, N)).astype(dtype)
    Cm = jax.random.normal(ks[2], (BH, C, Q, N)).astype(dtype)
    cum = -jnp.cumsum(jax.random.uniform(ks[3], (BH, C, Q), maxval=0.2), -1)
    y, st_ = ssd_intra(xdt, Bm, Cm, cum)
    atol = 1e-4 if dtype == jnp.float32 else 0.15
    for b in range(BH):
        for c in range(C):
            yr, sr = ref.ssd_intra_ref(xdt[b, c].astype(jnp.float32),
                                       Bm[b, c].astype(jnp.float32),
                                       Cm[b, c].astype(jnp.float32),
                                       cum[b, c])
            np.testing.assert_allclose(y[b, c], yr, atol=atol)
            np.testing.assert_allclose(st_[b, c], sr, atol=atol)


# ---------------------------------------------------------------------------
# time-to-event sampler
# ---------------------------------------------------------------------------
TTE_CASES = [
    (1, 64), (3, 1289), (2, 2048), (1, 50304),
    (2, 100),   # heavy padding path
    (16, 1289), (8, 4096),   # 8-row blocks: two row blocks; two vocab tiles
]


@pytest.mark.parametrize("B,V", TTE_CASES)
def test_tte_vs_ref(key, B, V):
    ks = jax.random.split(key, 2)
    logits = jax.random.normal(ks[0], (B, V)) * 3
    u = jax.random.uniform(ks[1], (B, V))
    e1, t1 = tte_sample(logits, u)
    e2, t2 = ref.tte_sample_ref(logits, u)
    assert e1.tolist() == e2.tolist()
    np.testing.assert_allclose(t1, t2, rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), V=st.integers(5, 700))
def test_tte_property_sweep(seed, V):
    k = jax.random.PRNGKey(seed)
    logits = jax.random.normal(k, (1, V)) * 2
    u = jax.random.uniform(jax.random.fold_in(k, 1), (1, V))
    e1, t1 = tte_sample(logits, u)
    e2, t2 = ref.tte_sample_ref(logits, u)
    assert int(e1[0]) == int(e2[0])
    np.testing.assert_allclose(t1, t2, rtol=1e-6)


def test_tte_matches_core_sampler(key):
    """Kernel == the in-graph sampler used by serving (one mechanism,
    three consumers: kernel, core, SDK)."""
    from repro.core import sample_next_event
    logits = jax.random.normal(key, (4, 999)) * 2
    u = jax.random.uniform(jax.random.fold_in(key, 1), (4, 999))
    e_k, t_k = tte_sample(logits, u)
    e_c, t_c = sample_next_event(logits, u)
    assert e_k.tolist() == e_c.tolist()
    np.testing.assert_allclose(t_k, t_c, rtol=1e-5)
