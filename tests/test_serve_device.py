"""Device-resident serving engine: SDK/core parity (C2/C3), max-age boundary
semantics, one-host-sync-per-tick, and the bucketed-prefill shape policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import generate_trajectories, init_delphi
from repro.core.parity import check_trajectory, logit_tolerance
from repro.sdk import InferenceSession, export_model
from repro.serve import BatchedEngine, Request
from repro.serve import engine as engine_mod


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=96, max_seq_len=48)
    params = init_delphi(cfg, jax.random.PRNGKey(7))
    d = str(tmp_path_factory.mktemp("artifact"))
    export_model(params, cfg, d)
    return params, cfg, d


TOKS = [3, 10, 20]
AGES = [0.0, 15.0, 28.0]


def _uniforms(max_new, V, seed=42):
    rng = np.random.default_rng(seed)
    return rng.uniform(size=(max_new, V)).astype(np.float32)


def _run_engine(params, cfg, *, uniforms, max_new=6, max_context=64,
                sampler="jnp"):
    eng = BatchedEngine(params, cfg, slots=1, max_context=max_context,
                        sampler=sampler)
    eng.submit(Request(tokens=np.asarray(TOKS, np.int32),
                       ages=np.asarray(AGES, np.float32),
                       max_new=max_new, uniforms=uniforms))
    done = eng.run()
    assert len(done) == 1 and done[0].done
    return done[0], eng


def test_engine_vs_sdk_parity(setup):
    """Claim C2/C3: engine in-graph generation and the SDK host loop, under
    the same injected uniforms, both follow the float32 reference logits.

    The two are separately compiled (in-graph jit vs the exported graph
    driven from the host), so each is held to the reference along its own
    path (``repro.core.parity``) instead of to the other: where two
    candidates' waiting times tie within fp noise, either may win."""
    params, cfg, d = setup
    max_new = 6
    u = _uniforms(max_new, cfg.vocab_size)
    # max_age=1e9 so neither path censors: pure sampling parity first
    sess = InferenceSession(d)
    sdk = sess.generate_trajectory(TOKS, AGES, max_new=max_new,
                                   uniforms=u, max_age=1e9)
    req, _ = _run_engine(params, cfg.replace(max_age=1e9), uniforms=u,
                         max_new=max_new)
    tol = logit_tolerance(cfg)
    for toks, ages in ((req.out_tokens, req.out_ages),
                       (sdk["tokens"], sdk["ages"])):
        assert len(toks) > 0
        check_trajectory(params, cfg, TOKS, AGES, toks, ages, u, tol=tol,
                         max_new=max_new, max_age=1e9)
    assert all(b >= a for a, b in zip(req.out_ages, req.out_ages[1:]))


def test_engine_vs_sdk_max_age_boundary(setup):
    """The max-age termination boundary: an event whose waiting time crosses
    max_age is censored BEFORE being emitted, in both runtimes."""
    params, cfg, d = setup
    max_new = 6
    u = _uniforms(max_new, cfg.vocab_size)
    sess = InferenceSession(d)
    free = sess.generate_trajectory(TOKS, AGES, max_new=max_new,
                                    uniforms=u, max_age=1e9)
    ages = free["ages"]
    assert len(ages) >= 3
    # max_age strictly between event k-1 and event k -> exactly k emitted.
    # k=2: early enough that the ~decade inter-event gaps dwarf any fp
    # age drift between the two runtimes, so both censor at the same event.
    k = 2
    boundary = (ages[k - 1] + ages[k]) / 2
    sdk = sess.generate_trajectory(TOKS, AGES, max_new=max_new,
                                   uniforms=u, max_age=boundary)
    assert len(sdk["tokens"]) == k
    req, _ = _run_engine(params, cfg.replace(max_age=boundary), uniforms=u,
                         max_new=max_new)
    assert req.out_tokens == sdk["tokens"]
    assert len(req.out_tokens) == k
    assert all(a <= boundary for a in req.out_ages)


def test_engine_vs_core_parity(setup):
    """Engine ticks == in-graph batched generator under the same uniforms."""
    params, cfg, _ = setup
    max_new = 6
    u = _uniforms(max_new, cfg.vocab_size, seed=5)
    cfg9 = cfg.replace(max_age=1e9)
    req, _ = _run_engine(params, cfg9, uniforms=u, max_new=max_new,
                         max_context=len(TOKS) + max_new)
    t = jnp.asarray(np.asarray(TOKS, np.int32)[None])
    a = jnp.asarray(np.asarray(AGES, np.float32)[None])
    core = generate_trajectories(params, cfg9, t, a, jax.random.PRNGKey(0),
                                 max_new=max_new, max_age=1e9,
                                 uniforms=jnp.asarray(u)[None])
    n = len(req.out_tokens)
    assert n == int(core["n_generated"][0])
    S = len(TOKS)
    assert req.out_tokens == core["tokens"][0, S:S + n].tolist()
    np.testing.assert_allclose(req.out_ages, core["ages"][0, S:S + n],
                               rtol=0.08)


def test_pallas_sampler_path_matches_jnp(setup):
    """sampler="pallas" routes eq. 1 through the fused kernel (interpret on
    CPU) and must reproduce the jnp reference path bit-exactly."""
    params, cfg, _ = setup
    u = _uniforms(6, cfg.vocab_size, seed=9)
    cfg9 = cfg.replace(max_age=1e9)
    r_jnp, _ = _run_engine(params, cfg9, uniforms=u, max_new=6)
    r_pal, _ = _run_engine(params, cfg9, uniforms=u, max_new=6,
                           sampler="pallas")
    assert r_jnp.out_tokens == r_pal.out_tokens
    np.testing.assert_allclose(r_jnp.out_ages, r_pal.out_ages, rtol=1e-5)


def test_one_host_sync_per_tick(setup, monkeypatch):
    """The device-resident loop transfers exactly ONE packed array per tick
    (plus one per admission batch) — counted at the module's only
    device->host boundary."""
    params, cfg, _ = setup
    calls = []
    orig = engine_mod._to_host

    def counting(x):
        calls.append(x.shape)
        return orig(x)
    monkeypatch.setattr(engine_mod, "_to_host", counting)

    eng = BatchedEngine(params, cfg, slots=2, max_context=64)
    for i in range(5):
        S = 3 + (i % 3)
        eng.submit(Request(tokens=np.arange(3, 3 + S, dtype=np.int32),
                           ages=np.linspace(0, 20 + i, S).astype(np.float32),
                           max_new=4))
    done = eng.run()
    assert len(done) == 5
    assert eng.ticks > 0
    assert len(calls) == eng.host_syncs == eng.ticks + eng.admit_batches
    # every transfer is the packed (4, B) tick/admission result, nothing else
    assert all(s[0] == 4 for s in calls)


def test_bucketed_prefill_shape_policy(setup):
    """Admissions compile a small fixed set of (batch, seq) buckets instead
    of one shape per prompt length."""
    params, cfg, _ = setup
    eng = BatchedEngine(params, cfg, slots=4, max_context=64)
    lengths = list(range(3, 19))          # 16 distinct prompt lengths
    for S in lengths:
        eng.submit(Request(tokens=np.arange(3, 3 + S, dtype=np.int32) % 90,
                           ages=np.linspace(0, 25, S).astype(np.float32),
                           max_new=3))
    done = eng.run()
    assert len(done) == len(lengths)
    assert len(eng.prefill_shapes) < len(set(lengths))
    for nb, sb in eng.prefill_shapes:
        assert sb in (8, 16, 32)          # power-of-two seq buckets
        assert nb in (1, 2, 4)            # power-of-two batch buckets


def test_seq_bucket_never_exceeds_ring_width(setup):
    """A prompt that fits the ring cache must not lose context to bucket
    rounding: 33 tokens in a 48-wide cache would bucket to 64 (> W) and the
    S>W ring pack would silently evict positions 0..15."""
    params, cfg, d = setup
    S = 33
    toks = (np.arange(3, 3 + S) % 90).astype(np.int32)
    ages = np.linspace(0.0, 30.0, S).astype(np.float32)
    max_new = 4
    u = _uniforms(max_new, cfg.vocab_size, seed=13)
    sess = InferenceSession(d)
    sdk = sess.generate_trajectory(list(toks), list(ages), max_new=max_new,
                                   uniforms=u, max_age=1e9)
    eng = BatchedEngine(params, cfg.replace(max_age=1e9), slots=1,
                        max_context=48)
    eng.submit(Request(tokens=toks, ages=ages, max_new=max_new, uniforms=u))
    done = eng.run()
    assert [(nb, sb) for nb, sb in eng.prefill_shapes] == [(1, 48)]
    assert done[0].out_tokens == sdk["tokens"]


def test_mixed_injected_and_rng_requests(setup):
    """Injected-uniform and RNG requests submitted together serialize into
    separate slot cohorts instead of crashing the tick."""
    params, cfg, _ = setup
    eng = BatchedEngine(params, cfg, slots=2, max_context=64)
    u = _uniforms(4, cfg.vocab_size, seed=3)
    eng.submit(Request(tokens=np.asarray(TOKS, np.int32),
                       ages=np.asarray(AGES, np.float32),
                       max_new=4, uniforms=u))
    eng.submit(Request(tokens=np.asarray(TOKS, np.int32),
                       ages=np.asarray(AGES, np.float32), max_new=4))
    done = eng.run()
    assert len(done) == 2
    assert all(r.done for r in done)


def test_lm_mode_device_engine():
    """Generic-LM slot decoding on the device-resident path (rope + gumbel
    categorical), including refill past slot capacity."""
    from repro.models import init_params
    cfg = get_config("tinyllama-1.1b", reduced=True).replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = BatchedEngine(params, cfg, slots=2, max_context=48)
    for i in range(3):
        eng.submit(Request(tokens=np.arange(1, 7 + i, dtype=np.int32),
                           max_new=5))
    done = eng.run()
    assert len(done) == 3
    for r in done:
        assert len(r.out_tokens) == 5
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens)
