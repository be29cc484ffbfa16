"""Unified client API + artifact spec v2.

Extends the parity pattern of ``tests/test_serve_device.py`` to the new
surfaces: v1 artifacts keep loading and bit-match v2 logits; v2
prefill+decode generation bit-matches the legacy ``InferenceSession`` host
loop and the engine under injected uniforms; all three ``repro.api`` backends
produce bit-identical event sequences; checksum verification reports
per-file status."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (ArtifactBackend, Client, EngineBackend,
                       GenerateRequest, RiskReport, TrajectoryResult)
from repro.configs import get_config
from repro.core import init_delphi
from repro.core.parity import check_trajectory, logit_tolerance
from repro.sdk import (ChecksumError, InferenceSession, Runtime, export_model,
                       read_manifest, verify_checksums)

TOKS = [3, 10, 20]
AGES = [0.0, 15.0, 28.0]


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=96, max_seq_len=48)
    params = init_delphi(cfg, jax.random.PRNGKey(7))
    d2 = str(tmp_path_factory.mktemp("artifact_v2"))
    export_model(params, cfg, d2)
    d1 = str(tmp_path_factory.mktemp("artifact_v1"))
    export_model(params, cfg, d1, spec_version="1")
    return params, cfg, d2, d1


def _uniforms(max_new, V, seed=42):
    rng = np.random.default_rng(seed)
    return rng.uniform(size=(max_new, V)).astype(np.float32)


def _follows_reference(params, cfg, tokens, ages, u, max_new):
    """Separately compiled backends are held to the fp32 reference logits
    along their own paths (``repro.core.parity``), not to each other."""
    assert len(tokens) > 0
    check_trajectory(params, cfg, TOKS, AGES, tokens, ages, u,
                     tol=logit_tolerance(cfg), max_new=max_new, max_age=1e9)


# ---------------------------------------------------------------------------
# Artifact versioning
# ---------------------------------------------------------------------------
def test_v1_artifact_still_loads_and_matches_v2_logits(setup):
    _, cfg, d2, d1 = setup
    rt1, rt2 = Runtime(d1), Runtime(d2)
    assert rt1.spec_version == "1.0" and not rt1.has_decode_graph
    assert rt2.spec_version == "2.0" and rt2.has_decode_graph
    S = cfg.max_seq_len
    t = np.zeros((1, S), np.int32)
    t[0, :3] = TOKS
    a = np.zeros((1, S), np.float32)
    a[0, :3] = AGES
    a[0, 3:] = AGES[-1]
    assert (rt1.run(t, a) == rt2.run(t, a)).all()


def test_v2_manifest_graphs_section(setup):
    _, cfg, d2, d1 = setup
    m = read_manifest(d2)
    assert m["spec_version"] == "2.0"
    g = m["graphs"]
    for name in ("full", "prefill", "decode_step"):
        assert g[name]["file"] in m["files"], name
    assert g["cache"]["n_leaves"] == len(g["cache"]["leaves"]) > 0
    assert g["cache"]["width"] == cfg.max_seq_len
    # decode graph I/O declares the cache explicitly (in AND out)
    assert any(i.get("name") == "cache" for i in g["decode_step"]["inputs"])
    assert any(o.get("name") == "cache" for o in g["decode_step"]["outputs"])
    assert "graphs" not in read_manifest(d1)


def test_v1_artifact_generates_via_full_graph_fallback(setup):
    params, cfg, d2, d1 = setup
    u = _uniforms(5, cfg.vocab_size)
    c1 = Client.from_artifact(d1)
    assert c1.backend.use_decode_graph is False       # auto fallback
    c2 = Client.from_artifact(d2)
    assert c2.backend.use_decode_graph is True
    r1 = c1.generate(tokens=TOKS, ages=AGES, max_new=5, uniforms=u,
                     max_age=1e9)
    r2 = c2.generate(tokens=TOKS, ages=AGES, max_new=5, uniforms=u,
                     max_age=1e9)
    for r in (r1, r2):
        _follows_reference(params, cfg, r.tokens, r.ages, u, 5)
    with pytest.raises(ValueError, match="decode graph"):
        ArtifactBackend(d1, use_decode_graph=True)


# ---------------------------------------------------------------------------
# Prefill+decode parity (the tentpole claim)
# ---------------------------------------------------------------------------
def test_v2_decode_matches_session_full_graph(setup):
    """v2 prefill+decode and the legacy full-graph-per-token host loop both
    follow the fp32 reference logits under the same uniforms (two compiled
    graphs: near-ties may break either way, so neither is the other's
    oracle)."""
    params, cfg, d2, _ = setup
    max_new = 6
    u = _uniforms(max_new, cfg.vocab_size)
    sess = InferenceSession(d2)
    sdk = sess.generate_trajectory(TOKS, AGES, max_new=max_new,
                                   uniforms=u, max_age=1e9)
    res = Client.from_artifact(d2).generate(
        tokens=TOKS, ages=AGES, max_new=max_new, uniforms=u, max_age=1e9)
    _follows_reference(params, cfg, res.tokens, res.ages, u, max_new)
    _follows_reference(params, cfg, sdk["tokens"], sdk["ages"], u, max_new)
    assert res.full_tokens == TOKS + res.tokens
    assert sdk["full_tokens"] == TOKS + sdk["tokens"]


def test_three_backends_bit_identical_tokens(setup):
    """Acceptance: artifact (prefill+decode), engine (in-graph tick), and
    local (in-graph batched) backends, under one injected uniform stream,
    each emit the events the fp32 reference logits give."""
    params, cfg, d2, _ = setup
    max_new = 6
    u = _uniforms(max_new, cfg.vocab_size, seed=5)
    cfg9 = cfg.replace(max_age=1e9)
    req = GenerateRequest(tokens=TOKS, ages=AGES, max_new=max_new, uniforms=u)

    r_art = Client.from_artifact(d2).generate(
        GenerateRequest(tokens=TOKS, ages=AGES, max_new=max_new, uniforms=u,
                        max_age=1e9))
    r_loc = Client.from_params(params, cfg9).generate(req)
    r_eng = Client.serving(params, cfg9, slots=1, max_context=64).generate(req)

    for r in (r_art, r_loc, r_eng):
        _follows_reference(params, cfg, r.tokens, r.ages, u, max_new)
    assert {r_art.backend, r_loc.backend, r_eng.backend} == \
        {"artifact", "local", "engine"}


def test_decode_path_max_age_censoring(setup):
    """The max-age boundary on the decode path: the crossing event is
    censored BEFORE being emitted, exactly like the legacy host loop."""
    _, cfg, d2, _ = setup
    max_new = 6
    u = _uniforms(max_new, cfg.vocab_size)
    client = Client.from_artifact(d2)
    free = client.generate(tokens=TOKS, ages=AGES, max_new=max_new,
                           uniforms=u, max_age=1e9)
    assert len(free.ages) >= 3
    k = 2
    boundary = (free.ages[k - 1] + free.ages[k]) / 2
    cut = client.generate(tokens=TOKS, ages=AGES, max_new=max_new,
                          uniforms=u, max_age=boundary)
    assert cut.tokens == free.tokens[:k]
    assert all(a <= boundary for a in cut.ages)


def test_v2_decode_matches_full_graph_generic_lm(tmp_path):
    """Regression: the exported non-delphi decode graph must receive
    (token, step) in the right argument slots — spec-v2 export used to pass
    the token into the age slot for age_encoding=False configs and crash at
    trace time."""
    from repro.models import init_params
    cfg = get_config("tinyllama-1.1b", reduced=True).replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    d = str(tmp_path / "lm_art")
    from repro.sdk import export_model as export
    export(params, cfg, d)
    u = _uniforms(4, cfg.vocab_size, seed=11)
    toks = [1, 5, 9]
    full = Client.from_artifact(d, use_decode_graph=False).generate(
        tokens=toks, max_new=4, uniforms=u)
    dec = Client.from_artifact(d).generate(tokens=toks, max_new=4,
                                           uniforms=u)
    assert len(dec.tokens) == 4
    assert dec.tokens == full.tokens


def test_uniforms_shape_validated(setup):
    """A malformed uniforms array must be a structured error at validation,
    not an IndexError mid-loop (on the engine that would poison every
    in-flight request)."""
    from repro.api.errors import InvalidRequestError
    params, cfg, d2, _ = setup
    bad = _uniforms(2, cfg.vocab_size)                 # rows < max_new
    for client in (Client.from_artifact(d2),
                   Client.from_params(params, cfg),
                   Client.serving(params, cfg, slots=1, max_context=64)):
        with pytest.raises(InvalidRequestError, match="uniforms"):
            client.generate(tokens=TOKS, ages=AGES, max_new=6, uniforms=bad)
    with pytest.raises(InvalidRequestError, match="uniforms"):
        Client.from_artifact(d2).generate(
            tokens=TOKS, ages=AGES, max_new=2,
            uniforms=_uniforms(2, cfg.vocab_size + 1))  # wrong vocab width


def test_stream_validates_eagerly_on_every_backend(setup):
    """stream() raises at the call, not at the consumer's first next() —
    the same timing on all backends."""
    params, cfg, d2, _ = setup
    for client in (Client.from_artifact(d2),
                   Client.from_params(params, cfg),
                   Client.serving(params, cfg, slots=1, max_context=64)):
        with pytest.raises(ValueError, match="empty"):
            client.stream(tokens=[], ages=[])       # no iteration needed


def test_engine_rejects_per_request_seed(setup):
    """The engine draws from its construction-time PRNG stream: a
    per-request seed would be silently ignored, so it raises instead."""
    params, cfg, _, _ = setup
    client = Client.serving(params, cfg, slots=1, max_context=64)
    with pytest.raises(ValueError, match="seed"):
        client.generate(tokens=TOKS, ages=AGES, max_new=3, seed=7)
    # seed with injected uniforms is inert and therefore fine
    u = _uniforms(3, cfg.vocab_size)
    out = client.generate(tokens=TOKS, ages=AGES, max_new=3, uniforms=u,
                          seed=7)
    assert out.backend == "engine"


# ---------------------------------------------------------------------------
# Streaming + batching
# ---------------------------------------------------------------------------
def test_stream_matches_generate(setup):
    params, cfg, d2, _ = setup
    max_new = 5
    u = _uniforms(max_new, cfg.vocab_size, seed=9)
    art = Client.from_artifact(d2)
    ref = art.generate(tokens=TOKS, ages=AGES, max_new=max_new, uniforms=u,
                       max_age=1e9)
    ev_art = list(art.stream(tokens=TOKS, ages=AGES, max_new=max_new,
                             uniforms=u, max_age=1e9))
    assert [e.token for e in ev_art] == ref.tokens
    assert [e.index for e in ev_art] == list(range(len(ref.tokens)))

    eng = Client.serving(params, cfg.replace(max_age=1e9), slots=1,
                         max_context=64)
    ev_eng = list(eng.stream(tokens=TOKS, ages=AGES, max_new=max_new,
                             uniforms=u))
    assert [e.token for e in ev_eng] == ref.tokens

    loc = Client.from_params(params, cfg.replace(max_age=1e9))
    ev_loc = list(loc.stream(tokens=TOKS, ages=AGES, max_new=max_new,
                             uniforms=u))
    assert [e.token for e in ev_loc] == ref.tokens


def test_engine_generate_batch(setup):
    params, cfg, _, _ = setup
    client = Client.serving(params, cfg, slots=4, max_context=64)
    reqs = [GenerateRequest(tokens=np.arange(3, 6 + i).tolist(),
                            ages=np.linspace(0, 20 + i, 3 + i).tolist(),
                            max_new=4)
            for i in range(6)]
    outs = client.generate_batch(reqs)
    assert len(outs) == 6
    assert all(isinstance(o, TrajectoryResult) for o in outs)
    # results are mapped back in submission order
    for req, out in zip(reqs, outs):
        assert out.prompt_tokens == list(req.tokens)
        assert len(out.tokens) == len(out.ages) <= 4


def test_engine_logits_accept_prompts_up_to_max_context(setup):
    """The engine backend's prompt axis is the ring (max_context), which may
    exceed cfg.max_seq_len — risk()/logits() must not overflow the padded
    buffer for prompts in between."""
    params, cfg, _, _ = setup
    assert cfg.max_seq_len == 48
    client = Client.serving(params, cfg, slots=1, max_context=64)
    n = 50                                    # > max_seq_len, <= max_context
    toks = (np.arange(3, 3 + n) % 90).tolist()
    ages = np.linspace(0.0, 40.0, n).tolist()
    lg = client.backend.logits(toks, ages)
    assert lg.shape == (cfg.vocab_size,) and np.isfinite(lg).all()
    rep = client.risk(toks, ages, top=3)
    assert len(rep.items) == 3


def test_local_generate_honors_host_rng(setup):
    """req.rng must not be silently ignored: LocalBackend falls back to the
    host loop, so generate == stream for the same seeded generator."""
    params, cfg, _, _ = setup
    client = Client.from_params(params, cfg.replace(max_age=1e9))
    gen = client.generate(tokens=TOKS, ages=AGES, max_new=4,
                          rng=np.random.default_rng(123))
    streamed = [e.token for e in client.stream(
        tokens=TOKS, ages=AGES, max_new=4, rng=np.random.default_rng(123))]
    assert gen.tokens == streamed
    # and a different generator produces a different draw (not seed-0 output)
    other = client.generate(tokens=TOKS, ages=AGES, max_new=4,
                            rng=np.random.default_rng(7))
    seed0 = client.generate(tokens=TOKS, ages=AGES, max_new=4, seed=0)
    assert gen.tokens != other.tokens or gen.tokens != seed0.tokens


def test_engine_rejects_per_request_termination_overrides(setup):
    params, cfg, _, _ = setup
    client = Client.serving(params, cfg, slots=1, max_context=64)
    with pytest.raises(ValueError, match="max_age"):
        client.generate(tokens=TOKS, ages=AGES, max_age=1e9)
    with pytest.raises(ValueError, match="death_token"):
        client.generate(tokens=TOKS, ages=AGES, death_token=5)


# ---------------------------------------------------------------------------
# Risk reports
# ---------------------------------------------------------------------------
def test_risk_parity_across_backends(setup):
    params, cfg, d2, _ = setup
    art = Client.from_artifact(d2).risk(TOKS, AGES, horizon=5.0, top=8)
    loc = Client.from_params(params, cfg).risk(TOKS, AGES, horizon=5.0, top=8)
    eng = Client.serving(params, cfg, slots=1, max_context=64).risk(
        TOKS, AGES, horizon=5.0, top=8)
    assert isinstance(art, RiskReport) and len(art.items) == 8
    assert [i.token for i in art.items] == [i.token for i in loc.items] \
        == [i.token for i in eng.items]
    np.testing.assert_allclose([i.risk for i in art.items],
                               [i.risk for i in loc.items], rtol=1e-5)
    # legacy schema delegation
    sess = InferenceSession(d2)
    legacy = sess.estimate_risk(TOKS, AGES, horizon=5.0, top=8)
    assert legacy == art.as_dicts()


# ---------------------------------------------------------------------------
# Checksum report (satellite)
# ---------------------------------------------------------------------------
def test_checksum_report_states(setup, tmp_path):
    params, cfg, _, _ = setup
    d = str(tmp_path / "art")
    export_model(params, cfg, d)
    rep = verify_checksums(d)
    assert rep and rep.ok and set(rep.files.values()) == {"ok"}

    with open(os.path.join(d, "params.npz"), "r+b") as f:
        f.seek(100)
        f.write(b"\x00\x01\x02")
    os.remove(os.path.join(d, "prefill.bin"))
    rep = verify_checksums(d)
    assert not rep
    assert rep.files["params.npz"] == "mismatch"
    assert rep.files["prefill.bin"] == "missing"
    assert rep.files["model.bin"] == "ok"
    assert rep.bad_files == {"params.npz": "mismatch",
                             "prefill.bin": "missing"}
    with pytest.raises(ChecksumError, match="params.npz"):
        verify_checksums(d, strict=True)
    with pytest.raises(ChecksumError, match="prefill.bin"):
        verify_checksums(d, strict=True)


# ---------------------------------------------------------------------------
# Export validation (satellite)
# ---------------------------------------------------------------------------
def test_export_validates_seq_len(setup, tmp_path):
    params, cfg, _, _ = setup
    with pytest.raises(ValueError, match="max_seq_len"):
        export_model(params, cfg, str(tmp_path / "bad"),
                     seq_len=cfg.max_seq_len + 1)


def test_export_rejects_custom_logits_fn_for_v2(setup, tmp_path):
    params, cfg, _, _ = setup
    with pytest.raises(ValueError, match="logits_fn"):
        export_model(params, cfg, str(tmp_path / "bad"),
                     logits_fn=lambda p, t, a: t)
    with pytest.raises(ValueError, match="spec_version"):
        export_model(params, cfg, str(tmp_path / "bad"), spec_version="3")


# ---------------------------------------------------------------------------
# Session shim
# ---------------------------------------------------------------------------
def test_session_is_a_client_shim(setup):
    _, _, d2, _ = setup
    sess = InferenceSession(d2)
    assert isinstance(sess.client, Client)
    # the shim pins the paper-faithful full-graph loop
    assert sess.client.backend.use_decode_graph is False
    with pytest.warns(DeprecationWarning, match="deprecated"):
        sess.getLogits(TOKS, AGES)


def test_client_kwargs_or_request_not_both(setup):
    _, _, d2, _ = setup
    client = Client.from_artifact(d2)
    with pytest.raises(TypeError, match="not both"):
        client.generate(GenerateRequest(tokens=TOKS, ages=AGES), max_new=3)
