"""HTTP/SSE front-end + RemoteBackend: the network as a fourth backend.

The acceptance contract of the wire-protocol redesign: trajectories through
``Client(RemoteBackend(url))`` are bit-identical to ``LocalBackend`` under
injected uniforms, SSE streaming yields the same events as non-streaming
generate, and every validation failure surfaces over HTTP as a structured
JSON error with a stable code — both as a raw body and as the same typed
``ApiError`` re-raised client-side."""
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.api import (ApiError, Client, GenerateRequest, RemoteBackend,
                       TrajectoryResult, WIRE_PROTOCOL_VERSION)
from repro.api.client import EngineBackend
from repro.configs import get_config
from repro.core import init_delphi
from repro.core.parity import check_trajectory, logit_tolerance
from repro.serve.server import InferenceServer

TOKS = [3, 10, 20]
AGES = [0.0, 15.0, 28.0]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=96, max_seq_len=48, max_age=1e9)
    params = init_delphi(cfg, jax.random.PRNGKey(7))
    backend = EngineBackend.create(params, cfg, slots=4, max_context=64)
    server = InferenceServer(backend, port=0).start()
    yield params, cfg, server
    server.stop()


def _uniforms(max_new, V, seed=42):
    rng = np.random.default_rng(seed)
    return rng.uniform(size=(max_new, V)).astype(np.float32)


def _long_running_uniforms(max_new, cfg, seed=42):
    """Uniforms that can never sample the death token (u -> 0 makes its
    competing waiting time huge), so a long request deterministically runs
    its full max_new instead of flaking out early under the engine RNG."""
    u = _uniforms(max_new, cfg.vocab_size, seed)
    u[:, cfg.death_token] = 1e-12
    return u


def _post_raw(url, path, payload):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# Discovery endpoints
# ---------------------------------------------------------------------------
def test_manifest_and_healthz(setup):
    _, cfg, server = setup
    with urllib.request.urlopen(server.address + "/v1/manifest") as r:
        m = json.loads(r.read())
    assert m["protocol_version"] == WIRE_PROTOCOL_VERSION
    assert m["backend"] == "engine"
    assert m["model"]["vocab_size"] == cfg.vocab_size
    assert m["model"]["has_ages"] is True
    assert set(m["endpoints"]) == {"generate", "generate_batch", "risk",
                                   "futures", "stream", "cancel",
                                   "manifest", "healthz"}
    with urllib.request.urlopen(server.address + "/v1/healthz") as r:
        h = json.loads(r.read())
    assert h["ok"] and h["engine"]["running"]


def test_background_engine_does_not_retain_completed(setup):
    """A long-running server must not leak finished requests: background
    start() disables the foreground-run() completed list."""
    _, _, server = setup
    remote = Client.connect(server.address)
    before = len(server.backend.engine.completed)
    for _ in range(3):
        remote.generate(tokens=TOKS, ages=AGES, max_new=2)
    assert len(server.backend.engine.completed) == before == 0


# ---------------------------------------------------------------------------
# Acceptance: remote == local, bit-identical under injected uniforms
# ---------------------------------------------------------------------------
def test_remote_bit_identical_to_local(setup):
    params, cfg, server = setup
    max_new = 6
    u = _uniforms(max_new, cfg.vocab_size)
    local = Client.from_params(params, cfg)
    remote = Client.connect(server.address)

    r_loc = local.generate(tokens=TOKS, ages=AGES, max_new=max_new,
                           uniforms=u)
    r_rem = remote.generate(tokens=TOKS, ages=AGES, max_new=max_new,
                            uniforms=u)
    # the server's own engine, called in-process: the same executable, so
    # the wire must add or lose nothing, bit for bit
    r_same = Client(server.backend).generate(tokens=TOKS, ages=AGES,
                                             max_new=max_new, uniforms=u)
    assert len(r_rem.tokens) > 0
    assert (r_rem.tokens, r_rem.ages) == (r_same.tokens, r_same.ages)
    assert r_rem.prompt_tokens == TOKS and r_rem.prompt_ages == AGES
    assert r_rem.backend == "remote[engine]"
    # the local backend is compiled separately: both follow the reference
    for r in (r_rem, r_loc):
        check_trajectory(params, cfg, TOKS, AGES, r.tokens, r.ages, u,
                         tol=logit_tolerance(cfg), max_new=max_new)


def test_remote_stream_matches_generate(setup):
    _, cfg, server = setup
    max_new = 5
    u = _uniforms(max_new, cfg.vocab_size, seed=9)
    remote = Client.connect(server.address)
    ref = remote.generate(tokens=TOKS, ages=AGES, max_new=max_new,
                          uniforms=u)
    evs = list(remote.stream(tokens=TOKS, ages=AGES, max_new=max_new,
                             uniforms=u))
    assert [e.token for e in evs] == ref.tokens
    assert [e.index for e in evs] == list(range(len(ref.tokens)))
    assert all(e.age is not None for e in evs)


def test_remote_generate_batch_order_and_concurrency(setup):
    """Concurrent remote clients continuously batch onto engine slots and
    every result maps back to its own prompt."""
    _, cfg, server = setup
    remote = Client.connect(server.address)
    reqs = [GenerateRequest(tokens=np.arange(3, 6 + i).tolist(),
                            ages=np.linspace(0, 20 + i, 3 + i).tolist(),
                            max_new=4)
            for i in range(6)]
    outs = remote.generate_batch(reqs)
    assert len(outs) == 6
    for req, out in zip(reqs, outs):
        assert isinstance(out, TrajectoryResult)
        assert out.prompt_tokens == list(req.tokens)
        assert len(out.tokens) == len(out.ages) <= 4

    # hammer the server from parallel threads: distinct prompts per thread
    results, errors = {}, []

    def worker(i):
        try:
            r = remote.generate(tokens=[3, 10 + i, 20 + i],
                                ages=AGES, max_new=3)
            results[i] = r
        except Exception as e:              # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    assert len(results) == 8
    for i, r in results.items():
        assert r.prompt_tokens == [3, 10 + i, 20 + i]


def test_remote_risk_matches_local(setup):
    params, cfg, server = setup
    local = Client.from_params(params, cfg)
    remote = Client.connect(server.address)
    rl = local.risk(TOKS, AGES, horizon=5.0, top=8)
    rr = remote.risk(TOKS, AGES, horizon=5.0, top=8)
    assert [i.token for i in rr.items] == [i.token for i in rl.items]
    np.testing.assert_allclose([i.risk for i in rr.items],
                               [i.risk for i in rl.items], rtol=1e-5)
    assert rr.backend == "remote[engine]"


# ---------------------------------------------------------------------------
# Error-code mapping (the satellite contract): every _validate failure is a
# stable code over HTTP, raised client-side as the same typed ApiError
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("payload,code", [
    ({"tokens": [], "ages": []}, "empty_trajectory"),
    ({"tokens": list(range(100)), "ages": [0.0] * 100}, "too_long"),
    ({"tokens": [3, 10]}, "ages_required"),
    ({"tokens": [3, 10], "ages": [0.0]}, "ages_length_mismatch"),
])
def test_http_error_codes(setup, payload, code):
    _, _, server = setup
    status, body = _post_raw(server.address, "/v1/generate", payload)
    assert status == 400
    assert body["error"]["code"] == code
    # and through RemoteBackend: same typed exception, same code
    remote = RemoteBackend(server.address)
    with pytest.raises(ApiError) as ei:
        remote.generate(GenerateRequest.from_json(dict(payload)))
    assert ei.value.code == code


def test_http_error_unsupported_override(setup):
    _, _, server = setup
    status, body = _post_raw(server.address, "/v1/generate",
                             {"tokens": TOKS, "ages": AGES, "max_age": 33.0})
    assert status == 400
    assert body["error"]["code"] == "unsupported_override"


def test_http_bad_uniforms_shape_is_structured(setup):
    """Short/misshapen uniforms must 400 with invalid_request instead of
    becoming an IndexError inside the engine loop (which would fail every
    other in-flight request)."""
    _, _, server = setup
    status, body = _post_raw(server.address, "/v1/generate",
                             {"tokens": TOKS, "ages": AGES, "max_new": 6,
                              "uniforms": [[0.5, 0.5]]})
    assert status == 400
    assert body["error"]["code"] == "invalid_request"
    # and the server keeps serving afterwards
    status, _ = _post_raw(server.address, "/v1/generate",
                          {"tokens": TOKS, "ages": AGES, "max_new": 2})
    assert status == 200


def test_http_engine_rejects_per_request_seed(setup):
    _, _, server = setup
    status, body = _post_raw(server.address, "/v1/generate",
                             {"tokens": TOKS, "ages": AGES, "seed": 7})
    assert status == 400
    assert body["error"]["code"] == "unsupported_override"


def test_http_error_invalid_json_and_unknown_endpoint(setup):
    _, _, server = setup
    req = urllib.request.Request(
        server.address + "/v1/generate", data=b"{not json",
        headers={"Content-Type": "application/json"}, method="POST")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400
    assert json.loads(ei.value.read())["error"]["code"] == "invalid_request"

    status, body = _post_raw(server.address, "/v1/nope", {})
    assert status == 404
    assert body["error"]["code"] == "unknown_endpoint"


def test_http_error_protocol_version(setup):
    """Every POST endpoint enforces the version handshake."""
    _, _, server = setup
    for path, payload in [
            ("/v1/generate", {"tokens": TOKS, "ages": AGES}),
            ("/v1/risk", {"tokens": TOKS, "ages": AGES}),
            ("/v1/stream", {"tokens": TOKS, "ages": AGES}),
            ("/v1/generate_batch", {"requests": []}),
    ]:
        status, body = _post_raw(server.address, path,
                                 {**payload, "protocol_version": "999"})
        assert status == 409, path
        assert body["error"]["code"] == "protocol_version_mismatch", path


def test_http_wrong_typed_fields_are_invalid_request(setup):
    """Coercion failures must be a 400 invalid_request, not a 500."""
    _, _, server = setup
    for path, payload in [
            ("/v1/generate", {"tokens": TOKS, "ages": AGES,
                              "max_new": "many"}),
            ("/v1/generate", {"tokens": ["x"], "ages": [0.0]}),
            ("/v1/risk", {"tokens": TOKS, "ages": AGES, "horizon": "x"}),
    ]:
        status, body = _post_raw(server.address, path, payload)
        assert status == 400, (path, payload)
        assert body["error"]["code"] == "invalid_request", (path, payload)


def test_engine_stop_unblocks_inflight_waiters(setup):
    """engine.stop() with requests in flight must fail them immediately —
    a background-mode waiter must never sit out request_timeout."""
    params, cfg, _ = setup
    backend = EngineBackend.create(params, cfg, slots=4, max_context=64)
    backend.request_timeout = 60.0
    backend.engine.start()
    outcome = {}

    def run():
        try:
            outcome["out"] = backend.generate_batch(
                [GenerateRequest(tokens=TOKS, ages=AGES, max_new=60)
                 for _ in range(8)])
        except Exception as e:              # noqa: BLE001
            outcome["err"] = e

    t = threading.Thread(target=run)
    t.start()
    time.sleep(0.2)
    backend.engine.stop()
    t.join(timeout=15)
    assert not t.is_alive()                 # unblocked promptly
    assert outcome                          # finished or structured error
    if "err" in outcome:
        assert "stopped" in str(outcome["err"])


def test_stream_validation_error_is_json_not_sse(setup):
    """Validation failures on /v1/stream must surface as plain JSON errors
    (proper status), not as an SSE body."""
    _, _, server = setup
    status, body = _post_raw(server.address, "/v1/stream",
                             {"tokens": [], "ages": []})
    assert status == 400
    assert body["error"]["code"] == "empty_trajectory"


def test_remote_stream_validates_eagerly(setup):
    """stream() raises at the call on the remote backend too — the POST
    fires (and the server's validation answer lands) before any next()."""
    _, _, server = setup
    remote = Client.connect(server.address)
    with pytest.raises(ApiError) as ei:
        remote.stream(tokens=[], ages=[])
    assert ei.value.code == "empty_trajectory"


def test_remote_rejects_rng_before_the_wire(setup):
    _, _, server = setup
    remote = Client.connect(server.address)
    with pytest.raises(ApiError) as ei:
        remote.generate(tokens=TOKS, ages=AGES,
                        rng=np.random.default_rng(0))
    assert ei.value.code == "rng_not_serializable"


# ---------------------------------------------------------------------------
# Serving a host-loop backend (artifact over the wire)
# ---------------------------------------------------------------------------
def test_serve_artifact_backend(setup, tmp_path):
    """The front-end is backend-agnostic: an exported FAIR artifact served
    over HTTP answers bit-identically to the engine-backed server."""
    params, cfg, server = setup
    from repro.sdk import export_model
    d = str(tmp_path / "art")
    export_model(params, cfg, d)
    art_server = InferenceServer(
        Client.from_artifact(d).backend, port=0).start()
    try:
        u = _uniforms(5, cfg.vocab_size, seed=3)
        via_engine = Client.connect(server.address).generate(
            tokens=TOKS, ages=AGES, max_new=5, uniforms=u)
        via_art = Client.connect(art_server.address).generate(
            tokens=TOKS, ages=AGES, max_new=5, uniforms=u)
        assert via_art.tokens == via_engine.tokens
        assert via_art.backend == "remote[artifact]"
        # FAIR manifest rides along on /v1/manifest
        m = RemoteBackend(art_server.address).server_manifest
        assert "artifact" in m and "provenance" in m["artifact"]
        evs = list(Client.connect(art_server.address).stream(
            tokens=TOKS, ages=AGES, max_new=5, uniforms=u))
        assert [e.token for e in evs] == via_art.tokens
    finally:
        art_server.stop()


# ---------------------------------------------------------------------------
# HTTP/1.1 keep-alive connection reuse
# ---------------------------------------------------------------------------
def test_keep_alive_reuses_one_connection(setup):
    """Sequential JSON calls ride ONE persistent connection; SSE gets its
    own socket."""
    _, cfg, server = setup
    remote = RemoteBackend(server.address)
    assert remote.connections_opened == 1       # the manifest handshake
    for _ in range(3):
        remote.generate(GenerateRequest(tokens=TOKS, ages=AGES, max_new=2))
    remote.healthz()
    assert remote.connections_opened == 1
    list(remote.stream(GenerateRequest(tokens=TOKS, ages=AGES, max_new=2)))
    assert remote.connections_opened == 2       # SSE is close-delimited
    remote.generate(GenerateRequest(tokens=TOKS, ages=AGES, max_new=2))
    assert remote.connections_opened == 2       # back on the pooled socket
    remote.close()


def test_keep_alive_off_dials_per_call(setup):
    _, _, server = setup
    remote = RemoteBackend(server.address, keep_alive=False)
    n0 = remote.connections_opened
    remote.healthz()
    remote.healthz()
    assert remote.connections_opened == n0 + 2


def test_keep_alive_survives_stale_socket(setup):
    """A pooled socket the server has since dropped retries once on a
    fresh connection instead of failing the call."""
    _, _, server = setup
    remote = RemoteBackend(server.address)
    remote.healthz()
    remote._conn.close()                        # simulate idle drop
    assert remote.healthz()["ok"]


# ---------------------------------------------------------------------------
# Cancellation over the wire
# ---------------------------------------------------------------------------
def test_cancel_unknown_id(setup):
    _, _, server = setup
    remote = Client.connect(server.address)
    assert remote.cancel("no-such-request") is False
    status, body = _post_raw(server.address, "/v1/cancel", {})
    assert status == 400 and body["error"]["code"] == "invalid_request"


def test_cancel_uses_dedicated_connection(setup):
    """/v1/cancel must not queue behind the pooled connection — it usually
    targets the very call holding that connection."""
    _, _, server = setup
    remote = RemoteBackend(server.address)
    remote.healthz()
    n0 = remote.connections_opened
    remote.cancel("whatever")
    assert remote.connections_opened == n0 + 1


def test_unknown_endpoint_with_body_keeps_connection_in_sync(setup):
    """A 404'd POST whose body was never parsed must drain it: with
    keep-alive the leftover bytes would otherwise be read as the next
    request line, failing the following valid call on the connection."""
    _, _, server = setup
    remote = RemoteBackend(server.address)
    with pytest.raises(ApiError) as ei:
        remote._request("POST", "/v1/generte",        # typo'd endpoint
                        {"tokens": [1, 2, 3], "junk": "x" * 256})
    assert ei.value.code == "unknown_endpoint"
    # same pooled connection must still serve a valid request
    assert remote.healthz()["ok"]
    assert remote.connections_opened == 1


def test_duplicate_request_id_is_rejected(setup):
    """A second in-flight request reusing a request_id would clobber the
    cancel registry — refused as a structured 400."""
    from repro.api.errors import InvalidRequestError
    params, cfg, _ = setup
    backend = EngineBackend.create(params, cfg, slots=1, max_context=512,
                                   cache="paged", block_size=16)
    server = InferenceServer(backend, port=0).start()
    try:
        remote = Client.connect(server.address)
        remote.generate(tokens=TOKS, ages=AGES, max_new=2)   # warm
        results = []

        def blocker():
            try:
                results.append(remote.generate(
                    GenerateRequest(tokens=TOKS, ages=AGES, max_new=480,
                                    uniforms=_long_running_uniforms(480, cfg),
                                    request_id="dup")))
            except ApiError as e:       # cancelled at teardown
                results.append(e)
        t = threading.Thread(target=blocker)
        t.start()
        time.sleep(0.3)
        with pytest.raises(InvalidRequestError) as ei:
            Client.connect(server.address).generate(
                GenerateRequest(tokens=TOKS, ages=AGES, max_new=2,
                                request_id="dup"))
        assert ei.value.code == "invalid_request"
        backend.cancel("dup")
        t.join(30)
    finally:
        server.stop()


def test_sse_streams_per_event_not_buffered(setup):
    """Frames must hit the wire as events occur: the first SSE frame has
    to arrive while the request is still in flight (a starred-tuple drain
    in _do_stream once buffered the whole trajectory until completion,
    which also made mid-stream cancellation unobservable)."""
    params, cfg, _ = setup
    backend = EngineBackend.create(params, cfg, slots=1, max_context=512,
                                   cache="paged", block_size=16)
    server = InferenceServer(backend, port=0).start()
    try:
        remote = Client.connect(server.address)
        remote.generate(tokens=TOKS, ages=AGES, max_new=2,
                        uniforms=_long_running_uniforms(2, cfg))  # warm
        it = remote.stream(GenerateRequest(
            tokens=TOKS, ages=AGES, max_new=400,
            uniforms=_long_running_uniforms(400, cfg)))
        next(it)
        eng = backend.engine
        assert any(r is not None for r in eng.slot_req), \
            "first frame only arrived after the request completed"
        n = 1 + sum(1 for _ in it)
        assert n == 400
    finally:
        server.stop()


def test_cancel_inflight_stream_emits_cancelled_frame(setup):
    """Cancel propagates to slot eviction mid-decode; the victim's SSE
    stream terminates with a `cancelled` frame raised client-side as
    RequestCancelledError, and the engine leaks nothing.  (stream()
    returns only once the server commits the SSE body — i.e. after the
    victim's first event — so by the time cancel fires the victim is
    decoding in a slot, with ~479 events still to go.)"""
    from repro.api import RequestCancelledError
    params, cfg, _ = setup
    backend = EngineBackend.create(params, cfg, slots=1, max_context=512,
                                   cache="paged", block_size=16)
    server = InferenceServer(backend, port=0).start()
    try:
        remote = Client.connect(server.address)
        remote.generate(tokens=TOKS, ages=AGES, max_new=2,
                        uniforms=_long_running_uniforms(2, cfg))  # warm
        # throttle the tick: the reduced config decodes hundreds of events
        # per second, so an unthrottled victim could finish before the
        # cancel round-trip lands — 20ms/tick gives it a ~10s runway
        orig_step = backend.engine.step
        backend.engine.step = lambda: (time.sleep(0.02), orig_step())[1]
        it = remote.stream(GenerateRequest(
            tokens=TOKS, ages=AGES, max_new=480,
            uniforms=_long_running_uniforms(480, cfg),
            request_id="cancel-me"))
        got = [next(it)]             # first event: the victim is in-slot
        assert remote.cancel("cancel-me") is True
        with pytest.raises(RequestCancelledError) as ei:
            for ev in it:
                got.append(ev)
        assert ei.value.code == "request_cancelled"
        assert ei.value.http_status == 409
        assert len(got) < 480        # cut short, not drained
        h = remote.backend.healthz()
        assert h["engine"]["memory"]["blocks_used"] == 0
        assert h["engine"]["memory"]["cache"] == "paged"
    finally:
        server.stop()
