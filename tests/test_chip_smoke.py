"""``chip_smoke.py`` on the CPU: its phases at reduced size, its refusal to
report a result without a TPU, the compile-cache helper, and in-process
replicas placed one per device."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
REDUCED_ARGV = ["--config", "delphi-2m", "--reduced", "--cache", "paged",
                "--slots", "8", "--max-context", "128"]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def served(smoke):
    """The reduced served backend and the http phase's results."""
    from repro.serve.server import build_backend, build_parser
    args = build_parser().parse_args(REDUCED_ARGV + ["--port", "0"])
    backend = build_backend(args)
    reqs = smoke.make_requests(backend.cfg.vocab_size)
    return args, backend, reqs, smoke.phase_http(backend, reqs)


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    env.update(kw)
    return env


def test_http_phase_serves_every_endpoint(served):
    _, backend, reqs, out = served
    kinds = [k for k, _ in reqs]
    assert kinds == ["generate"] * 3 + ["stream", "futures"]
    assert [len(per) for per in out] == [1, 1, 1, 1, 8]
    assert all(len(t) == len(a) for per in out for t, a in per)
    assert sum(len(t) for per in out for t, _ in per) > 0
    assert not backend.engine.running          # the server stopped it


def test_replay_phase_equals_http(smoke, served):
    args, _, reqs, out = served
    smoke.phase_replay(args, reqs, out)
    tampered = [list(per) for per in out]
    toks, ages = tampered[0][0]
    tampered[0][0] = (toks, [a + 1.0 for a in ages])
    with pytest.raises(AssertionError, match="replay"):
        smoke.phase_replay(args, reqs, tampered)


def test_logits_phase_against_fp32_reference(smoke, served):
    _, backend, reqs, out = served
    worst = smoke.phase_logits(backend, reqs, out)
    assert 0.0 <= worst <= smoke.logit_tolerance(backend.cfg)


def test_sampler_phase_pallas_matches_jnp(smoke, served):
    _, backend, reqs, _ = served
    assert smoke.phase_sampler(backend, reqs) > 0


def test_main_refuses_without_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_main_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = _env(PYTHONPATH="")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


_CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.runtime import CHECKOUT_CACHE_DIR, use_compile_cache
a, b = use_compile_cache(), use_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(json.dumps([a, b, jax.config.jax_compilation_cache_dir,
                  CHECKOUT_CACHE_DIR]))
"""


def _cache_probe(env, compile_: bool):
    r = subprocess.run([sys.executable, "-c",
                        _CACHE_PROBE.format(compile=compile_)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


def test_compile_cache_honours_env(tmp_path):
    d = str(tmp_path / "cache")
    a, b, configured, _ = _cache_probe(
        _env(JAX_COMPILATION_CACHE_DIR=d), compile_=True)
    assert a == b == configured == d
    assert os.listdir(d), "nothing was written to JAX_COMPILATION_CACHE_DIR"


def test_compile_cache_fixed_checkout_path_without_env():
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    a, b, configured, fixed = _cache_probe(env, compile_=False)
    assert a == b == configured == fixed == str(ROOT / ".jax_cache")


_ROUTER_PROBE = """
import sys
sys.path.insert(0, {root!r})
import chip_smoke
chip_smoke.four_chips({argv!r}, 4)
"""


def test_in_process_replicas_one_per_device():
    """The router phase on four virtual CPU devices, in its own process:
    each replica on its own device, results equal to one device's."""
    code = _ROUTER_PROBE.format(root=str(ROOT), argv=REDUCED_ARGV)
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-4000:]
    placed = [ln.split()[2].rstrip(",") for ln in r.stdout.splitlines()
              if ln.strip().startswith("replica r")]
    assert len(placed) == 4 and len(set(placed)) == 4, r.stdout
    assert "phase router: ok" in r.stdout


def test_subprocess_replicas_refused_on_tpu_host(monkeypatch):
    from repro.serve import router
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(router.importlib.util, "find_spec",
                        lambda name: object() if name == "libtpu" else None)
    with pytest.raises(RuntimeError, match="in-process replicas"):
        router.ReplicaSupervisor.spawn(lambda i, port: [], 2)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    router._refuse_on_tpu_host(2)              # CPU-only: spawning is fine


def test_request_set_is_deterministic(smoke):
    a, b = smoke.make_requests(96), smoke.make_requests(96)
    for (ka, ra), (kb, rb) in zip(a, b):
        assert ka == kb and ra.tokens == rb.tokens
        np.testing.assert_array_equal(ra.uniforms, rb.uniforms)
        assert max(ra.tokens) < 96
