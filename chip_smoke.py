#!/usr/bin/env python3
"""Bring-up smoke: serve Delphi-2M at published widths on the TPU, end to end.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the router over one replica per chip

One chip.  The backend ``repro-serve --config delphi-2m --cache paged
--slots 8`` serves (the configuration's own dtype, bfloat16; the prefix
cache on), built by the server's own functions and served in this process
on port 0.  Phases, each raising on failure:

* ``http``: three ``/v1/generate`` requests, one ``/v1/stream`` and one
  ``/v1/futures`` with ``n_futures=8``, through ``Client.connect``, on
  synthetic histories (``repro.data.synthetic``) with injected uniforms.
* ``replay``: the same requests, in the same order, on an in-process
  ``EngineBackend`` built from the same arguments, hence on the same
  compiled executables: every trajectory must be equal, event for event
  and age for age.
* ``logits``: prefill logits from the engine's admission executable and
  the logits of a few decode ticks against the float32 reference
  (``repro.core.parity``), and every trajectory of ``http`` replayed
  along its own path against it, at the served dtype's tolerance.
* ``sampler``: the engine with ``sampler="pallas"`` (the fused
  ``tte_sample`` kernel) against ``sampler="jnp"`` under the same uniforms:
  the same events, ages within :data:`SAMPLER_AGE_RTOL`.

Four chips (``--chips 4``), and nothing else: the router over 4
in-process replicas, one per chip, takes shared-prefix requests one at a
time; each replica's requests are then replayed in order on one engine on
the first chip.  Tokens must be equal and ages agree within
:data:`REPLICA_AGE_RTOL`; ``memory_stats()`` must show a replica's pool on
every chip.

JAX's persistent compile cache is on (``repro.launch.runtime``).  The
script exits nonzero, printing no result, where JAX finds no TPU.  The
last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":          # run as a script: this checkout's package
    sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.api import Client, FuturesRequest, GenerateRequest  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.parity import (check_logits, check_trajectory,  # noqa: E402
                               logit_tolerance, reference_logits)
from repro.data.synthetic import patient  # noqa: E402
from repro.launch.runtime import use_compile_cache  # noqa: E402
from repro.serve import BatchedEngine, Request  # noqa: E402
from repro.serve.router import build_router  # noqa: E402
from repro.serve.server import (InferenceServer, build_backend,  # noqa: E402
                                build_parser)

#: how ``repro-serve`` is started for the served configuration
SERVE_ARGV = ["--config", "delphi-2m", "--cache", "paged", "--slots", "8"]

#: ``sampler="pallas"`` vs ``"jnp"``: the two ticks compute the same
#: float32 waiting times with different exp/log code, so an age may differ
#: in its last float32 digits (rounding grows with each event's addition)
SAMPLER_AGE_RTOL = 1e-5

#: one replica's requests on its own chip vs replayed on the first chip:
#: the same program on identical chips, so equal up to float32 rounding
REPLICA_AGE_RTOL = 1e-6


def log(msg: str) -> None:
    print(msg, flush=True)


def _histories(n: int, vocab: int, first: int = 0):
    """Synthetic patients ``first..first+n-1`` (tokens folded into
    ``vocab`` for reduced test configurations; a no-op at 1,289)."""
    out = []
    for i in range(first, first + n):
        toks, ages = patient(i)
        out.append(((toks % vocab).astype(np.int32), ages))
    return out


def make_requests(vocab: int, *, max_new: int = 16,
                  n_futures: int = 8, seed: int = 0):
    """The one-chip request set: [(kind, request)] with kind in generate /
    stream / futures.  Prompts are the first halves of synthetic histories;
    uniforms come from ``seed``."""
    rng = np.random.default_rng(seed)
    reqs = []
    for kind, (toks, ages) in zip(
            ("generate", "generate", "generate", "stream", "futures"),
            _histories(5, vocab)):
        h = max(len(toks) // 2, 1)
        prompt = dict(tokens=toks[:h].tolist(), ages=ages[:h].tolist(),
                      max_new=max_new)
        if kind == "futures":
            u = rng.uniform(size=(n_futures, max_new, vocab))
            reqs.append((kind, FuturesRequest(
                **prompt, n_futures=n_futures,
                uniforms=u.astype(np.float32))))
        else:
            u = rng.uniform(size=(max_new, vocab)).astype(np.float32)
            reqs.append((kind, GenerateRequest(**prompt, uniforms=u)))
    return reqs


def _uniform_rows(req):
    """Per-trajectory (max_new, V) uniforms of a request."""
    u = np.asarray(req.uniforms)
    return list(u) if isinstance(req, FuturesRequest) else [u]


def run_requests(client: Client, reqs):
    """Send ``reqs`` one at a time; -> per request, its trajectories as
    [(tokens, ages)]."""
    out = []
    for kind, req in reqs:
        if kind == "generate":
            r = client.generate(req)
            out.append([(r.tokens, r.ages)])
        elif kind == "stream":
            evs = list(client.stream(req))
            out.append([([e.token for e in evs], [e.age for e in evs])])
        else:
            fr = client.sample_futures(req)
            out.append([(t.tokens, t.ages) for t in fr.trajectories])
    return out


def _count_events(trajs) -> int:
    return sum(len(t) for per in trajs for t, _ in per)


def phase_http(backend, reqs):
    """Serve ``backend`` on port 0 and send ``reqs`` over the wire."""
    server = InferenceServer(backend, port=0).start()
    try:
        client = Client.connect(server.address)
        try:
            out = run_requests(client, reqs)
        finally:
            client.backend.close()
    finally:
        server.stop()
    if _count_events(out) == 0:
        raise AssertionError("http: no request emitted an event")
    return out


def phase_replay(args, reqs, http_out):
    """The same requests in-process on a backend built from the same
    arguments (same executables): results must be equal."""
    got = run_requests(Client(build_backend(args)), reqs)
    for (kind, _), a, b in zip(reqs, http_out, got):
        if a != b:
            raise AssertionError(f"replay: {kind} over HTTP {a} != "
                                 f"in-process {b}")


def _engine_kwargs(eng: BatchedEngine) -> dict:
    """Construction knobs of ``eng``: an engine built with them shares its
    compiled executables."""
    kw = dict(slots=eng.slots, max_context=eng.max_context,
              temperature=eng.temperature, sampler=eng.sampler,
              min_seq_bucket=eng.min_seq_bucket)
    if eng.paged:
        kw.update(cache="paged", blocks=eng.allocator.num_blocks,
                  block_size=eng.block_size,
                  prefix_cache=eng.prefix is not None,
                  prefill_chunk_tokens=eng.prefill_chunk_tokens)
    return kw


def _decode_uniforms(vocab: int, death: int, n: int, seed: int):
    u = np.random.default_rng(seed).uniform(size=(n, vocab))
    u[:, death] = 1e-12              # no death: every slot keeps decoding
    return u.astype(np.float32)


def phase_logits(backend, reqs, http_out, *, n_ticks: int = 4,
                 seed: int = 1) -> float:
    """Engine logits (prefill executable + ``n_ticks`` decode ticks) and the
    ``http`` trajectories against the float32 reference; -> the largest
    logit error seen."""
    src = backend.engine
    params, cfg = src.params, src.cfg
    tol = logit_tolerance(cfg)
    prompts = [(np.asarray(r.tokens, np.int32), np.asarray(r.ages, np.float32))
               for kind, r in reqs if kind != "futures"]
    worst = 0.0
    eng = BatchedEngine(params, cfg, **_engine_kwargs(src))
    for i, (toks, ages) in enumerate(prompts):
        ref = reference_logits(params, cfg, toks, ages)
        worst = max(worst, check_logits(
            eng.prefill_logits(toks, ages), ref[-1], tol=tol,
            what=f"prefill logits, prompt {i}"))
    # max_new outlasts the watched ticks: a request that finishes in a tick
    # leaves its slot, and its row can no longer be told from the others
    live = [Request(tokens=t, ages=a, max_new=n_ticks + 2,
                    uniforms=_decode_uniforms(cfg.vocab_size,
                                              cfg.death_token, n_ticks + 2,
                                              seed + i))
            for i, (t, a) in enumerate(prompts)]
    for r in live:
        eng.submit(r)
    rows = []                        # (request, event index, logits row)
    for _ in range(n_ticks):
        eng.step()
        lg = np.asarray(eng.last_logits)
        for r in live:
            # still running after the tick: it ticked and emitted an event
            if not r.done and r in eng.slot_req:
                rows.append((r, len(r.out_tokens) - 1,
                             lg[eng.slot_req.index(r)]))
    eng.run()
    if not rows:
        raise AssertionError("logits: no decode tick ran")
    for j, r in enumerate(live):
        ref = reference_logits(params, cfg,
                               list(r.tokens) + list(r.out_tokens),
                               list(r.ages) + list(r.out_ages))
        for rr, k, row in rows:
            if rr is r:
                worst = max(worst, check_logits(
                    row, ref[len(r.tokens) - 1 + k], tol=tol,
                    what=f"decode logits, prompt {j}, event {k}"))
    for (kind, req), trajs in zip(reqs, http_out):
        for u, (toks, ages) in zip(_uniform_rows(req), trajs):
            check_trajectory(params, cfg, req.tokens, req.ages, toks, ages,
                             u, tol=tol, max_new=req.max_new)
    return worst


def phase_sampler(backend, reqs):
    """``sampler="pallas"`` against ``sampler="jnp"`` on the generate
    requests, same uniforms: same events; -> events compared."""
    src = backend.engine
    out = {}
    for sampler in ("jnp", "pallas"):
        kw = dict(_engine_kwargs(src), sampler=sampler)
        client = Client(type(backend)(BatchedEngine(src.params, src.cfg,
                                                     **kw)))
        out[sampler] = run_requests(
            client, [(k, r) for k, r in reqs if k == "generate"])
    n = 0
    for a, b in zip(out["jnp"], out["pallas"]):
        (ta, aa), = a
        (tb, ab), = b
        if ta != tb:
            raise AssertionError(f"sampler: pallas events {tb} != jnp {ta}")
        np.testing.assert_allclose(ab, aa, rtol=SAMPLER_AGE_RTOL,
                                   err_msg="sampler: pallas vs jnp ages")
        n += len(ta)
    if n == 0:
        raise AssertionError("sampler: no events to compare")
    return n


def make_router_requests(vocab: int, *, max_new: int = 12, seed: int = 2):
    """Shared-prefix requests: four synthetic histories, each at three
    prompt lengths, the lengths in turn, so every later prompt extends an
    earlier one's blocks."""
    rng = np.random.default_rng(seed)
    reqs = []
    hist = _histories(4, vocab, first=5)
    for frac in (0.4, 0.5, 0.6):
        for toks, ages in hist:
            h = max(int(len(toks) * frac), 1)
            u = rng.uniform(size=(max_new, vocab)).astype(np.float32)
            reqs.append(("generate", GenerateRequest(
                tokens=toks[:h].tolist(), ages=ages[:h].tolist(),
                max_new=max_new, uniforms=u)))
    return reqs


def phase_router(args, reqs):
    """The router over ``args.replicas`` in-process replicas, one per
    device; each replica's requests replayed on one engine on the first
    device.  -> {replica: (device, requests, bytes_in_use)}."""
    router = build_router(args).start()
    try:
        client = Client.connect(router.address)
        by_replica: dict = {}
        try:
            for kind, req in reqs:
                r = client.generate(req)
                name = re.search(r"router\[(\w+):", r.backend).group(1)
                by_replica.setdefault(name, []).append((kind, req,
                                                        r.tokens, r.ages))
                # fresh load figures: a new history goes to the replica
                # with the most free blocks, not to the first of equals
                router.supervisor.probe_once()
        finally:
            client.backend.close()
        engines = {h.name: h.server.backend.engine
                   for h in router.supervisor.replicas}
        devices = {n: e.device for n, e in engines.items()}
        if len(set(devices.values())) != len(engines):
            raise AssertionError(f"router: replicas share devices {devices}")
        usage = {}
        for name, eng in engines.items():
            stats = eng.device.memory_stats()
            if stats is not None:        # the TPU reports it; the CPU not
                usage[name] = stats["bytes_in_use"]
                if usage[name] < eng.cache_bytes:
                    raise AssertionError(
                        f"router: {name} on {eng.device} holds "
                        f"{usage[name]} bytes < its pool's "
                        f"{eng.cache_bytes}")
    finally:
        router.stop()
    # one chip: each replica's sequence, in order, on a fresh engine there
    for name, seq in by_replica.items():
        with jax.default_device(jax.devices()[0]):
            one = Client(build_backend(args))
        got = run_requests(one, [(k, q) for k, q, _, _ in seq])
        for (_, _, toks, ages), ((t1, a1),) in zip(seq, got):
            if toks != t1:
                raise AssertionError(f"router: replica {name} events "
                                     f"{toks} != one-chip {t1}")
            np.testing.assert_allclose(
                ages, a1, rtol=REPLICA_AGE_RTOL,
                err_msg=f"router: replica {name} ages vs one chip")
    return {n: (str(devices[n]), len(by_replica.get(n, [])),
                usage.get(n)) for n in engines}


def _timed(name: str, fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    log(f"phase {name}: ok in {time.perf_counter() - t0:.2f} s")
    return out


def _memory(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def one_chip(serve_argv) -> None:
    args = build_parser().parse_args(serve_argv + ["--port", "0"])
    backend = _timed("build", build_backend, args)
    cfg = backend.cfg
    log(f"served config: {cfg.name} dtype={cfg.dtype} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads} vocab={cfg.vocab_size}")
    reqs = make_requests(cfg.vocab_size)
    out = _timed("http", phase_http, backend, reqs)
    log(f"  {len(reqs)} requests, {sum(len(p) for p in out)} trajectories, "
        f"{_count_events(out)} events")
    _timed("replay", phase_replay, args, reqs, out)
    worst = _timed("logits", phase_logits, backend, reqs, out)
    log(f"  max |logit - fp32 reference| = {worst:.4g} "
        f"(tolerance {logit_tolerance(cfg):g} for {cfg.dtype})")
    n = _timed("sampler", phase_sampler, backend, reqs)
    log(f"  pallas == jnp on {n} events")


def four_chips(serve_argv, n: int) -> None:
    args = build_parser().parse_args(
        serve_argv + ["--port", "0", "--replicas", str(n)])
    reqs = make_router_requests(
        get_config(args.config, reduced=args.reduced).vocab_size)
    placed = _timed("router", phase_router, args, reqs)
    for name, (dev, nreq, used) in placed.items():
        log(f"  replica {name}: {dev}, {nreq} requests, "
            f"bytes_in_use={used}")


def _compile_cache_counter():
    counts = {"requests": 0, "hits": 0}
    names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "hits"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1
    jax.monitoring.register_event_listener(listen)
    return counts


def main(argv=None) -> int:
    cache_dir = use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the router over one replica per chip")
    opts = ap.parse_args(argv)
    pkg = os.path.join(HERE, "src", "repro")
    found = sorted({os.path.abspath(p) for p in repro.__path__})
    if found != [pkg]:
        print(f"chip_smoke: runs from a checkout: repro must come from "
              f"{pkg}, not {found}", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    counts = _compile_cache_counter()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    if opts.chips == 1:
        one_chip(SERVE_ARGV)
    else:
        four_chips(SERVE_ARGV, opts.chips)
    log(f"all phases ok in {time.perf_counter() - t0:.2f} s; compile cache: "
        f"{counts['hits']} of {counts['requests']} compiles read from it")
    for d in devices[:opts.chips]:
        log(f"memory {d}: {_memory(d)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
