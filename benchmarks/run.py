"""Benchmark harness — one benchmark per paper claim/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  logits_native / logits_artifact   Fig. 3: same graph under the in-framework
                                    runtime vs the exported FAIR artifact
                                    (the paper's ONNX portability claim)
  trajectory_sdk_host               Fig. 2 App loop: host-side NumPy SDK
                                    generation (paper-faithful client path)
  trajectory_batched_graph          beyond-paper: in-graph batched sampler
                                    (lax.fori_loop + KV cache), events/s
  sdk_v1_fullgraph / sdk_v2_decode  artifact spec v2: full-graph-per-token
                                    client loop vs exported prefill + KV-
                                    cached decode graphs (tokens/s, same
                                    injected uniforms -> same events)
  tte_fused_kernel / tte_ref        eq. 1 sampler: fused Pallas kernel
                                    (interpret-mode CPU proxy) vs jnp oracle
  train_step_delphi                 dual-loss training throughput, tokens/s
  serving_engine_batched            slot continuous batching end-to-end
  serving_ring/paged_fixed_mem      paged KV cache vs dense ring at EQUAL
                                    resident KV bytes: tokens/s, ticks/s,
                                    peak concurrent requests, pool
                                    utilization, preemptions
  futures_shared / futures_naive    N Monte-Carlo futures per patient:
                                    prefix-shared engine fork (1 prefill,
                                    COW tails) vs N independent requests —
                                    events/s + peak resident KV bytes
  http_generate_p50/p95             wire-protocol serving: concurrent
                                    RemoteBackend clients vs the threaded
                                    HTTP front-end (req/s + latency tails)
  http_keepalive_*                  HTTP/1.1 keep-alive connection reuse vs
                                    a fresh socket per call (req/s delta)
  router_Nx_p50 / router_2x_speedup horizontal serving: the prefix-affinity
                                    router over 1/2/4 engine replicas under
                                    mixed generate/futures load (req/s +
                                    latency tails; 2x row asserts >= 1.5x
                                    the 1-replica req/s)
  cohort_sweep / cohort_*           cohort-scale scenario engine: 1000
                                    patients x 4 futures through the paged +
                                    prefix-cached engine, bit-identical to
                                    the straight-line foreground oracle;
                                    counterfactual re-fork amortization and
                                    shared-vs-naive resident KV (rows append
                                    to BENCH_cohort.json)
  roofline_*                        derived = dominant roofline term (reads
                                    experiments/dryrun; skipped when absent)

CPU numbers are proxies for relative comparisons, not TPU projections — the
TPU story lives in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, n: int = 10, warmup: int = 2) -> float:
    """Median wall time per call in microseconds."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def bench_runtime_portability():
    from repro.configs import get_config
    from repro.core import get_logits, init_delphi
    from repro.sdk import Runtime, export_model

    cfg = get_config("delphi-2m").replace(dtype="float32", max_seq_len=64)
    params = init_delphi(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 64), jnp.int32)
    ages = jnp.zeros((1, 64), jnp.float32)

    native = jax.jit(lambda p, t, a: get_logits(p, cfg, t, a))
    us_native = _time(native, params, tokens, ages)
    _row("logits_native", us_native, f"{1e6 / us_native:.1f} calls/s")

    d = tempfile.mkdtemp()
    export_model(params, cfg, d)
    rt = Runtime(d)
    t_np, a_np = np.asarray(tokens), np.asarray(ages)
    us_art = _time(lambda: rt.run(t_np, a_np), n=10)
    _row("logits_artifact", us_art,
         f"overhead {us_art / us_native:.2f}x vs native")


def bench_trajectory_generation():
    from repro.configs import get_config
    from repro.core import generate_trajectories_jit, init_delphi
    from repro.sdk import InferenceSession, export_model

    cfg = get_config("delphi-2m").replace(dtype="float32", max_seq_len=96)
    params = init_delphi(cfg, jax.random.PRNGKey(0))
    d = tempfile.mkdtemp()
    export_model(params, cfg, d)
    sess = InferenceSession(d)

    toks, ags = [3, 500, 700], [0.0, 30.0, 40.0]
    n_events = 16

    def sdk_loop():
        return sess.generate_trajectory(toks, ags, max_new=n_events,
                                        max_age=1e9)
    t0 = time.perf_counter()
    out = sdk_loop()
    us = (time.perf_counter() - t0) * 1e6
    ev = max(len(out["tokens"]), 1)
    _row("trajectory_sdk_host", us / ev, f"{ev * 1e6 / us:.1f} events/s")

    B = 16
    tokens = jnp.tile(jnp.asarray(toks, jnp.int32)[None], (B, 1))
    ages = jnp.tile(jnp.asarray(ags, jnp.float32)[None], (B, 1))
    fn = lambda: generate_trajectories_jit(  # noqa: E731
        params, cfg, tokens, ages, jax.random.PRNGKey(1), max_new=n_events)
    us_g = _time(fn, n=3, warmup=1)
    ev_g = B * n_events
    _row("trajectory_batched_graph", us_g / ev_g,
         f"{ev_g * 1e6 / us_g:.1f} events/s (beyond-paper batched path)")


def bench_sdk():
    """Before/after for the artifact spec-v2 redesign: the v1 client path
    (re-running the O(S·V) full graph per generated token) vs the v2 path
    (one prefill, then one KV-cached decode_step per token), same artifact,
    same injected uniforms.  Early events are bit-identical; over a long
    horizon fp fusion noise compounds through the age feedback (the caveat
    tests/test_serve_device.py documents), so parity is asserted on the
    leading prefix and the agreement length is reported."""
    from repro.api import Client
    from repro.configs import get_config
    from repro.core import init_delphi
    from repro.sdk import export_model

    # the artifact keeps the config's native fixed axis (S=256): that is the
    # graph the paper's App ships, and exactly what the v1 client re-runs
    # once per generated token
    cfg = get_config("delphi-2m").replace(dtype="float32", max_age=1e9)
    params = init_delphi(cfg, jax.random.PRNGKey(0))
    d = tempfile.mkdtemp()
    export_model(params, cfg, d)

    toks, ags = [3, 500, 700], [0.0, 30.0, 40.0]
    max_new = 48
    rng = np.random.default_rng(7)
    u = rng.uniform(size=(max_new, cfg.vocab_size)).astype(np.float32)

    v1 = Client.from_artifact(d, use_decode_graph=False)
    v2 = Client.from_artifact(d)

    def measure(client):
        def gen():
            return client.generate(tokens=toks, ages=ags, max_new=max_new,
                                   uniforms=u)
        gen()                                    # warm the graph jits
        ts, ev = [], None
        for _ in range(3):
            t0 = time.perf_counter()
            out = gen()
            ts.append(time.perf_counter() - t0)
            ev = len(out.tokens)
        return ev, float(np.median(ts)), out

    ev1, dt1, out1 = measure(v1)
    _row("sdk_v1_fullgraph", dt1 * 1e6 / max(ev1, 1),
         f"{ev1 / dt1:.1f} tokens/s (full graph per token)")
    ev2, dt2, out2 = measure(v2)
    _row("sdk_v2_decode", dt2 * 1e6 / max(ev2, 1),
         f"{ev2 / dt2:.1f} tokens/s (prefill + KV-cached decode)")
    agree = 0
    for a, b in zip(out1.tokens, out2.tokens):
        if a != b:
            break
        agree += 1
    assert agree >= min(8, ev1), \
        f"v1/v2 diverged after {agree} events — expected >= 8"
    _row("sdk_v2_speedup", 0.0,
         f"{(ev2 / dt2) / max(ev1 / dt1, 1e-9):.2f}x tokens/s v2 vs v1 "
         f"({ev1} events, first {agree} bit-identical)")


def bench_tte_kernel():
    from repro.kernels import tte_sample
    from repro.kernels.ref import tte_sample_ref

    for V in (1289, 256206):
        logits = jax.random.normal(jax.random.PRNGKey(0), (8, V))
        u = jax.random.uniform(jax.random.PRNGKey(1), (8, V))
        us_ref = _time(jax.jit(tte_sample_ref), logits, u)
        _row(f"tte_ref_V{V}", us_ref, f"{8e6 / us_ref:.0f} samples/s")
        us_k = _time(lambda l, uu: tte_sample(l, uu), logits, u, n=3,
                     warmup=1)
        _row(f"tte_fused_kernel_V{V}", us_k,
             "interpret-mode proxy; HBM-fusion win is a TPU property")


def bench_train_step():
    from repro.configs import get_config
    from repro.core import init_delphi
    from repro.data import SimulatorConfig, generate_dataset, pack_trajectories
    from repro.train import OptimizerConfig, init_opt_state, make_train_step

    cfg = get_config("delphi-2m").replace(dtype="float32", max_seq_len=96)
    params = init_delphi(cfg, jax.random.PRNGKey(0))
    train, _ = generate_dataset(SimulatorConfig(n_train=64, n_val=1))
    packed = pack_trajectories(train, 96)
    batch = {k: jnp.asarray(v[:32]) for k, v in packed.items()}
    step = jax.jit(make_train_step(cfg, OptimizerConfig(), "delphi"))
    opt = init_opt_state(params)

    def run(p, o, b):
        p2, o2, m = step(p, o, b)
        return m["loss"]
    us = _time(run, params, opt, batch, n=5, warmup=1)
    toks = 32 * 96
    _row("train_step_delphi", us, f"{toks * 1e6 / us:.0f} tokens/s")


def bench_serving_engine():
    """Before/after for the device-resident engine rework: the seed host-loop
    engine (vmap-of-single-slot decode + per-slot host sampling) vs the
    jitted decode_and_sample tick with ONE host sync per tick."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import BatchedEngine, ReferenceEngine, Request

    cfg = get_config("delphi-2m").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def _requests(n):
        return [Request(tokens=np.arange(3, 9, dtype=np.int32),
                        ages=np.linspace(0, 30, 6).astype(np.float32),
                        max_new=12) for _ in range(n)]

    def _measure(make_engine):
        # warm then measure the SAME instance: compiles of the (slots,
        # bucket) prefill, the tick, and the insert/commit shapes all land
        # in the warmup (the device engine additionally shares compiles
        # across instances via its module-level jits)
        eng = make_engine()
        for r in _requests(8):
            eng.submit(r)
        eng.run()
        n_done = len(eng.completed)
        ticks0 = getattr(eng, "ticks", 0)
        for r in _requests(16):
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        new = done[n_done:]
        ev = sum(len(r.out_tokens) for r in new)
        ticks = getattr(eng, "ticks", None)
        ticks = ticks - ticks0 if ticks is not None else None
        return ev, dt, ticks, len(new)

    ev_r, dt_r, _, n_r = _measure(
        lambda: ReferenceEngine(params, cfg, slots=8, max_context=128))
    _row("serving_engine_seed", dt_r * 1e6 / max(ev_r, 1),
         f"{ev_r / dt_r:.1f} events/s across {n_r} requests (host-loop)")

    ev_d, dt_d, ticks, n_d = _measure(
        lambda: BatchedEngine(params, cfg, slots=8, max_context=128))
    _row("serving_engine_device", dt_d * 1e6 / max(ev_d, 1),
         f"{ev_d / dt_d:.1f} events/s, {ticks / dt_d:.1f} ticks/s "
         f"across {n_d} requests (device-resident)")
    _row("serving_engine_speedup", 0.0,
         f"{(ev_d / dt_d) / max(ev_r / dt_r, 1e-9):.2f}x tokens/s "
         f"device-resident vs seed")
    bench_paged_vs_ring(params, cfg)
    bench_chunked_prefill()


def bench_paged_vs_ring(params, cfg):
    """Fixed-memory concurrency: a dense ring burns slots x max_context
    whether a trajectory is 5 events or 500; the paged pool admits by
    free-block budget, so at the SAME resident KV bytes it sustains far
    more concurrent short requests (Delphi trajectories are short-median/
    long-tail).  Reports KV-cache bytes, block-pool utilization and peak
    concurrent requests alongside tokens/s + ticks/s."""
    from repro.serve import BatchedEngine, Request

    W, bs, dense_slots = 128, 16, 4
    n_req, max_new = 24, 12

    def _requests():
        return [Request(tokens=np.arange(3, 9, dtype=np.int32),
                        ages=np.linspace(0, 30, 6).astype(np.float32),
                        max_new=max_new) for _ in range(n_req)]

    def _measure(eng):
        for r in _requests():
            eng.submit(r)
        eng.run()                        # warm ALL jit shapes (same load)
        eng.peak_active, t0 = 0, time.perf_counter()
        ticks0 = eng.ticks
        for r in _requests():
            eng.submit(r)
        done = eng.run()
        dt = time.perf_counter() - t0
        ev = sum(len(r.out_tokens) for r in done[-n_req:])
        return ev, dt, eng.ticks - ticks0

    ring = BatchedEngine(params, cfg, slots=dense_slots, max_context=W)
    ev, dt, ticks = _measure(ring)
    _row("serving_ring_fixed_mem", dt * 1e6 / max(ev, 1),
         f"{ev / dt:.1f} events/s, {ticks / dt:.1f} ticks/s, "
         f"kv_bytes={ring.cache_bytes} peak_concurrent={ring.peak_active} "
         f"({dense_slots} dense slots)")

    # same resident KV bytes: pool holds dense_slots * (W/bs) real blocks
    paged = BatchedEngine(params, cfg, slots=4 * dense_slots, max_context=W,
                          cache="paged", block_size=bs,
                          blocks=dense_slots * (W // bs) + 1)
    ev_p, dt_p, ticks_p = _measure(paged)
    st = paged.pool_stats()
    _row("serving_paged_fixed_mem", dt_p * 1e6 / max(ev_p, 1),
         f"{ev_p / dt_p:.1f} events/s, {ticks_p / dt_p:.1f} ticks/s, "
         f"kv_bytes={paged.cache_bytes} peak_concurrent={paged.peak_active} "
         f"peak_pool_util={st['blocks_peak_used'] / max(paged.allocator.capacity, 1):.2f} "
         f"preemptions={st['preemptions']} "
         f"shared_peak={st['shared_blocks_peak']} cow={st['cow_copies']}")
    assert paged.allocator.used == 0, "paged benchmark leaked blocks"
    assert paged.peak_active > ring.peak_active, \
        (paged.peak_active, ring.peak_active)
    _row("serving_paged_concurrency_gain", 0.0,
         f"{paged.peak_active / max(ring.peak_active, 1):.1f}x peak "
         f"concurrent requests at equal KV bytes "
         f"({paged.cache_bytes / max(ring.cache_bytes, 1):.2f}x bytes)")


def _git_rev() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=10).stdout.strip() \
            or "unknown"
    except Exception:               # noqa: BLE001 — bench must not die on VCS
        return "unknown"


def _bench_serve_record(mode: str, config: dict, metrics: dict) -> None:
    """Append one machine-readable record to BENCH_serve.json (JSON lines:
    each run appends, nothing is rewritten — diffable across commits)."""
    import json
    path = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "BENCH_serve.json")
    rec = {"schema": 1, "bench": "serve", "mode": mode,
           "git_rev": _git_rev(), "timestamp": round(time.time(), 1),
           "config": config, "metrics": metrics}
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def bench_chunked_prefill():
    """Mixed long/short workload: in-flight short decodes tick while long
    prompts keep arriving.  Unchunked, every long admission's monolithic
    prefill stalls ALL in-flight decode slots for the whole prompt — the
    stall lands in the short requests' per-event latency tail.  Chunked
    (``prefill_chunk_tokens``), prefill is metered through the per-tick
    budget between decode ticks, so the tail collapses while throughput
    holds (bit-identical outputs either way — the parity invariant
    scripts/paged_parity.py and tests/test_prefix.py pin down).  A third
    run shows partial-prefix suffix prefill: a long prompt extending an
    already-cached prefix admits by reference and prefills only the
    suffix.  Every row is appended to BENCH_serve.json."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import BatchedEngine, Request

    cfg = get_config("delphi-2m", reduced=True).replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    W, bs, chunk = 512, 16, 64
    S_long, n_long, n_short, max_new = 448, 6, 6, 48

    def shorts():
        return [Request(
            tokens=((np.arange(3, 9) + 7 * i) % 90).astype(np.int32),
            ages=np.linspace(0.0, 30.0, 6).astype(np.float32),
            max_new=max_new) for i in range(n_short)]

    def longs():
        return [Request(
            tokens=((np.arange(3, 3 + S_long) + 11 * i) % 90).astype(
                np.int32),
            ages=np.linspace(0.0, 60.0, S_long).astype(np.float32),
            max_new=4) for i in range(n_long)]

    def run(chunk_tokens):
        eng = BatchedEngine(params, cfg, slots=12, max_context=W,
                            cache="paged", block_size=bs, blocks=256,
                            prefill_chunk_tokens=chunk_tokens)

        def drive():
            ss, ls = shorts(), longs()
            for r in ss:
                eng.submit(r)
            pending = list(ls)
            lat: list = []
            seen = [0] * n_short
            now = time.perf_counter()
            last = [now] * n_short
            tick, t0 = 0, now
            while not all(r.done for r in ss + ls):
                if pending and tick % 8 == 3:   # longs arrive mid-decode
                    eng.submit(pending.pop(0))
                eng.step()
                tick += 1
                now = time.perf_counter()
                for i, r in enumerate(ss):
                    k = len(r.out_tokens)
                    if k > seen[i]:
                        dt = (now - last[i]) / (k - seen[i])
                        lat.extend([dt] * (k - seen[i]))
                        seen[i], last[i] = k, now
            wall = now - t0
            ev = sum(len(r.out_tokens) for r in ss + ls)
            return np.asarray(lat), ev, wall
        drive()                                 # warm every jit shape
        lat, ev, wall = drive()
        assert eng.allocator.used == 0, "mixed-workload bench leaked blocks"
        return lat, ev, wall, eng.pool_stats()

    config = {"slots": 12, "max_context": W, "block_size": bs,
              "blocks": 256, "S_long": S_long, "n_long": n_long,
              "n_short": n_short, "max_new_short": max_new}
    results = {}
    for mode, ct in (("monolithic", None), ("chunked", chunk)):
        lat, ev, wall, st = run(ct)
        p50, p95 = np.percentile(lat, 50), np.percentile(lat, 95)
        results[mode] = (p50, p95, ev / wall)
        derived = (f"{ev / wall:.1f} events/s, p50 {p50 * 1e3:.1f} ms "
                   f"per short-request event")
        if ct is not None:
            derived += (f" (chunk={ct}, {st['prefill_chunks']} chunks / "
                        f"{st['chunked_prefills']} prefills)")
        _row(f"serving_mixed_{mode}_p95", p95 * 1e6, derived)
        _bench_serve_record(
            mode, dict(config, prefill_chunk_tokens=ct),
            {"p50_event_latency_us": round(p50 * 1e6, 1),
             "p95_event_latency_us": round(p95 * 1e6, 1),
             "events_per_s": round(ev / wall, 2),
             "chunked_prefills": st["chunked_prefills"],
             "prefill_chunks": st["prefill_chunks"],
             "suffix_tokens_saved": st["suffix_tokens_saved"],
             "preemptions": st["preemptions"]})
    gain = results["monolithic"][1] / max(results["chunked"][1], 1e-12)
    thru = results["chunked"][2] / max(results["monolithic"][2], 1e-12)
    _row("serving_chunked_p95_gain", 0.0,
         f"{gain:.1f}x lower p95 per-event latency at {thru:.2f}x "
         f"throughput, chunked vs monolithic prefill")
    assert gain >= 2.0, \
        f"chunked prefill p95 gain {gain:.2f}x < 2x over monolithic"

    # partial-prefix suffix prefill: the second long prompt extends the
    # first's prefix, so only the unmatched suffix runs through prefill
    eng = BatchedEngine(params, cfg, slots=4, max_context=W, cache="paged",
                        block_size=bs, blocks=128, prefix_cache=True,
                        prefill_chunk_tokens=chunk)
    base = longs()[0]
    eng.submit(base)
    eng.run()
    matched = (S_long // bs) * bs
    ext = Request(
        tokens=np.concatenate([np.asarray(base.tokens),
                               (np.arange(10, 26) % 90)]).astype(np.int32),
        ages=np.concatenate([np.asarray(base.ages),
                             np.linspace(61.0, 70.0, 16)]).astype(
                                 np.float32),
        max_new=4)
    t0 = time.perf_counter()
    eng.submit(ext)
    eng.run()
    dt_suffix = time.perf_counter() - t0
    st = eng.pool_stats()
    assert st["suffix_tokens_saved"] >= matched, \
        f"suffix admission saved {st['suffix_tokens_saved']} < {matched}"
    _row("serving_suffix_prefill", dt_suffix * 1e6,
         f"suffix_tokens_saved={st['suffix_tokens_saved']} of "
         f"S={S_long + 16} prompt, partial_hits="
         f"{st['prefix_cache']['partial_hits']} (prefix-cache reuse)")
    _bench_serve_record(
        "suffix", {"slots": 4, "max_context": W, "block_size": bs,
                   "blocks": 128, "prefill_chunk_tokens": chunk,
                   "S_base": S_long, "S_ext": S_long + 16},
        {"suffix_tokens_saved": st["suffix_tokens_saved"],
         "partial_hits": st["prefix_cache"]["partial_hits"],
         "prefill_chunks": st["prefill_chunks"],
         "ext_request_wall_us": round(dt_suffix * 1e6, 1)})
    eng.drop_prefix_cache()
    assert eng.allocator.used == 0


def bench_futures():
    """The paper's headline workload at serving scale: N Monte-Carlo
    futures per patient.  `futures_shared` forks N decode slots off ONE
    prefilled history (prefix blocks shared by reference, tails copy-on-
    write); `futures_naive` runs the same N continuations as independent
    requests, each re-prefilling and holding its own KV.  Reports events/s
    and the PEAK RESIDENT KV bytes actually backing the N futures — the
    shared path should sit well under 2x a single request's bytes where
    naive pays ~Nx."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import BatchedEngine, Request

    cfg = get_config("delphi-2m").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    N, max_new, W, bs = 8, 8, 256, 16
    # long history, one token past a block boundary: the shared prefix is
    # 16 blocks and each future's 8 decode writes fit inside its single
    # copy-on-written tail block
    S = 241
    toks = (np.arange(3, 3 + S) % 1200).astype(np.int32)
    ages = np.linspace(0.0, 60.0, S).astype(np.float32)

    def block_bytes(eng):
        pc = eng.cache["self"]
        per = (pc.k.size + pc.v.size) // pc.k.shape[1]
        return per * pc.k.dtype.itemsize

    def run_shared():
        eng = BatchedEngine(params, cfg, slots=N, max_context=W,
                            cache="paged", block_size=bs,
                            blocks=4 * (W // bs) + 1)
        eng.sample_futures(toks, ages, n=N, max_new=max_new)   # warm jits
        eng.allocator.peak_used = 0
        t0 = time.perf_counter()
        kids = eng.sample_futures(toks, ages, n=N, max_new=max_new)
        dt = time.perf_counter() - t0
        ev = sum(len(k.out_tokens) for k in kids)
        return ev, dt, eng.allocator.peak_used * block_bytes(eng), eng

    def run_naive():
        # same pool geometry, no sharing: N independent identical requests
        eng = BatchedEngine(params, cfg, slots=N, max_context=W,
                            cache="paged", block_size=bs,
                            blocks=N * (W // bs) + 1)
        def submit_all():
            rs = [Request(tokens=toks.copy(), ages=ages.copy(),
                          max_new=max_new) for _ in range(N)]
            for r in rs:
                eng.submit(r)
            return rs
        submit_all(); eng.run()                                # warm jits
        eng.allocator.peak_used = 0
        t0 = time.perf_counter()
        rs = submit_all()
        eng.run()
        dt = time.perf_counter() - t0
        ev = sum(len(r.out_tokens) for r in rs)
        return ev, dt, eng.allocator.peak_used * block_bytes(eng), eng

    ev_n, dt_n, bytes_n, eng_n = run_naive()
    ev_s, dt_s, bytes_s, eng_s = run_shared()
    # one request's resident blocks (plus its growth block when the prompt
    # lands exactly on a block boundary)
    single = -(-S // bs) + (1 if S % bs == 0 else 0)
    single_bytes = single * block_bytes(eng_s)
    st = eng_s.pool_stats()
    _row("futures_naive", dt_n * 1e6 / max(ev_n, 1),
         f"{ev_n / dt_n:.1f} events/s, resident_kv={bytes_n} "
         f"({bytes_n / single_bytes:.1f}x one request) N={N} S={S}")
    _row("futures_shared", dt_s * 1e6 / max(ev_s, 1),
         f"{ev_s / dt_s:.1f} events/s, resident_kv={bytes_s} "
         f"({bytes_s / single_bytes:.1f}x one request) "
         f"shared_peak={st['shared_blocks_peak']} cow={st['cow_copies']} "
         f"forks={st['forks']}")
    _row("futures_sharing_gain", 0.0,
         f"{(ev_s / dt_s) / max(ev_n / dt_n, 1e-9):.2f}x events/s and "
         f"{bytes_n / max(bytes_s, 1):.1f}x less resident KV, "
         f"fork-shared vs naive-N-requests")
    assert eng_s.allocator.used == 0 and eng_n.allocator.used == 0, \
        "futures benchmark leaked blocks"
    assert bytes_s < 2 * single_bytes, \
        (f"shared futures resident KV {bytes_s} not < 2x a single "
         f"request's {single_bytes}")


def bench_http():
    """End-to-end wire-protocol serving: N concurrent RemoteBackend clients
    against the threaded HTTP front-end over a background-ticking engine —
    requests/s plus p50/p95 request latency, the numbers that sit alongside
    the in-process `serve`/`sdk` rows to show what the network hop and
    admission queueing cost."""
    import threading

    from repro.api import Client
    from repro.api.client import EngineBackend
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.server import InferenceServer

    cfg = get_config("delphi-2m", reduced=True).replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    backend = EngineBackend.create(params, cfg, slots=8, max_context=128)
    server = InferenceServer(backend, port=0).start()
    try:
        n_clients, per_client, max_new = 4, 6, 12
        toks = list(range(3, 9))
        ages = np.linspace(0, 30, 6).tolist()
        # warm: compiles (tick + the batch-bucketed prefill shapes a
        # concurrent burst admits under) land outside the clock
        from repro.api import GenerateRequest
        warm = Client.connect(server.address)
        warm.generate(tokens=toks, ages=ages, max_new=max_new)
        for nb in (2, n_clients):       # power-of-two admission batch buckets
            warm.generate_batch([GenerateRequest(tokens=toks, ages=ages,
                                                 max_new=2)
                                 for _ in range(nb)])

        lat: list = []
        failures: list = []
        lock = threading.Lock()

        def worker(i):
            try:
                client = Client.connect(server.address)
                for j in range(per_client):
                    t0 = time.perf_counter()
                    out = client.generate(tokens=toks, ages=ages,
                                          max_new=max_new)
                    dt = time.perf_counter() - t0
                    with lock:
                        lat.append((dt, len(out.tokens)))
            except Exception as e:          # noqa: BLE001 — surface after join
                with lock:
                    failures.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    finally:
        server.stop()
    if failures:
        raise RuntimeError(
            f"http benchmark: {len(failures)} worker(s) failed "
            f"({len(lat)} requests completed): {failures[0]}")

    n = len(lat)
    times = np.asarray([d for d, _ in lat])
    ev = sum(k for _, k in lat)
    p50, p95 = np.percentile(times, 50), np.percentile(times, 95)
    _row("http_generate_p50", p50 * 1e6,
         f"{n / wall:.1f} req/s, {ev / wall:.1f} events/s "
         f"({n_clients} concurrent clients)")
    _row("http_generate_p95", p95 * 1e6,
         f"{n} requests end-to-end over HTTP (engine async admission)")


def bench_http_keepalive():
    """HTTP/1.1 keep-alive vs socket-per-call: the same sequential risk()
    round-trips through one persistent RemoteBackend connection and through
    a fresh TCP connection each call — the wire-overhead delta the
    keep-alive rework buys (model work is identical, so the gap is pure
    connection setup)."""
    from repro.api import RemoteBackend
    from repro.api.client import EngineBackend
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.server import InferenceServer

    cfg = get_config("delphi-2m", reduced=True).replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    backend = EngineBackend.create(params, cfg, slots=2, max_context=64)
    server = InferenceServer(backend, port=0).start()
    try:
        n_calls = 40
        toks = list(range(3, 9))
        ages = np.linspace(0, 30, 6).tolist()

        def measure(keep_alive):
            rb = RemoteBackend(server.address, keep_alive=keep_alive)
            rb.risk(toks, ages, top=4)          # warm the logits jit
            t0 = time.perf_counter()
            for _ in range(n_calls):
                rb.risk(toks, ages, top=4)
            dt = time.perf_counter() - t0
            opened = rb.connections_opened
            rb.close()
            return dt, opened

        dt_ka, conns_ka = measure(True)
        dt_na, conns_na = measure(False)
    finally:
        server.stop()
    _row("http_keepalive_req", dt_ka * 1e6 / n_calls,
         f"{n_calls / dt_ka:.1f} req/s over {conns_ka} connection(s)")
    _row("http_per_call_conn_req", dt_na * 1e6 / n_calls,
         f"{n_calls / dt_na:.1f} req/s over {conns_na} connections")
    _row("http_keepalive_speedup", 0.0,
         f"{(n_calls / dt_ka) / max(n_calls / dt_na, 1e-9):.2f}x req/s "
         f"keep-alive vs socket-per-call")


def bench_router():
    """Horizontal serving: mixed generate/futures load through the
    prefix-affinity router at 1/2/4 in-process engine replicas, equal
    per-replica settings — req/s and p50/p95 end-to-end latency next to the
    single-server `http` row.  The 2-replica row must clear 1.5x the
    1-replica req/s: with small per-replica admission width the single
    replica is queue-bound, and a second replica doubles the slot budget
    while ticks stay overhead-dominated for the reduced model (jitted
    compute also releases the GIL, so replicas overlap on multicore)."""
    import threading

    from repro.api import Client, FuturesRequest
    from repro.api.client import EngineBackend
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.router import ReplicaSupervisor, RouterServer

    cfg = get_config("delphi-2m", reduced=True).replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_clients, per_client, max_new = 8, 3, 12

    def make_backend(i):
        # params shared across replicas: N replicas cost N KV pools, and
        # the module-level jit cache means replica 2..n compile nothing
        return EngineBackend.create(params, cfg, slots=2, max_context=64,
                                    cache="paged", prefix_cache=True,
                                    seed=i + 1)

    def measure(n_replicas):
        sup = ReplicaSupervisor.in_process(make_backend, n_replicas,
                                           probe_interval=0.5)
        router = RouterServer(sup, port=0).start()
        try:
            warm = Client.connect(router.address)     # compiles off-clock
            warm.generate(tokens=[3, 4, 5], ages=[0., 1., 2.],
                          max_new=max_new)
            warm.backend.sample_futures(FuturesRequest(
                tokens=[3, 4, 5], ages=[0., 1., 2.], n_futures=2,
                max_new=6))
            lat: list = []
            failures: list = []
            lock = threading.Lock()

            def worker(i):
                try:
                    client = Client.connect(router.address)
                    # per-worker histories: load spreads by free blocks,
                    # repeats within a worker ride prefix affinity
                    toks = [3 + i] * 20     # >= one full 16-token block:
                    ages = [float(j)        # repeats ride prefix affinity
                            for j in range(20)]
                    for j in range(per_client):
                        t0 = time.perf_counter()
                        if j % 3 == 2:      # mixed load: 1/3 futures
                            client.backend.sample_futures(FuturesRequest(
                                tokens=toks, ages=ages, n_futures=2,
                                max_new=6))
                        else:
                            client.generate(tokens=toks, ages=ages,
                                            max_new=max_new)
                        with lock:
                            lat.append(time.perf_counter() - t0)
                except Exception as e:      # noqa: BLE001 — after join
                    with lock:
                        failures.append(e)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            sched = router.scheduler.stats()
        finally:
            router.stop()
        if failures:
            raise RuntimeError(
                f"router benchmark ({n_replicas} replicas): "
                f"{len(failures)} worker(s) failed: {failures[0]}")
        times = np.asarray(lat)
        return (len(lat) / wall, np.percentile(times, 50),
                np.percentile(times, 95), sched["affinity_rate"])

    rps = {}
    for n in (1, 2, 4):
        req_s, p50, p95, aff = measure(n)
        rps[n] = req_s
        _row(f"router_{n}x_p50", p50 * 1e6,
             f"{req_s:.1f} req/s, p95 {p95 * 1e3:.0f} ms "
             f"({n} replica(s) x 2 slots, {n_clients} clients, "
             f"affinity {aff:.2f})")
    speedup = rps[2] / max(rps[1], 1e-9)
    _row("router_2x_speedup", 0.0,
         f"{speedup:.2f}x req/s 2 replicas vs 1 (equal per-replica "
         f"settings)")
    assert speedup >= 1.5, \
        f"2-replica router speedup {speedup:.2f}x < 1.5x over 1 replica"


def bench_calibration():
    """Delphi-style evaluation: generated cohort vs held-out cohort stats."""
    from repro.configs import get_config
    from repro.core import calibration_report, init_delphi
    from repro.data import (SimulatorConfig, batches, generate_dataset,
                            pack_trajectories)
    from repro.train import OptimizerConfig, init_opt_state, make_train_step

    cfg = get_config("delphi-2m").replace(dtype="float32", max_seq_len=96)
    params = init_delphi(cfg, jax.random.PRNGKey(0))
    train, val = generate_dataset(SimulatorConfig(n_train=256, n_val=64))
    it = batches(pack_trajectories(train, 96), 32, seed=0)
    step = jax.jit(make_train_step(
        cfg, OptimizerConfig(lr=1e-3, total_steps=50), "delphi"))
    opt = init_opt_state(params)
    for _ in range(50):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        params, opt, _ = step(params, opt, b)
    t0 = time.perf_counter()
    rep = calibration_report(params, cfg, val, n_batches=1)
    us = (time.perf_counter() - t0) * 1e6
    _row("calibration_chapter_l1", us,
         f"L1={rep['chapter_l1']:.3f} data_rate={rep['data']['events_per_year']:.2f}/y "
         f"model_rate={rep['model']['events_per_year']:.2f}/y (50-step model)")


def bench_roofline():
    from repro.launch.roofline import analyse, load_records
    for dirpath in ("experiments/dryrun", "experiments/dryrun_multipod"):
        if not os.path.isdir(dirpath):
            continue
        for rec in load_records(dirpath):
            a = analyse(rec)
            dom_s = a[f"{a['dominant']}_s"]
            _row(f"roofline_{a['arch']}_{a['shape']}_{a['mesh']}",
                 dom_s * 1e6,
                 f"dominant={a['dominant']} useful={a['useful_ratio']:.3f}"
                 if a["useful_ratio"] else f"dominant={a['dominant']}")


def _bench_cohort_record(mode: str, config: dict, metrics: dict) -> None:
    """Append one machine-readable record to BENCH_cohort.json (JSON
    lines, schema 1 — same append-only discipline as BENCH_serve.json)."""
    import json
    path = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "BENCH_cohort.json")
    rec = {"schema": 1, "bench": "cohort", "mode": mode,
           "git_rev": _git_rev(), "timestamp": round(time.time(), 1),
           "config": config, "metrics": metrics}
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def bench_cohort():
    """Cohort-scale scenario analysis: a 1000-patient x 4-futures sweep
    through the paged + prefix-cached engine via the ``ScenarioEngine``
    scheduler, verified **bit-identical** to the straight-line per-patient
    foreground oracle (which doubles as the naive no-scheduler baseline
    timing).  Then the counterfactual workload: K edited arms re-forked
    off one long history's cached prefix vs the same arms with the prefix
    cache off (every arm re-prefills) — the amortization factor the
    counterfactual API exists for.  Rows append to BENCH_cohort.json."""
    from repro.api.client import EngineBackend
    from repro.cohort import (CounterfactualEdit, ScenarioEngine,
                              apply_edit, assert_sweep_parity)
    from repro.configs import get_config
    from repro.data.synthetic import SimulatorConfig, patient
    from repro.models import init_params

    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=1289, max_age=1e9)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_patients, n_fut, max_new, S = 1000, 4, 8, 8
    W, bs, slots = 64, 8, 8
    sim = SimulatorConfig(seed=7)
    pats, i = [], 0
    while len(pats) < n_patients:       # O(1) access: no split materialized
        tok, age = patient(i, sim)
        i += 1
        if len(tok) > S:                # uniform prompt shape: one prefill
            pats.append((tok[:S], age[:S]))     # bucket, one oracle shape

    def make_backend():
        return EngineBackend.create(params, cfg, slots=slots,
                                    max_context=W, cache="paged",
                                    block_size=bs, blocks=256,
                                    prefix_cache=True)

    se = ScenarioEngine(make_backend(), max_in_flight=4, seed=13)
    se.sweep(pats[:2], n_futures=n_fut, max_new=max_new)   # warm the jits
    se = ScenarioEngine(make_backend(), max_in_flight=4, seed=13)
    res = se.sweep(pats, n_futures=n_fut, max_new=max_new, horizon=10.0)
    assert res.n_failed == 0, f"{res.n_failed} patients failed"

    t0 = time.perf_counter()
    stats = assert_sweep_parity(res, params, cfg, pats, seed=13,
                                n_futures=n_fut, max_new=max_new,
                                horizon=10.0, slots=slots, max_context=W)
    dt_oracle = time.perf_counter() - t0
    assert stats["patients_checked"] == n_patients
    naive_ps = n_patients / dt_oracle   # straight-line foreground baseline
    _row("cohort_sweep", res.wall_s * 1e6 / n_patients,
         f"{res.patients_per_s:.1f} patients/s ({res.events_per_s:.1f} "
         f"events/s, prefix hit rate {res.prefix_hit_rate:.2f}), "
         f"{stats['events_checked']} events bit-identical to oracle at "
         f"{naive_ps:.1f} patients/s foreground")

    # shared-vs-naive resident KV for one patient's N futures (the same
    # invariant bench_futures pins down, here at cohort geometry)
    from repro.serve import BatchedEngine, Request
    S_kv = 9 * bs + 1
    toks = (np.arange(3, 3 + S_kv) % 1200).astype(np.int32)
    ages = np.linspace(0.0, 60.0, S_kv).astype(np.float32)
    Wkv = 128

    def block_bytes(eng):
        pc = eng.cache["self"]
        per = (pc.k.size + pc.v.size) // pc.k.shape[1]
        return per * pc.k.dtype.itemsize

    eng = BatchedEngine(params, cfg, slots=n_fut, max_context=Wkv,
                        cache="paged", block_size=bs, blocks=128)
    eng.sample_futures(toks, ages, n=n_fut, max_new=max_new)
    eng.allocator.peak_used = 0
    eng.sample_futures(toks, ages, n=n_fut, max_new=max_new)
    bytes_shared = eng.allocator.peak_used * block_bytes(eng)
    eng2 = BatchedEngine(params, cfg, slots=n_fut, max_context=Wkv,
                         cache="paged", block_size=bs, blocks=128)
    for _ in range(2):                  # second pass is the measured one
        eng2.allocator.peak_used = 0
        for _ in range(n_fut):
            eng2.submit(Request(tokens=toks.copy(), ages=ages.copy(),
                                max_new=max_new))
        eng2.run()
    bytes_naive = eng2.allocator.peak_used * block_bytes(eng2)
    kv_ratio = bytes_naive / max(bytes_shared, 1)

    # counterfactual amortization: K edited arms off one long history.
    # Shared = the counterfactual API (forked futures + prefix-cache
    # re-fork of the baseline's blocks).  Naive = what a user without the
    # API pays: every arm's N futures as N independent requests, each
    # re-prefilling the full history and holding its own KV.
    S_cf = 120
    cf_fut, cf_new = 8, 4
    rng = np.random.default_rng(5)
    ctoks = np.concatenate([[3], rng.choice(
        np.arange(13, 1289), S_cf - 1, replace=False)]).astype(np.int32)
    cages = np.concatenate([[0.0], np.sort(
        rng.uniform(1.0, 60.0, S_cf - 1))]).astype(np.float32)
    edits = [CounterfactualEdit("substitute", int(ctoks[-1 - k]),
                                new_code=int(1288 - k)) for k in range(6)]
    arms = [(ctoks, cages)]
    for e in edits:
        t2, a2, _ = apply_edit(ctoks, cages, e)
        arms.append((t2, a2))

    def run_cf_shared():
        be = EngineBackend.create(params, cfg, slots=cf_fut,
                                  max_context=256, cache="paged",
                                  block_size=bs, blocks=512,
                                  prefix_cache=True)
        eng_cf = ScenarioEngine(be, seed=4)
        eng_cf.counterfactual(ctoks, cages, edits[:1], n_futures=cf_fut,
                              max_new=cf_new)            # warm the jits
        be.engine.drop_prefix_cache()
        t0 = time.perf_counter()
        reps = eng_cf.counterfactual(ctoks, cages, edits,
                                     n_futures=cf_fut, max_new=cf_new)
        dt = time.perf_counter() - t0
        ev = sum(len(t.tokens) for t in reps[0].baseline.trajectories)
        ev += sum(len(t.tokens) for r in reps
                  for t in r.edited.trajectories)
        return ev / dt, reps

    def run_cf_naive():
        eng_cf = BatchedEngine(params, cfg, slots=cf_fut, max_context=256,
                               cache="paged", block_size=bs, blocks=512)

        def drive():
            ev = 0
            for at, aa in arms:
                rs = [Request(tokens=np.asarray(at).copy(),
                              ages=np.asarray(aa).copy(), max_new=cf_new)
                      for _ in range(cf_fut)]
                for r in rs:
                    eng_cf.submit(r)
                eng_cf.run()
                ev += sum(len(r.out_tokens) for r in rs)
            return ev
        drive()                                          # warm the jits
        t0 = time.perf_counter()
        ev = drive()
        return ev / (time.perf_counter() - t0)

    eps_shared, reps = run_cf_shared()
    eps_naive = run_cf_naive()
    amort = eps_shared / max(eps_naive, 1e-9)
    assert all(r.shared_prefix_len >= S_cf - 7 for r in reps)
    assert amort >= 2.0, \
        f"counterfactual amortization {amort:.2f}x < 2x over naive"
    _row("cohort_counterfactual", 0.0,
         f"{amort:.2f}x events/s re-forking {len(edits)} arms off the "
         f"cached prefix vs unshared per-future requests "
         f"({eps_shared:.1f} vs {eps_naive:.1f} events/s, S={S_cf}, "
         f"N={cf_fut})")
    _row("cohort_resident_kv", 0.0,
         f"{kv_ratio:.1f}x less resident KV, fork-shared futures vs "
         f"naive N requests (N={n_fut}, S={S_kv})")
    _bench_cohort_record(
        "sweep",
        {"n_patients": n_patients, "n_futures": n_fut, "max_new": max_new,
         "prompt_events": S, "slots": slots, "max_context": W,
         "block_size": bs, "blocks": 256, "max_in_flight": 4,
         "vocab_size": cfg.vocab_size},
        {"patients_per_s": round(res.patients_per_s, 2),
         "events_per_s": round(res.events_per_s, 2),
         "prefix_hit_rate": round(res.prefix_hit_rate, 4),
         "events_total": res.events_total,
         "oracle_patients_per_s": round(naive_ps, 2),
         "oracle_events_checked": stats["events_checked"],
         "resident_kv_shared_bytes": int(bytes_shared),
         "resident_kv_naive_bytes": int(bytes_naive),
         "resident_kv_ratio": round(kv_ratio, 2),
         "counterfactual_amortization": round(amort, 2),
         "counterfactual_events_per_s": round(eps_shared, 2),
         "counterfactual_naive_events_per_s": round(eps_naive, 2)})


BENCHES = {
    "portability": bench_runtime_portability,
    "trajectory": bench_trajectory_generation,
    "sdk": bench_sdk,
    "tte": bench_tte_kernel,
    "train": bench_train_step,
    "serve": bench_serving_engine,
    "futures": bench_futures,
    "http": bench_http,
    "http_keepalive": bench_http_keepalive,
    "router": bench_router,
    "calibration": bench_calibration,
    "cohort": bench_cohort,
    "roofline": bench_roofline,
}


def main() -> None:
    from repro.launch.runtime import use_compile_cache
    use_compile_cache()
    which = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    for name in which:
        BENCHES[name]()


if __name__ == "__main__":
    main()
