"""Serving launcher: batched trajectory generation via the slot engine.

    PYTHONPATH=src python -m repro.launch.serve --arch delphi-2m \
        [--requests 16] [--slots 8] [--ckpt runs/delphi] [--replicas 2]

``--replicas N`` shards the request set across N engines through the same
:class:`repro.serve.PrefixAffinityScheduler` the HTTP router uses — shared
history prefixes land on the engine whose pool already holds them, and the
engines run their ticks on concurrent background threads (jitted compute
releases the GIL, so CPU replicas genuinely overlap).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data import SimulatorConfig, generate_dataset
from repro.data import vocab as V
from repro.launch.runtime import served_config, use_compile_cache
from repro.models import init_params
from repro.serve import BatchedEngine, Request
from repro.train import restore


class _EngineShard:
    """Just enough of ``ReplicaHandle``'s surface (``name`` / ``inflight``
    / ``free_blocks``) for the affinity scheduler to rank local engines."""

    def __init__(self, name: str, engine: BatchedEngine):
        self.name = name
        self.engine = engine
        self.requests: list = []

    @property
    def inflight(self) -> int:
        return len(self.requests)

    def free_blocks(self):
        st = self.engine.pool_stats()
        return st.get("blocks_free")


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="delphi-2m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="shard requests across N engines via the router's "
                         "prefix-affinity scheduler")
    ap.add_argument("--cache", choices=("ring", "paged"), default="ring",
                    help="KV layout per engine (paged enables chunked "
                         "prefill and prefix sharing)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    metavar="N",
                    help="--cache paged: prefill in N-token chunks "
                         "interleaved with decode ticks (multiple of the "
                         "16-token block size)")
    args = ap.parse_args()
    if args.prefill_chunk_tokens is not None and args.cache != "paged":
        ap.error("--prefill-chunk-tokens requires --cache paged")

    cfg = served_config(get_config(args.arch))
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.ckpt:
        params = restore(args.ckpt, params)

    n_rep = max(args.replicas, 1)
    shards = [_EngineShard(f"r{i}", BatchedEngine(
        params, cfg, slots=args.slots, max_context=cfg.max_seq_len,
        seed=args.seed + i, cache=args.cache,
        prefill_chunk_tokens=args.prefill_chunk_tokens))
        for i in range(n_rep)]

    # prompts: prefixes of fresh synthetic patients (their known history)
    trajs, _ = generate_dataset(SimulatorConfig(
        n_train=args.requests, n_val=1, seed=args.seed + 17))
    if n_rep == 1:
        for tok, age in trajs:
            half = max(len(tok) // 2, 1)
            shards[0].requests.append(Request(
                tokens=tok[:half], ages=age[:half], max_new=args.max_new))
    else:
        from repro.serve import PrefixAffinityScheduler
        sched = PrefixAffinityScheduler(block_size=16)
        for tok, age in trajs:
            half = max(len(tok) // 2, 1)
            req = Request(tokens=tok[:half], ages=age[:half],
                          max_new=args.max_new)
            shard, _ = sched.route(req.tokens, req.ages, shards)
            shard.requests.append(req)
        st = sched.stats()
        counts = ", ".join(f"{s.name}={len(s.requests)}" for s in shards)
        print(f"sharded {args.requests} requests over {n_rep} engines "
              f"({counts}; affinity rate {st['affinity_rate']:.2f})")

    n_events = 0
    t0 = time.time()
    for shard in shards:
        for req in shard.requests:
            shard.engine.submit(req)
    if n_rep == 1:
        done = shards[0].engine.run()
    else:
        # concurrent ticks: start every engine's background thread, then
        # park on the per-request done flags (engine queue/slot stats are
        # racy between admission and slot publication)
        for shard in shards:
            shard.engine.start(retain_completed=True)
        done = []
        try:
            deadline = time.time() + 600.0
            all_reqs = [r for s in shards for r in s.requests]
            while time.time() < deadline:
                if all(r.done for r in all_reqs):
                    break
                time.sleep(0.05)
        finally:
            for shard in shards:
                shard.engine.stop()
                done.extend(shard.engine.completed)
    dt = time.time() - t0
    for r in done:
        n_events += len(r.out_tokens)
    print(f"served {len(done)} requests, {n_events} events "
          f"in {dt:.1f}s ({n_events / dt:.1f} events/s)")
    r = done[0]
    names = [V.code_name(t) for t in r.out_tokens[:8]]
    print("sample trajectory:", list(zip(names,
                                         [round(a, 1) for a in r.out_ages[:8]])))


if __name__ == "__main__":
    main()
