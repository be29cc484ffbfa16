"""Training launcher.

On this CPU container it runs the paper-scale Delphi training end-to-end
(synthetic data -> dual loss -> checkpoint).  On a real TPU slice the same
entry point builds the production mesh and shards the identical
``make_train_step`` with the identical sharding rules the dry-run proves out.

    PYTHONPATH=src python -m repro.launch.train --arch delphi-2m --steps 200 \
        [--patients 2048] [--out runs/delphi]
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro.configs import get_config
from repro.data import (SimulatorConfig, batches, dataset_stats,
                        generate_dataset, pack_trajectories)
from repro.launch.runtime import served_config, use_compile_cache
from repro.models import init_params, param_count
from repro.train import OptimizerConfig, save, train_loop


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="delphi-2m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--patients", type=int, default=7144)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cfg = served_config(get_config(args.arch, reduced=args.reduced))
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    print(f"{cfg.name}: {param_count(params):,} params "
          f"({jax.default_backend()} backend, {len(jax.devices())} devices)")

    if cfg.age_encoding:
        sim = SimulatorConfig(n_train=args.patients, n_val=args.patients,
                              seed=args.seed)
        train, val = generate_dataset(sim)
        print("train data:", dataset_stats(train))
        pt = pack_trajectories(train, args.seq_len)
        pv = pack_trajectories(val, args.seq_len)
        ti = batches(pt, args.batch, seed=args.seed)
        vi = batches(pv, args.batch, seed=args.seed + 1)
        objective = "delphi"
    else:
        rng = np.random.default_rng(args.seed)
        def lm_iter():
            while True:
                yield {"tokens": rng.integers(
                    0, cfg.vocab_size, (args.batch, args.seq_len)).astype(np.int32)}
        ti, vi = lm_iter(), lm_iter()
        objective = "lm"

    ocfg = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                           total_steps=args.steps)
    params, hist = train_loop(params, cfg, ocfg, ti, objective=objective,
                              steps=args.steps, eval_iter=vi,
                              eval_every=max(args.steps // 4, 25))
    if args.out:
        save(args.out, params, cfg, extra={"history": hist})
        with open(os.path.join(args.out, "history.json"), "w") as f:
            json.dump(hist, f)
        print("saved to", args.out)


if __name__ == "__main__":
    main()
