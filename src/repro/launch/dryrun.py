"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

Usage (one combination, or sweep):
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Writes one JSON per combination with cost_analysis, memory_analysis and the
collective-bytes breakdown parsed from the partitioned HLO — the §Roofline
inputs.
"""
# The placeholder-device override MUST precede any jax-touching import, but
# only for the CLI (`python -m repro.launch.dryrun` imports this module as
# __main__ before anything touches jax).  Library importers (tests,
# benchmarks) get NO side effect: mutating process-global XLA_FLAGS at plain
# import time leaked 512 fake devices into every pytest run that merely
# *collected* a module importing the pure helpers below, perturbing fp
# reduction order across the whole suite.
import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config, get_shape  # noqa: E402
from repro.configs.base import InputShape, ModelConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs, long_context_cfg  # noqa: E402
from repro.models import decode_step, forward, param_count  # noqa: E402
from repro.train.optimizer import OptimizerConfig  # noqa: E402
from repro.train.trainer import make_train_step  # noqa: E402

# dtype sizes for HLO shape parsing
_DT = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
       "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
       "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DT:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DT[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes (per device) from partitioned HLO."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str = m.group(1) or m.group(2)
        kind = m.group(3)
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_str)
    return out


def build_step_fn(cfg: ModelConfig, shape: InputShape,
                  moe_impl: str = "dense_scan"):
    if shape.mode == "train":
        objective = "delphi" if cfg.age_encoding else "lm"
        step = make_train_step(cfg, OptimizerConfig(), objective,
                               moe_impl=moe_impl)
        return lambda params, opt_state, batch: step(params, opt_state, batch)
    if shape.mode == "prefill":
        def prefill_step(params, batch):
            out = forward(params, cfg, batch, mode="prefill",
                          moe_impl=moe_impl)
            return out["logits"][:, -1], out["cache"]
        return prefill_step
    def serve_step(params, cache, batch, step):
        out = decode_step(params, cfg, cache, batch, step, moe_impl=moe_impl)
        return out["logits"], out["cache"]
    return serve_step


def _count_one(cfg: ModelConfig, shape: InputShape, mesh,
               moe_impl: str = "dense_scan") -> Dict:
    """Compile one straight-line twin and return its counters."""
    args, shardings = input_specs(cfg, shape, mesh)
    step = build_step_fn(cfg, shape, moe_impl)
    with mesh:
        c = jax.jit(step, in_shardings=shardings).lower(*args).compile()
    ca = c.cost_analysis() or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collectives": collective_bytes(c.as_text())}


def _lin(a: Dict, d: Dict, n: int) -> Dict:
    """a + n*d for counter dicts."""
    coll = dict(a["collectives"])
    for k, v in d["collectives"].items():
        coll[k] = coll.get(k, 0) + n * v
    return {"flops": a["flops"] + n * d["flops"],
            "bytes": a["bytes"] + n * d["bytes"],
            "collectives": {k: max(v, 0) for k, v in coll.items()}}


def _diff(b: Dict, a: Dict) -> Dict:
    return {"flops": b["flops"] - a["flops"],
            "bytes": b["bytes"] - a["bytes"],
            "collectives": {k: b["collectives"].get(k, 0)
                            - a["collectives"].get(k, 0)
                            for k in set(b["collectives"])
                            | set(a["collectives"])}}


def _extrapolated_counts(cfg: ModelConfig, shape: InputShape, mesh,
                         moe_impl: str = "dense_scan") -> Dict:
    base = cfg.replace(unroll_layers=True, attn_direct=True)
    L = cfg.n_layers
    if cfg.arch_type in ("audio", "enc_dec"):
        a = _count_one(base.replace(n_layers=1, n_encoder_layers=1),
                       shape, mesh, moe_impl)
        b = _count_one(base.replace(n_layers=2, n_encoder_layers=1),
                       shape, mesh, moe_impl)
        c = _count_one(base.replace(n_layers=1, n_encoder_layers=2),
                       shape, mesh, moe_impl)
        out = _lin(a, _diff(b, a), L - 1)
        out = _lin(out, _diff(c, a), cfg.n_encoder_layers - 1)
    elif cfg.arch_type == "hybrid":
        k = cfg.attn_every
        n_apps = -(-L // k)
        a = _count_one(base.replace(n_layers=1), shape, mesh, moe_impl)
        b = _count_one(base.replace(n_layers=2), shape, mesh, moe_impl)
        c = _count_one(base.replace(n_layers=k + 1), shape, mesh, moe_impl)
        per_mamba = _diff(b, a)
        per_attn = _diff(c, _lin(a, per_mamba, k))
        out = _lin(a, per_mamba, L - 1)
        out = _lin(out, per_attn, n_apps - 1)
    else:
        a = _count_one(base.replace(n_layers=1), shape, mesh, moe_impl)
        b = _count_one(base.replace(n_layers=2), shape, mesh, moe_impl)
        out = _lin(a, _diff(b, a), L - 1)
    return out


VARIANTS = {
    # §Perf hillclimb variants (EXPERIMENTS.md): cfg override, moe dispatch
    "seqshard": (dict(seq_shard_attn=True), "dense_scan"),
    "moe-einsum": ({}, "dense_einsum"),
    "moe-ragged": ({}, "ragged"),
    "moe-ragged-local": ({}, "ragged_local"),
    "no-remat": (dict(remat=False), "dense_scan"),
}


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               cfg_override: Optional[ModelConfig] = None,
               donate: bool = True, variant: Optional[str] = None) -> Dict:
    shape = get_shape(shape_name)
    cfg = cfg_override or get_config(arch)
    cfg = long_context_cfg(cfg, shape)
    if shape.mode == "train" and cfg_override is None:
        # activation checkpointing over the layer scan is the deployment
        # baseline for 4k x 256 training (see EXPERIMENTS.md §Perf)
        cfg = cfg.replace(remat=True)
    moe_impl = "dense_scan"
    if variant:
        over, moe_impl = VARIANTS[variant]
        cfg = cfg.replace(**over)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    args, shardings = input_specs(cfg, shape, mesh)
    step_fn = build_step_fn(cfg, shape, moe_impl)
    donate_argnums = ()
    if donate and shape.mode == "train":
        donate_argnums = (0, 1)   # params + opt state donated (memory truth)
    elif donate and shape.mode == "decode":
        donate_argnums = (1,)     # cache donated

    t0 = time.time()
    with mesh:
        jitted = jax.jit(step_fn, in_shardings=shardings,
                         donate_argnums=donate_argnums)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost_loop = compiled.cost_analysis() or {}

    # Exact FLOP/byte/collective counts: XLA's CPU cost analysis counts
    # while-loop bodies ONCE, so the scanned deployment graph undercounts by
    # ~n_layers.  We compile straight-line (unrolled, loop-free attention)
    # twins at depth 1 and 2 and extrapolate linearly — exact, because every
    # layer is an identical subgraph (DESIGN.md / EXPERIMENTS.md §Method).
    cost = _extrapolated_counts(cfg, shape, mesh, moe_impl)
    coll = cost.pop("collectives")
    rec = {
        "arch": arch, "shape": shape_name,
        "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "mode": shape.mode,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "sliding_window": cfg.sliding_window,
        "n_params": None,   # filled below (cheap eval_shape count)
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": cost.get("flops"),
        "bytes_per_device": cost.get("bytes"),
        "flops_per_device_loop_counted": cost_loop.get("flops"),
        "collective_bytes_per_device": coll,
        "collective_total": sum(coll.values()),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
    }
    import numpy as np
    from repro.launch.specs import params_spec
    rec["n_params"] = int(sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(params_spec(cfg))))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default=None, choices=sorted(VARIANTS))
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    combos = []
    if args.all:
        combos = [(a, s.name) for a in ASSIGNED_ARCHS for s in INPUT_SHAPES]
    else:
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        tag = f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
        if args.variant:
            tag += f"_{args.variant}"
        try:
            rec = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                             variant=args.variant)
            path = os.path.join(args.out, tag + ".json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=2)
            print(f"OK   {tag}: lower {rec['lower_s']}s compile "
                  f"{rec['compile_s']}s flops/dev {rec['flops_per_device']:.3e} "
                  f"coll {rec['collective_total']:.3e}B "
                  f"peak {rec['memory']['peak_estimate_bytes']/2**30:.2f}GiB")
        except Exception as e:  # noqa: BLE001
            failures.append((tag, repr(e)))
            print(f"FAIL {tag}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: "
                         + ", ".join(t for t, _ in failures))
    print("all dry-runs passed")


if __name__ == "__main__":
    main()
