"""A cell at a size the CPU holds: the harness's runs without the chip.

``small_run`` builds the run object ``run.py`` hands to a driver, for a cell
of ``BENCHMARK.json`` with its configuration and traffic cut down, on the
CPU's devices, skipping only the look for a TPU.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(ROOT)
for p in (os.path.join(CHECKOUT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_MODEL = {
    "delphi-2m": dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                      head_dim=8, d_ff=64),
    "h2o-danube-1.8b": dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
                            head_dim=32, d_ff=256, vocab_size=512),
}
#: limits at the cut-down widths, between the readings there: Danube's
#: sound runs read a logit gap of 0.02-0.04 on a CPU and its fp8 control
#: 0.23-0.71, which the full-size limit of 0.25 does not always separate
SMALL_LIMITS = {"h2o-danube-1.8b": {"gap": 0.1}}
#: cells whose files are in place but that ``BENCHMARK.json`` does not hold
#: yet (``PERF.md``, open questions): their runs are tested all the same
PREPARED = [{"name": "delphi-2m.clinic", "config": "delphi-2m",
             "traffic": "clinic", "chips": 1}]
SMALL_MIX = {
    "clinic": {"server": {"slots": 4}, "arrivals": {"rate": 6},
               "warm": {"largest_group": 4}, "warm_s": 1,
               "check": {"sample": 6, "min_tokens": 5}},
    "decode": {"server": {"slots": 4, "max_context": 128},
               "source": {"token_range": [3, 512], "median": 24,
                          "length_range": [8, 60]},
               "output": {"max_new": [4, 8]},
               "arrivals": {"concurrency": 6, "ramp_s": 0.3},
               "warm": {"largest_group": 4}, "warm_s": 1,
               "check": {"sample": 8, "min_tokens": 5}},
    "cohort": {"server": {"slots": 16},
               "arrivals": {"max_in_flight": 2, "n_futures": 4},
               "output": {"max_new": 8}, "warm_s": 1,
               "check": {"sample": 6, "min_tokens": 5}},
}


def _merge(d, o):
    for k, v in o.items():
        if isinstance(v, dict) and isinstance(d.get(k), dict):
            _merge(d[k], v)
        else:
            d[k] = v
    return d


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_run(workload: str, seed: int = 5, seconds: float = 2.0,
              trace: int = 0, limits=None, widths: bool = False):
    """-> (run.py module, benchmark, run object) for ``workload`` with its
    traffic cut to CPU size and its model too, unless ``widths`` keeps the
    configuration's own sizes."""
    import jax
    runmod = load(os.path.join(ROOT, "run.py"), "perfbench_run")
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] + PREPARED
                if c["name"] == workload)
    opts = types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)
    run = runmod.Run(opts, bench, cell)
    run.cfg = copy.deepcopy(run.cfg)
    if not widths:
        _merge(run.cfg["model"], SMALL_MODEL[cell["config"]])
        limits = limits or SMALL_LIMITS.get(cell["config"])
    if limits is not None:
        run.cfg["check"]["limits"] = dict(limits)
    _merge(run.mix, copy.deepcopy(SMALL_MIX[cell["traffic"]]))
    run.devices = jax.devices()[:int(cell["chips"])]
    run.count_compiles()
    return runmod, bench, run
