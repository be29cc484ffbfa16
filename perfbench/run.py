#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one process.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Run from the root of a checkout on a machine holding the chips the cell
asks for.  The cell names a configuration (``perfbench/configs/<config>``:
its sizes and its plain reference) and a traffic mix
(``perfbench/traffic/<traffic>.json``); the mix names its prompt source,
its arrivals and the driver that offers its load, each a file of its own
(``perfbench/sources/``, ``perfbench/arrivals/``, ``perfbench/drivers/``).
The run makes the weights from ``--seed`` on the chip, builds the served
path, warms up every shape the mix uses, measures for ``--seconds`` and
checks a seeded sample of what the window served against the float32
reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (``perfbench/metrics/<metric>.py``, one reader each) from
a profiler trace of the middle of the window and the engine's counters.
The last line of standard output is one JSON object; the last lines of
standard error are the numbers compared, each beside its limit.  Without a
TPU, with fewer chips than the cell asks for, or on a chip missing from
``perfbench/peaks.json``, it exits 2 and prints no result.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(ROOT)
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
TRACE_SECONDS = 4.0


def fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    return 2


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """One run's settings, files and device, handed to the mix's load."""

    def __init__(self, opts, bench: dict, cell: dict):
        self.opts = opts
        self.root = ROOT
        self.cell = cell
        self.seed = int(opts.seed)
        self.seconds = float(opts.seconds)
        self.traced = bool(opts.trace)
        self.trace_seconds = TRACE_SECONDS
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        with open(os.path.join(CHECKOUT, entry["file"])) as f:
            self.cfg = json.load(f)
        mix = os.path.join(ROOT, "traffic", cell["traffic"] + ".json")
        with open(mix) as f:
            self.mix = json.load(f)
        base = os.path.splitext(os.path.join(CHECKOUT, entry["file"]))[0]
        self.ref = load_module(base + ".py", "reference_" + entry["name"]
                               .replace("-", "_").replace(".", "_"))
        self.setup_s = None
        self._compiles = [0]
        self.devices = None

    def derived(self, tag: int) -> int:
        import numpy as np
        return int(np.random.default_rng([self.seed, tag]).integers(2**31 - 1))

    @property
    def engine_seed(self) -> int:
        return self.derived(2)

    def weights(self):
        import jax
        if not hasattr(self, "_w"):
            self._w = self.ref.make_weights(
                self.cfg, jax.random.PRNGKey(self.derived(1)))
            jax.block_until_ready(self._w)
        return self._w

    def count_compiles(self) -> None:
        import jax

        def listen(event, **_):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self._compiles[0] += 1
        jax.monitoring.register_event_listener(listen)

    def compiles(self) -> int:
        return self._compiles[0]

    def mark_setup(self, t_window: float) -> None:
        self.setup_s = t_window - T_PROCESS


def per_layer(bench: dict, cell: dict, reports) -> list:
    """The per-layer metrics this cell reports."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in reports:
            out.append(m)
    return out


def end_to_end(bench: dict, cell: dict) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def compared(cfg: dict, mix: dict, readings: dict, failed: int) -> dict:
    """Each number the check compares, with its limit: the configuration's
    ``check.limits`` (``gap`` and/or ``dt_rel``), no failed request, and at
    least the mix's ``min_tokens`` served events compared."""
    out = {k: {"value": readings[k], "limit": v}
           for k, v in cfg["check"]["limits"].items()}
    out["failed"] = {"value": failed, "limit": 0}
    out["tokens"] = {"value": readings["tokens"],
                     "limit": int(mix["check"]["min_tokens"])}
    return out


def is_correct(check: dict) -> bool:
    ok = True
    for name, c in check.items():
        if c["limit"] is None:
            ok = False
        elif name == "tokens":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


def measure(run, control: bool = False):
    """Drive the cell, then check a seeded sample of what it served against
    the reference (and, with ``control``, the reference's float8 twin):
    -> (outcome, readings)."""
    from harness import byname, check
    outcome = byname.load("drivers", run.mix["driver"]).run(run)
    readings = check.readings(run.ref, run.cfg, run.weights(),
                              outcome.samples,
                              pad=int(run.mix["server"]["max_context"]),
                              batch=int(run.cfg["check"]["batch"]),
                              control=control)
    return outcome, readings


class Refused(Exception):
    """The run cannot measure here: no result is printed."""


def open_run(opts, cache_dir: str = CACHE_DIR):
    """Check the checkout and the chips, turn on the compile cache in
    ``cache_dir``: -> (benchmark, cell, run, peaks of the chip)."""
    try:
        with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cell = next(c for c in bench["workloads"]
                    if c["name"] == opts.workload)
    except (OSError, StopIteration, ValueError) as e:
        raise Refused(f"no workload {opts.workload!r} in BENCHMARK.json "
                      f"({e})") from None
    if not os.path.isdir(os.path.join(CHECKOUT, "src", "repro")):
        raise Refused(f"no program to measure under {CHECKOUT}/src")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    sys.path[:0] = [os.path.join(CHECKOUT, "src"), ROOT]
    run = Run(opts, bench, cell)

    import jax
    with open(os.path.join(ROOT, "peaks.json")) as f:
        peaks = json.load(f)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {dev.platform}")
    if len(devices) < int(cell["chips"]):
        raise Refused(f"{cell['name']} needs {cell['chips']} chips; JAX "
                      f"found {len(devices)}")
    if dev.device_kind not in peaks:
        raise Refused(f"no peaks for {dev.device_kind!r} in peaks.json")
    run.devices = devices[:int(cell["chips"])]
    from repro.launch.runtime import use_compile_cache
    use_compile_cache()
    run.count_compiles()
    return bench, cell, run, peaks[dev.device_kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    try:
        bench, cell, run, peak = open_run(opts)
    except Refused as e:
        return fail(str(e))
    dev = run.devices[0]
    outcome, readings = measure(run)
    for note in outcome.notes:
        print(note, file=sys.stderr)
    print(f"readings: {json.dumps(readings)}", file=sys.stderr)
    checked = compared(run.cfg, run.mix, readings, outcome.failed)
    correct = is_correct(checked)

    e2e = end_to_end(bench, cell)
    metrics = {}
    import jax
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": outcome.memory_peak}
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    if not run.traced:
        for m in e2e:
            v = run.setup_s if m["name"] == "setup_s" \
                else outcome.e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from harness import trace
        rec = outcome.layer["trace"]
        busy = trace.busy_s(rec)
        if busy is None:
            return fail("the trace holds no device operation")
        device.update(busy_s=busy, window_s=rec["window_s"])
        from harness import work
        ctx = dict(outcome.layer, chips=int(cell["chips"]),
                   peaks=peak, counts=work.counts(run.cfg))
        from harness import byname
        for m in per_layer(bench, cell, {x["name"] for x in e2e}):
            v = byname.load("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": trace.device_ops(rec),
                               "idle_gaps": trace.idle_gaps(rec)}
    result.update(metrics=metrics, device=device, check=checked)
    for name, c in checked.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
