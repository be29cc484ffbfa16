#!/usr/bin/env python3
"""Calibration probe: several runs of one cell in one process, on the chip.

    python3 perfbench/tools/probe.py --workload NAME --seeds 1,2,3 \
        --seconds 5 [--rates 40,80] [--control] [--trace] [--out FILE]

Each (seed, rate) pair is a run as ``run.py`` makes it, sharing this
process's compiled programs: the knee sweep of an open-loop cell
(``--rates`` overrides the mix's rate), the program's and the control's
readings over many seeds for the check's limits (``--control``), a look at
the trace (``--trace`` keeps a shortened copy of the first one).  One JSON
object per run goes to ``--out``; a summary line per run to standard
output.  The benchmark's own runs never call this.
"""
import argparse
import gc
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--out", default=None)
    o = ap.parse_args(argv)
    seeds = [int(s) for s in o.seeds.split(",")]
    rates = [float(r) for r in o.rates.split(",") if r] or [None]
    opts = types.SimpleNamespace(workload=o.workload, seed=seeds[0],
                                 seconds=o.seconds, trace=int(o.trace))
    try:
        # the machine's own cache directory, where it has one, carries
        # compiled programs from one probe call to the next
        bench, cell, first, peak = bench_run.open_run(
            opts, os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or bench_run.CACHE_DIR)
    except bench_run.Refused as e:
        return bench_run.fail(str(e))
    from harness import byname, trace, work
    rows = []
    kept_trace = False
    for rate in rates:
        for seed in seeds:
            run = bench_run.Run(types.SimpleNamespace(
                workload=o.workload, seed=seed, seconds=o.seconds,
                trace=int(o.trace)), bench, cell)
            run.devices, run._compiles = first.devices, first._compiles
            if rate is not None:
                run.mix["arrivals"]["rate"] = rate
            t0 = time.monotonic()
            try:
                out, readings = bench_run.measure(run, control=o.control)
            except Exception as e:  # noqa: BLE001 - one run's failure
                rows.append({"seed": seed, "rate": rate,
                             "error": f"{type(e).__name__}: {e}"})
                print(json.dumps(rows[-1]), flush=True)
                continue
            lay = {k: v for k, v in out.layer.items() if k != "trace"}
            row = {"seed": seed, "rate": rate, "e2e": out.e2e,
                   "setup_s": run.setup_s, "wall_s": time.monotonic() - t0,
                   "attempted": out.attempted, "failed": out.failed,
                   "memory_peak": out.memory_peak, "readings": readings,
                   "layer": lay, "notes": out.notes}
            rec = out.layer.get("trace")
            if rec is not None:
                ctx = dict(out.layer, chips=int(cell["chips"]), peaks=peak,
                           counts=work.counts(run.cfg))
                vals = {}
                for m in bench_run.per_layer(bench, cell, set(out.e2e)):
                    vals[m["name"]] = byname.load("metrics",
                                                  m["name"]).read(ctx)
                row["per_layer"] = vals
                row["busy_s"] = trace.busy_s(rec)
                row["window_s"] = rec["window_s"]
                row["device_ops"] = trace.device_ops(rec)
                row["idle_gaps"] = trace.idle_gaps(rec)
                if not kept_trace:
                    row["trace_sample"] = trace.shrink(rec)
                    row["trace_lines"] = {
                        k: {"modules": sorted({e[0] for e in v["modules"]})[:40],
                            "n_ops": len(v["ops"])}
                        for k, v in rec["devices"].items()}
                    row["host_threads"] = sorted({h[0] for h in rec["host"]})
                    kept_trace = True
            rows.append(row)
            print(json.dumps({k: row[k] for k in
                              ("seed", "rate", "e2e", "setup_s", "wall_s",
                               "attempted", "failed", "memory_peak",
                               "readings")} | {"per_layer": row.get(
                                   "per_layer")}), flush=True)
            for n in out.notes:
                print("  " + n, flush=True)
            del run, out
            gc.collect()
    if o.out:
        os.makedirs(os.path.dirname(os.path.abspath(o.out)), exist_ok=True)
        with open(o.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
