"""Open-loop ``/v1/stream`` load from a separate load generator process
(``harness/loadgen.py``) against one ``InferenceServer`` on one chip.

End-to-end numbers: ``ttfe_p95_ms``, from when each request of the window
was due to its first event on the client (a failed request counts as
unbounded), and ``gap_p95_ms``, over the gaps between consecutive streamed
events that fell inside the window.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

from harness import program, traffic
from harness.check import pick
from harness.window import (UNBOUNDED_MS, Outcome, Window, memory_peak, p95,
                            prompt_lengths)

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "harness", "loadgen.py")


def run(run) -> Outcome:
    cfg, mix = run.cfg, run.mix
    srv, served = mix["server"], cfg["served"]
    mcfg = program.model_config(cfg)
    w = run.weights()
    params = run.ref.to_program(w)
    program.check_tree(params, mcfg)
    injected = bool(mix["output"].get("uniforms"))
    be = program.engine_backend(params, mcfg, srv, served, run.engine_seed)
    program.warm_admissions(be.engine, prompt_lengths(mix),
                            int(mix["warm"]["largest_group"]), injected,
                            run.seed)
    server = program.http_server(be)
    engines = [be.engine]
    warm, secs = float(mix["warm_s"]), run.seconds
    t0 = time.monotonic() + 1.5
    argv = [sys.executable, LOADGEN,
            "--mix", json.dumps(mix), "--seed", str(run.seed),
            "--warm", str(warm),
            "--seconds", str(secs), "--url", server.address,
            "--t0", repr(t0),
            "--vocab", str(mcfg.vocab_size)] + (["--uniforms"]
                                                if injected else [])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    try:
        win = Window(run, engines)
        win.measure(t0 + warm)
        run.mark_setup(win.snaps["start"]["t"])
        out, err = proc.communicate(timeout=warm + secs + 150)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}: "
                           f"{err[-2000:]}")
    lines = [json.loads(x) for x in out.splitlines() if x.strip()]
    late = lines.pop()["lateness"]
    mem = memory_peak(run.devices)
    server.stop()
    # the program's state goes before the reference runs on the chip
    win.engines = engines = server = be = None

    in_win = [r for r in lines if warm <= r["due"] < warm + secs]
    failed = [r for r in in_win if r["status"] != "ok"]
    first = [((r["events"][0][0] if r["events"] else r["end"]) - r["due"])
             * 1e3 if r["status"] == "ok" else UNBOUNDED_MS for r in in_win]
    gaps = [(b[0] - a[0]) * 1e3 for r in lines
            for a, b in zip(r["events"], r["events"][1:])
            if warm <= b[0] < warm + secs]
    lay = win.layer()
    lay["slots"] = srv["slots"]
    notes = [f"load generator lateness: {json.dumps(late)}",
             f"requests in window {len(in_win)}, failed {len(failed)}, "
             f"gaps {len(gaps)}, compiles in window "
             f"{lay['compiles_in_window']}"] + win.watched
    notes += [f"failed request: {r['status']}" for r in failed[:3]]
    specs = {s.index: s for s in traffic.specs(mix, run.seed, warm, secs)}
    done = [r for r in in_win if r["status"] == "ok" and r["events"]]
    rng = np.random.default_rng([run.seed, 4242])
    samples = []
    for r in pick(rng, done, int(mix["check"]["sample"]),
                  lambda r: len(r["events"])):
        spec = specs[r["i"]]
        toks, ages = traffic.prompt(mix, spec, run.seed)
        n = len(r["events"])
        samples.append({
            "tokens": toks, "ages": ages,
            "out": [e[1] for e in r["events"]],
            "out_ages": ([e[2] for e in r["events"]] if ages is not None
                         else None),
            "u": (traffic.uniforms(run.seed, spec.index, spec.max_new,
                                   mcfg.vocab_size)[:n]
                  if injected else None)})
    return Outcome(e2e={"ttfe_p95_ms": p95(first), "gap_p95_ms": p95(gaps)},
                   attempted=len(in_win), failed=len(failed), layer=lay,
                   memory_peak=mem, samples=samples, notes=notes)
