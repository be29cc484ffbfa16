"""Cohort workers (``arrivals.max_in_flight``) asking an in-process
``EngineBackend`` for ``arrivals.n_futures`` Monte-Carlo futures of one
patient at a time, with injected uniforms, and aggregating chapter risks
as the cohort engine does: no wire.

End-to-end number: ``events_per_s``, as in ``engine_generate``.
"""
import threading
import time

import numpy as np

from harness import program, traffic, work
from harness.check import pick
from harness.window import (Ledger, Outcome, Window, engine_layer,
                            memory_peak, prompt_lengths)


def run(run) -> Outcome:
    from repro.api import FuturesRequest
    cfg, mix = run.cfg, run.mix
    srv, served = mix["server"], cfg["served"]
    mcfg = program.model_config(cfg)
    V = mcfg.vocab_size
    w = run.weights()
    params = run.ref.to_program(w)
    program.check_tree(params, mcfg)
    be = program.engine_backend(params, mcfg, srv, served, run.engine_seed)
    arr, out_cfg = mix["arrivals"], mix["output"]
    nf, mx = int(arr["n_futures"]), int(out_cfg["max_new"])
    program.warm_futures(be, prompt_lengths(mix), list(range(1, nf + 1)),
                         3, run.seed)
    eng = be.engine
    eng.start(retain_completed=True)        # the ledger reads what finished
    warm, secs = float(mix["warm_s"]), run.seconds
    specs = traffic.specs(mix, run.seed, warm, secs)
    ledger, results, errors = Ledger(), {}, []
    lock, stop = threading.Lock(), threading.Event()
    nxt = iter(specs)
    horizon = float(out_cfg.get("horizon", 5.0))

    def worker():
        while not stop.is_set():
            with lock:
                spec = next(nxt)
            toks, ages = traffic.prompt(mix, spec, run.seed)
            u = traffic.uniforms(run.seed, spec.index, mx, V, n=nf)
            pid = f"c{spec.index}"
            kids = [f"{pid}/fork-{j}" for j in range(nf)]
            with lock:
                now = time.monotonic()
                for k in kids:
                    ledger.add(k, len(toks), now, parent=pid)
            try:
                res = be.sample_futures(FuturesRequest(
                    tokens=toks.tolist(), ages=ages.tolist(), n_futures=nf,
                    max_new=mx, horizon=horizon, uniforms=u,
                    request_id=pid))
                trajs = [(t.tokens, t.ages) for t in res.trajectories]
                program.chapter_risk(trajs, float(ages[-1]), horizon, V)
            except Exception as e:          # noqa: BLE001 - counted, reported
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                continue
            now = time.monotonic()
            with lock:
                for k, (t, _) in zip(kids, trajs):
                    ledger.done(k, len(t), now)
                results[pid] = (spec, toks, ages, trajs)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(arr["max_in_flight"]))]
    start = time.monotonic()
    for t in threads:
        t.start()
    win = Window(run, [eng])
    win.measure(start + warm)
    run.mark_setup(win.snaps["start"]["t"])
    stop.set()
    for t in threads:
        t.join(timeout=120)
    mem = memory_peak(run.devices)
    eng.stop()
    win.engines = []
    counts = work.counts(cfg)
    lay = engine_layer(win, ledger, counts, srv["slots"])
    attempted = len({r["parent"] for r in ledger.overlapping(
        win.snaps["start"], win.snaps["end"])})
    rng = np.random.default_rng([run.seed, 4242])
    items = [(pid, j) for pid, v in results.items()
             for j in range(len(v[3]))]
    samples = []
    for pid, j in pick(rng, items, int(mix["check"]["sample"]),
                       lambda it: len(results[it[0]][3][it[1]][0])):
        spec, toks, ages, trajs = results[pid]
        out, out_ages = trajs[j]
        samples.append({"tokens": toks, "ages": ages, "out": out,
                        "out_ages": out_ages,
                        "u": traffic.uniforms(run.seed, spec.index, mx, V,
                                              n=nf)[j, :len(out)]})
    del be, eng
    notes = [f"patients finished {len(results)}, errors {len(errors)}, "
             f"events in window {lay['events']}, compiles in window "
             f"{lay['compiles_in_window']}"] + win.watched + errors[:3]
    return Outcome(e2e={"events_per_s": lay["events"] / lay["window_s"]},
                   attempted=attempted, failed=len(errors), layer=lay,
                   memory_peak=mem, samples=samples, notes=notes)
