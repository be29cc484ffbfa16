"""Closed-loop callers (``arrivals.concurrency``, started over
``arrivals.ramp_s``) on an in-process ``EngineBackend``: no wire.

End-to-end number: ``events_per_s``, every event emitted inside the window
over the window, counted as emitted from snapshots of the engine's slots,
queue and finished requests (``window.Ledger``).
"""
import threading
import time

import numpy as np

from harness import program, traffic, work
from harness.check import pick
from harness.window import (Ledger, Outcome, Window, engine_layer,
                            memory_peak, prompt_lengths)


def run(run) -> Outcome:
    from repro.api import GenerateRequest, RequestCancelledError
    cfg, mix = run.cfg, run.mix
    srv, served = mix["server"], cfg["served"]
    mcfg = program.model_config(cfg)
    w = run.weights()
    params = run.ref.to_program(w)
    program.check_tree(params, mcfg)
    be = program.engine_backend(params, mcfg, srv, served, run.engine_seed)
    program.warm_admissions(be.engine, prompt_lengths(mix),
                            int(mix["warm"]["largest_group"]), False,
                            run.seed)
    eng = be.engine
    eng.start(retain_completed=True)        # the ledger reads what finished
    warm, secs = float(mix["warm_s"]), run.seconds
    specs = traffic.specs(mix, run.seed, warm, secs)
    ledger, results, errors = Ledger(), {}, []
    lock, stop = threading.Lock(), threading.Event()
    nxt = iter(specs)

    arr = mix["arrivals"]
    ramp = float(arr.get("ramp_s", 0.0)) / int(arr["concurrency"])

    def worker(k):
        time.sleep(k * ramp)
        while not stop.is_set():
            with lock:
                spec = next(nxt)
            toks, _ = traffic.prompt(mix, spec, run.seed)
            rid = f"g{spec.index}"
            with lock:
                ledger.add(rid, len(toks), time.monotonic())
            try:
                res = be.generate(GenerateRequest(
                    tokens=toks.tolist(), max_new=spec.max_new,
                    request_id=rid))
            except RequestCancelledError:
                continue                    # cut at the window's close
            except Exception as e:          # noqa: BLE001 - counted, reported
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                continue
            with lock:
                ledger.done(rid, len(res.tokens), time.monotonic())
                results[rid] = (toks, res.tokens)

    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(int(arr["concurrency"]))]
    start = time.monotonic()
    for t in threads:
        t.start()
    win = Window(run, [eng])
    win.measure(start + warm)
    run.mark_setup(win.snaps["start"]["t"])
    stop.set()
    # the check samples requests finished by the close: cut the rest
    with lock:
        cut = [rid for rid, r in ledger.reqs.items() if r["ret"] is None]
    for rid in cut:
        be.cancel(rid)
    for t in threads:
        t.join(timeout=120)
    mem = memory_peak(run.devices)
    eng.stop()
    win.engines = []
    counts = work.counts(cfg)
    lay = engine_layer(win, ledger, counts, srv["slots"])
    attempted = len(ledger.overlapping(win.snaps["start"], win.snaps["end"]))
    rng = np.random.default_rng([run.seed, 4242])
    items = list(results.values())
    samples = [{"tokens": t, "ages": None, "out": o, "out_ages": None,
                "u": None}
               for t, o in pick(rng, items, int(mix["check"]["sample"]),
                                lambda it: len(it[0]) + len(it[1]))]
    del be, eng
    notes = [f"requests finished {len(results)}, errors {len(errors)}, "
             f"events in window {lay['events']}, compiles in window "
             f"{lay['compiles_in_window']}"] + win.watched + errors[:3]
    return Outcome(e2e={"events_per_s": lay["events"] / lay["window_s"]},
                   attempted=attempted, failed=len(errors), layer=lay,
                   memory_peak=mem, samples=samples, notes=notes)
