"""Closed-loop callers (``arrivals.concurrency``, started over
``arrivals.ramp_s``) on the prefix-affinity ``RouterServer`` over
``replicas`` in-process engine replicas, one per chip
(``ReplicaSupervisor.in_process``), each behind its own HTTP server: the
router's hop to a replica goes over its pooled localhost connections, as
deployed.  Each caller runs sessions of the mix's source (``visits``: one
patient's visits, each extending the last), one visit at a time, with
injected uniforms.

End-to-end number: ``events_per_s``, every event the replicas' engines
emitted inside the window over the window (``window.Ledger`` over all of
them).
"""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import byname, program, traffic, work
from harness.check import pick
from harness.window import (Ledger, Outcome, Window, engine_layer,
                            memory_peak, prompt_lengths)

#: visits a session may hold: request ``index`` of visit ``v`` of the
#: stream's session ``i`` is ``i * MAX_VISITS + v``
MAX_VISITS = 8


def run(run) -> Outcome:
    import jax
    from repro.api import GenerateRequest
    from repro.serve.router import ReplicaSupervisor, RouterServer
    cfg, mix = run.cfg, run.mix
    srv, served = mix["server"], cfg["served"]
    mcfg = program.model_config(cfg)
    V = mcfg.vocab_size
    params = run.ref.to_program(run.weights())
    program.check_tree(params, mcfg)
    lengths = prompt_lengths(mix)
    n = int(mix["replicas"])
    devices = jax.devices()

    def build(i):
        # on the replica's chip, as ReplicaSupervisor.in_process places it:
        # its params, pool and state live there
        with jax.default_device(devices[i % len(devices)]):
            be = program.engine_backend(params, mcfg, srv, served,
                                        run.derived(10 + i))
        # warmed as the engine's own thread runs, with no default device:
        # the default device is part of what a compiled program is cached by
        with jax.default_device(None):
            program.warm_admissions(be.engine, lengths,
                                    int(mix["warm"]["largest_group"]), True,
                                    run.seed)
        be.engine.start(retain_completed=True)  # the ledger reads these
        return be

    # every replica compiles each shape for its own chip: side by side
    with ThreadPoolExecutor(n) as pool:
        built = list(pool.map(build, range(n)))
    engines = [be.engine for be in built]
    sup = ReplicaSupervisor.in_process(built.__getitem__, n)
    router = RouterServer(sup, port=0).start()
    source = byname.load("sources", mix["source"]["kind"])
    warm, secs = float(mix["warm_s"]), run.seconds
    mx = int(mix["output"]["max_new"])
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
            for v, (toks, ages) in enumerate(
                    source.visits(mix["source"], spec)[:MAX_VISITS]):
                if stop.is_set():
                    return
                index = spec.index * MAX_VISITS + v
                rid = f"s{spec.index}.{v}"
                u = traffic.uniforms(run.seed, index, mx, V)
                with lock:
                    ledger.add(rid, len(toks), time.monotonic())
                try:
                    res = router.generate(GenerateRequest(
                        tokens=toks.tolist(), ages=ages.tolist(),
                        max_new=mx, uniforms=u, request_id=rid))
                except Exception as e:      # noqa: BLE001 - counted, reported
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
                    break
                with lock:
                    ledger.done(rid, len(res.tokens), time.monotonic())
                    results[rid] = (toks, ages, res.tokens, res.ages, index)

    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(int(arr["concurrency"]))]
    start = time.monotonic()
    for t in threads:
        t.start()
    win = Window(run, engines)
    win.measure(start + warm)
    run.mark_setup(win.snaps["start"]["t"])
    stop.set()
    # visits under way finish (64 events at most); none starts after this
    deadline = time.monotonic() + 120
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    mem = memory_peak(run.devices)
    router.stop()
    win.engines = []
    lay = engine_layer(win, ledger, work.counts(cfg), srv["slots"])
    attempted = len(ledger.overlapping(win.snaps["start"], win.snaps["end"]))
    rng = np.random.default_rng([run.seed, 4242])
    samples = [{"tokens": t, "ages": a, "out": o, "out_ages": oa,
                "u": traffic.uniforms(run.seed, i, mx, V)[:len(o)]}
               for t, a, o, oa, i in pick(rng, list(results.values()),
                                          int(mix["check"]["sample"]),
                                          lambda it: len(it[2]))]
    affinity = router.scheduler.stats()
    del router, sup, engines
    notes = [f"visits finished {len(results)}, errors {len(errors)}, "
             f"events in window {lay['events']}, compiles in window "
             f"{lay['compiles_in_window']}, routing {affinity}"] \
        + win.watched + errors[:3]
    return Outcome(e2e={"events_per_s": lay["events"] / lay["window_s"]},
                   attempted=attempted, failed=len(errors), layer=lay,
                   memory_peak=mem, samples=samples, notes=notes)
