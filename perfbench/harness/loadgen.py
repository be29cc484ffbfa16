"""Open-loop load generator: ``/v1/stream`` requests on a fixed schedule.

Runs as its own process and never imports JAX, so it shares no chip and no
interpreter lock with the server it drives.  It speaks the server's JSON/SSE
wire protocol (v1) with asyncio streams: each request is sent when it is
due, whether or not earlier ones have been answered, and every streamed
event is stamped with ``time.monotonic()`` (one clock for every process of
the machine) the moment its frame is read.

    python loadgen.py --mix '{...mix...}' --seed N --warm S --seconds S \
        --url http://127.0.0.1:PORT --t0 MONOTONIC --vocab V [--uniforms]

Prints, once the last stream has ended, one JSON object per request
(``i``, ``due``, ``sent``, ``end``, ``status``, ``events`` as
``[t, token, age]``,
all times in seconds after ``t0``) and then a ``lateness`` line.  Requests
due after the window are never sent.
"""
from __future__ import annotations

import argparse
import asyncio
import base64
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from harness import traffic  # noqa: E402

#: a request's body is built this long before it is due
LEAD_S = 0.25
#: streams still open this long after the window closes are cut
DRAIN_S = 60.0


def body(mix: dict, spec, seed: int, vocab: int, with_uniforms: bool) -> bytes:
    toks, ages = traffic.prompt(mix, spec, seed)
    d = {"protocol_version": "1", "tokens": toks.tolist(),
         "max_new": spec.max_new, "seed": 0}
    if ages is not None:
        d["ages"] = [float(a) for a in ages]
    if with_uniforms:
        u = traffic.uniforms(seed, spec.index, spec.max_new, vocab)
        d["uniforms"] = {"shape": list(u.shape), "dtype": "float32",
                         "b64": base64.b64encode(u.tobytes()).decode()}
    return json.dumps(d).encode()


async def one(spec, payload_fn, host: str, port: int, t0: float,
              out: list) -> None:
    rec = {"i": spec.index, "due": spec.due, "sent": None, "end": None,
           "status": "failed", "events": []}
    out.append(rec)
    await asyncio.sleep(max(0.0, t0 + spec.due - LEAD_S - time.monotonic()))
    payload = payload_fn(spec)
    head = (f"POST /v1/stream HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Accept: text/event-stream\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n").encode()
    await asyncio.sleep(max(0.0, t0 + spec.due - time.monotonic()))
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        rec["sent"] = time.monotonic() - t0
        writer.write(head + payload)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            rec["status"] = f"http {status.decode(errors='replace').strip()}"
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        event = None
        while True:
            line = await reader.readline()
            if not line:
                rec["status"] = "closed without a terminal frame"
                return
            line = line.decode().rstrip("\r\n")
            if line.startswith("event:"):
                event = line[6:].strip()
            elif line.startswith("data:"):
                if event == "event":
                    d = json.loads(line[5:])
                    rec["events"].append([time.monotonic() - t0,
                                          int(d["token"]), d.get("age")])
                elif event == "done":
                    rec["status"] = "ok"
                    rec["end"] = time.monotonic() - t0
                    return
                else:
                    rec["status"] = f"{event}: {line[5:].strip()[:200]}"
                    return
    except OSError as e:
        rec["status"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def run(opts) -> list:
    mix = json.loads(opts.mix)
    host, port = opts.url.rsplit("//", 1)[-1].rsplit(":", 1)
    reqs = [s for s in traffic.specs(mix, opts.seed, opts.warm, opts.seconds)
            if s.due < opts.warm + opts.seconds]
    out: list = []

    def payload_fn(spec):
        return body(mix, spec, opts.seed, opts.vocab, opts.uniforms)

    tasks = [asyncio.ensure_future(one(s, payload_fn, host, int(port),
                                       opts.t0, out)) for s in reqs]
    end = opts.t0 + opts.warm + opts.seconds + DRAIN_S
    done, pending = await asyncio.wait(
        tasks, timeout=max(1.0, end - time.monotonic()))
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending)
    for t in done:
        t.result()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mix", required=True, help="the mix, as JSON text")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--warm", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--url", required=True)
    ap.add_argument("--t0", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--uniforms", action="store_true")
    opts = ap.parse_args(argv)
    out = asyncio.run(run(opts))
    late = np.array([r["sent"] - r["due"] for r in out
                     if r["sent"] is not None])
    for r in sorted(out, key=lambda r: r["i"]):
        print(json.dumps(r))
    print(json.dumps({"lateness": {
        "n": int(late.size),
        "p50_ms": float(np.percentile(late, 50) * 1e3) if late.size else None,
        "p99_ms": float(np.percentile(late, 99) * 1e3) if late.size else None,
        "max_ms": float(late.max() * 1e3) if late.size else None}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
