#!/usr/bin/env python3
"""Where a request's first-token time goes between the client and the
scheduler. Run by hand, on the chip; it rides on ``sweep_rate.py`` (one
set-up, one rung per rate) with clock probes hung on the program's front
door, and changes nothing but what it reads:

    python3 benchmark/probe_first_token.py --workload serve-batch-long \
        --mix chat --rates 1 3 4.8 --out chiprun_out/probe

    sent -> accept            the listener takes the connection
    accept -> handler         a handler thread starts and parses the request
    handler -> api.submit     gateway._submit, ReplicaPool.submit, routing
    api.submit: lock wait     ServingAPI.submit waits for ServingAPI._lock,
                              which the pump loop holds through every step
    queued -> first emit      scheduler queue, admission, prefill
    first emit -> client      the SSE writer and the socket

All probes read ``time.monotonic()``, the clock the load generator's
records are on; a request is followed by its client port. The probes wrap
methods of the program by name (``Gateway._submit``, ``ReplicaPool.submit``,
``ServingAPI.submit``, ``Scheduler._emit``, ``Scheduler.step``): a refactor
that renames one makes this tool fail loudly, not read wrongly. The result
of PR 23's run is in ``benchmark/records/first_token_stages.md``.
"""
from __future__ import annotations

import argparse
import json
import os
import socketserver
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

mono = time.monotonic
_here = threading.local()
REQ, ACCEPT, EMIT, STEPS, RUNGS = {}, {}, {}, [], []

STAGES = [("sent -> accept", "sent", "accept"),
          ("accept -> handler", "accept", "handler"),
          ("handler -> api.submit", "handler", "api_enter"),
          ("api.submit: lock wait", "api_enter", "api_locked"),
          ("api.submit: locked -> queued", "api_locked", "queued"),
          ("queued -> first emit", "queued", "emit"),
          ("first emit -> client", "emit", "client_first"),
          ("sent -> the 200 (client)", "sent", "headers"),
          ("sent -> first token (client)", "sent", "client_first")]


def hang_probes() -> None:
    from benchmark.harness import serve
    from paddle_tpu.serving import api, scheduler
    from paddle_tpu.serving.gateway import gateway

    take = socketserver.ThreadingMixIn.process_request

    def process_request(self, request, client_address):
        ACCEPT[client_address[1]] = mono()
        return take(self, request, client_address)

    socketserver.ThreadingMixIn.process_request = process_request
    make = gateway._make_handler

    def make_handler(gw):
        handler = make(gw)
        post = handler.do_POST

        def do_POST(self):
            _here.rec = REQ[self.client_address[1]] = {"handler": mono()}
            try:
                return post(self)
            finally:
                _here.rec = None

        handler.do_POST = do_POST
        return handler

    gateway._make_handler = make_handler
    submit = api.ServingAPI.submit

    def api_submit(self, *a, **k):
        rec, t = getattr(_here, "rec", None), mono()
        with self._lock:  # an RLock: the wrapped submit takes it again
            locked = mono()
            req = submit(self, *a, **k)
        if rec is not None:
            rec.update(api_enter=t, api_locked=locked, queued=mono(),
                       rid=req.request_id)
        return req

    api.ServingAPI.submit = api_submit
    emit = scheduler.Scheduler._emit

    def first_emit(self, req, token):
        EMIT.setdefault(req.request_id, mono())
        return emit(self, req, token)

    scheduler.Scheduler._emit = first_emit
    step = scheduler.Scheduler.step

    def timed_step(self):
        t, before = mono(), len(self.running) + len(self.prefilling)
        out = step(self)
        STEPS.append((t, mono(), len(self.running) + len(self.prefilling)
                      - before))
        return out

    scheduler.Scheduler.step = timed_step
    offer = serve.Server.offer

    def kept_offer(self, sched, **k):
        out = offer(self, sched, **k)
        RUNGS.append({"t0": out["t0"], "seconds": sched["seconds"],
                      "records": out["records"]})
        return out

    serve.Server.offer = kept_offer


def reduce(rates) -> list:
    from benchmark.harness import stats

    ms = lambda xs, p: 1e3 * stats.percentile(xs, p)[0]
    out = []
    for rate, rung in zip(rates, RUNGS):
        t0, rows = rung["t0"], []
        for r in rung["records"]:
            s = REQ.get(r.get("port"))
            # a port used twice (one rung, then another) is told apart by
            # the handler's time lying after this request was sent
            if not s or not r["t_tokens"] or not (
                    0.0 <= s["handler"] - (t0 + r["sent_s"]) < 60.0):
                continue
            rows.append(dict(s, sent=t0 + r["sent_s"],
                             accept=ACCEPT.get(r["port"]),
                             headers=t0 + r["headers_s"],
                             client_first=t0 + r["t_tokens"][0],
                             emit=EMIT.get(s.get("rid"))))
        table = {}
        for name, a, b in STAGES:
            d = [x[b] - x[a] for x in rows
                 if x.get(a) is not None and x.get(b) is not None]
            if d:
                table[name] = {"n": len(d), "p50_ms": ms(d, 50),
                               "p95_ms": ms(d, 95), "max_ms": 1e3 * max(d)}
        steps = [s for s in STEPS if t0 <= s[0] < t0 + rung["seconds"]]
        between = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
        out.append({"rate_per_s": rate, "requests": len(rows),
                    "stages": table, "steps": len(steps),
                    "steps_that_admitted": sum(s[2] > 0 for s in steps),
                    "most_admitted_in_one_step": max(
                        (s[2] for s in steps), default=0),
                    "step_p50_ms": ms([s[1] - s[0] for s in steps], 50),
                    "lock_free_between_steps_p50_ms": ms(between, 50),
                    "lock_free_between_steps_p95_ms": ms(between, 95)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mix", default=None)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", nargs="+", required=True)
    ap.add_argument("--rung-seconds", default="40")
    ap.add_argument("--out", required=True, help="a directory")
    a = ap.parse_args(argv)
    from benchmark import run as R, sweep_rate

    R.environment()
    hang_probes()
    os.makedirs(a.out, exist_ok=True)
    code = sweep_rate.main(
        ["--workload", a.workload, "--seed", str(a.seed), "--rates",
         *a.rates, "--rung-seconds", a.rung_seconds, "--out",
         os.path.join(a.out, "sweep.json")]
        + (["--mix", a.mix] if a.mix else []))
    if code:
        return code
    rungs = reduce(sorted(float(r) for r in a.rates))
    with open(os.path.join(a.out, "first_token_stages.json"), "w") as f:
        json.dump(rungs, f, indent=1)
    for rung in rungs:
        print(f"rate {rung['rate_per_s']}/s, {rung['requests']} requests, "
              f"{rung['steps']} steps of which "
              f"{rung['steps_that_admitted']} admitted (most in one: "
              f"{rung['most_admitted_in_one_step']}); the lock is free "
              f"{rung['lock_free_between_steps_p50_ms']:.3f} ms between steps")
        for name, row in rung["stages"].items():
            print(f"  {name:30s} p50 {row['p50_ms']:9.1f} ms   p95 "
                  f"{row['p95_ms']:9.1f}   max {row['max_ms']:9.1f}")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
