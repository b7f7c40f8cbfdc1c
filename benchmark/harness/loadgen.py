#!/usr/bin/env python3
"""The load generator: a process of its own that never imports jax.

    python benchmark/harness/loadgen.py --url http://127.0.0.1:PORT \
        --schedule sched.json --out result.json --t0 <monotonic seconds>

It speaks HTTP and Server-Sent Events only (``POST /v1/stream``), as a
client of the token API does, and runs beside a parent that holds the chip.
``--t0`` is the window's start on ``time.monotonic()``, a clock both
processes share. Every time it writes is relative to ``t0``.

Open loop: each request is sent when it is due, whatever the server is
doing; how late the generator itself ran (sent minus due) is recorded.
Closed loop: ``clients`` threads each send their next request when their
stream ends. Load starts ``ramp_s`` before ``t0``. At ``t0 + seconds`` no
more is sent; an open loop then waits ``drain_s`` for what is in flight, a
closed loop cuts its streams (those requests are neither finished nor
failed: the window ended first).
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import socket
import sys
import threading
import time
from urllib.parse import urlparse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.harness import traffic  # noqa: E402  (numpy only)


class Client:
    def __init__(self, url: str, t0: float):
        u = urlparse(url)
        self.host, self.port, self.t0 = u.hostname, u.port, t0
        self.lock = threading.Lock()
        self.live = set()
        self.stop = threading.Event()

    def stream(self, req: dict, body: bytes) -> dict:
        """Send one request and read its stream to the end."""
        rec = {"id": req["id"], "due_s": req["due_s"],
               "asked": req["max_new_tokens"], "t_tokens": [], "tokens": [],
               "state": None, "error": None}
        conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
        with self.lock:
            self.live.add(conn)
        try:
            rec["sent_s"] = time.monotonic() - self.t0
            conn.connect()
            rec["connected_s"] = time.monotonic() - self.t0
            rec["port"] = conn.sock.getsockname()[1]
            if self.stop.is_set():  # cut() ran while this one connected
                raise OSError("cut")
            conn.request("POST", "/v1/stream", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["headers_s"] = time.monotonic() - self.t0  # accepted
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
                return rec
            event = None
            for line in resp:
                if self.stop.is_set():
                    raise OSError("cut")
                if line.startswith(b"data:"):
                    now = time.monotonic() - self.t0
                    data = json.loads(line[5:])
                    if event == "done":
                        rec["state"] = data.get("state")
                    elif event == "error":
                        rec["error"] = json.dumps(data)[:300]
                    else:
                        rec["t_tokens"].append(now)
                        rec["tokens"].append(int(data["token"]))
                    event = None
                elif line.startswith(b"event:"):
                    event = line[6:].strip().decode()
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = ("cut" if self.stop.is_set()
                            else f"{type(e).__name__}: {e}")
        finally:
            with self.lock:
                self.live.discard(conn)
            conn.close()
        rec["end_s"] = time.monotonic() - self.t0
        return rec

    def cut(self) -> None:
        """Close every live stream (the gateway cancels those requests)."""
        self.stop.set()
        with self.lock:
            conns = list(self.live)
        for c in conns:
            try:
                if c.sock is not None:
                    c.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05) if left > 0.002 else 0)


def run(sched: dict, url: str, t0: float) -> dict:
    reqs = sched["requests"]
    bodies = [json.dumps({"prompt": traffic.prompt_tokens(sched, r),
                          "max_new_tokens": r["max_new_tokens"]}).encode()
              for r in reqs]
    client = Client(url, t0)
    done, done_lock = [], threading.Lock()
    t_end = t0 + sched["seconds"]

    def one(i):
        rec = client.stream(reqs[i], bodies[i])
        with done_lock:
            done.append(rec)

    threads = []
    ready_s = time.monotonic() - t0
    if sched["loop"] == "open":
        for i, r in enumerate(reqs):
            _sleep_until(t0 + r["due_s"])
            t = threading.Thread(target=one, args=(i,), daemon=True)
            t.start()
            threads.append(t)
        limit = t_end + sched["drain_s"]
        for t in threads:
            t.join(max(0.0, limit - time.monotonic()))
        client.cut()
    else:
        nxt = iter(range(len(reqs)))
        nxt_lock = threading.Lock()
        exhausted = []

        def walk():
            while time.monotonic() < t_end and not client.stop.is_set():
                with nxt_lock:
                    i = next(nxt, None)
                if i is None:
                    exhausted.append(True)
                    return
                one(i)

        _sleep_until(t0 - sched["ramp_s"])
        for _ in range(sched["clients"]):
            t = threading.Thread(target=walk, daemon=True)
            t.start()
            threads.append(t)
        _sleep_until(t_end)
        client.cut()
        if exhausted:
            raise SystemExit("loadgen: the closed loop ran out of requests; "
                             "raise requests_per_client in the traffic file")
    for t in threads:
        t.join(10.0)
    alive = sum(t.is_alive() for t in threads)
    with done_lock:
        out = sorted(done, key=lambda r: r["id"])
    return {"ready_s": ready_s, "threads_left": alive, "requests": out}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--t0", type=float, required=True)
    a = ap.parse_args()
    with open(a.schedule) as f:
        sched = json.load(f)
    res = run(sched, a.url, a.t0)
    with open(a.out + ".tmp", "w") as f:
        json.dump(res, f)
    os.replace(a.out + ".tmp", a.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
