#!/usr/bin/env python3
"""The benchmark's own checks, run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py

It is not collected by ``pytest tests/`` and describes no TPU topology.

1. ``BENCHMARK.json`` against the contract's rules that can be checked
   here: every file it names exists, every reader exists, every ``moves``
   names an end-to-end metric that each listed cell reports, names, units
   and lengths.
2. The statistics: a percentile comes with its sample count; a failed
   request enters the first-token times with the window's length.
3. The schedule is a pure function of the seed, every seed gets the same
   lengths and gaps in another order, and the load generator (a child
   process, against a stub server) reports how late it ran.
4. The trace reduction on the small trace recorded on the chip and kept in
   ``benchmark/records/`` gives the numbers recorded beside it.
5. The plain reference against the program at a tiny size: the program's
   full forward pass, and prefill then decode through the engine behind
   the gateway (a whole tiny run comes out correct).
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check(cond, msg):
    if not cond:
        raise SystemExit(f"selfcheck: FAILED: {msg}")


def check_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    check(set(b) == {"command", "paths", "run_seconds", "configs",
                     "workloads", "end_to_end", "per_layer"}, "top-level keys")
    check(os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536,
          "BENCHMARK.json over 64 KiB")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    check("setup_s" in e2e and "workloads" not in e2e["setup_s"],
          "setup_s must be reported by every cell")
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        check(NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
              and 1 <= len(c["why"]) <= 200, f"config {c['name']}")
        path = os.path.join(ROOT, c["file"])
        check(os.path.isfile(path), f"missing {c['file']}")
        with open(path) as f:
            cfg = json.load(f)
        check(cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"],
              f"{c['file']} disagrees with BENCHMARK.json")
        for hook in cfg["hooks"].values():
            check(os.path.isfile(os.path.join(ROOT, hook)), f"missing {hook}")
        check(os.path.isfile(os.path.join(
            BENCH, "harness", cfg["product"] + ".py")), "missing product")
        check(any(w["config"] == c["name"] for w in b["workloads"]),
              f"config {c['name']} is used by no cell")
    four = sum(w["chips"] == 4 for w in b["workloads"])
    check(four <= max(1, len(cells) // 4), "too many four-chip cells")
    for w in b["workloads"]:
        check(NAME.match(w["name"]) and NAME.match(w["traffic"])
              and w["config"] in configs and w["chips"] in (1, 4)
              and 1 <= len(w["why"]) <= 200, f"workload {w['name']}")
        for sub in ("traffic", "cells"):
            name = w["traffic"] if sub == "traffic" else w["name"]
            check(os.path.isfile(os.path.join(BENCH, sub, name + ".json")),
                  f"missing benchmark/{sub}/{name}.json")
    reports = {n: set() for n in cells}
    for m in b["end_to_end"]:
        check(NAME.match(m["name"]) and UNIT.match(m["unit"])
              and m["better"] in ("lower", "higher")
              and 0 < m["bound"] <= 0.1
              and m["source"] in ("host_clock", "device_trace"),
              f"end-to-end metric {m['name']}")
        for n in m.get("workloads", cells):
            check(n in cells, f"{m['name']} lists unknown cell {n}")
            reports[n].add(m["name"])
    layered = set()
    for m in b["per_layer"]:
        check(NAME.match(m["name"]) and UNIT.match(m["unit"])
              and m["better"] in ("lower", "higher") and m["source"] in (
                  "device_trace", "program_span", "program_counter",
                  "host_clock") and 1 <= len(m["layer"]) <= 200
              and set(m) <= {"name", "unit", "better", "source", "layer",
                             "moves", "workloads"},
              f"per-layer metric {m['name']}")
        check(os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                          m["name"] + ".py")),
              f"no reader for {m['name']}")
        check(m["moves"] in e2e, f"{m['name']} moves unknown {m['moves']}")
        for n in m.get("workloads", cells):
            check(m["moves"] in reports[n],
                  f"{m['name']} moves {m['moves']}, which {n} does not report")
            layered.add(n)
    for n in cells:
        check(len(reports[n]) >= 2 and n in layered,
              f"cell {n} needs setup_s, another end-to-end metric and a "
              f"per-layer metric")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    check(len(names) == len(set(names)), "two metrics share a name")
    runs = 2 + 14 * 24
    check((b["run_seconds"] + 60) * runs + 24 * 180 + 1200 <= 43200,
          "run_seconds does not fit a full check of 24 cells")
    print(f"1. BENCHMARK.json: {len(cells)} cells, {len(b['end_to_end'])} "
          f"end-to-end and {len(b['per_layer'])} per-layer metrics: ok")


def check_stats():
    from benchmark.harness import stats

    v, n = stats.percentile([1, 2, 3, 4, 5], 95)
    check(n == 5 and abs(v - 4.8) < 1e-12, "percentile interpolation")
    check(stats.percentile([], 95) == (None, 0), "percentile of nothing")
    ok = {"due_s": 1.0, "sent_s": 1.001, "t_tokens": [1.2, 1.3], "end_s": 1.3,
          "tokens": [5, 6], "asked": 2, "state": "FINISHED", "error": None}
    short = dict(ok, tokens=[5], t_tokens=[1.2])
    win = stats.serve_window([ok, short], 10.0, "open")
    check(win["attempted"] == 2 and win["failed"] == 1
          and sorted(win["ttft_s"]) == [0.19999999999999996, 10.0]
          and len(win["late_s"]) == 2, "a failed request = the window's length")
    counts = [0] * 97
    counts[stats.hist_bucket_of(0.05)] = 10
    p, n = stats.hist_percentile(counts, 50)
    check(n == 10 and 0.04 < p < 0.0625, "histogram percentile")
    print("2. statistics: ok")


class _Stub(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.0"

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        for i in range(body["max_new_tokens"]):
            time.sleep(0.002)
            self.wfile.write(b'data: {"token": %d}\n\n' % (i + 7))
            self.wfile.flush()
        self.wfile.write(b'event: done\ndata: {"state": "FINISHED"}\n\n')


def check_schedule_and_loadgen():
    from benchmark.harness import stats, traffic

    with open(os.path.join(BENCH, "traffic", "chat.json")) as f:
        mix = json.load(f)
    a = traffic.schedule(mix, 2 ** 31 + 5, 10, 50304)
    b = traffic.schedule(mix, 2 ** 31 + 5, 10, 50304)
    c = traffic.schedule(mix, 3, 10, 50304)
    check(a == b, "the schedule is not a pure function of the seed")
    key = lambda s, k: sorted(r[k] for r in s["requests"])
    check(a != c and key(a, "prompt_len") == key(c, "prompt_len")
          and key(a, "max_new_tokens") == key(c, "max_new_tokens"),
          "seeds must give the same lengths in another order")
    with open(os.path.join(BENCH, "traffic", "batch-long.json")) as f:
        closed = json.load(f)
    heads = [sorted((r["prompt_len"], r["max_new_tokens"])
                    for r in traffic.schedule(closed, seed, 45, 50304)[
                        "requests"][:closed["shuffle_block"]])
             for seed in (3, 2 ** 31 + 5)]
    check(heads[0] == heads[1], "a closed loop's first block differs by seed")
    check(traffic.prompt_tokens(a, a["requests"][3])
          == traffic.prompt_tokens(b, b["requests"][3]), "prompt tokens")
    small = dict(mix, rate_per_s=40.0, ramp_s=0.5, drain_s=5.0,
                 prompt={"median": 8, "sigma": 0.3, "min": 4, "max": 16},
                 output={"median": 6, "sigma": 0.3, "min": 3, "max": 9})
    sched = traffic.schedule(small, 11, 2.0, 1000)
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "s.json"), "w") as f:
            json.dump(sched, f)
        t0 = time.monotonic() + 2.5
        env = {k: v for k, v in os.environ.items()}
        out = subprocess.run(
            [sys.executable, os.path.join(BENCH, "harness", "loadgen.py"),
             "--url", f"http://127.0.0.1:{srv.server_port}",
             "--schedule", os.path.join(tmp, "s.json"),
             "--out", os.path.join(tmp, "r.json"), "--t0", repr(t0)],
            env=env, timeout=60,
            capture_output=True, text=True)
        check(out.returncode == 0, f"loadgen failed: {out.stderr[-400:]}")
        with open(os.path.join(tmp, "r.json")) as f:
            res = json.load(f)
    srv.shutdown()
    win = stats.serve_window(res["requests"], 2.0, "open")
    check(win["attempted"] > 40 and win["failed"] == 0
          and len(win["late_s"]) == win["attempted"]
          and max(win["late_s"]) < 0.25 and min(win["late_s"]) >= 0.0,
          f"loadgen window {win['attempted']} attempted, {win['failed']} failed")
    probe = subprocess.run(
        [sys.executable, "-c", "import sys; sys.argv=['x','-h']\n"
         "import runpy\ntry:\n runpy.run_path(%r, run_name='__main__')\n"
         "except SystemExit: pass\nprint('jax' in sys.modules)"
         % os.path.join(BENCH, "harness", "loadgen.py")],
        capture_output=True, text=True, timeout=60)
    check(probe.stdout.strip().endswith("False"),
          "the load generator imports jax")
    print(f"3. schedule and load generator: {win['attempted']} requests, "
          f"latest {max(win['late_s']) * 1e3:.2f} ms late: ok")


def check_trace_reduction():
    from benchmark.harness import trace as T

    rec = os.path.join(BENCH, "records")
    with open(os.path.join(rec, "tiny_train.expected.json")) as f:
        want = json.load(f)
    tr = T.load(os.path.join(rec, "tiny_train.xplane.pb"))
    got = T.catalog(tr, 5)
    for k in ("chips", "window_s", "busy_s", "modules", "host_spans"):
        check(json.dumps(got[k], sort_keys=True)
              == json.dumps(want[k], sort_keys=True),
              f"trace reduction: {k} is {got[k]}, recorded {want[k]}")
    check([n for n, _ in got["top_ops"]] == [n for n, _ in want["top_ops"]],
          "trace reduction: top operations")
    print(f"4. trace reduction: {len(got['modules'])} programs, busy "
          f"{got['busy_s']:.4f} s of {got['window_s']:.4f} s: ok")


def check_reference_against_program():
    import numpy as np

    import run_tiny
    from benchmark import run as R

    R.environment()
    import jax

    from benchmark.reference import gpt as ref
    from paddle_tpu.core.tensor import Tensor

    cell = run_tiny.tiny_cell("tiny-chat")
    cfg = dict(cell.config, dtype="float32")
    model = cell.hook("model").build_model(cfg, 5, "float32", train=False)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg["vocab_size"], 20).tolist()
    served = rng.integers(0, cfg["vocab_size"], 12).tolist()
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(model(Tensor(np.asarray(
            [prompt + served], np.int32)))._data[0], np.float32)
    want = np.asarray(ref.teacher_forced_logits(
        5, cfg, "float32", prompt, served, pad_to=64, cap=32))
    got = prog[len(prompt) - 1:len(prompt) + len(served) - 1]
    err = float(np.abs(got - want).max())
    check(err < 2e-4, f"program's forward vs reference: max abs {err}")
    out = run_tiny.run("tiny-chat", 2 ** 31 + 9, 4.0, False)
    check(out["correct"] is True and out["failed"] == 0,
          f"a tiny run through the engine came out {out}")
    print(f"5. reference vs program: forward max abs {err:.2e}; engine run "
          f"correct with {out['attempted']} requests: ok")


if __name__ == "__main__":
    check_benchmark_json()
    check_stats()
    check_schedule_and_loadgen()
    check_trace_reduction()
    check_reference_against_program()
    print("selfcheck: all ok")
    sys.stdout.flush()
    os._exit(0)
