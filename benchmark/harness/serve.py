"""The serving product: one cell's run, from the seeded model to the
result. The process that calls :func:`run` holds the chip; the load comes
from a child process that never imports jax (``loadgen.py``).

    build the model from the seed -> ``gateway.serve`` on loopback -> warm
    exactly the prefill buckets the mix can reach and the decode step ->
    [window: the load generator offers the mix; counters, histograms and,
    in a traced run, a stretch of the device trace are taken] -> close the
    engine -> reference pass over a seeded sample of finished requests.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark.harness import common, stats, traffic
from benchmark.harness.spec import ROOT

#: the parent fixes the window's start this long ahead (plus the ramp), for
#: the child to start, read its schedule and make its prompts
CHILD_LEAD_S = 4.0


def _snapshot():
    from paddle_tpu.core import compile_cache
    from paddle_tpu.serving import metrics, telemetry

    return {"counters": dict(metrics.stats()),
            "compile": dict(compile_cache.stats()),
            "hists": {k: list(h.counts)
                      for k, h in telemetry.histograms().items()}}


def _delta(before: dict, after: dict) -> dict:
    num = lambda v: isinstance(v, (int, float)) and not isinstance(v, bool)
    out = {"counters": {}, "hists": {}}
    for section in ("counters", "compile"):
        for k, v in after[section].items():
            if num(v):
                out["counters"][k] = v - (before[section].get(k) or 0)
    for k, counts in after["hists"].items():
        out["hists"][k] = stats.hist_delta(counts, before["hists"].get(k))
    return out


def warm_buckets(engine, mix: dict, max_len: int) -> list:
    """The prefill buckets this mix can reach: every prompt length it can
    draw, and, where 32 full lanes can outgrow the arena (so a request may
    be preempted and prefilled again with what it had generated), every
    context length up to prompt + output."""
    from paddle_tpu.core import compile_cache

    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    longest = hi + int(mix["output"]["max"])
    arena_tokens = engine.arena.stats()["blocks_total"] * engine.block_size
    if engine.num_slots * longest > arena_tokens:
        hi = min(longest, max_len)
    return sorted({compile_cache.prefill_bucket(
        n, max_len, engine.prefill_bucket_min) for n in range(lo, hi + 1)})


def _warm(url: str, buckets, max_len: int, vocab: int, seed: int) -> None:
    from benchmark.harness.loadgen import Client

    client = Client(url, time.monotonic())
    rng = np.random.default_rng([int(seed), 2])
    for b in buckets:
        n = min(b, max_len - 2)
        rec = client.stream(
            {"id": -b, "due_s": None, "max_new_tokens": 2},
            json.dumps({"prompt": rng.integers(0, vocab, n).tolist(),
                        "max_new_tokens": 2}).encode())
        if rec["state"] != "FINISHED" or len(rec["tokens"]) != 2:
            raise RuntimeError(f"warm-up of prefill bucket {b} failed: {rec}")


class _Poller(threading.Thread):
    """Once a second, the arena's and the lanes' gauges."""

    def __init__(self):
        super().__init__(daemon=True)
        self.rows, self.done = [], threading.Event()

    def run(self):
        from paddle_tpu.serving import metrics

        while not self.done.wait(1.0):
            g = metrics.gauges()
            self.rows.append({k: g.get(k) for k in (
                "arena.blocks_free", "arena.blocks_total", "slots.active",
                "queue.depth")})


def program_facts(model, engine) -> dict:
    """Sizes read from the program's own objects (not assumed): what the
    roofline functions need."""
    a = engine.arena.stats()
    tokens = int(a["blocks_total"]) * int(engine.block_size)
    return {"weight_bytes": int(sum(p._data.nbytes
                                    for p in model.parameters())),
            "arena_bytes": int(engine.arena.bytes_total()),
            "arena_tokens": tokens,
            "kv_bytes_per_token": engine.arena.bytes_total() / tokens,
            "num_slots": int(engine.num_slots),
            "block_size": int(engine.block_size)}


def check_outputs(cell, seed: int, finished, sched, checks) -> None:
    """The reference's verdict on what the window served: a seeded sample
    of finished requests, the longest among them, each re-run once,
    teacher-forced, by the plain reference."""
    cfg, lim = cell.config, cell.limits["check"]
    ref = cell.hook("reference")
    k = int(cell.mix.get("check_sample", 4))
    if not finished:
        checks.add("requests_finished_in_window", 0, -1,
                   "nothing finished, so nothing to compare")
        return
    reqs = {q["id"]: q for q in sched["requests"]}
    by_len = sorted(finished, key=lambda r: -(
        len(r["tokens"]) + reqs[r["id"]]["prompt_len"]))
    rest = by_len[1:]
    pick = np.random.default_rng([int(seed), 3]).permutation(len(rest))
    sample = [by_len[0]] + [rest[i] for i in pick[:max(0, k - 1)]]
    gaps = []
    t0 = time.perf_counter()
    for r in sample:
        prompt = traffic.prompt_tokens(sched, reqs[r["id"]])
        gaps += ref.served_token_gaps(seed, cfg, cfg["dtype"], prompt,
                                      r["tokens"], **lim.get("shape", {}))
    common.note(reference_s=time.perf_counter() - t0,
                sampled_requests=len(sample), sampled_tokens=len(gaps),
                tokens_not_first=int(sum(g > 0 for g in gaps)))
    checks.add("gap_mean", float(np.mean(gaps)), lim["gap_mean"],
               "mean over sampled served tokens of (reference's best logit "
               "- reference's logit of the served token)")
    checks.add("gap_max", float(np.max(gaps)), lim["gap_max"],
               "the widest such gap")


class Server:
    """The seeded model behind ``gateway.serve``, its prefill buckets and
    decode step warmed: the system under test, ready for load."""

    def __init__(self, cell, seed: int):
        from paddle_tpu.serving import ServingConfig
        from paddle_tpu.serving.gateway.gateway import serve

        cfg = cell.config
        self.cell, self.seed = cell, seed
        t = [time.monotonic()]
        self.model = cell.hook("model").build_model(cfg, seed, cfg["dtype"],
                                                    train=False)
        t.append(time.monotonic())
        self.gw = serve(self.model,
                        replicas=int(cfg["serving"].get("replicas", 1)),
                        port=0, guard=False,
                        config=ServingConfig(**cfg["serving"]["engine"]))
        self.url = f"http://127.0.0.1:{self.gw.port}"
        t.append(time.monotonic())
        self.tmp = tempfile.mkdtemp(prefix="bench_serve_")
        try:
            engine = self.gw.pool.replicas()[0].api.engine
            max_len = int(cfg["max_position_embeddings"])
            self.buckets = warm_buckets(engine, cell.mix, max_len)
            _warm(self.url, self.buckets, max_len, int(cfg["vocab_size"]),
                  seed)
            self.facts = program_facts(self.model, engine)
            t.append(time.monotonic())
            common.note(setup_phases_s={
                "model_from_seed": t[1] - t[0], "gateway_and_engine":
                t[2] - t[1], "warm_buckets_and_decode": t[3] - t[2]})
        except BaseException:
            self.close()
            raise

    def offer(self, sched: dict, trace: bool = False, poll: bool = False):
        """One window of load from a child process. Returns the child's
        records with the snapshots taken when the window opened and
        closed, and the window's start on ``time.monotonic()``."""
        seconds, tag = sched["seconds"], f"{time.monotonic():.3f}"
        spath = os.path.join(self.tmp, f"sched_{tag}.json")
        rpath = os.path.join(self.tmp, f"result_{tag}.json")
        with open(spath, "w") as f:
            json.dump(sched, f)
        t0 = time.monotonic() + sched["ramp_s"] + CHILD_LEAD_S
        child = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "harness",
                                          "loadgen.py"),
             "--url", self.url, "--schedule", spath, "--out", rpath,
             "--t0", repr(t0)],
            env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"})
        poller = _Poller() if poll else None
        stretch = None
        try:
            time.sleep(max(0.0, t0 - time.monotonic()))
            # -------------------------------------------- the window opens
            before = _snapshot()
            if poller:
                poller.start()
            if trace:
                time.sleep(max(0.0, t0 + 0.4 * seconds - time.monotonic()))
                stretch = common.TracedStretch()
                stretch.hold(min(5.0, seconds / 4.0))
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            after = _snapshot()
            peak = common.memory_peak_bytes(self.cell.chips)
            # ------------------------------------------- the window closed
            if poller:
                poller.done.set()
            try:
                rc = child.wait(timeout=sched["drain_s"] + 60.0)
            except subprocess.TimeoutExpired:
                raise RuntimeError("the load generator did not end in time")
            if rc != 0:
                raise RuntimeError(f"the load generator exited {rc}")
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        with open(rpath) as f:
            result = json.load(f)
        keep = os.environ.get("BENCH_KEEP_RECORDS")
        if keep:  # the load generator's raw records, for a look by hand
            os.makedirs(keep, exist_ok=True)
            shutil.copyfile(rpath, os.path.join(
                keep, f"{self.cell.name}_{self.seed}_{tag}.json"))
        if result["ready_s"] > -sched["ramp_s"] or result["threads_left"]:
            raise RuntimeError(
                f"the load generator was ready {result['ready_s']:.2f} s "
                f"from the window's start (ramp {sched['ramp_s']} s) and "
                f"left {result['threads_left']} threads")
        return {"t0": t0, "records": result["requests"],
                "delta": _delta(before, after), "peak": peak,
                "polls": poller.rows if poller else [], "stretch": stretch}

    def close(self) -> None:
        """Close the gateway and drop the model, the engine and its arena,
        so that the reference finds the chip's memory free."""
        import jax

        self.gw.close()
        shutil.rmtree(self.tmp, ignore_errors=True)
        self.gw = self.model = None
        gc.collect()
        jax.clear_caches()
        gc.collect()


def run(cell, seed: int, seconds: float, trace: bool, t_proc: float,
        keep_trace: str = None) -> dict:
    """One run of a serving cell. Returns what the result line needs."""
    cfg = cell.config
    server = Server(cell, seed)
    try:
        sched = traffic.schedule(cell.mix, seed, seconds,
                                 int(cfg["vocab_size"]))
        out = server.offer(sched, trace=trace, poll=trace)
        facts, buckets = server.facts, server.buckets
    finally:
        server.close()
    setup_s = out["t0"] - t_proc
    delta = out["delta"]
    checks = common.Checks()
    win = stats.serve_window(out["records"], seconds, sched["loop"])
    for key in ("serving.decode_compiles", "serving.prefill_compiles",
                "compile.backend"):
        checks.add(f"window_delta:{key}", delta["counters"].get(key, 0), 0,
                   "nothing compiles inside the window")
    checks.add("failed_requests", win["failed"],
               cell.limits["check"].get("failed_requests", 0),
               "requests that did not end FINISHED with every token")
    check_outputs(cell, seed, win["finished"], sched, checks)

    def pct(key, p):
        v, n = stats.percentile(win[key], p)
        return (None if v is None else v * 1e3), n

    ttft, n_ttft = pct("ttft_s", 95.0)
    itl, n_itl = pct("gaps_s", 95.0)
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": win["tokens"] / seconds,
           "ttft_p95_ms": ttft, "itl_p95_ms": itl}
    # what a look by hand wants of every run: the longest stall of the
    # whole decode loop, the front door's accept path, and every counter
    # of the program that moved (rebuilds, preemptions, sheds among them)
    common.note(window={"attempted": win["attempted"],
                        "failed": win["failed"], "tokens": win["tokens"],
                        "finished": len(win["finished"]),
                        "ttft_samples": n_ttft, "itl_samples": n_itl,
                        "ttft_p50_ms": pct("ttft_s", 50.0)[0],
                        "itl_p50_ms": pct("gaps_s", 50.0)[0],
                        "accept_p95_ms": pct("accept_s", 95.0)[0],
                        "connect_p95_ms": pct("connect_s", 95.0)[0],
                        "stall_max_s": win["stall_max_s"],
                        "prefill_buckets_warmed": buckets},
                e2e=e2e,
                counters_moved={k: v for k, v in sorted(
                    delta["counters"].items()) if v})
    tr = out["stretch"].reduce(keep_trace) if out["stretch"] else None
    return {"cell": cell, "seconds": seconds, "checks": checks,
            "attempted": win["attempted"], "failed": win["failed"],
            "e2e": e2e, "client": win, "counters": delta["counters"],
            "hists": delta["hists"], "polls": out["polls"],
            "trace": tr, "program": facts,
            "memory_peak_bytes": out["peak"]}
