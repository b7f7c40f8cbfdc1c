#!/usr/bin/env python3
"""The one ladder sweep that fixes an open-loop cell's rate. Run once, by
hand, on the chip; no cell searches for a rate inside a run.

    python3 benchmark/sweep_rate.py --workload serve-batch-long --mix chat \
        --seed 7 --rates 1 2 3 4.8 6 --rung-seconds 40 --out chiprun_out/sweep.json

``--workload`` names a cell of ``BENCHMARK.json`` (its configuration is
served); ``--mix`` offers another mix of ``benchmark/traffic/`` than the
cell's own, so that a mix can be swept before a cell uses it.

One set-up (the cell's model, gateway and warmed shapes), then one rung per
rate: the cell's mix offered at that rate for ``--rung-seconds``, in-flight
requests drained before the next rung. A rung holds when requests completed
per second stay within 3% of those offered and the queue at its end is no
deeper than at its middle (means of the once-a-second ``queue.depth`` polls
over the last fifth and the middle fifth of the rung, one request of
tolerance). The knee is the highest rate that holds with every lower rung
holding too; the cell's traffic file gets 0.8 x the knee, to two figures.
The output is kept under ``benchmark/records/`` and quoted in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def rung_summary(rate: float, seconds: float, records, polls) -> dict:
    from benchmark.harness import stats

    win = stats.serve_window(records, seconds, "open")
    # throughput out: every stream that ended FINISHED inside the rung,
    # whenever it was due (the ramp's requests among them)
    done = sum(1 for r in records if r.get("state") == "FINISHED"
               and 0.0 <= r.get("end_s", -1.0) < seconds)
    depth = [p["queue.depth"] or 0 for p in polls]
    fifth = max(1, len(depth) // 5)
    mid = depth[len(depth) // 2 - fifth // 2:][:fifth] if depth else [0]
    end = depth[-fifth:] if depth else [0]
    mean = lambda xs: sum(xs) / max(1, len(xs))
    offered = win["attempted"] / seconds
    completed = done / seconds
    ttft, _ = stats.percentile(win["ttft_s"], 95.0)
    itl, _ = stats.percentile(win["gaps_s"], 95.0)
    conn, _ = stats.percentile(win["connect_s"], 95.0)
    ttft50, _ = stats.percentile(win["ttft_s"], 50.0)
    return {"connect_p95_ms": None if conn is None else conn * 1e3,
            "ttft_p50_ms": None if ttft50 is None else ttft50 * 1e3,
            "rate_per_s": rate, "offered_per_s": offered,
            "completed_per_s": completed, "failed": win["failed"],
            "queue_mid": mean(mid), "queue_end": mean(end),
            "lanes_active_mean": mean([p["slots.active"] or 0
                                       for p in polls]),
            "ttft_p95_ms": None if ttft is None else ttft * 1e3,
            "itl_p95_ms": None if itl is None else itl * 1e3,
            "tokens_per_s": win["tokens"] / seconds,
            "holds": bool(completed >= 0.97 * offered
                          and mean(end) <= mean(mid) + 1.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mix", default=None)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rung-seconds", type=float, default=60.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common, serve, spec, traffic

    cell = spec.Cell(a.workload)
    if a.mix:
        with open(os.path.join(spec.BENCH, "traffic", a.mix + ".json")) as f:
            cell.mix = json.load(f)
    device = common.device_record()
    if device["platform"] != "tpu":
        print(f"sweep_rate: needs a TPU; jax found {device}", file=sys.stderr)
        return 2
    server = serve.Server(cell, a.seed)
    rungs = []
    try:
        for i, rate in enumerate(sorted(a.rates)):
            mix = dict(cell.mix, rate_per_s=rate, drain_s=20.0)
            sched = traffic.schedule(mix, a.seed + i, a.rung_seconds,
                                     int(cell.config["vocab_size"]))
            out = server.offer(sched, poll=True)
            rungs.append(rung_summary(rate, a.rung_seconds, out["records"],
                                      out["polls"]))
            common.note(rung=rungs[-1])
    finally:
        server.close()
    knee = None
    for r in rungs:
        if not r["holds"]:
            break
        knee = r["rate_per_s"]
    result = {"workload": a.workload, "mix": a.mix or cell.row["traffic"],
              "seed": a.seed, "device": device,
              "rung_seconds": a.rung_seconds, "rungs": rungs, "knee": knee,
              "rate_at_four_fifths": None if knee is None
              else float(f"{0.8 * knee:.2g}")}
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
