#!/usr/bin/env python
"""Resilience stats CLI: dump skip/rollback/retry/preemption counters and
inspect a checkpoint directory's integrity state (mirrors
tools/cache_stats.py for core.resilience).

Usage:
    python tools/resilience_stats.py --ckpt DIR     # steps / manifests /
                                                    # resume marker of a
                                                    # TrainCheckpointer dir
    python tools/resilience_stats.py --run CMD ...  # run CMD..., report the
                                                    # run's counters
    python tools/resilience_stats.py --json         # machine-readable output

Without --run this only inspects the filesystem — it never initializes a
jax backend, so it never claims a chip another process holds. With --run,
CMD executes in-process via runpy with the framework imported first, and the
delta of ``core.resilience.stats()`` across the run is reported — a healthy
chaos run shows ``sentinel.skipped`` / ``retry.*`` / ``fault.*`` counters
matching the faults it injected. Serving-side resilience lands on the same
surface: ``serving.preemptions`` (priority-admission victim evictions),
``serving.rebuilds`` / ``serving.replays`` (supervisor rebuild-and-replay
recovery), ``serving.drains`` / ``serving.drain_stragglers`` (graceful
drain) — see docs/robustness.md, "Serving under failure".
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _ckpt_report(d: str) -> dict:
    """Filesystem-only view of a TrainCheckpointer directory: step dirs,
    which steps carry a manifest, and the resume marker (no orbax import —
    validity here means "manifest present", not a data read)."""
    out = {"dir": d, "exists": os.path.isdir(d), "steps": [],
           "manifest_steps": [], "resume_marker": None}
    if not out["exists"]:
        return out
    for name in sorted(os.listdir(d)):
        if name.isdigit() and os.path.isdir(os.path.join(d, name)):
            out["steps"].append(int(name))
    mdir = os.path.join(d, "manifests")
    if os.path.isdir(mdir):
        for name in sorted(os.listdir(mdir)):
            stem = name.rsplit(".", 1)[0]
            if stem.isdigit():
                out["manifest_steps"].append(int(stem))
    marker = os.path.join(d, "RESUME.json")
    if os.path.exists(marker):
        try:
            with open(marker) as f:
                out["resume_marker"] = json.load(f)
        except (OSError, ValueError):
            out["resume_marker"] = "unreadable"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ckpt", help="TrainCheckpointer directory to inspect")
    ap.add_argument("--json", action="store_true", help="JSON output")
    ap.add_argument("--run", nargs=argparse.REMAINDER,
                    help="script [args...] to execute in-process; resilience "
                         "counters are reported for that run")
    args = ap.parse_args(argv)

    if args.run:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import runpy

        from paddle_tpu.core import resilience

        before = resilience.stats()
        t0 = time.perf_counter()
        sys.argv = list(args.run)
        try:
            runpy.run_path(args.run[0], run_name="__main__")
        finally:
            # if the script served traffic, exit through the graceful path:
            # drain any ServingAPI it left open so serving.drain_* counters
            # reflect a real drain and no engine exits holding live slots
            if "paddle_tpu.serving.api" in sys.modules:
                sys.modules["paddle_tpu.serving.api"].drain_all()
        wall = time.perf_counter() - t0
        delta = {k: v for k, v in resilience.stats_delta(
                     before, resilience.stats(), drop_zero=True).items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
        rec = {"wall_secs": round(wall, 3), "stats": delta}
        if args.ckpt:
            rec.update(_ckpt_report(args.ckpt))
        print(json.dumps(rec) if args.json else
              "\n".join([f"wall_secs: {rec['wall_secs']}"]
                        + [f"{k}: {v}" for k, v in sorted(delta.items())]))
        return 0

    if args.ckpt:
        rep = _ckpt_report(args.ckpt)
        if args.json:
            print(json.dumps(rep))
        else:
            for k, v in rep.items():
                print(f"{k}: {v}")
        return 0

    ap.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
