#!/usr/bin/env python
"""Compile-cache stats CLI: print hit/miss/compile-time counters for the
persistent XLA cache and the in-process caches, or inspect/clear the cache
directory itself.

Usage:
    python tools/cache_stats.py                 # inspect the on-disk cache
    python tools/cache_stats.py --run CMD ...   # run CMD..., then report the
                                                # run's counters (in-process)
    python tools/cache_stats.py --clear         # delete cache entries
    python tools/cache_stats.py --json          # machine-readable output

Without --run this only inspects the directory (entry count / bytes /
newest entry age) — it never initializes a jax backend, so it never
claims a chip another process holds. With --run, CMD executes in-process via
runpy with the framework imported first, and the delta of
``core.compile_cache.stats()`` across the run is reported — warm runs show
``persistent.hits`` > 0 and near-zero ``compile.backend_secs``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _dir_report(d: str) -> dict:
    out = {"dir": d, "exists": os.path.isdir(d), "entries": 0, "bytes": 0,
           "newest_age_secs": None}
    if not out["exists"]:
        return out
    newest = 0.0
    for name in os.listdir(d):
        if not name.endswith("-cache"):
            continue
        p = os.path.join(d, name)
        try:
            st = os.stat(p)
        except OSError:
            continue
        out["entries"] += 1
        out["bytes"] += st.st_size
        newest = max(newest, st.st_mtime)
    if newest:
        out["newest_age_secs"] = round(time.time() - newest, 1)
    return out


def _resolve_dir(args) -> str:
    if args.dir:
        return args.dir
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.core import compile_cache  # no backend is initialized

    return compile_cache.resolve_cache_dir()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", help="cache directory (default: the framework's "
                                  "resolution order)")
    ap.add_argument("--json", action="store_true", help="JSON output")
    ap.add_argument("--clear", action="store_true",
                    help="delete cache entries in the directory")
    ap.add_argument("--run", nargs=argparse.REMAINDER,
                    help="script [args...] to execute in-process; counters "
                         "are reported for that run")
    args = ap.parse_args(argv)
    d = _resolve_dir(args)

    if args.clear:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from paddle_tpu.core import compile_cache

        n = compile_cache.clear(d)
        print(f"removed {n} cache file(s) from {d}")
        return 0

    if args.run:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import runpy

        from paddle_tpu.core import compile_cache

        before = compile_cache.stats()
        t0 = time.perf_counter()
        sys.argv = list(args.run)
        runpy.run_path(args.run[0], run_name="__main__")
        wall = time.perf_counter() - t0
        delta = {k: v for k, v in compile_cache.stats_delta(
                     before, compile_cache.stats(), drop_zero=True).items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
        rec = {"wall_secs": round(wall, 3), "stats": delta,
               "cache_dir": compile_cache.cache_dir(), **_dir_report(d)}
        print(json.dumps(rec) if args.json else
              "\n".join([f"wall_secs: {rec['wall_secs']}"]
                        + [f"{k}: {v}" for k, v in sorted(delta.items())]))
        return 0

    rep = _dir_report(d)
    if args.json:
        print(json.dumps(rep))
    else:
        for k, v in rep.items():
            print(f"{k}: {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
