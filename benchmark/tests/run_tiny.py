#!/usr/bin/env python3
"""Drive a run of one of the TINY cells under ``benchmark/tests/data``
through the harness, skipping only its look for a chip: the same
``run_cell`` that ``run.py`` calls, on whatever device jax has (the CPU in a
test run). Used by the tests here, by ``selfcheck.py``, and on the chip to
record the small trace fixture (``--keep-trace``).

    python3 benchmark/tests/run_tiny.py tiny-chat --seconds 6 --trace 0
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(ROOT, "benchmark", "tests", "data")


def tiny_cell(name: str, overrides: dict = None):
    from benchmark.harness import spec

    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return spec.Cell(name, bench=bench, overrides=overrides, data_dir=DATA)


def run(name: str, seed: int, seconds: float, trace: bool,
        keep_trace: str = None, overrides: dict = None) -> dict:
    """The result line of one tiny run, parsed. The device kind is given
    as the v5e's so that the peaks table is found; nothing a CPU run reads
    is a device metric, and its line is never kept."""
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    device = dict(common.device_record(), kind="TPU v5 lite")
    line = R.run_cell(tiny_cell(name, overrides), seed, seconds, trace,
                      device, t_proc=time.monotonic(), keep_trace=keep_trace)
    return json.loads(line)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--keep-trace", default=None)
    a = ap.parse_args()
    out = run(a.cell, a.seed, a.seconds, bool(a.trace), a.keep_trace)
    print(json.dumps(out), flush=True)
    sys.stdout.flush()
    os._exit(0)
