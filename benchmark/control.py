#!/usr/bin/env python3
"""The output check's control, on the chip: what the check reads when the
cell is computed one precision lower than its configuration states. It has
to come out NOT correct (PERF.md, "How correct is decided").

    python3 benchmark/control.py --workload <cell> --seeds 11 12 13 [--seconds 20]

The control is named by the configuration file's ``"control"``:

* ``{"serving": {"engine": {...}}}``: the program itself with its own
  lower-precision path switched on (int8 weights and int8 KV arena), run
  through the same harness with a short window at the cell's own load.
* ``{"reference_mode": "fp8"}``: the plain reference put in the program's
  place with every matrix product's operands rounded to fp8 (the program
  computes them in bf16), compared with the float32 reference. Needs no
  window.

Prints one JSON line per seed with every number compared beside its limit,
then a line ``{"control": ..., "all_failed": true|false}``. Exit code 0
when every seed came out not correct, 1 otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def reference_control(cell, seed: int, mode: str):
    """The training check's numbers for the reference in ``mode`` against
    the float32 reference (same seed, same batches)."""
    import jax

    from benchmark.harness import common, traffic, train

    cfg, tr, lim = cell.config, cell.config["training"], cell.limits["check"]
    feed = traffic.train_batches(cell.mix, seed, int(tr["batch_per_chip"])
                                 * cell.chips, int(tr["seq_len"]),
                                 int(cfg["vocab_size"]))
    batches = [next(feed) for _ in range(int(lim["steps"]))]
    ref_mod = cell.hook("reference")
    low = ref_mod.train_trajectory(seed, cfg, tr["optimizer"], batches, mode)
    jax.clear_caches()
    ref = ref_mod.train_trajectory(seed, cfg, tr["optimizer"], batches)
    checks = common.Checks()
    train.compare(checks, lim, low["losses"], train._flatten(
        low["grad_norms"]), train._flatten(low["change_norms"]), ref)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sound-seeds", type=int, nargs="*", default=[],
                    help="also read the SOUND program on these seeds, in "
                         "the same process (what the limits are set above)")
    a = ap.parse_args(argv)
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common, spec

    base = spec.Cell(a.workload)
    device = common.device_record()
    if device["platform"] != "tpu" or device["count"] < base.chips:
        print(f"control: needs {base.chips} TPU chip(s); jax found {device}",
              file=sys.stderr)
        return 2
    control = base.config["control"]
    for seed in a.sound_seeds:
        checks = base.product().run(base, seed, a.seconds, False,
                                    time.monotonic())["checks"]
        print(json.dumps({"sound_seed": seed, "correct": checks.correct,
                          "checks": checks.rows}), flush=True)
    verdicts = []
    for seed in a.seeds:
        if "reference_mode" in control:
            checks = reference_control(base, seed, control["reference_mode"])
        else:
            cell = spec.Cell(a.workload, overrides=control)
            checks = cell.product().run(cell, seed, a.seconds, False,
                                        time.monotonic())["checks"]
        print(json.dumps({"seed": seed, "correct": checks.correct,
                          "checks": checks.rows}), flush=True)
        verdicts.append(checks.correct)
    print(json.dumps({"control": control, "seeds": a.seeds,
                      "all_failed": not any(verdicts)}), flush=True)
    return 0 if not any(verdicts) else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
