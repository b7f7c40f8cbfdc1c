#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip. It fails (non-zero, no result) without a
TPU, with fewer chips than the cell asks for, or on a device kind that
``benchmark/peaks.json`` does not list. Its last line of standard output is
the result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, traced, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics (tracing off); with ``--trace 1`` its per-layer metrics.
Everything a run needs is found by name from ``BENCHMARK.json`` (see
``harness/spec.py`` and ``README.md``).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_PROC = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def environment() -> None:
    """Before the program is imported: its one compile cache is where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``
    (``core.compile_cache``), and here it keeps EVERY program, however
    quickly it compiled, so that a cell's second run compiles nothing."""
    os.environ.setdefault("FLAGS_xla_compile_cache_min_compile_secs", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             t_proc: float = None, keep_trace: str = None) -> str:
    """Everything of a run but the look for a chip: the product's run, the
    per-layer readers, the result line. Returns the line."""
    from benchmark.harness import common, spec, trace as T

    run = cell.product().run(cell, seed, seconds, trace,
                      T_PROC if t_proc is None else t_proc, keep_trace)
    run["peaks"] = spec.peaks(device["kind"])
    run["checks"].emit()
    device = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    units = {m["name"]: m["unit"]
             for m in cell.bench["end_to_end"] + cell.bench["per_layer"]}
    breakdown = None
    if trace:
        tr = run["trace"]
        metrics = common.run_readers(cell, run)
        device.update(busy_s=T.busy_seconds(tr), window_s=tr.window_s)
        breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr)}
        common.note(trace_catalog=T.catalog(tr))
    else:
        metrics = {m["name"]: run["e2e"].get(m["name"])
                   for m in cell.end_to_end()}
        missing = [k for k, v in metrics.items() if v is None]
        if missing:
            raise SystemExit(f"benchmark: the run gave no {missing}")
    return common.result_line(run["checks"], run["attempted"], run["failed"],
                              metrics, units, device, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw .xplane.pb here (a path)")
    a = ap.parse_args(argv)
    environment()
    from benchmark.harness import common, spec

    cell = spec.Cell(a.workload)
    device = common.device_record()
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"benchmark: {a.workload} needs {cell.chips} TPU chip(s); "
              f"jax found {device}", file=sys.stderr)
        return 2
    spec.peaks(device["kind"])  # an unlisted kind is an error, not a default
    line = run_cell(cell, a.seed, a.seconds, bool(a.trace), device,
                    keep_trace=a.keep_trace)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    # leave through os._exit: a serving thread that a failed run left
    # behind must not keep the process (and the chip) after the verdict
    import traceback

    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int) and e.code is not None:
            print(e.code, file=sys.stderr)
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
