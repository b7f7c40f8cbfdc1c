"""What both products share: the device record, the checks' ledger, the
traced stretch, and the result line."""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell uses."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Checks:
    """Every number the output check compares, beside its limit. One
    number over its limit makes the run not correct. Printed in full in
    every run."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value, limit, note: str = "") -> None:
        ok = value is not None and value <= limit
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": bool(ok), "note": note})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def emit(self) -> None:
        for r in self.rows:
            print(json.dumps(r), flush=True)


class TracedStretch:
    """Profile a stretch of the window into a directory under ``TMPDIR``
    and reduce it; the raw trace is removed afterwards. The python tracer
    is off (it would slow every call of a busy server)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.trace = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def hold(self, seconds: float) -> None:
        """Trace for ``seconds`` while other threads do the work."""
        import jax

        self.start()
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(seconds)
        self.stop()

    def reduce(self, keep_to: str = None):
        from benchmark.harness import trace as T

        try:
            path = T.find_xplane(self.dir)
            if keep_to:
                os.makedirs(os.path.dirname(keep_to), exist_ok=True)
                shutil.copyfile(path, keep_to)
            self.trace = T.load(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace


def result_line(checks: Checks, attempted: int, failed: int, metrics: dict,
                units: dict, device: dict, breakdown: dict = None) -> str:
    out = {"correct": checks.correct, "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in metrics.items() if v is not None},
           "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    return json.dumps(out)


def run_readers(cell, run: dict) -> dict:
    """Every per-layer metric that lists this cell, read by its own file.
    A reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell.per_layer():
        value = cell.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = float(value)
    return out


def note(**kw) -> None:
    """A line of the run's own record (anything but the last line)."""
    print(json.dumps(kw, default=str), flush=True)
