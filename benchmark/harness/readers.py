"""What the per-layer readers (``benchmark/layer_metrics/<metric>.py``)
share. A reader takes the run's record and returns a number, or None when
there is nothing to read."""
from __future__ import annotations

from benchmark.harness import stats, trace as T


def hist_ms(run: dict, key: str, p: float):
    """p-th percentile, in ms, of the window's delta of one of the
    program's ``latency.*`` histograms."""
    counts = run["hists"].get(key)
    if not counts:
        return None
    v, n = stats.hist_percentile(counts, p)
    return None if v is None else v * 1e3


def client_ms(run: dict, key: str, p: float):
    v, n = stats.percentile(run.get("client", {}).get(key, []), p)
    return None if v is None else v * 1e3


def module_ms(run: dict, module: str):
    """Median device time, in ms, of one program's executions."""
    if run.get("trace") is None:
        return None
    ds = T.module_durations(run["trace"], module)
    return stats.median(ds) * 1e3 if ds else None


def idle_pct(run: dict):
    tr = run.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - T.busy_seconds(tr) / tr.window_s)


def peak_hbm_gb(run: dict):
    return run["memory_peak_bytes"] / 1e9 if run["memory_peak_bytes"] else None
