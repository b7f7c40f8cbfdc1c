"""Model step: median device time of the training step program (the
program that took most device time in the traced stretch)."""
from benchmark.harness import stats, trace as T


def read(run):
    tr = run.get("trace")
    step = T.busiest_module(tr) if tr is not None else None
    if step is None:
        return None
    return stats.median(T.module_durations(tr, step)) * 1e3
