"""Scheduler: 95th percentile of the window's ``latency.queue_wait``."""
from benchmark.harness.readers import hist_ms


def read(run):
    return hist_ms(run, "latency.queue_wait", 95.0)
