"""Scheduler: 95th percentile of a handler thread's wait for the API lock in
``ServingAPI.submit`` (``latency.submit.lock_wait``, window delta)."""
from benchmark.harness.readers import hist_ms


def read(run):
    return hist_ms(run, "latency.submit.lock_wait", 95.0)
