"""Engine: median host time of one decode step (``latency.decode_step``,
window delta): dispatch, the device step and the host read that ends it."""
from benchmark.harness.readers import hist_ms


def read(run):
    return hist_ms(run, "latency.decode_step", 50.0)
