"""Engine: median host time of one prefill (``latency.prefill``)."""
from benchmark.harness.readers import hist_ms


def read(run):
    return hist_ms(run, "latency.prefill", 50.0)
