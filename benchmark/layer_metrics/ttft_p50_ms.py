"""Client's view, recorded without a bound: median first-token time over the
requests due in the window, counted from when each was due."""
from benchmark.harness.readers import client_ms


def read(run):
    return client_ms(run, "ttft_s", 50.0)
