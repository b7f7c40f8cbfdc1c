"""Device: 1 - (union of the device operations' intervals) over the traced
stretch of the window, mean over the chips used."""
from benchmark.harness.readers import idle_pct


def read(run):
    return idle_pct(run)
