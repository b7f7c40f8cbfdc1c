"""Engine: the share of the window's wall time spent in prefill calls
(``time_us.prefill``), during which no lane decodes."""
from benchmark.harness.phases import phase_us, window_pct


def read(run):
    return window_pct(run, phase_us(run, "prefill"))
