"""Engine: block-table growth and the host's slot state going to the device
before the decode step is dispatched (``time_us.decode.prepare``), a mean
per decode step."""
from benchmark.harness.phases import per_step_ms, phase_us


def read(run):
    return per_step_ms(run, phase_us(run, "decode.prepare"))
