"""Engine: the call of the compiled decode step returning
(``time_us.decode.dispatch``), a mean per decode step."""
from benchmark.harness.phases import per_step_ms, phase_us


def read(run):
    return per_step_ms(run, phase_us(run, "decode.dispatch"))
