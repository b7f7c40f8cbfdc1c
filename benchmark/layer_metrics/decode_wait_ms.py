"""Engine: the host read that ends a decode step
(``time_us.decode.wait``: the device finishing, the token vector coming
back, the GIL coming back), a mean per decode step."""
from benchmark.harness.phases import per_step_ms, phase_us


def read(run):
    return per_step_ms(run, phase_us(run, "decode.wait"))
