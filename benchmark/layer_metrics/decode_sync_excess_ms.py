"""Engine: what the host waits for a decode step beyond what the device
computes: dispatch plus wait (the program's phases, a mean per step) less
the median device time of the ``jit_step`` program in the traced stretch."""
from benchmark.harness.phases import per_step_ms, phase_us
from benchmark.harness.readers import module_ms


def read(run):
    dispatch = phase_us(run, "decode.dispatch")
    wait = phase_us(run, "decode.wait")
    device = module_ms(run, "jit_step")
    if dispatch is None or wait is None or device is None:
        return None
    host = per_step_ms(run, dispatch + wait)
    return None if host is None else host - device
