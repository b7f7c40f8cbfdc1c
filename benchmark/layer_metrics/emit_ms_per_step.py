"""Scheduler: handing a step's tokens to their streams and the boundary
checks (``time_us.sched.emit``, one phase around the whole loop), a mean
per decode step."""
from benchmark.harness.phases import per_step_ms, phase_us


def read(run):
    return per_step_ms(run, phase_us(run, "sched.emit"))
