"""Scheduler: the pump thread's time outside its locked turn
(``time_us.pump.unlocked``: the guard poll, the wait to take the API lock
back from submitting handler threads, ``has_work``), a mean per decode step."""
from benchmark.harness.phases import per_step_ms, phase_us


def read(run):
    return per_step_ms(run, phase_us(run, "pump.unlocked"))
