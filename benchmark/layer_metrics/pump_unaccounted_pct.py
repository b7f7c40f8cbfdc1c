"""Scheduler: the closure check of the phases. ``pump.unlocked`` and
``sched.step`` partition the pump thread's time, so the window less both is
what no phase covers; a reading over 2 means a phase is missing."""
from benchmark.harness.phases import phase_us, window_pct


def read(run):
    unlocked = phase_us(run, "pump.unlocked")
    step = phase_us(run, "sched.step")
    if unlocked is None or step is None:
        return None
    return window_pct(run, run["seconds"] * 1e6 - unlocked - step)
