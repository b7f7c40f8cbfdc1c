"""Scheduler: the locked turn without the calls into the engine and without
the emits (``time_us.sched.step`` less ``prefill``, ``decode_step`` and
``sched.emit``): the scheduler's own pass, its gauges and the supervisor's
note, a mean per decode step."""
from benchmark.harness.phases import per_step_ms, phase_us


def read(run):
    return per_step_ms(run, phase_us(run, "sched.step", "prefill",
                                     "decode_step", "sched.emit"))
