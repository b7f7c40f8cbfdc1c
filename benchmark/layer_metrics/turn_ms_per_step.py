"""Scheduler: the pump's locked turn without its prefills, a mean in ms
over the window's decode steps: (``time_us.sched.step`` -
``time_us.prefill``) / ``engine.steps``. Since one decode step stays in
flight from turn to turn, ``decode_step_host_ms.batch`` is only the decode
call's part of a turn; this is the turn the device paces (admission pass,
decode call, emit), PERF.md Open question 20(a). It reads counters every
program with phases has."""
from benchmark.harness.phases import per_step_ms, phase_us


def read(run):
    return per_step_ms(run, phase_us(run, "sched.step", "prefill"))
