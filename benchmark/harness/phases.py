"""What the readers of the program's phase counters share.

The serving loop times its layer boundaries itself
(``paddle_tpu.serving.telemetry.phase``): every phase adds its elapsed
whole microseconds to the counter ``time_us.<phase>``, and
``harness/serve.py`` hands the readers the window's delta of every counter.
A program without phases (a parent commit from before them) has no such
counter: every function here then returns None, and the reader's metric is
left out of the line.
"""
from __future__ import annotations


def phase_us(run: dict, phase: str, *children: str):
    """Microseconds the window spent in ``phase``, less those it spent in
    the ``children`` phases inside it (its self time). None when the
    program has no such phase; a child that never ran counts 0."""
    total = run["counters"].get("time_us." + phase)
    if total is None:
        return None
    return total - sum(run["counters"].get("time_us." + c) or 0
                       for c in children)


def per_step_ms(run: dict, us):
    """``us`` microseconds as a mean in ms over the window's decode steps
    (the delta of ``engine.steps``)."""
    steps = run["counters"].get("engine.steps")
    if us is None or not steps:
        return None
    return us / steps / 1e3


def window_pct(run: dict, us):
    """``us`` microseconds as a share of the window, in percent."""
    if us is None or not run["seconds"]:
        return None
    return 100.0 * us / (run["seconds"] * 1e6)
