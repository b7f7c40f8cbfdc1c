"""Engine: the share of the window's decode steps that were dispatched
while the step before them was still unread (``engine.steps_run_ahead``
over ``engine.steps``): the host's part of those turns ran under the
device's step instead of between two of them. Near 100 in a steady pump;
every admission and every retirement costs one step that starts from the
host's mirrors; 0 while a constrained request or the speculative path
keeps the turns synchronous. A program that does not count them gives
nothing."""


def read(run):
    ahead = run["counters"].get("engine.steps_run_ahead")
    steps = run["counters"].get("engine.steps")
    if ahead is None or not steps:
        return None
    return 100.0 * ahead / steps
