"""Scheduler: the share of the pump's lane-time in which a request that
could decode did not, because a prefill held the pump: 100 x
``prefill.lane_us_blocked`` / (``num_slots`` x the pump's wall). The
counter adds, for every admission or chunk, its elapsed microseconds
times the lanes active when it began. The pump's wall is
``time_us.pump.unlocked`` + ``time_us.sched.step`` of the same delta, not
the window's seconds, so a closing snapshot that comes late (a traced
run's, PERF.md Open question 18(a)) keeps numerator and denominator on one
stretch; the drain inside a late snapshot still dilutes the share. A
program that does not count the blocked lane-time gives nothing."""


def read(run):
    c = run["counters"]
    blocked = c.get("prefill.lane_us_blocked")
    wall = (c.get("time_us.pump.unlocked") or 0) + \
        (c.get("time_us.sched.step") or 0)
    lanes = run["program"].get("num_slots", 0)
    if blocked is None or not wall or not lanes:
        return None
    return 100.0 * blocked / (lanes * wall)
