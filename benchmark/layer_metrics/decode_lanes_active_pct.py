"""Scheduler: how full the decode step's lanes were, exactly and per
step: 100 x ``engine.lane_steps`` (lanes a read step ran) /
(``engine.steps`` x ``num_slots``). No poll: ``lanes_busy_pct`` reads a
gauge once a second and runs into the drain when a traced run's closing
snapshot comes late; this is a ratio of two counters of one delta. A
program that does not count lane-steps gives nothing."""


def read(run):
    ran = run["counters"].get("engine.lane_steps")
    steps = run["counters"].get("engine.steps")
    lanes = run["program"].get("num_slots", 0)
    if ran is None or not steps or not lanes:
        return None
    return 100.0 * ran / (steps * lanes)
