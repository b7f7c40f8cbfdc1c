"""Engine: the share of the pump's wall in which the engine knew that
nothing it had dispatched was unfinished, by what ended each such
stretch: 100 x (``time_us.device.empty.restart`` + ``.admit`` +
``.sync``) / (``time_us.pump.unlocked`` + ``time_us.sched.step``). A
stretch begins when a blocking read of the newest program returns and
ends at the next compiled call; the host learns of a program's end a
millisecond or two after the device, so this is a lower bound of the
trace's ``device_idle_pct.batch`` by that much a gap. ``.idle`` (the
scheduler had no work) is kept out. Over the pump's own wall, not the
window's seconds (as ``decode_lanes_blocked_pct``); a drain inside a late
closing snapshot still dilutes it. A program without the phase gives
nothing."""

CAUSES = ("restart", "admit", "sync")


def read(run):
    c = run["counters"]
    parts = [c.get("time_us.device.empty." + cause) for cause in CAUSES]
    wall = (c.get("time_us.pump.unlocked") or 0) + \
        (c.get("time_us.sched.step") or 0)
    if all(p is None for p in parts) or not wall:
        return None
    return 100.0 * sum(p or 0 for p in parts) / wall
