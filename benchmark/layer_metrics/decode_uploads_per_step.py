"""Engine: host-to-device transfers made while the decode step was prepared
(``engine.step_uploads``: the packed slot state after a host write, the
block table after a lane grew, stale mask rows), a mean per decode step
(``engine.steps``). A program that does not count them gives nothing."""


def read(run):
    uploads = run["counters"].get("engine.step_uploads")
    steps = run["counters"].get("engine.steps")
    if uploads is None or not steps:
        return None
    return uploads / steps
