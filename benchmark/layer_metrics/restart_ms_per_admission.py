"""Engine: what the device stood empty for before decode steps that the
pump had to prepare from the host's mirrors (an admission's or a
retirement's write made the carried slot state stale), in ms an admission:
``time_us.device.empty.restart`` / ``engine.admits``. ROADMAP A1(5)
(admissions and retirements carried into the device's slot state) is
judged by it; ``engine.restarts.<why>`` among ``counters_moved`` says who
made the state stale. A program without the phase gives nothing."""


def read(run):
    empty = run["counters"].get("time_us.device.empty.restart")
    admits = run["counters"].get("engine.admits")
    if empty is None or not admits:
        return None
    return empty / admits / 1e3
