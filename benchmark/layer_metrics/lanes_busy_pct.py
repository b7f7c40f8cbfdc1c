"""Scheduler: mean of the once-a-second ``slots.active`` polls over the
lanes of the one compiled decode step: how full the step's lanes were."""


def read(run):
    rows = [r["slots.active"] for r in run["polls"]
            if r.get("slots.active") is not None]
    lanes = run["program"].get("num_slots", 0)
    if not rows or not lanes:
        return None
    return 100.0 * sum(rows) / len(rows) / lanes
