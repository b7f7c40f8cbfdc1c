"""Model step: the share of the window's decode-step assignments that went
to zero-compute experts: 100 x ``moe.zero_assignments`` /
``moe.assignments``, both summed by the step program over its expert layers
and read back with the step's tokens (the seeded selection bias is fit to
256 of 768 columns: 33). A program that counts none has nothing here to
read."""


def read(run):
    c = run["counters"]
    total = c.get("moe.assignments", 0)
    if not total or "moe.zero_assignments" not in c:
        return None
    return 100.0 * c["moe.zero_assignments"] / total
