"""Model step: how far the busiest expert stands over an even share, over
the window's decode steps: 100 x (``moe.max_expert_assignments`` x experts /
``moe.assignments`` - 1), both summed by the step program over its expert
layers and read back with the step's tokens (0: every expert got the same;
100: the busiest got twice its share). A program that counts no
assignments has nothing here to read."""


def read(run):
    c = run["counters"]
    total = c.get("moe.assignments", 0)
    if not total:
        return None
    experts = int(run["cell"].config["n_routed_experts"])
    return 100.0 * (c.get("moe.max_expert_assignments", 0) * experts
                    / total - 1.0)
