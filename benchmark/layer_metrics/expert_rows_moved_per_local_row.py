"""Model step: rows the grouped matmuls' path gathered over the rows the
held experts took, over the window's decode steps: ``moe.rows_moved`` /
``moe.local_assignments`` (1: only what a held expert multiplies was moved;
a path that gathers every assignment for a sixteenth of a share reads 48).
A program that counts neither has nothing here to read."""


def read(run):
    c = run["counters"]
    local = c.get("moe.local_assignments", 0)
    if not local or "moe.rows_moved" not in c:
        return None
    return c["moe.rows_moved"] / local
