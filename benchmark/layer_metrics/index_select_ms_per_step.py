"""Kernels: device time a decode step spends choosing its tokens: the
operations of the traced ``jit_step`` executions that carry the ``[lanes,
context]`` index scores (the passes that find each lane's threshold, the
running count and the search of it that compact the kept positions, and
the scores' own copies: ``sparse_moe.scope_patterns``'s ``select``), in ms
a step,
all layers together. A program that selects nothing has nothing here to
read."""
from benchmark.roofline import sparse_moe as R


def read(run):
    scoped = R.step_scope_seconds(run)
    if scoped is None:
        return None
    steps, seconds = scoped
    return 1e3 * seconds["select"] / steps
