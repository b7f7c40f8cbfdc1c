"""Kernels: the least time a decode step's indexer, selection and sparse
attention need (the live index keys and the K/V rows the attention read,
each once at the chip's HBM rate, with the lanes' queries in and outputs
out; ``benchmark/roofline/sparse_moe.py``) over the device time a step
spends in the operations of those three (the kernel the program names
``paged_index_scores``, the passes over the ``[lanes, context]`` scores that
choose, the gather of the kept rows and the attention over them:
``sparse_moe.scope_patterns``) inside the traced ``jit_step`` executions.
Rows are the window's means by the program's ``sparse.*`` counters; active
lanes the mean of the polls. A program without the kernel or the counters
has nothing here to read."""
from benchmark.roofline import sparse_moe as R


def read(run):
    scoped, rows, held = R.step_scope_seconds(run), R.rows_per_step(run), \
        R.live(run)
    if None in (scoped, rows, held):
        return None
    steps, seconds = scoped
    least = R.sparse_decode_least(run["cell"].config, rows, held[1],
                                  run["peaks"])
    return 100.0 * least["seconds"] / (sum(seconds.values()) / steps)
