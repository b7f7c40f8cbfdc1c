"""Model step: operations the prefills of the traced stretch needed (every
position of the bucket through the attention's four matrices, the
indexer's three, the router and the held experts as the window's tokens
were routed; the index scores over the causal pairs; the attention over
``min(t + 1, topk)`` keys a query; the head once:
``benchmark/roofline/sparse_moe.py``), over the device time of
``jit_prefill`` there and the chip's bf16 peak. What the program does
beside (the search behind each row's threshold, the index scores computed
a second time inside the flash kernel, the pairs the kernel multiplies and
then masks) is no part of the need.

The prefills are the ``jit_prefill`` executions wholly inside the traced
stretch, each at its bucket, read off the flash kernel's result. A stretch
that holds none has nothing here to read, and neither has a program
without the kernel (the parent's): the device's time is read from the
device's trace or not at all."""
from benchmark.roofline import sparse_moe as R


def read(run):
    traced, picks = R.traced_prefills(run), R.local_picks(run)
    if traced is None or picks is None:
        return None
    need = sum(R.prefill_flops(run["cell"].config, n, picks)
               for n, _ in traced)
    seconds = sum(d for _, d in traced)
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops_per_s"])
