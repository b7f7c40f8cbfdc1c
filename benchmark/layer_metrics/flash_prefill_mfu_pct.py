"""Model step: operations the prefills of the traced stretch needed (the
layers before the tail over every position of the bucket, the tail over one:
``benchmark/roofline/flash_prefill.py``; the bucket is read off the trace by
``hybrid_prefill.traced_prefills``), over the device time of ``jit_prefill``
there and the chip's bf16 peak."""
from benchmark.roofline import flash_prefill, hybrid_prefill


def read(run):
    traced = hybrid_prefill.traced_prefills(run)
    if traced is None:
        return None
    need = sum(sum(flash_prefill.flops(run["cell"].config, n).values())
               for n, _ in traced)
    seconds = sum(d for _, d in traced)
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops_per_s"])
