"""Model step: operations the prefills of the traced stretch needed, each
over the positions of its bucket (``benchmark/roofline/hybrid_prefill.py``,
which reads the bucket off the trace), over the device time of
``jit_prefill`` there and the chip's bf16 peak."""
from benchmark.roofline import hybrid_prefill


def read(run):
    traced = hybrid_prefill.traced_prefills(run)
    if traced is None:
        return None
    need = sum(hybrid_prefill.flops(run["cell"].config, n) for n, _ in traced)
    seconds = sum(d for _, d in traced)
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops_per_s"])
