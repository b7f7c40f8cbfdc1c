"""Model step: device time of ``jit_prefill`` in the traced stretch per
1,000 positions prefilled there, each prefill at its bucket
(``benchmark/roofline/hybrid_prefill.py`` reads the bucket off the trace)."""
from benchmark.roofline import hybrid_prefill


def read(run):
    traced = hybrid_prefill.traced_prefills(run)
    if traced is None:
        return None
    return 1e6 * sum(d for _, d in traced) / sum(n for n, _ in traced)
