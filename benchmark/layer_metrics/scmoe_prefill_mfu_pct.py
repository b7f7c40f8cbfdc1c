"""Model step: operations the prefills of the traced stretch needed (every
position of the bucket through both attentions and both dense MLPs of every
layer, the router, the held experts and the zero-compute picks as the
window's tokens were routed, the causal half of the expanded attention, the
head once: ``benchmark/roofline/scmoe.py``; the bucket is read off the
trace by ``roofline/latent_moe.py``), over the device time of
``jit_prefill`` there and the chip's bf16 peak."""
from benchmark.roofline import scmoe as R


def read(run):
    traced, picks = R.traced_prefills(run), R.routed_here(run)
    if traced is None or picks is None:
        return None
    need = sum(R.prefill_flops(run["cell"].config, n, *picks)
               for n, _ in traced)
    seconds = sum(d for _, d in traced)
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops_per_s"])
