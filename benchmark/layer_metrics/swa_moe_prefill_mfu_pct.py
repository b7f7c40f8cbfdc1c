"""Model step: operations the prefills of the traced stretch needed (every
position of the bucket through the attention's five matrices, the dense MLP
or the router, the shared expert and the held experts as the window's
tokens were routed, the band of the sliding layers and the causal half of
the full one, the head once: ``benchmark/roofline/swa_moe.py``), over the
device time of ``jit_prefill`` there and the chip's bf16 peak."""
from benchmark.roofline import swa_moe as R


def read(run):
    traced, picks = R.traced_prefills(run), R.local_picks(run)
    if traced is None or picks is None:
        return None
    need = sum(R.prefill_flops(run["cell"].config, n, picks)
               for n, _, _ in traced)
    seconds = sum(d for _, d, _ in traced)
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops_per_s"])
