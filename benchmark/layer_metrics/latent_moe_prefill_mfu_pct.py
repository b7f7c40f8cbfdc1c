"""Model step: operations the prefills of the traced stretch needed (every
position of the bucket through the attention, the mixers and its chosen
experts, the causal half of the expanded attention, the head once:
``benchmark/roofline/latent_moe.py``, which also reads the bucket off the
trace), over the device time of ``jit_prefill`` there and the chip's bf16
peak."""
from benchmark.roofline import latent_moe as R


def read(run):
    traced = R.traced_prefills(run)
    if traced is None:
        return None
    need = sum(R.prefill_flops(run["cell"].config, n) for n, _ in traced)
    seconds = sum(d for _, d in traced)
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops_per_s"])
