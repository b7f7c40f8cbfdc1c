"""Model step: device time of ``jit_prefill`` in the traced stretch per
1,000 positions prefilled there, each prefill at its bucket, which
``benchmark/roofline/latent_moe.py`` reads off the flash kernel's own result
``[heads, positions, 128]`` (``prefill_device_ms_per_ktoken``'s reader takes
the commonest ``[n, hidden]`` shape, and this model's programs hold two
others, so it is not asked here)."""
from benchmark.roofline import latent_moe as R


def read(run):
    traced = R.traced_prefills(run)
    if traced is None:
        return None
    return 1e6 * sum(d for _, d in traced) / sum(n for n, _ in traced)
