"""Kernels: the least time the banded flash prefill kernel needs for the
prefills of the traced stretch (the multiply-adds of the (query, key) pairs
it cannot avoid: the band of the sliding layers, the causal half of the
full ones, at the chip's bf16 peak, or its queries, keys, values and output
moved once at the HBM rate if longer; ``benchmark/roofline/swa_moe.py``)
over the device seconds of the instructions the program names
``swa_prefill_flash`` inside those same ``jit_prefill`` executions. Each
prefill counts at its bucket, read off the kernel's own result. A program
that runs no such kernel has nothing here to read."""
from benchmark.roofline import swa_moe as R


def read(run):
    traced = R.traced_prefills(run)
    if traced is None:
        return None
    cfg, peaks = run["cell"].config, run["peaks"]
    kernel_s = sum(k for _, _, k in traced)
    if not kernel_s:
        return None
    least = sum(max(R.attention_flops(cfg, n) / peaks["bf16_flops_per_s"],
                    R.attention_bytes(cfg, n) / peaks["hbm_bytes_per_s"])
                for n, _, _ in traced)
    return 100.0 * least / kernel_s
