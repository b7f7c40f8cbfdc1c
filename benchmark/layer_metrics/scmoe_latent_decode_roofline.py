"""Kernels: the least time the latent decode kernel needs a decode step of
the shortcut-connected expert model (two calls a layer at 64 heads: the live
rows of every pool entry read once at the chip's HBM rate, or the absorbed
scores and values at the bf16 peak if longer; ``benchmark/roofline/scmoe.py``)
over the device time its calls take a step. The calls are found by the name
the program gives the ``pallas_call`` (``paged_latent_decode``) inside the
traced ``jit_step`` executions."""
from benchmark.roofline import scmoe as R


def read(run):
    steps, kernel_s = R.step_op_seconds(
        run, lambda name: "paged_latent_decode" in name)
    held = R.live(run)
    if not steps or not kernel_s or held is None:
        return None
    least = R.latent_decode_least(
        run["cell"].config, run["program"]["kv_bytes_per_token"], held[0],
        run["peaks"])
    return 100.0 * least["seconds"] / (kernel_s / steps)
