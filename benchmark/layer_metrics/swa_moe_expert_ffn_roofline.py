"""Kernels: the least time the held experts' grouped matmuls need a decode
step (the weights of the held experts that got a token, read once at the
chip's HBM rate; ``benchmark/roofline/swa_moe.py``) over the device time
they take a step. The calls are the megablox kernel's (HLO instructions
named ``gmm``, inside the passes of ``expert_ffn``) within the traced
``jit_step`` executions; the experts touched are the window's
``moe.experts_touched`` over its ``moe.layer_steps`` (a mean per layer per
step) times the expert layers."""
from benchmark.roofline import swa_moe as R


def read(run):
    steps, kernel_s = R.step_op_seconds(run, lambda name: "gmm" in name)
    touched = R.experts_touched_per_step(run)
    if not steps or not kernel_s or touched is None:
        return None
    least = R.expert_ffn_least(run["cell"].config, touched, run["peaks"])
    return 100.0 * least["seconds"] / (kernel_s / steps)
