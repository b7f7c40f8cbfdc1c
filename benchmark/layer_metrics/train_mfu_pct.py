"""Model step: operations the forward and backward passes need per token
(``benchmark/roofline/train_step.py``, recompute not counted) x this run's
tokens per second, over chips x the chip's bf16 peak."""
from benchmark.roofline import train_step


def read(run):
    t = run.get("train")
    if not t:
        return None
    per_token = train_step.flops_per_token(run["cell"].config, t["seq"])
    return 100.0 * per_token * run["e2e"]["train_tokens_per_s"] / (
        t["chips"] * run["peaks"]["bf16_flops_per_s"])
