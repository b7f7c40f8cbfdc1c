"""Model step: the least time a decode step of the shortcut-connected
expert model needs on this chip (every weight but the token table and the
held experts no token reached + the live latent rows, each once at the
chip's HBM rate, or the lanes' matmuls at the bf16 peak if longer;
``benchmark/roofline/scmoe.py``) over the median device time of
``jit_step``. Live rows and active lanes are the means of the once-a-second
polls, the experts reached and the picks a token the window's means by the
program's own counters; sizes are read from the program."""
from benchmark.harness.readers import module_ms
from benchmark.roofline import scmoe as R


def read(run):
    step_ms = module_ms(run, "jit_step")
    held, touched = R.live(run), R.experts_touched_per_step(run)
    picks = R.routed_here(run)
    if step_ms is None or held is None or touched is None or picks is None:
        return None
    p = run["program"]
    least = R.decode_step_least(
        run["cell"].config, p["weight_bytes"], p["kv_bytes_per_token"],
        held[0], held[1], touched, picks[0], run["peaks"])
    return 100.0 * least["seconds"] / (step_ms * 1e-3)
