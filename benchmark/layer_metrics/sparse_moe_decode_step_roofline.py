"""Model step: the least time a decode step of the sparse-attention-and-
experts model needs on this chip (every weight outside the experts and but
the token table, the held experts a token reached, the live index keys and
the K/V rows the attention read, each once at the chip's HBM rate, or the
lanes' matmuls at the bf16 peak if longer;
``benchmark/roofline/sparse_moe.py``) over the median device time of
``jit_step``. Active lanes are the mean of the once-a-second polls; the
experts reached, the picks a token and the rows are the window's means by
the program's own counters (``moe.*``, ``sparse.*``); sizes are read from
the program."""
from benchmark.harness.readers import module_ms
from benchmark.roofline import sparse_moe as R


def read(run):
    step_ms = module_ms(run, "jit_step")
    held, touched = R.live(run), R.experts_touched_per_step(run)
    picks, rows = R.local_picks(run), R.rows_per_step(run)
    if None in (step_ms, held, touched, picks, rows):
        return None
    least = R.decode_step_least(
        run["cell"].config, run["program"]["weight_bytes"], rows, held[1],
        touched, picks, run["peaks"])
    return 100.0 * least["seconds"] / (step_ms * 1e-3)
