"""Model step: the least time a decode step of the window-and-experts model
needs on this chip (every weight outside the experts and but the token
table, the held experts a token reached, the LIVE rows of the window rings
and the live rows of the paged pool, each once at the chip's HBM rate, or
the lanes' matmuls at the bf16 peak if longer;
``benchmark/roofline/swa_moe.py``) over the median device time of
``jit_step``. Pool rows and active lanes are the means of the once-a-second
polls; the experts reached, the picks a token and the ring rows that hold a
key are the window's means by the program's own counters (``moe.*``,
``window.rows_live``); sizes are read from the program."""
from benchmark.harness.readers import module_ms
from benchmark.roofline import swa_moe as R


def read(run):
    step_ms = module_ms(run, "jit_step")
    held, touched = R.live(run), R.experts_touched_per_step(run)
    picks, rings = R.local_picks(run), R.ring_rows_live_per_step(run)
    if None in (step_ms, held, touched, picks, rings):
        return None
    p = run["program"]
    least = R.decode_step_least(
        run["cell"].config, p["weight_bytes"], p["kv_bytes_per_token"],
        held[0], rings, held[1], touched, picks, run["peaks"])
    return 100.0 * least["seconds"] / (step_ms * 1e-3)
