"""Model step: the least time a decode step of the decoder-hybrid-decoder
model needs (the weights once + the live K/V of the one paged layer once for
each of its readers + the active lanes' window rings + their Mamba state
read and written, at the chip's HBM rate;
``benchmark/roofline/flash_decode_step.py``) over the median device time of
``jit_step``. Live context and active lanes are the means of the
once-a-second polls; weights and K/V sizes are read from the program, the
windows' and the state's from the configuration file."""
from benchmark.harness.readers import module_ms
from benchmark.roofline import flash_decode_step


def read(run):
    step_ms = module_ms(run, "jit_step")
    rows = [r for r in run["polls"] if r.get("arena.blocks_total")]
    if step_ms is None or not rows:
        return None
    p = run["program"]
    used = sum(r["arena.blocks_total"] - r["arena.blocks_free"]
               for r in rows) / len(rows)
    lanes = sum(r["slots.active"] or 0 for r in rows) / len(rows)
    least = flash_decode_step.least_seconds(
        run["cell"].config, p["weight_bytes"], p["kv_bytes_per_token"],
        used * p["block_size"], lanes, run["peaks"])
    return 100.0 * least["seconds"] / (step_ms * 1e-3)
