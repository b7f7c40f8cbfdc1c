"""Model step: the least time a decode step needs (weights once + the live
context's K and V once, at the chip's HBM rate; see
``benchmark/roofline/decode_step.py``) over the median device time of
``jit_step``. Live context and active lanes are the means of the
once-a-second polls; sizes are read from the program's arena and weights."""
from benchmark.harness.readers import module_ms
from benchmark.roofline import decode_step, train_step


def read(run):
    step_ms = module_ms(run, "jit_step")
    rows = [r for r in run["polls"] if r.get("arena.blocks_total")]
    if step_ms is None or not rows:
        return None
    p, cfg = run["program"], run["cell"].config
    used = sum(r["arena.blocks_total"] - r["arena.blocks_free"]
               for r in rows) / len(rows)
    lanes = sum(r["slots.active"] or 0 for r in rows) / len(rows)
    least = decode_step.least_seconds(
        p["weight_bytes"], p["kv_bytes_per_token"], used * p["block_size"],
        lanes, train_step.matmul_params(cfg), cfg["hidden_size"],
        cfg["num_layers"], run["peaks"])
    return 100.0 * least["seconds"] / (step_ms * 1e-3)
