"""Kernels: the least time the paged decode kernel needs a decode step (the
live context's K and V read once at the chip's HBM rate;
``benchmark/roofline/paged_decode.py``) over the device time its calls take
a step. The calls are found by the name the program gives the
``pallas_call`` (``paged_decode``), which the compiler carries into the HLO
instruction's own name; their seconds in the traced stretch over the count
of ``jit_step`` executions there are the kernel's seconds a step. Live
context is the mean of the once-a-second polls, as ``decode_step_roofline``
counts it. A program whose decode step does not run that kernel (the XLA
gather route) has nothing here to read."""
from benchmark.harness import trace as T
from benchmark.roofline import paged_decode


def _is_paged_decode(event_name: str) -> bool:
    # the event is named by its whole HLO text: match the instruction's
    # own name, not an operand that mentions another instruction
    return "paged_decode" in event_name.split(" = ", 1)[0]


def read(run):
    tr = run.get("trace")
    rows = [r for r in run["polls"] if r.get("arena.blocks_total")]
    if tr is None or not rows:
        return None
    steps = len(T.module_durations(tr, "jit_step"))
    kernel_s = sum(T.op_durations(tr, _is_paged_decode))
    if not steps or not kernel_s:
        return None
    p = run["program"]
    used = sum(r["arena.blocks_total"] - r["arena.blocks_free"]
               for r in rows) / len(rows)
    least = paged_decode.least_seconds(
        p["kv_bytes_per_token"], used * p["block_size"], run["peaks"])
    return 100.0 * least["seconds"] / (kernel_s / steps)
