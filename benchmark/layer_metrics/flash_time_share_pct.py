"""Kernels: the flash-attention kernels' share of the device's busy time
in the traced stretch. Their operations are found by the name the program
gives its ``pallas_call``s (``flash_fwd``, ``flash_bwd_dkv``,
``flash_bwd_dq``), which the compiler carries into the HLO instruction's
own name (``%jvp_flash_fwd_.1 = ... custom-call(...)``); a program that
names them otherwise has nothing here to read."""
from benchmark.harness import trace as T


def _is_flash(event_name: str) -> bool:
    # the event is named by its whole HLO text: match the instruction's
    # own name, not an operand that mentions another instruction
    own = event_name.split(" = ", 1)[0]
    return "flash_fwd" in own or "flash_bwd" in own


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    busy, flash = T.busy_seconds(tr), T.op_seconds(tr, _is_flash)
    if not busy or not flash:
        return None
    return 100.0 * flash / busy
