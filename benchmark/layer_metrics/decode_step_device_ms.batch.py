"""Model step: median device time of the ``jit_step`` program's
executions in the traced stretch."""
from benchmark.harness.readers import module_ms


def read(run):
    return module_ms(run, "jit_step")
