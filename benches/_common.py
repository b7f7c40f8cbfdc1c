"""Shared bench-record emitter: one JSON line to stdout + append to
benches/BASELINE_RESULTS.jsonl with a timestamp (the accumulating-baselines
protocol in BASELINE.md)."""
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def emit(rec, path=None):
    rec["ts"] = time.time()
    line = json.dumps(rec)
    print(line, flush=True)
    with open(path or os.path.join(HERE, "BASELINE_RESULTS.jsonl"), "a") as f:
        f.write(line + "\n")


def sync(x):
    """Host-read completion barrier: fetch one element of every array
    leaf to the host — a device-to-host read cannot complete before the
    value exists. Costs one tiny slice + round trip, negligible against
    any timed region here. Kept until a ledger shows
    ``jax.block_until_ready`` agrees with it on the chip."""
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(x):
        leaf = getattr(leaf, "_data", leaf)
        if isinstance(leaf, jax.Array):
            np.asarray(jax.device_get(leaf[tuple(0 for _ in leaf.shape)]
                                      if leaf.ndim else leaf))
    return x
