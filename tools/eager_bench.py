"""Eager-dispatch overhead microbenchmark.

The reference gates per-op perf in CI (ref:tools/ci_op_benchmark.sh). Here
the eager hot loop is Python -> dispatch.apply -> per-(op, shape) jax.jit
cache -> PJRT; this tool measures µs/op for representative ops, the same
chain fully compiled (one program), and the framework overhead ratio.

Writes one JSON line; run with BENCH_RECORD=path to append to a budget file.
A budget: eager dispatch should stay under ~150µs/op on CPU-class hosts
(SURVEY.md §3.1 flags the per-op boundary as the dygraph hot-loop risk).
"""
from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import numpy as np


def main():
    import jax

    # EAGER_BENCH_PLATFORM=cpu pins the backend BEFORE any device touch
    # (host-side dispatch overhead is what this measures)
    plat = os.environ.get("EAGER_BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)

    import paddle_tpu as paddle

    dev = jax.devices()[0]
    x = paddle.to_tensor(np.random.rand(256, 256).astype(np.float32))
    y = paddle.to_tensor(np.random.rand(256, 256).astype(np.float32))
    # unique inputs per iteration, so no layer can short-cut a repeated
    # (program, inputs) execution
    n = 200
    xs = [paddle.to_tensor(np.random.rand(256, 256).astype(np.float32))
          for _ in range(n)]
    # materialize every input on device BEFORE timing: the first op over a
    # lazily-uploaded tensor would otherwise absorb 200 H2D transfers into
    # whichever op runs first (observed: "add" at 10.4 ms/op on TPU)
    for t in xs:
        t._data = jax.device_put(t._data)
    jax.block_until_ready([t._data for t in xs])

    ops = {
        "add": lambda xi: paddle.add(xi, y),
        "matmul": lambda xi: paddle.matmul(xi, y),
        "relu": lambda xi: paddle.nn.functional.relu(xi),
        "sum": lambda xi: paddle.sum(xi),
        "transpose": lambda xi: paddle.transpose(xi, [1, 0]),
    }

    results = {}
    first = True
    for name, f in ops.items():
        f(x)  # compile/cache
        if first:
            # one untimed pass: the first sustained burst after session
            # start pays a relay ramp-up (~10 ms/op observed) that is not
            # steady-state dispatch; prime it off the clock
            for xi in xs:
                out = f(xi)
            np.asarray(out._data if hasattr(out, "_data") else out)
            first = False
        t0 = time.perf_counter()
        for xi in xs:
            out = f(xi)
        np.asarray(out._data if hasattr(out, "_data") else out)
        results[name] = (time.perf_counter() - t0) / n * 1e6  # µs/op

    # raw jax.jit equivalents: same math, no framework — the difference IS
    # the dispatch overhead (per-op timings above include real compute,
    # e.g. the 256x256 matmul itself)
    import jax.numpy as jnp

    raw_ops = {
        "add": jax.jit(lambda a, b: a + b),
        "matmul": jax.jit(lambda a, b: a @ b),
        "relu": jax.jit(lambda a, b: jnp.maximum(a, 0)),
        "sum": jax.jit(lambda a, b: a.sum()),
        "transpose": jax.jit(lambda a, b: a.T),
    }
    raw = {}
    xds = [t._data for t in xs]
    for name, f in raw_ops.items():
        f(x._data, y._data)
        t0 = time.perf_counter()
        for xd in xds:
            out = f(xd, y._data)
        np.asarray(out)
        raw[name] = (time.perf_counter() - t0) / n * 1e6
    overhead = {k: max(results[k] - raw[k], 0.0) for k in results}

    # the same 5-op chain as ONE compiled program
    def chain(xa, ya):
        import jax.numpy as jnp

        a = xa + ya
        b = a @ ya
        c = jnp.maximum(b, 0)
        return c.sum() + xa.T.sum()

    cf = jax.jit(chain)
    cf(x._data, y._data)
    t0 = time.perf_counter()
    for xd in xds:
        out = cf(xd, y._data)
    np.asarray(out)
    compiled_us = (time.perf_counter() - t0) / n * 1e6

    eager_mean = float(np.mean(list(results.values())))
    overhead_mean = float(np.mean(list(overhead.values())))
    rec = {
        "metric": "eager dispatch overhead",
        "unit": "us/op",
        "platform": dev.platform,
        "per_op_us": {k: round(v, 1) for k, v in results.items()},
        "raw_jax_us": {k: round(v, 1) for k, v in raw.items()},
        "overhead_us": {k: round(v, 1) for k, v in overhead.items()},
        "eager_mean_us": round(eager_mean, 1),
        "overhead_mean_us": round(overhead_mean, 1),
        "compiled_chain_us": round(compiled_us, 1),
        "overhead_ratio": round(eager_mean * len(results) / max(compiled_us, 1e-9), 2),
        "budget_us": 150.0,
        "within_budget": overhead_mean <= 150.0,
    }
    line = json.dumps(rec)
    print(line)
    path = os.environ.get("BENCH_RECORD")
    if path:
        with open(path, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
