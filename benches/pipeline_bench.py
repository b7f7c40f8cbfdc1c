"""GPipe vs interleaved pipeline: measured wall-clock, not just the formula.

The closed form says interleaving V chunks shrinks the fill/drain bubble
from (S-1)/(M+S-1) to (S-1)/(M*V+S-1) at the price of V x the ppermute
hops (ref:python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py:514). This bench times both schedules on the virtual
CPU mesh with a compute-heavy stage so the prediction is checked against a
clock: on one host the virtual devices share cores, so wall-clock tracks
TOTAL issued compute — which is exactly what the tick formula counts
(bubble ticks still burn a stage of compute in the masked-scan design).

Usage: python benches/pipeline_bench.py [d] [iters]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benches import _common  # noqa: E402
from benches._common import emit  # noqa: E402

# always the 8-virtual-device CPU mesh: this bench compares SCHEDULES on a
# multi-device pipe axis, which a single TPU chip cannot host
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.distributed.mesh import init_hybrid_mesh  # noqa: E402
from paddle_tpu.distributed.pipeline import (  # noqa: E402
    pipeline_apply, pipeline_apply_interleaved, pipeline_tick_cost,
    stack_chunk_params, stack_stage_params)

S = 4          # pipe stages
V = 2          # virtual chunks per device (interleaved)
L = 8          # total layers; GPipe stage = L/S layers, chunk = L/(S*V)
MB_ROWS = 8    # rows per microbatch (constant across M)


def _layers(d, rng):
    return [jnp.asarray(rng.standard_normal((d, d), np.float32) * 0.05)
            for _ in range(L)]


def _apply(ws, h):
    for w in ws:
        h = jnp.tanh(h @ w)
    return h


def _time(fn, *args, iters=8, warmup=2):
    for _ in range(warmup):
        _common.sync(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _common.sync(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def measure(M: int, d: int = 1024, iters: int = 8):
    mesh = init_hybrid_mesh(pp=S)
    rng = np.random.default_rng(0)
    layers = _layers(d, rng)
    x = jnp.asarray(rng.standard_normal((M * MB_ROWS, d), np.float32))

    per_stage = L // S
    stage_p = stack_stage_params(
        [{"ws": jnp.stack(layers[j * per_stage:(j + 1) * per_stage])}
         for j in range(S)], S, mesh=mesh)
    per_chunk = L // (S * V)
    chunk_p = stack_chunk_params(
        [{"ws": jnp.stack(layers[j * per_chunk:(j + 1) * per_chunk])}
         for j in range(S * V)], S, V, mesh=mesh)

    gpipe = jax.jit(lambda p, xb: pipeline_apply(
        lambda lp, h: _apply(lp["ws"], h), p, xb,
        num_microbatches=M, mesh=mesh, remat=False))
    inter = jax.jit(lambda p, xb: pipeline_apply_interleaved(
        lambda lp, h, v: _apply(lp["ws"], h), p, xb,
        num_microbatches=M, num_chunks=V, mesh=mesh, remat=False))

    # both schedules compute the same function — sanity before timing
    np.testing.assert_allclose(np.asarray(gpipe(stage_p, x)),
                               np.asarray(inter(chunk_p, x)),
                               rtol=2e-4, atol=2e-5)

    t_g = _time(gpipe, stage_p, x, iters=iters)
    t_i = _time(inter, chunk_p, x, iters=iters)
    predicted = (pipeline_tick_cost(M, S, 1) / pipeline_tick_cost(M, S, V))
    return {"M": M, "S": S, "V": V, "d": d,
            "gpipe_ms": round(t_g * 1e3, 2),
            "interleaved_ms": round(t_i * 1e3, 2),
            "speedup": round(t_g / t_i, 3),
            "predicted_speedup": round(predicted, 3)}


def main():
    d = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    rows = [measure(M, d=d, iters=iters) for M in (4, 8, 16)]
    rec = {"bench": "pipeline-interleave",
           "config": f"S{S} V{V} L{L} d{d} mb{MB_ROWS}",
           "platform": jax.devices()[0].platform,
           "rows": rows}
    emit(rec)


if __name__ == "__main__":
    main()
