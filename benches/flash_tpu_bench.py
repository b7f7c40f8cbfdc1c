"""On-chip Pallas flash-attention check + bench (Mosaic, not interpreter).

Runs OUTSIDE pytest on purpose: tests/conftest.py pins JAX_PLATFORMS=cpu
(a test process never claims the chip), which means
the flash tests exercise the Pallas *interpreter* there. This script runs on
the default backend — on a live TPU that is the real Mosaic lowering, the
first time these kernels compile as actual TPU kernels.

Two phases:
  1. Correctness: forward + backward vs the XLA softmax reference at
     training shapes (causal + bidirectional), tolerance matched to bf16/f32
     accumulation differences.
  2. Perf: wall-clock fwd+bwd of flash vs the naive XLA attention at the
     GPT bench shape and at long-context shapes where the S^2 materialized
     matrix starts to dominate HBM traffic (the thing flash deletes —
     ref:paddle/phi/kernels/gpu/flash_attn_kernel.cu:213 is the CUDA analog).

Emits one JSON record per phase to benches/BASELINE_RESULTS.jsonl.
"""
from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import _common  # noqa: E402
from _common import emit  # noqa: E402

from paddle_tpu.ops import pallas_ops as po  # noqa: E402


def _watchdog(limit_s: float):
    import threading

    def fire():
        emit({"bench": "flash-tpu", "error":
              f"watchdog: no result within {limit_s:.0f}s (hang)"})
        os._exit(3)

    t = threading.Timer(limit_s, fire)
    t.daemon = True
    t.start()
    return t


def _qkv(rng, b, s, h, d, dtype, sk=None):
    sk = sk or s
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sk, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sk, h, d)), dtype)
    return q, k, v


def check_correctness():
    rng = np.random.RandomState(0)
    worst = 0.0
    for causal in (False, True):
        for dtype, tol in ((jnp.float32, 5e-2), (jnp.bfloat16, 1e-1)):
            q, k, v = _qkv(rng, 2, 512, 4, 64, dtype)
            scale = 1.0 / np.sqrt(64)

            def loss_flash(q, k, v):
                return (po._flash_attention(q, k, v, scale, causal)
                        .astype(jnp.float32) ** 2).sum()

            def loss_ref(q, k, v):
                return (po._attention_reference(q, k, v, scale, causal)
                        .astype(jnp.float32) ** 2).sum()

            o1 = jax.jit(po._flash_attention, static_argnums=(3, 4))(
                q, k, v, scale, causal)
            o2 = po._attention_reference(q, k, v, scale, causal)
            fwd_err = float(jnp.max(jnp.abs(o1.astype(jnp.float32)
                                            - o2.astype(jnp.float32))))
            g1 = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            bwd_err = 0.0
            for a, b in zip(g1, g2):
                denom = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) or 1.0
                bwd_err = max(bwd_err, float(
                    jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32)))) / denom)
            ok = fwd_err < tol and bwd_err < tol
            print(f"[flash-tpu] causal={causal} {jnp.dtype(dtype).name}: "
                  f"fwd_err={fwd_err:.2e} bwd_rel_err={bwd_err:.2e} "
                  f"{'OK' if ok else 'FAIL'}", flush=True)
            worst = max(worst, bwd_err)
            if not ok:
                emit({"bench": "flash-tpu-correctness", "causal": causal,
                      "dtype": jnp.dtype(dtype).name, "fwd_err": fwd_err,
                      "bwd_rel_err": bwd_err, "ok": False,
                      "platform": jax.devices()[0].platform})
                return False
    emit({"bench": "flash-tpu-correctness", "ok": True,
          "worst_bwd_rel_err": worst,
          "device": str(jax.devices()[0]),
          "platform": jax.devices()[0].platform})
    return True


def _time_fwd_bwd(fn, q, k, v, iters=20):
    def loss(q, k, v):
        return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    g = step(q, k, v)
    _common.sync(g)
    # UNIQUE inputs per iteration, so no layer can short-cut a repeated
    # (program, inputs) execution; a per-iter scale (25 MB of extra HBM
    # traffic vs the multi-GB attention) does not change the workload
    qs = [q * (1.0 + 1e-6 * (i + 1)) for i in range(iters)]
    _common.sync(qs[-1])
    t0 = time.time()
    for qi in qs:
        g = step(qi, k, v)
    _common.sync(g)
    return (time.time() - t0) / iters


def bench_perf():
    rng = np.random.RandomState(1)
    shapes = [
        # (b, s, h, d) — GPT bench shape, then long-context; 2048 pins the
        # XLA break-even now that tuned blocks win at 4096
        (16, 1024, 12, 64),
        (8, 2048, 12, 64),
        (4, 4096, 12, 64),
        (1, 8192, 12, 64),
    ]
    for b, s, h, d in shapes:
        q, k, v = _qkv(rng, b, s, h, d, jnp.bfloat16)
        scale = 1.0 / np.sqrt(d)
        # resolve blocks the way production attention does (tuned record >
        # flags > 128 defaults) — benchmarking the hardcoded 128s would
        # mis-measure the kernel users actually run
        blk_q, blk_k = po._default_blocks(s)
        flash = functools.partial(po._flash_attention, scale=scale,
                                  causal=True, blk_q=blk_q, blk_k=blk_k)
        naive = functools.partial(po._attention_reference, scale=scale,
                                  causal=True)
        t_flash = _time_fwd_bwd(lambda q, k, v: flash(q, k, v), q, k, v)
        t_naive = _time_fwd_bwd(lambda q, k, v: naive(q, k, v), q, k, v)
        # causal attention training FLOPs: fwd QK^T + PV = 2 * 2*b*h*s^2*d / 2
        # (causal half), bwd 2x fwd -> 3x total
        flops = 3 * 2 * b * h * s * s * d
        emit({"bench": "flash-tpu-perf", "shape": [b, s, h, d],
              "blocks": [blk_q, blk_k],
              "flash_ms": t_flash * 1e3, "xla_naive_ms": t_naive * 1e3,
              "speedup": t_naive / t_flash,
              "flash_tflops": flops / t_flash / 1e12,
              "platform": jax.devices()[0].platform})


def main():
    wd = _watchdog(float(os.environ.get("BENCH_WATCHDOG", "1500")))
    d = jax.devices()[0]
    print(f"[flash-tpu] device: {d} ({d.platform})", flush=True)
    if d.platform == "cpu":
        print("[flash-tpu] WARNING: running on CPU — interpreter, not "
              "Mosaic; results are not TPU evidence", flush=True)
    if check_correctness():
        bench_perf()
    wd.cancel()


if __name__ == "__main__":
    main()
