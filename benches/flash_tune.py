"""On-chip block-size autotune for the Pallas flash-attention kernels.

The kernels default to 128x128 tiles (MXU/lane width). This sweeps
(blk_q, blk_k) over the training shapes where flash is (or is near) the
profitable path — the long-context shapes from benches/flash_tpu_bench.py —
times fwd+bwd under jit, verifies each candidate against the XLA reference
before timing (a mis-tiled kernel must never win on wrong numbers), and
emits per-point records plus a final "best" line with the flag settings to
adopt (FLAGS_flash_block_q/_k).

Run standalone on a live TPU: python benches/flash_tune.py
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
        emit({"bench": "flash-tune", "error":
              f"watchdog: no result within {limit_s:.0f}s (hang)"})
        os._exit(3)

    t = threading.Timer(limit_s, fire)
    t.daemon = True
    t.start()
    return t


def _time_step(step, q, k, v, iters=10):
    """Time an ALREADY-COMPILED fwd+bwd step (the numerics check's first
    call pays the compile; never compile the same program twice against
    the watchdog budget). Inputs are made unique per iteration, so no
    layer can short-cut a repeated (program, inputs) execution."""
    qs = [q * (1.0 + 1e-6 * (i + 1)) for i in range(iters)]
    _common.sync(qs[-1])
    t0 = time.time()
    for qi in qs:
        g = step(qi, k, v)
    _common.sync(g)
    return (time.time() - t0) / iters


def main():
    wd = _watchdog(float(os.environ.get("BENCH_WATCHDOG", "2100")))
    d = jax.devices()[0]
    print(f"[flash-tune] device: {d} ({d.platform})", flush=True)
    rng = np.random.RandomState(7)
    # 1024/2048 included since the tuned 512-blocks moved the XLA
    # break-even below 4096 — the short end needs its own best tiling
    # before FLAGS_flash_attention_min_seqlen can be set from data
    shapes = [(16, 1024, 12, 64), (8, 2048, 12, 64),
              (4, 4096, 12, 64), (1, 8192, 12, 64)]
    candidates = [(128, 128), (128, 256), (128, 512), (256, 256),
                  (256, 512), (512, 512), (256, 128), (512, 256)]
    best_by_shape = {}
    for b, s, h, dd in shapes:
        q = jnp.asarray(rng.standard_normal((b, s, h, dd)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, s, h, dd)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, s, h, dd)), jnp.bfloat16)
        scale = 1.0 / np.sqrt(dd)
        ref = po._attention_reference(q, k, v, scale, True)

        def _ref_loss(q, k, v):
            return (po._attention_reference(q, k, v, scale, True)
                    .astype(jnp.float32) ** 2).sum()

        # adopted winners drive TRAINING: the backward must be verified
        # too, not just the forward — a tiling with a subtly wrong dq/dk/dv
        # but correct outputs must never win
        ref_grads = jax.jit(jax.grad(_ref_loss, argnums=(0, 1, 2)))(q, k, v)
        best = None
        for bq, bk in candidates:
            fn = functools.partial(po._flash_attention, scale=scale,
                                   causal=True, blk_q=bq, blk_k=bk)
            try:
                out = jax.jit(lambda q, k, v: fn(q, k, v))(q, k, v)
                err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                            - ref.astype(jnp.float32))))
                if err > 1e-1:  # bf16 tolerance — wrong tiling, not noise
                    emit({"bench": "flash-tune", "shape": [b, s, h, dd],
                          "blk": [bq, bk], "error": f"numerics {err:.2e}"})
                    continue

                def _loss(q, k, v):
                    return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

                step = jax.jit(jax.grad(_loss, argnums=(0, 1, 2)))
                grads = step(q, k, v)  # compiles once; timed below as-is
                _common.sync(grads)
                gerr = max(float(jnp.max(jnp.abs(
                    g.astype(jnp.float32) - rg.astype(jnp.float32))))
                    for g, rg in zip(grads, ref_grads))
                # grads accumulate over s contributions: scale tolerance
                if gerr > 1e-1 * np.sqrt(s / 128):
                    emit({"bench": "flash-tune", "shape": [b, s, h, dd],
                          "blk": [bq, bk],
                          "error": f"bwd numerics {gerr:.2e}"})
                    continue
                t = _time_step(step, q, k, v)
            except Exception as e:  # mosaic lowering can reject a tiling
                emit({"bench": "flash-tune", "shape": [b, s, h, dd],
                      "blk": [bq, bk], "error": str(e)[:200]})
                continue
            flops = 3 * 2 * b * h * s * s * dd
            rec = {"bench": "flash-tune", "shape": [b, s, h, dd],
                   "blk": [bq, bk], "ms": t * 1e3,
                   "tflops": flops / t / 1e12, "platform": d.platform}
            emit(rec)
            print(f"[flash-tune] s={s} blk=({bq},{bk}): {t*1e3:.2f} ms "
                  f"{rec['tflops']:.2f} TFLOP/s", flush=True)
            if best is None or t < best[0]:
                best = (t, bq, bk)
        if best:
            best_by_shape[s] = best
    for s, (t, bq, bk) in best_by_shape.items():
        emit({"bench": "flash-tune-best", "seq": s, "blk": [bq, bk],
              "ms": t * 1e3, "platform": d.platform})
        print(f"[flash-tune] BEST s={s}: blk_q={bq} blk_k={bk} "
              f"({t*1e3:.2f} ms) -> FLAGS_flash_block_q={bq} "
              f"FLAGS_flash_block_k={bk}", flush=True)
    if best_by_shape and d.platform != "cpu":
        # ADOPT the winners: pallas_ops._default_blocks reads this when the
        # block flags sit at their 128 defaults (explicit flags still win).
        # Only numerics-verified candidates can reach best_by_shape, and
        # only an on-chip run publishes (a CPU-interpret timing would be
        # meaningless). Atomic write: a partial file must never load.
        import json

        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "FLASH_TUNED.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            # device_kind stamp: tiles verified on one TPU generation must
            # not be adopted on another (VMEM limits differ; Mosaic may
            # reject them) — _tuned_blocks checks it against the live chip
            json.dump({"device_kind": d.device_kind,
                       "blocks": {str(s): [bq, bk]
                                  for s, (t, bq, bk)
                                  in best_by_shape.items()}}, f)
        os.replace(tmp, path)
        print(f"[flash-tune] wrote {path}", flush=True)
        # mirror the winners into the SHARED kernel-tuning store
        # (ops.tuning — per-(kernel, chip, shape-bucket), the store every
        # Pallas kernel reads first; FLASH_TUNED.json above stays as the
        # legacy fallback for pre-store checkouts)
        from paddle_tpu.ops import tuning

        persisted = sum(
            tuning.adopt("flash_fwd", tuning.bucket_key(s=s),
                         {"blk_q": bq, "blk_k": bk}, t * 1e6)
            for s, (t, bq, bk) in best_by_shape.items())
        if persisted == len(best_by_shape):
            print(f"[flash-tune] adopted {persisted} records into "
                  f"{tuning.store_path()}", flush=True)
        else:
            print(f"[flash-tune] WARNING: only {persisted}/"
                  f"{len(best_by_shape)} records persisted to "
                  f"{tuning.store_path()} (write failed — the store is "
                  "NOT published)", flush=True)
    wd.cancel()


if __name__ == "__main__":
    main()
