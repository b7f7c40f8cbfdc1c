"""Autoregressive decode throughput: tokens/sec through the compiled
KV-cache generate loop (the serving-side companion to bench.py's training
number).

Usage: python benches/decode_bench.py  (TPU: GPT-base; CPU: tiny smoke)
Env: DECODE_BATCH, DECODE_PROMPT, DECODE_NEW, DECODE_ITERS.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _common  # noqa: E402,F401 — compile cache + sync()


def main():
    import jax

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, gpt_tiny

    dev = jax.devices()[0]
    platform = dev.platform
    if platform == "tpu":
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=2048)
        batch = int(os.environ.get("DECODE_BATCH", "8"))
        prompt = int(os.environ.get("DECODE_PROMPT", "128"))
        new = int(os.environ.get("DECODE_NEW", "128"))
        iters = int(os.environ.get("DECODE_ITERS", "5"))
    else:
        cfg = gpt_tiny()
        batch, prompt, new, iters = 2, 16, 16, 2

    model = GPTForCausalLM(cfg)
    model.eval()
    # serving dtype: bf16 weights halve the per-step HBM read that bounds
    # autoregressive decode (the TPU deployment default); DECODE_DTYPE=
    # float32 restores full precision
    dtype = os.environ.get("DECODE_DTYPE",
                           "bfloat16" if platform == "tpu" else "float32")
    if dtype not in ("bfloat16", "float32"):
        raise SystemExit(f"DECODE_DTYPE must be bfloat16|float32, got "
                         f"{dtype!r}")
    if dtype == "bfloat16":
        model.bfloat16()
    rng = np.random.default_rng(0)
    ids = Tensor(rng.integers(0, cfg.vocab_size, (batch, prompt),
                              dtype=np.int32))

    out = model.generate(ids, max_new_tokens=new)  # compile + warm
    _common.sync(out)
    # distinct prompts per iteration, so no layer can short-cut a repeated
    # (program, inputs) execution
    prompts = [Tensor(rng.integers(0, cfg.vocab_size, (batch, prompt),
                                   dtype=np.int32)) for _ in range(iters)]
    t0 = time.perf_counter()
    for p in prompts:
        out = model.generate(p, max_new_tokens=new)
    _common.sync(out)
    dt = time.perf_counter() - t0

    # prefill share: a 1-new-token generate is prefill + one decode step.
    # Measured after the main loop (own warmup) so its compilation doesn't
    # perturb the headline timing.
    p1 = model.generate(ids, max_new_tokens=1)
    _common.sync(p1)
    # fresh prompts: the main loop already executed the prefill program
    # on `prompts`, so reusing them would leave dt_prefill replay-servable
    prompts2 = [Tensor(rng.integers(0, cfg.vocab_size, (batch, prompt),
                                    dtype=np.int32)) for _ in range(iters)]
    t0 = time.perf_counter()
    for p in prompts2:
        p1 = model.generate(p, max_new_tokens=1)
    _common.sync(p1)
    dt_prefill = time.perf_counter() - t0

    toks = batch * new * iters
    decode_dt = dt - dt_prefill  # time spent in steps 2..new
    # on tiny CPU smokes the two loops' noise can swamp the split; only
    # report a decode-only rate when the subtraction is meaningful
    decode_only = (round(batch * (new - 1) * iters / decode_dt, 1)
                   if decode_dt > 0.05 * dt else None)
    rec = {
        "metric": f"decode tokens/sec (GPT {cfg.hidden_size}h/"
                  f"{cfg.num_layers}L b{batch} p{prompt}+{new} "
                  f"{dtype} {platform})",
        "value": round(toks / dt, 1),
        "unit": "tokens/sec",
        "ms_per_token": round(dt / toks * 1e3, 3),
        "platform": platform,
        "prefill_ms": round(dt_prefill / iters * 1e3, 3),
        "decode_only_tokens_per_sec": decode_only,
        "prefill_tokens_per_sec": round(
            batch * prompt * iters / dt_prefill, 1),
    }
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _common import emit

    emit({"bench": "decode", **rec})


if __name__ == "__main__":
    main()
