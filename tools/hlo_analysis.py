"""Compiler-level perf evidence for the GPT training step (VERDICT r4 #1b).

Without a chip, this extracts what the compiler itself knows:
jit(TrainStep).lower().compile().cost_analysis() at the REAL bench shapes
(GPT-base 768h/12L, b16 s1024, bf16 autocast — the exact program bench.py
times on hardware), plus HLO-text statistics (fusion counts, remat
duplication, collective ops) and a v5e roofline projection.

The compile target here is XLA:CPU (no chip): analytic FLOPs are
backend-independent (counted from HLO dot/conv shapes); bytes-accessed is
layout-dependent and treated as an upper-bound estimate. Both are stated
with that caveat in the generated report.

Usage: python tools/hlo_analysis.py [out_md]
Writes benches/HLO_ANALYSIS.md and prints a summary JSON line.
HLO_PLATFORM=tpu compiles for the attached TPU backend instead:
bytes-accessed then reflects real bf16 TPU layouts and TPU fusion,
replacing the CPU upper bound.
"""
from __future__ import annotations

import json
import os
import re
import sys

_PLAT = os.environ.get("HLO_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _PLAT

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402

if _PLAT == "cpu":
    jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: E402

V5E_PEAK_BF16 = 197e12   # FLOP/s, public spec
V5E_HBM_BW = 819e9       # bytes/s
BATCH, SEQ = 16, 1024


def build_step(remat: bool, hidden=768, layers=12, batch=BATCH, seq=SEQ,
               amp_level="O1", chunk=0, scan=False):
    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu import amp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.optimizer import AdamW

    cfg = GPTConfig(vocab_size=50304, hidden_size=hidden, num_layers=layers,
                    num_heads=max(1, hidden // 64),
                    max_position_embeddings=2048,
                    use_recompute=remat, loss_chunk_size=chunk,
                    use_scan_layers=scan)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01)
    if amp_level == "O2":
        amp.decorate(model, opt, level="O2")

    def loss_fn(x, y):
        # always O1 autocast: bench.py's BENCH_AMP=O2 means decorate(O2)
        # (bf16 params + master slots) UNDER O1 autocast — this compiles
        # the exact program the sweep's "O2" rows time on hardware
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return model(x, y)

    step = TrainStep(loss_fn, opt, layers=model)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    return cfg, step, (Tensor(ids), Tensor(np.roll(ids, -1, axis=1)))


def analyze(remat: bool, **kw):
    cfg, step, batch = build_step(remat, **kw)
    compiled = step.lower(*batch).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    hlo = compiled.as_text()
    stats = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "hlo_instructions": hlo.count("\n"),
        "fusions": len(re.findall(r"^\s*\S+ = .* fusion\(", hlo, re.M)),
        "dots": len(re.findall(r"\bdot\(", hlo)),
        "custom_calls": len(re.findall(r"custom-call", hlo)),
        "while_loops": len(re.findall(r"^\s*\S+ = .* while\(", hlo, re.M)),
        "all_reduces": len(re.findall(r"all-reduce", hlo)),
    }
    n_params = int(sum(int(np.prod(p.shape)) for p in step._train_params))
    return cfg, stats, n_params


def model_flops(cfg, batch=BATCH, seq=SEQ) -> float:
    """Analytic 6N-per-token training FLOPs for the bench shapes (the same
    accounting bench.py uses for MFU)."""
    h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    i = cfg.intermediate_size
    n_matmul = L * (4 * h * h + 2 * h * i) + h * V
    attn = 6 * L * seq * h
    per_token = 6.0 * n_matmul + attn
    return per_token * batch * seq


def main():
    default_name = ("HLO_ANALYSIS.md" if _PLAT == "cpu"
                    else f"HLO_ANALYSIS_{_PLAT.upper()}.md")
    out_md = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(HERE), "benches", default_name)
    rows = {}
    for remat in (False, True):
        cfg, stats, n_params = analyze(remat)
        rows[remat] = stats
    mf = model_flops(cfg)

    def project(stats):
        t_flops = stats["flops"] / V5E_PEAK_BF16
        t_mem = stats["bytes_accessed"] / V5E_HBM_BW
        t = max(t_flops, t_mem)
        return {
            "t_flops_ms": t_flops * 1e3,
            "t_mem_ms": t_mem * 1e3,
            "bound": "memory" if t_mem > t_flops else "compute",
            "proj_step_ms": t * 1e3,
            "proj_tokens_per_sec": BATCH * SEQ / t,
            "proj_mfu": mf / (t * V5E_PEAK_BF16),
        }

    proj = {k: project(v) for k, v in rows.items()}
    # bf16 layouts roughly halve the CPU fp32-biased traffic estimate
    proj_bf16 = {k: project({**v, "bytes_accessed": v["bytes_accessed"] / 2})
                 for k, v in rows.items()}
    # what the 0.35 MFU target structurally requires of HBM traffic
    t_target = mf / (0.35 * V5E_PEAK_BF16)
    bytes_for_target = t_target * V5E_HBM_BW
    summary = {
        "model": f"GPT {cfg.hidden_size}h/{cfg.num_layers}L b{BATCH} s{SEQ}",
        "params": n_params,
        "model_flops_per_step": mf,
        "hlo_flops_per_step": rows[False]["flops"],
        "flops_overhead_vs_6N": rows[False]["flops"] / mf,
        "remat_flops_ratio": rows[True]["flops"] / rows[False]["flops"],
        "proj_mfu_no_remat": round(proj[False]["proj_mfu"], 3),
        "proj_mfu_remat": round(proj[True]["proj_mfu"], 3),
    }

    lines = [
        "# HLO cost analysis — GPT training step at bench shapes",
        "",
        "Generated by `tools/hlo_analysis.py` (XLA:CPU compile of the exact",
        "jitted TrainStep bench.py runs; no TPU needed). FLOPs are counted",
        "from HLO op shapes and are backend-independent; bytes-accessed is",
        "an XLA:CPU estimate (fp32-biased layouts) — treat the memory-side",
        "numbers as upper bounds for a bf16 TPU executable.",
        "",
        f"Model: **{summary['model']}**, {n_params / 1e6:.1f}M params, "
        f"bf16 autocast O1, AdamW, donated buffers.",
        "",
        "| metric | no remat | full remat |",
        "|---|---|---|",
    ]
    fmt = [
        ("HLO FLOPs/step", "flops", "{:.3e}"),
        ("bytes accessed/step", "bytes_accessed", "{:.3e}"),
        ("transcendentals", "transcendentals", "{:.2e}"),
        ("HLO instructions", "hlo_instructions", "{}"),
        ("fusions", "fusions", "{}"),
        ("dot ops", "dots", "{}"),
        ("while loops (scan)", "while_loops", "{}"),
    ]
    for label, key, f in fmt:
        lines.append(f"| {label} | {f.format(rows[False][key])} | "
                     f"{f.format(rows[True][key])} |")
    lines += [
        "",
        f"Analytic model FLOPs (6N accounting, the bench's MFU denominator): "
        f"**{mf:.3e}/step** — the compiled program issues "
        f"{summary['flops_overhead_vs_6N']:.2f}x that "
        "(backward + optimizer + attention softmax overhead).",
        f"Rematerialization multiplies issued FLOPs by "
        f"{summary['remat_flops_ratio']:.2f}x (recompute of checkpointed "
        "activations in the backward).",
        "",
        "## v5e roofline projection (197 TF/s bf16, 819 GB/s HBM)",
        "",
        "| | no remat | full remat |",
        "|---|---|---|",
        f"| compute time/step | {proj[False]['t_flops_ms']:.1f} ms | "
        f"{proj[True]['t_flops_ms']:.1f} ms |",
        f"| memory time/step (upper bound) | {proj[False]['t_mem_ms']:.1f} ms"
        f" | {proj[True]['t_mem_ms']:.1f} ms |",
        f"| bound | {proj[False]['bound']} | {proj[True]['bound']} |",
        f"| projected tokens/sec | {proj[False]['proj_tokens_per_sec']:.0f} |"
        f" {proj[True]['proj_tokens_per_sec']:.0f} |",
        f"| projected MFU (CPU-layout bytes) | {proj[False]['proj_mfu']:.2f}"
        f" | {proj[True]['proj_mfu']:.2f} |",
        f"| projected MFU (bf16-scaled bytes) | "
        f"{proj_bf16[False]['proj_mfu']:.2f} | "
        f"{proj_bf16[True]['proj_mfu']:.2f} |",
        "",
        "## What 0.35 MFU requires at these shapes",
        "",
        f"Compute side is NOT the limit: at peak the issued FLOPs take "
        f"{proj[False]['t_flops_ms']:.0f} ms/step — an MFU ceiling of "
        f"{mf / (proj[False]['t_flops_ms'] / 1e3 * V5E_PEAK_BF16):.2f}. "
        f"The program is HBM-bound: hitting MFU 0.35 needs step time "
        f"<= {t_target * 1e3:.0f} ms, i.e. HBM traffic "
        f"<= {bytes_for_target:.2e} B/step.",
        "",
        f"- XLA:CPU upper bound measured here: "
        f"{rows[False]['bytes_accessed']:.2e} B "
        f"({rows[False]['bytes_accessed'] / bytes_for_target:.1f}x over "
        "budget in fp32-biased layouts).",
        f"- bf16 layouts halve that to ~"
        f"{rows[False]['bytes_accessed'] / 2:.2e} B; XLA:TPU additionally "
        "fuses far more aggressively than XLA:CPU (whose fusion count is "
        "what this bound reflects).",
        f"- The single largest removable term is the materialized s x s "
        f"attention: b*h*s^2 softmax tensors cost ~"
        f"{16 * 12 * SEQ * SEQ * 2 * 12 * 3 / 1e9:.0f} GB/step across "
        "fwd+bwd in bf16 — the Pallas flash kernels exist precisely to "
        "delete it (ops/pallas_ops.py; unverified on hardware, "
        "interpreter-only so far).",
        "",
        "Conclusion: at b16/s1024 the step is structurally memory-bound;",
        "0.35 MFU hinges on TPU-side fusion + flash attention, not on more",
        "raw FLOPs. The first on-chip run should profile bytes, not FLOPs.",
    ]
    os.makedirs(os.path.dirname(out_md), exist_ok=True)
    with open(out_md, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
