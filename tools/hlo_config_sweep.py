"""Roofline projection across the sweep's candidate configs (compiler-level
evidence, no chip needed — extends benches/HLO_ANALYSIS.md to the round-4
sweep points: chunked fused LM-head loss, AMP O2 bf16 params, 350M-class
model scale).

For each config: compile the exact jitted TrainStep on XLA:CPU, read
cost_analysis() FLOPs/bytes, project v5e step time as max(flops/peak,
bytes/bw) with bytes halved as the bf16-layout estimate (the CPU compile
keeps fp32-biased layouts — stated as an estimate, not a measurement), and
report projected MFU against the 6N analytic accounting.

Usage: python tools/hlo_config_sweep.py   (writes benches/HLO_CONFIG_SWEEP.md)
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import hlo_analysis as H  # noqa: E402  (sets JAX_PLATFORMS before jax import)

CONFIGS = [
    # label, kwargs
    ("768h/12L b16 O1 (bench headline)",
     dict(remat=False, hidden=768, layers=12, batch=16, chunk=0,
          amp_level="O1")),
    ("768h/12L b32 O1 chunk1024",
     dict(remat=False, hidden=768, layers=12, batch=32, chunk=1024,
          amp_level="O1")),
    ("768h/12L b32 O2 chunk1024",
     dict(remat=False, hidden=768, layers=12, batch=32, chunk=1024,
          amp_level="O2")),
    ("1024h/24L b16 O2 chunk1024 (350M)",
     dict(remat=False, hidden=1024, layers=24, batch=16, chunk=1024,
          amp_level="O2")),
    ("1024h/24L b32 remat O2 chunk1024",
     dict(remat=True, hidden=1024, layers=24, batch=32, chunk=1024,
          amp_level="O2")),
    # single-chip ceiling candidates: arithmetic intensity rises with h,
    # bounded by HBM (O2 state ~14 B/param + activations in 16 GB)
    ("1536h/24L b8 O2 chunk1024 (680M)",
     dict(remat=False, hidden=1536, layers=24, batch=8, chunk=1024,
          amp_level="O2")),
    ("2048h/16L b8 O2 chunk1024 (807M)",
     dict(remat=False, hidden=2048, layers=16, batch=8, chunk=1024,
          amp_level="O2")),
    ("2048h/24L b8 remat O2 chunk1024 (1.2B)",
     dict(remat=True, hidden=2048, layers=24, batch=8, chunk=1024,
          amp_level="O2")),
    # longer sequences: tokens/step doubles while the per-step weight+state
    # traffic stays constant, so weight-bound configs gain intensity (the
    # attention term grows with s but routes through the measured-break-even
    # flash path above 4.6k)
    ("768h/12L b8 s2048 O2 chunk1024",
     dict(remat=False, hidden=768, layers=12, batch=8, seq=2048, chunk=1024,
          amp_level="O2")),
    ("2048h/16L b4 s2048 O2 chunk1024 (807M)",
     dict(remat=False, hidden=2048, layers=16, batch=4, seq=2048, chunk=1024,
          amp_level="O2")),
    ("2048h/16L b8 s2048 remat O2 chunk1024 (807M)",
     dict(remat=True, hidden=2048, layers=16, batch=8, seq=2048, chunk=1024,
          amp_level="O2")),
]


def main():
    out_md = os.path.join(os.path.dirname(HERE), "benches",
                          "HLO_CONFIG_SWEEP.md")
    rows = []
    for label, kw in CONFIGS:
        remat = kw.pop("remat")
        batch = kw["batch"]
        seq = kw.get("seq", H.SEQ)
        cfg, stats, n_params = H.analyze(remat, **kw)
        mf = H.model_flops(cfg, batch=batch, seq=seq)
        t_flops = stats["flops"] / H.V5E_PEAK_BF16
        bytes_bf16 = stats["bytes_accessed"] / 2  # bf16-layout estimate
        t_mem = bytes_bf16 / H.V5E_HBM_BW
        t = max(t_flops, t_mem)
        rows.append({
            "label": label, "params_m": n_params / 1e6,
            "hlo_flops": stats["flops"], "bytes_bf16_est": bytes_bf16,
            "fusions": stats["fusions"],
            "bound": "memory" if t_mem > t_flops else "compute",
            "proj_step_ms": t * 1e3,
            "proj_tokens_per_sec": batch * seq / t,
            "proj_mfu": mf / (t * H.V5E_PEAK_BF16),
        })
        print(json.dumps(rows[-1]), flush=True)

    lines = [
        "# Roofline projection across sweep configs",
        "",
        "**Every number in this table is a HYPOTHESIS, not a measurement** —",
        "estimates stacked on estimates. Generated by",
        "`tools/hlo_config_sweep.py` from XLA:**CPU** compiles of the exact",
        "TrainStep programs the TPU sweep runs, projected onto v5e (197 TF/s",
        "bf16, 819 GB/s HBM). Bytes are the CPU cost-model estimate halved",
        "for bf16 layouts; TPU layout assignment, fusion, and remat choices",
        "all differ, so a projected 0.41 could plausibly measure 0.3 on",
        "hardware. These projections ORDER the sweep candidates; only",
        "`benches/sweep.py` rows with `platform: tpu` (an earlier chip "
        "record, since removed; not measured on current code)",
        "are evidence.",
        "",
        "| config | params | HLO FLOPs | est bytes | bound | proj ms/step "
        "| proj tok/s | proj MFU |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['label']} | {r['params_m']:.0f}M | {r['hlo_flops']:.2e} "
            f"| {r['bytes_bf16_est']:.2e} | {r['bound']} "
            f"| {r['proj_step_ms']:.0f} | {r['proj_tokens_per_sec']:.0f} "
            f"| {r['proj_mfu']:.2f} |")
    lines += [
        "",
        "Reading: every config is HBM-bound; arithmetic intensity (h) is",
        "the lever. The 124M bench config caps near 0.21 and the 350M",
        "near 0.21–0.23 — **2048h/16L b8 O2 chunk1024 (807M) is the first",
        "config to project OVER the 0.35 bar (0.41)**, and it leads the",
        "on-chip sweep order (with a remat fallback: ~18 GB of",
        "activations+state may OOM the 16 GB chip without it; remat's",
        "recompute FLOPs land it near 0.29, same as the 1.2B config).",
        "",
    ]
    with open(out_md, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {out_md}")


if __name__ == "__main__":
    main()
