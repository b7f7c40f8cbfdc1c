"""Train a GPT causal LM with the fully-compiled TrainStep.

Usage:
  python examples/train_gpt.py                  # tiny config, synthetic data
  python examples/train_gpt.py --hidden 768 --layers 12 --amp O2
  Measured runs: the benchmark (BENCHMARK.json, benchmark/README.md).
"""
import argparse

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import amp
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.optimizer import AdamW
from paddle_tpu.optimizer.lr import CosineAnnealingDecay


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--amp", default="O1", choices=["O0", "O1", "O2"])
    ap.add_argument("--accumulate", type=int, default=1,
                    help="gradient-merge microbatches per step")
    ap.add_argument("--scan_layers", action="store_true",
                    help="lax.scan the decoder block over stacked "
                         "per-layer params: compile time stops growing "
                         "with --layers (same math; docs/performance.md #9)")
    ap.add_argument("--recompute", default="off",
                    choices=["off", "full", "full_attn", "core_attn"],
                    help="activation remat: full saves nothing; core_attn "
                         "saves weight-matmul outputs and recomputes only "
                         "attention scores/softmax (cheaper backward)")
    ap.add_argument("--moment_dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="Adam moment storage dtype; bfloat16 halves "
                         "optimizer-state HBM, update math stays f32")
    args = ap.parse_args()

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=1024, hidden_size=args.hidden,
                    num_layers=args.layers,
                    num_heads=max(1, args.hidden // 64),
                    max_position_embeddings=max(2048, args.seq),
                    use_recompute=args.recompute != "off",
                    recompute_policy=(args.recompute
                                      if args.recompute != "off" else "full"),
                    use_scan_layers=args.scan_layers)
    model = GPTForCausalLM(cfg)
    sched = CosineAnnealingDecay(learning_rate=3e-4, T_max=args.steps)
    opt = AdamW(learning_rate=sched, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype=args.moment_dtype)
    if args.amp == "O2":
        amp.decorate(model, opt, level="O2")

    def loss_fn(x, y):
        if args.amp in ("O1", "O2"):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                return model(x, y)
        return model(x, y)

    step = TrainStep(loss_fn, opt, layers=model,
                     accumulate_steps=args.accumulate)
    rng = np.random.default_rng(0)
    for i in range(args.steps):
        ids = rng.integers(0, cfg.vocab_size, (args.batch, args.seq),
                           dtype=np.int32)
        loss = step(Tensor(ids), Tensor(np.roll(ids, -1, 1)))
        sched.step()
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.4f}  lr {opt.get_lr():.2e}")

    out = model.generate(Tensor(ids[:1, :8]), max_new_tokens=8,
                         do_sample=True, top_p=0.9)
    print("sampled continuation:", out.numpy()[0, 8:].tolist())


if __name__ == "__main__":
    main()
