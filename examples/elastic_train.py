"""Preemption-tolerant training: elastic world resize + async checkpoints.

The worker (default mode) trains a tiny GPT with a compiled TrainStep,
checkpointing every step through the ASYNC TrainCheckpointer (the save
overlaps the next steps; a kill mid-save never exposes a torn checkpoint).
On restart it resumes from the latest complete step — at WHATEVER world
size the launcher gives it (reshard-on-load makes a topology change safe).

Demo mode spawns the elastic launcher on this same script with two ranks
and preempts rank 1 mid-run (SIGKILL, the TPU-pod preemption model); the
launcher rescales the world 2 -> 1 within the --np range and training
finishes on the survivor:

  python examples/elastic_train.py --demo            # full scale-in cycle
  python -m paddle_tpu.distributed.launch \
      --nproc_per_node 2 --elastic_level 2 --np 1:2 \
      examples/elastic_train.py --steps 12            # the same, manually

Parity targets: ref:python/paddle/distributed/fleet/elastic/manager.py
(np-range rescale) + ref:python/paddle/fluid/incubate/checkpoint/
auto_checkpoint.py (auto-resume).
"""
import argparse
import os
import signal
import sys

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.checkpoint import TrainCheckpointer
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.optimizer import AdamW


def worker(args):
    # pin the backend IN-PROCESS: launcher-spawned workers bypass any outer
    # wrapper, and one chip cannot host two ranks (a chip belongs to one
    # process). ELASTIC_EXAMPLE_PLATFORM overrides for real pods.
    import jax

    jax.config.update("jax_platforms",
                      os.environ.get("ELASTIC_EXAMPLE_PLATFORM", "cpu"))

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))

    paddle.seed(42)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=2, max_position_embeddings=128)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(lambda x, y: model(x, y), opt, layers=model)

    ck = TrainCheckpointer(args.ckpt_dir)  # async_save=True by default
    start = 0
    # restore() scans newest-first and skips a torn/corrupt newest step
    # (manifest verification, docs/robustness.md); last_restored_step says
    # which step actually won
    restored = ck.restore()
    latest = ck.last_restored_step if restored is not None else None
    if restored is not None:
        model.set_state_dict(restored["model"])
        opt.set_state_dict(restored["opt"])
        start = latest + 1
        print(f"[rank {rank}/{world}] resumed from step {latest}",
              flush=True)
    # graceful preemption (SIGTERM, the TPU eviction notice): finish the
    # step, write one final synchronous checkpoint + resume marker, exit 0.
    # The --preempt_at SIGKILL below stays as the HARD-preemption model —
    # that path is covered by the async commit protocol instead.
    from paddle_tpu.core import resilience

    guard = resilience.PreemptionGuard()
    if start >= args.steps:
        print(f"nothing to do: {args.ckpt_dir} is already at step "
              f"{latest}; raise --steps or point --ckpt_dir elsewhere",
              flush=True)
        ck.close()
        return
    first_life = latest is None

    # each rank trains its shard of a fixed synthetic batch; world-size
    # changes simply re-shard the same data
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (8, 64), dtype=np.int32)
    shard = ids[rank::world]
    x = paddle.to_tensor(shard)
    y = paddle.to_tensor(np.roll(shard, -1, axis=1))

    for s in range(start, args.steps):
        loss = step(x, y)
        if rank == 0:
            # async: returns immediately, the write overlaps the next steps
            ck.save(s, {"model": model.state_dict(),
                        "opt": opt.state_dict()})
        print(f"[rank {rank}/{world}] step {s} loss "
              f"{float(np.asarray(loss._data)):.4f}", flush=True)
        if rank == 0:
            guard.maybe_finalize(
                s, ck, lambda: {"model": model.state_dict(),
                                "opt": opt.state_dict()})
        elif guard.requested():
            sys.exit(0)  # non-primary ranks just leave at the boundary
        if (args.preempt_at >= 0 and s == args.preempt_at and first_life
                and world > 1 and rank == world - 1):
            print(f"[rank {rank}] simulating preemption", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
    if rank == 0:
        ck.wait_until_finished()  # settle the last async save before exit
        print(f"done: {args.steps} steps, final world {world}", flush=True)
    ck.close()


def demo(args):
    import subprocess
    import tempfile

    preempt_at = args.preempt_at if args.preempt_at >= 0 else 4
    if args.steps <= preempt_at + 1:
        raise SystemExit(f"--steps must exceed --preempt_at + 1 "
                         f"({preempt_at + 1}) for the demo to demonstrate "
                         "a preemption AND a resumed finish")
    work = tempfile.mkdtemp(prefix="elastic_demo_")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--elastic_level", "2", "--np", "1:2",
           "--log_dir", os.path.join(work, "logs"),
           os.path.abspath(__file__),
           "--steps", str(args.steps), "--preempt_at", str(preempt_at),
           "--ckpt_dir", os.path.join(work, "ckpt")]
    print("demo:", " ".join(cmd), flush=True)
    r = subprocess.run(cmd, timeout=600, capture_output=True, text=True)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise SystemExit(f"demo launcher failed: rc={r.returncode}")
    if "rescaling world 2 -> 1" not in r.stderr:
        sys.stderr.write(r.stderr)
        raise SystemExit("demo did not rescale — no 'rescaling world' "
                         "marker in the launcher log")
    print(f"elastic demo OK: preempted at step {preempt_at}, "
          "rescaled 2 -> 1, finished", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--preempt_at", type=int, default=-1)
    ap.add_argument("--ckpt_dir", default="/tmp/elastic_train_ckpt")
    ap.add_argument("--demo", action="store_true",
                    help="spawn the 2-rank elastic launcher and preempt one")
    args = ap.parse_args()
    if args.demo:
        demo(args)
    else:
        worker(args)


if __name__ == "__main__":
    main()
