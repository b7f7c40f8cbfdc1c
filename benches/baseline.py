"""BASELINE.md measurement harness — one config per reference benchmark row.

Usage: python benches/baseline.py [config ...]   (default: all)
  lenet     — MNIST LeNet, compiled TrainStep          (BASELINE row 1)
  resnet50  — ResNet-50 + AMP O2, synthetic ImageNet   (row 2)
  ernie     — ERNIE-base MLM pretraining step           (row 3, single chip;
              DP scaling is compiler-parallel — see dryrun_multichip)
  gpt-hybrid— GPT hybrid-parallel proxy                 (row 4: the 1.3B
              config needs >1 chip's HBM for optimizer state; measured here
              as the largest single-chip GPT (345M-class) + the 8-way CPU
              dryrun for the hybrid product; pod numbers require a pod)
  widedeep  — Wide&Deep with PS sparse embedding        (row 5)

Each config prints one JSON line {config, samples_per_sec, platform, ...}
and appends to benches/BASELINE_RESULTS.jsonl. Protocol per BASELINE.md:
>=2 warmup, >=8 timed steps, median-free mean (steady state), compile time
excluded and reported separately.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import _common  # noqa: E402,F401 — enables the persistent compile cache


def _timed(step, args, warmup=2, iters=8):
    import jax

    t0 = time.perf_counter()
    loss = step(*args)
    _common.sync(loss)
    compile_s = time.perf_counter() - t0
    for _ in range(warmup - 1):
        loss = step(*args)
    np.asarray(loss._data if hasattr(loss, "_data") else loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(*args)
    np.asarray(loss._data if hasattr(loss, "_data") else loss)
    dt = (time.perf_counter() - t0) / iters
    return dt, compile_s, float(np.asarray(loss._data if hasattr(loss, "_data") else loss))


def _emit(rec):
    from _common import emit

    emit(rec)


def _platform():
    import jax

    return jax.devices()[0].platform


def bench_lenet():
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import LeNet

    on_tpu = _platform() != "cpu"
    batch = 256 if on_tpu else 64
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(lambda x, y: paddle.nn.functional.cross_entropy(
        model(x), y).mean(), opt, layers=model)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((batch, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, batch).astype(np.int64))
    dt, comp, loss = _timed(step, (x, y))
    _emit({"config": "lenet-mnist", "samples_per_sec": round(batch / dt, 1),
           "batch": batch, "step_ms": round(dt * 1e3, 2),
           "compile_s": round(comp, 1), "loss": loss, "platform": _platform()})


def bench_resnet50():
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    on_tpu = _platform() != "cpu"
    batch = int(os.environ.get("BENCH_BATCH", "64" if on_tpu else "4"))
    size = 224 if on_tpu else 64
    model = resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())

    def loss_fn(x, y):
        if on_tpu:  # AMP O2: bf16 compute (BASELINE row 2 contract)
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                return paddle.nn.functional.cross_entropy(model(x), y).mean()
        return paddle.nn.functional.cross_entropy(model(x), y).mean()

    step = TrainStep(loss_fn, opt, layers=model)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((batch, 3, size, size)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 1000, batch).astype(np.int64))
    dt, comp, loss = _timed(step, (x, y))
    _emit({"config": "resnet50-amp", "samples_per_sec": round(batch / dt, 1),
           "batch": batch, "image": size, "step_ms": round(dt * 1e3, 2),
           "compile_s": round(comp, 1), "loss": loss, "platform": _platform()})


def bench_ernie():
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining

    on_tpu = _platform() != "cpu"
    if on_tpu:
        cfg = ErnieConfig()  # base: 12L/768h
        batch, seq = 16, 512
    else:
        from paddle_tpu.models.ernie import ernie_tiny

        cfg = ernie_tiny()
        batch, seq = 4, 64
    model = ErnieForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    from paddle_tpu import amp

    def loss_fn(ids, labels):
        if on_tpu:
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                return model(ids, masked_lm_labels=labels)
        return model(ids, masked_lm_labels=labels)

    step = TrainStep(loss_fn, opt, layers=model)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    dt, comp, loss = _timed(step, (ids, labels))
    _emit({"config": "ernie-base-pretrain", "samples_per_sec": round(batch / dt, 1),
           "tokens_per_sec": round(batch * seq / dt, 1), "batch": batch,
           "seq": seq, "step_ms": round(dt * 1e3, 2),
           "compile_s": round(comp, 1), "loss": loss, "platform": _platform()})


def bench_gpt_hybrid():
    """Row 4 proxy: largest practical single-chip GPT (345M-class). The
    1.3B hybrid product itself is validated by dryrun_multichip (4-D mesh
    with loss parity); pod-scale throughput needs pod hardware."""
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, gpt_tiny

    on_tpu = _platform() != "cpu"
    if on_tpu:
        # scan-over-layers: same math (dropout=0), ~4x faster cold compile
        # at 24L. BASELINE_SCAN=0 restores the unrolled stack.
        scan = os.environ.get("BASELINE_SCAN", "1") == "1"
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                        num_heads=16, max_position_embeddings=2048,
                        use_recompute=True, use_scan_layers=scan)
        batch, seq = 8, 1024
    else:
        cfg = gpt_tiny()
        batch, seq = 2, 64
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(x, y):
        if on_tpu:
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                return model(x, y)
        return model(x, y)

    step = TrainStep(loss_fn, opt, layers=model)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    x = paddle.to_tensor(ids)
    y = paddle.to_tensor(np.roll(ids, -1, axis=1))
    dt, comp, loss = _timed(step, (x, y))
    _emit({"config": "gpt-345m-single-chip", "samples_per_sec": round(batch / dt, 1),
           "tokens_per_sec": round(batch * seq / dt, 1), "batch": batch,
           "seq": seq, "step_ms": round(dt * 1e3, 2),
           "compile_s": round(comp, 1), "loss": loss, "platform": _platform(),
           "scan_layers": bool(cfg.use_scan_layers)})


def bench_widedeep():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import ps
    from paddle_tpu.models.widedeep import WideDeep

    on_tpu = _platform() != "cpu"
    batch = 2048 if on_tpu else 256
    svc = ps.start_local_cluster(dim=16, num_shards=2, rule="adagrad")
    wide = ps.start_local_cluster(dim=1, num_shards=2)
    try:
        model = WideDeep(
            num_fields=26, num_dense=13, hidden_sizes=(400, 400, 400),
            sparse_embedding=ps.PSEmbedding(svc.client(), learning_rate=0.05),
            wide_embedding=ps.PSEmbedding(wide.client(), learning_rate=0.05),
            embedding_dim=16)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())

        # feed through the PS ingestion path (InMemoryDataset: file-list
        # load -> in-RAM shuffle -> collated batches), not raw arrays
        import tempfile

        from paddle_tpu.distributed import InMemoryDataset

        rng = np.random.default_rng(0)
        tmpd = tempfile.mkdtemp(prefix="wd_data_")
        files = []
        rows_per_file = batch * 3
        for fi in range(4):
            lines = []
            for _ in range(rows_per_file):
                label = int(rng.random() > 0.5)
                dense_s = ",".join(f"{v:.4f}" for v in rng.standard_normal(13))
                sparse_s = ",".join(str(int(v))
                                    for v in rng.integers(0, 1 << 40, 26))
                lines.append(f"{label}\t{dense_s}\t{sparse_s}")
            p = os.path.join(tmpd, f"part-{fi}.txt")
            with open(p, "w") as f:
                f.write("\n".join(lines) + "\n")
            files.append(p)
        ds = InMemoryDataset()
        ds.init(batch_size=batch)
        ds.set_filelist(files)
        ds.load_into_memory(is_shuffle=True)

        def step(sparse_b, dense_b, label_b):
            logits = model(paddle.to_tensor(sparse_b),
                           paddle.to_tensor(dense_b))
            loss = model.loss(logits, paddle.to_tensor(label_b))
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        it = iter(ds.epochs(100))
        step(*next(it))  # warm
        step(*next(it))
        t0 = time.perf_counter()
        iters = 8
        for _ in range(iters):
            loss = step(*next(it))
        dt = (time.perf_counter() - t0) / iters
        import shutil

        shutil.rmtree(tmpd, ignore_errors=True)
        rows, nbytes = model.embedding.client.stats()
        _emit({"config": "widedeep-ps", "samples_per_sec": round(batch / dt, 1),
               "batch": batch, "step_ms": round(dt * 1e3, 2),
               "table_rows": rows, "table_mb": round(nbytes / 1e6, 1),
               "loss": float(np.asarray(loss._data)), "platform": _platform()})
    finally:
        svc.stop()
        wide.stop()


CONFIGS = {"lenet": bench_lenet, "resnet50": bench_resnet50,
           "ernie": bench_ernie, "gpt-hybrid": bench_gpt_hybrid,
           "widedeep": bench_widedeep}


def main():
    # PADDLE_TPU_BENCH_PLATFORM=cpu pins the backend BEFORE first device
    # query (rows record the platform they ran on)
    want = os.environ.get("PADDLE_TPU_BENCH_PLATFORM")
    if want:
        import jax

        jax.config.update("jax_platforms", want)
    names = sys.argv[1:] or list(CONFIGS)
    for name in names:
        CONFIGS[name]()


if __name__ == "__main__":
    main()
