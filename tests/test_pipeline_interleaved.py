"""Interleaved virtual-stage pipeline schedule
(ref:python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:514
PipelineParallelWithInterleave)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import rng as prng
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.mesh import init_hybrid_mesh
from paddle_tpu.distributed.pipeline import (pipeline_apply,
                                             pipeline_apply_interleaved,
                                             pipeline_tick_cost,
                                             stack_chunk_params,
                                             stack_stage_params)


def test_interleaved_forward_matches_sequential():
    mesh = init_hybrid_mesh(pp=4, dp=2)
    S, V = 4, 2
    rng = np.random.default_rng(0)
    Ws = [{"w": jnp.asarray(rng.standard_normal((16, 16), np.float32) * 0.3)}
          for _ in range(S * V)]
    x = jnp.asarray(rng.standard_normal((12, 16), np.float32))

    def chunk_fn(p, h, v):
        return jnp.tanh(h @ p["w"])

    ref = np.asarray(x)
    for wj in Ws:
        ref = np.tanh(ref @ np.asarray(wj["w"]))

    cp = stack_chunk_params(Ws, S, V, mesh=mesh)
    out = pipeline_apply_interleaved(chunk_fn, cp, x, num_microbatches=6,
                                     num_chunks=V, mesh=mesh)
    assert np.allclose(np.asarray(out), ref, atol=1e-5)

    # microbatch count NOT a multiple of S exercises group padding
    out2 = pipeline_apply_interleaved(chunk_fn, cp, x, num_microbatches=3,
                                      num_chunks=V, mesh=mesh)
    assert np.allclose(np.asarray(out2), ref, atol=1e-5)


def test_interleaved_gradients_match_sequential():
    mesh = init_hybrid_mesh(pp=4)
    S, V = 4, 2
    rng = np.random.default_rng(1)
    Ws = [jnp.asarray(rng.standard_normal((8, 8), np.float32) * 0.3)
          for _ in range(S * V)]
    x = jnp.asarray(rng.standard_normal((8, 8), np.float32))

    def seq_loss(ws):
        h = x
        for w in ws:
            h = jnp.tanh(h @ w)
        return (h ** 2).mean()

    ref_grads = jax.grad(seq_loss)(Ws)

    def pipe_loss(ws):
        cp = stack_chunk_params([{"w": w} for w in ws], S, V, mesh=mesh)
        out = pipeline_apply_interleaved(
            lambda p, h, v: jnp.tanh(h @ p["w"]), cp, x,
            num_microbatches=4, num_chunks=V, mesh=mesh, remat=True)
        return (out ** 2).mean()

    got = jax.grad(pipe_loss)(Ws)
    for g, r in zip(got, ref_grads):
        assert np.allclose(np.asarray(g), np.asarray(r), atol=1e-4)


def test_interleaved_bubble_smaller_than_gpipe():
    # equal microbatches: the virtual-stage schedule has strictly fewer
    # idle stage-units whenever S > 1 and V > 1
    for S in (2, 4, 8):
        for M in (S, 2 * S, 4 * S):
            gpipe = pipeline_tick_cost(M, S, 1)
            for V in (2, 4):
                inter = pipeline_tick_cost(M, S, V)
                assert inter < gpipe, (S, M, V)
                # closed form: bubble (S-1)/V vs (S-1) stage-units
                assert inter == pytest.approx(M + (S - 1) / V)


def test_gpt_pipe_interleaved_loss_parity():
    """2 training steps of the interleaved GPT pipe match a single-device
    run from identical init (the dryrun's parity bar)."""
    from paddle_tpu.distributed.fleet.meta_parallel import PipelineParallel
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe
    from paddle_tpu.optimizer import AdamW

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 1024, (8, 32), dtype=np.int32)
    lbl = np.roll(ids, -1, axis=1)
    devices = jax.devices()[:4]

    def run(n_dev, stages, virtual):
        prng.seed(777)
        init_hybrid_mesh(pp=stages if n_dev > 1 else 1,
                         dp=1, devices=devices[:n_dev])
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                        num_heads=4, max_position_embeddings=256)
        m = GPTForCausalLMPipe(cfg, num_stages=stages,
                               num_microbatches=2,
                               num_virtual_pipeline_stages=virtual)
        w = PipelineParallel(m)
        o = AdamW(learning_rate=1e-3, parameters=m.parameters())
        out = []
        for _ in range(2):
            l = w.train_batch((Tensor(ids), Tensor(lbl)), o)
            out.append(float(np.asarray(l._data)))
        return out

    ref = run(1, 1, None)
    inter = run(4, 2, 2)  # 2 devices' worth of stages x 2 virtual chunks
    assert np.allclose(ref, inter, rtol=5e-3, atol=5e-3), (ref, inter)


def test_interleaved_degenerate_paths():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((8, 8), np.float32))

    def chunk_fn(p, h, v):
        return jnp.tanh(h @ p["w"])

    # S == 1 (no pipe axis): all chunks run sequentially per microbatch
    mesh1 = init_hybrid_mesh(dp=8)
    V = 3
    Ws = [{"w": jnp.asarray(rng.standard_normal((8, 8), np.float32) * 0.3)}
          for _ in range(V)]
    ref = np.asarray(x)
    for wj in Ws:
        ref = np.tanh(ref @ np.asarray(wj["w"]))
    cp = stack_chunk_params(Ws, 1, V, mesh=mesh1)
    out = pipeline_apply_interleaved(chunk_fn, cp, x, num_microbatches=2,
                                     num_chunks=V, mesh=mesh1)
    assert np.allclose(np.asarray(out), ref, atol=1e-5)

    # V == 1 on a real pipe mesh: falls back to the GPipe schedule
    mesh2 = init_hybrid_mesh(pp=4, dp=2)
    Ws4 = [{"w": jnp.asarray(rng.standard_normal((8, 8), np.float32) * 0.3)}
           for _ in range(4)]
    ref2 = np.asarray(x)
    for wj in Ws4:
        ref2 = np.tanh(ref2 @ np.asarray(wj["w"]))
    cp2 = stack_chunk_params(Ws4, 4, 1, mesh=mesh2)
    out2 = pipeline_apply_interleaved(chunk_fn, cp2, x, num_microbatches=4,
                                      num_chunks=1, mesh=mesh2)
    assert np.allclose(np.asarray(out2), ref2, atol=1e-5)
