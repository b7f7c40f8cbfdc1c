"""Mesh-sharded execution core (ISSUE 14): tensor-parallel serving and
data-parallel training over the ("data", "model") mesh, on the 8 virtual
CPU devices conftest forces.

The contract under test is the one the engine sells on a single chip,
extended to a mesh:

* sharded-vs-single-device GREEDY TOKEN PARITY — decode, prefix-cache
  hits, speculative decode, quantized serving, and LoRA adapters each
  reproduce the no-mesh engine token-for-token (GSPMD resharding may
  reassociate float reductions, so parity is asserted on emitted tokens,
  the serving observable);
* ZERO RECOMPILES under admit/retire churn with the mesh live
  (trace-counter asserted — block tables/positions stay runtime data,
  committed shardings never change between steps);
* the supervisor's rebuild/replay path re-commits the SAME pool
  shardings (``_arena_args`` carry the mesh), so recovery is
  zero-recompile and token-identical on a mesh too;
* a 1-DEVICE mesh is bit-identical to no mesh at all (same programs,
  same tokens) while keying differently (``mesh_axes_key`` joins the
  program keys like quant/donation);
* the acceptance shape: a model whose bf16-scale weights+arena would
  exceed one device's equal share actually serves with every device
  holding strictly less than the logical total (tensor parallelism is
  real, not annotation theater).
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache, resilience
from paddle_tpu.distributed.mesh import get_mesh, serving_mesh
from paddle_tpu.distributed.sharding_util import mesh_axes_key
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    LoraAdapter,
    RequestState,
    SamplingParams,
    ServingAPI,
    ServingConfig,
)

MAX_LEN = 128


def _model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _workload(rng, n=5, max_new=8):
    lens = [8, 12, 20, 7, 16]
    return [(rng.integers(0, 1024, (lens[i % len(lens)],), dtype=np.int32),
             max_new) for i in range(n)]


def _serve(model, workload, submit_kw=None, **cfg_kw):
    cfg = ServingConfig(num_slots=4, kv_block_size=16, max_model_len=MAX_LEN,
                        **cfg_kw)
    api = ServingAPI(model, cfg)
    try:
        kws = submit_kw or [{}] * len(workload)
        reqs = [api.submit(p, max_new_tokens=n, **kw)
                for (p, n), kw in zip(workload, kws)]
        api.run_until_idle()
        assert all(r.state == RequestState.FINISHED for r in reqs)
        outs = [np.asarray(r.output_ids()) for r in reqs]
        stats = api.engine.stats()
        engine = api.engine
    finally:
        api.close()
    return outs, stats, engine


def _device0_bytes(arrays):
    """Bytes the first mesh device actually holds for ``arrays`` (the
    per-chip HBM share the sharding buys)."""
    total = 0
    for a in arrays:
        sh = getattr(a, "addressable_shards", None)
        total += int(sh[0].data.nbytes) if sh else int(a.nbytes)
    return total


def _model_arrays(model):
    params, buffers = model.functional_state()
    return [p._data for p in list(params.values()) + list(buffers.values())]


def _pool_arrays(arena):
    out = []
    for pools in [arena.pools] + [arena.ns_pools(n)
                                  for n in arena.namespaces()]:
        for entry in pools:
            out.extend(entry)
    return out


# --------------------------------------------------------------- parity


def test_tp_decode_token_parity_and_per_chip_share():
    """The headline gate: a (data=2, model=4) mesh engine reproduces the
    single-device engine token-for-token on a mixed workload, while every
    device holds strictly less than the logical weights+arena bytes —
    the config serves even where one device's equal share could not."""
    assert get_mesh() is None  # conftest reset: the reference is mesh-free
    w = _workload(np.random.default_rng(0))
    ref_outs, _, _ = _serve(_model(), w)

    serving_mesh(4, data=2)
    model = _model()
    outs, stats, engine = _serve(model, w)
    assert stats["mesh.key"] == (("data", 2), ("model", 4))
    for a, b in zip(ref_outs, outs):
        np.testing.assert_array_equal(a, b)

    arrays = _model_arrays(model) + _pool_arrays(engine.arena)
    logical = sum(int(a.nbytes) for a in arrays)
    per_chip = _device0_bytes(arrays)
    # tensor parallelism is real: the big arrays (attention/MLP weights,
    # vocab embedding, KV pools) are 4-way sharded; only the small
    # replicated remainder (LayerNorms, positions, biases) keeps this
    # above logical/4
    assert per_chip <= 0.55 * logical, (per_chip, logical)
    kp = engine.arena.pools[0][0]
    assert kp.addressable_shards[0].data.shape[2] \
        == kp.shape[2] // 4  # heads dim model-sharded


def test_zero_recompile_churn_on_live_mesh():
    """Admit/retire churn on a live mesh is runtime data only: ONE decode
    trace, one prefill trace per bucket, arena clean at the end."""
    serving_mesh(4, data=2)
    rng = np.random.default_rng(1)
    w = _workload(rng, n=8, max_new=6)
    outs, stats, engine = _serve(_model(), w)
    assert stats["decode_traces"] == 1
    assert all(v == 1 for v in stats["prefill_traces"].values())
    assert stats["arena.blocks_in_use"] == 0
    assert stats["arena.blocks_reserved"] == 0
    assert stats["arena.mesh"] == (("data", 2), ("model", 4))


def test_carried_slot_state_keeps_the_placement_it_is_uploaded_in():
    """The decode step hands the next step's slot state back (ISSUE 25).
    On a mesh an upload and a carried state must be ONE call signature:
    replicated both ways, one decode trace, and no backend compile after
    the first step whichever way the state arrived."""
    from paddle_tpu.serving import ServingEngine

    serving_mesh(4, data=2)
    engine = ServingEngine(_model(), ServingConfig(
        num_slots=4, kv_block_size=16, max_model_len=MAX_LEN))
    rng = np.random.default_rng(7)
    engine.admit(rng.integers(0, 1024, (9,), dtype=np.int32), 20)
    engine.decode_step()  # state uploaded; what comes back is carried
    placed = engine._state_dev.sharding
    assert placed.is_fully_replicated
    assert len(placed.device_set) == 8
    compiled = compile_cache.stats().get("compile.backend", 0)
    engine.decode_step()  # carried
    assert engine._state_dev.sharding == placed
    engine.admit(rng.integers(0, 1024, (9,), dtype=np.int32), 20)
    assert engine._state_dev is None
    engine.decode_step()  # uploaded again, beside a carried lane
    engine.decode_step()
    assert engine._state_dev.sharding == placed
    np.testing.assert_array_equal(
        np.asarray(engine._state_dev),
        engine._pack_slot_state(engine._active))
    assert engine.decode_traces == 1
    assert compile_cache.stats().get("compile.backend", 0) == compiled


def test_prefix_hit_parity_on_mesh():
    """Radix-cache hits attach host-side block ids — layout-agnostic by
    construction: hit-path tokens equal the no-mesh hit-path tokens."""
    rng = np.random.default_rng(2)
    sys_p = rng.integers(0, 1024, (32,), dtype=np.int32)
    w = [(np.concatenate([sys_p,
                          rng.integers(0, 1024, (6,), dtype=np.int32)]), 8)
         for _ in range(4)]
    ref_outs, ref_stats, _ = _serve(_model(), w, prefix_cache=True)
    assert ref_stats["prefix.hits"] >= 3

    serving_mesh(4, data=2)
    outs, stats, _ = _serve(_model(), w, prefix_cache=True)
    assert stats["prefix.hits"] >= 3
    for a, b in zip(ref_outs, outs):
        np.testing.assert_array_equal(a, b)


def test_spec_decode_parity_on_mesh():
    """Lockstep speculative decode (fused multi-token sub-steps) over
    sharded pools: tokens equal the plain no-mesh engine's."""
    w = _workload(np.random.default_rng(3), n=4)
    ref_outs, _, _ = _serve(_model(), w)

    serving_mesh(4, data=2)
    outs, stats, _ = _serve(_model(), w, spec_k=2)
    assert stats["spec.mode"] == "lockstep"
    assert stats["spec.emitted"] > 0
    for a, b in zip(ref_outs, outs):
        np.testing.assert_array_equal(a, b)


def test_quant_serving_parity_on_mesh():
    """int8 weight-only decode + int8 KV arena on the mesh: tokens equal
    the quantized no-mesh engine's; the int8 payload pools shard over the
    model axis while the per-block scale pools replicate (the 4-tuple
    placement rule of sharding_util.shard_kv_entry)."""
    w = _workload(np.random.default_rng(4), n=4)
    ref_outs, _, _ = _serve(_model(), w, quant_weights=True, quant_kv=True)

    serving_mesh(4, data=2)
    outs, stats, engine = _serve(_model(), w, quant_weights=True,
                                 quant_kv=True)
    assert stats["quant.weights"] == 1 and stats["quant.kv"] == 1
    for a, b in zip(ref_outs, outs):
        np.testing.assert_array_equal(a, b)
    entry = engine.arena.pools[0]
    assert len(entry) == 4
    assert entry[0].addressable_shards[0].data.shape[2] \
        == entry[0].shape[2] // 4        # int8 payload: heads sharded
    assert entry[2].addressable_shards[0].data.shape \
        == entry[2].shape                # scale pool: replicated


def test_lora_adapter_parity_on_mesh():
    """Per-slot LoRA over sharded base weights: the adapter pools
    replicate, the base matmuls stay model-sharded, tokens match the
    no-mesh adapter engine (adapter-0 lanes stay base-identical)."""
    w = _workload(np.random.default_rng(5), n=3)

    def run(model):
        cfg = ServingConfig(num_slots=4, kv_block_size=16,
                            max_model_len=MAX_LEN, lora_rank=4)
        api = ServingAPI(model, cfg)
        try:
            aid = api.register_adapter(
                LoraAdapter.random(model.cfg, rank=4, seed=7, scale=0.25,
                                   name="m"))
            kws = [{"adapter": aid}, {}, {"adapter": aid}]
            reqs = [api.submit(p, max_new_tokens=n, **kw)
                    for (p, n), kw in zip(w, kws)]
            api.run_until_idle()
            assert all(r.state == RequestState.FINISHED for r in reqs)
            return [np.asarray(r.output_ids()) for r in reqs]
        finally:
            api.close()

    ref = run(_model())
    serving_mesh(4, data=2)
    outs = run(_model())
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)


def test_sampling_parity_on_mesh():
    """Seeded per-slot sampling is positional-PRNG runtime data — the
    sampled stream is reproduced exactly on the mesh."""
    w = _workload(np.random.default_rng(6), n=3)
    sp = SamplingParams(temperature=0.8, top_k=40, seed=123)
    kws = [{"sampling": sp}, {}, {"sampling": sp}]
    ref, _, _ = _serve(_model(), w, submit_kw=kws)
    serving_mesh(4, data=2)
    outs, _, _ = _serve(_model(), w, submit_kw=kws)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------- recovery / identity


def test_supervisor_rebuild_replay_on_mesh():
    """A transient device failure mid-run on the mesh: the supervisor
    rebuilds (same shapes AND same committed shardings via _arena_args)
    and replays every journal — tokens identical to the undisturbed
    no-mesh run, pools sharded again afterwards."""
    w = _workload(np.random.default_rng(8), n=3)
    ref, _, _ = _serve(_model(), w)

    serving_mesh(4, data=2)
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    try:
        cfg = ServingConfig(num_slots=4, kv_block_size=16,
                            max_model_len=MAX_LEN)
        api = ServingAPI(_model(), cfg)
        try:
            resilience.inject_fault("serving_device", times=1, after=6)
            reqs = [api.submit(p, max_new_tokens=n) for p, n in w]
            api.run_until_idle()
            assert all(r.state == RequestState.FINISHED for r in reqs)
            assert api.supervisor.rebuild_count == 1
            assert api.supervisor.replay_count >= 1
            assert api.engine.decode_traces == 1  # rebuild never recompiles
            outs = [np.asarray(r.output_ids()) for r in reqs]
            kp = api.engine.arena.pools[0][0]
            assert kp.addressable_shards[0].data.shape[2] \
                == kp.shape[2] // 4
        finally:
            api.close()
    finally:
        resilience.clear_faults()
        paddle.set_flags({"fault_injection": keep})
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)


def test_one_device_mesh_bitwise_identity():
    """A 1-device mesh runs the same ops on the same chip: tokens are
    identical to the flag-off (no-mesh) engine, while the mesh key still
    distinguishes the builds (committed shardings differ)."""
    w = _workload(np.random.default_rng(9), n=4)
    ref, ref_stats, _ = _serve(_model(), w)
    assert ref_stats["mesh.key"] is None

    serving_mesh(1, data=1)
    outs, stats, _ = _serve(_model(), w)
    assert stats["mesh.key"] == (("data", 1),)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)


def test_generate_runner_cache_is_mesh_keyed():
    """generate()'s memoized runner keys on the mesh fingerprint like the
    quant/donation tags: installing a mesh between calls rebuilds instead
    of replaying a runner traced against the old placement."""
    model = _model()
    ids = paddle.to_tensor(
        np.random.default_rng(10).integers(0, 1024, (1, 8)).astype(np.int32))
    before = compile_cache.stats().get("decode.builds", 0)
    model.generate(ids, max_new_tokens=4)
    model.generate(ids, max_new_tokens=4)  # warm: same key, cache hit
    mid = compile_cache.stats()
    assert mid.get("decode.builds", 0) == before + 1
    assert mid.get("decode.cache_hits", 0) >= 1

    serving_mesh(1, data=1)
    model.generate(ids, max_new_tokens=4)
    assert compile_cache.stats().get("decode.builds", 0) == before + 2
    assert mesh_axes_key() == (("data", 1),)


def test_explicit_config_mesh_threads_everywhere():
    """An explicit ServingConfig.mesh (equal to the installed mesh the
    model was built under) reaches every engine-placed buffer: int8
    weight payloads+scales, KV pools, adapter pools — no piece silently
    follows a different global."""
    mesh = serving_mesh(4, data=2)
    model = _model()
    cfg = ServingConfig(num_slots=4, kv_block_size=16, max_model_len=MAX_LEN,
                        quant_weights=True, quant_kv=True, lora_rank=4,
                        mesh=mesh)
    api = ServingAPI(model, cfg)
    try:
        eng = api.engine
        assert eng.mesh is mesh
        qkv = model.gpt.layers[0].attn.qkv
        assert qkv.weight._data.sharding.spec == (None, "model")
        assert qkv.weight_scale._data.sharding.spec[-1] == "model"
        a_pool, _ = eng.lora.device_pools()[0]
        assert a_pool.sharding.mesh.devices.size == 8  # replicated on-mesh
        p = api.submit(np.arange(8, dtype=np.int32) + 1, max_new_tokens=4)
        api.run_until_idle()
        assert p.state == RequestState.FINISHED
    finally:
        api.close()


def test_paged_kernel_serves_on_data_only_mesh():
    """ISSUE 16 closed the kernels-on-mesh gap: a data-only mesh (no
    model axis to split heads over) serves the KERNEL with every operand
    replicated inside the shard_map wrapper — no warning, no gather
    fallback, token parity with the mesh-gather engine. (The full
    model-sharded route is tests/test_paged_kernel.py's mesh family.)"""
    serving_mesh(1, data=2)  # drops the size-1 model axis: ("data", 2)
    w = _workload(np.random.default_rng(11), n=2)
    model = _model()
    off, _, _ = _serve(model, w, paged_kernel=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        outs, stats, _ = _serve(model, w, paged_kernel=True)
    assert stats["kernel.paged"] == 1
    assert stats["kernel.mesh"] == "kernel@data2"
    assert stats["mesh.key"] == (("data", 2),)
    for a, b in zip(off, outs):
        np.testing.assert_array_equal(a, b)


# -------------------------------------------------------------- training


def test_trainstep_data_parallel_on_mesh():
    """TrainStep over the mesh: batch on the data axis, weights on the
    model axis — losses track the single-device run (float reassociation
    across shards bounds this to close, not bitwise) and decrease."""
    from paddle_tpu.jit import TrainStep

    def run(mesh_on):
        if mesh_on:
            serving_mesh(4, data=2)
        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny())
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        step = TrainStep(lambda x, y: model(x, y), opt, layers=model)
        rng = np.random.default_rng(0)
        x = rng.integers(0, 1024, (8, 64)).astype(np.int32)
        y = np.roll(x, -1, 1).astype(np.int32)
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        if mesh_on:
            from paddle_tpu.distributed import shard_batch

            xt, yt = shard_batch(xt), shard_batch(yt)
        return [float(step(xt, yt).numpy()) for _ in range(4)]

    ref = run(False)
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod._global_mesh = None  # fresh reference run done; now the mesh
    losses = run(True)
    assert losses[-1] < losses[0]
    np.testing.assert_allclose(losses, ref, rtol=2e-3, atol=2e-4)
