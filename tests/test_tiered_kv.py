"""Tiered KV cache (ISSUE 15): host-RAM/disk spill with compiled restore.

Unit half: the :class:`~paddle_tpu.serving.tiered.HostKVCache` LRU byte
budget with disk overflow, the crc-checked disk tier (a corrupt file is a
MISS, never garbage), and the :class:`GlobalRadixIndex` residency
accounting. Engine half: spill/restore byte-exactness (int8 payload AND
per-row scale pools), restore-cost admission sizing, disk-corruption
fallback to recompute with token parity, the one-trace restore program
under churn, the cross-replica host hit through a shared store, chaos
``serving_device`` rebuild with a warm host tier (token parity,
``decode_traces`` frozen), and the flag-off build being tier-free.

Engine tests pin tiering per-instance (``kv_tiering=True`` +
an explicit ``tier_store``) rather than flipping the global flag, so the
rest of the suite — which must pass byte-identically with
``FLAGS_serving_kv_tiering=0`` — is never affected by ordering."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    GlobalRadixIndex,
    HostKVCache,
    ReplicaPool,
    RequestState,
    ServingAPI,
)
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.tiered import _payload_bytes

pytestmark = pytest.mark.serving

MAX_LEN = 48
BS = 8


@pytest.fixture(scope="module", autouse=True)
def _invariants_on():
    keep = paddle.get_flags(
        "serving_arena_invariants")["serving_arena_invariants"]
    paddle.set_flags({"serving_arena_invariants": 1})
    yield
    paddle.set_flags({"serving_arena_invariants": keep})


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _prompt(rng, n):
    return rng.integers(0, 1024, (n,), dtype=np.int32)


def _ref(model, prompt, max_new):
    out = model.generate(Tensor(np.asarray(prompt)[None]),
                         max_new_tokens=max_new)
    return np.asarray(out._data)[0]


def _tiered_api(model, store, num_blocks=6, **kw):
    return ServingAPI(model, num_slots=2, kv_block_size=BS,
                      max_model_len=MAX_LEN, num_blocks=num_blocks,
                      prefix_cache=True, kv_tiering=True,
                      tier_store=store, **kw)


def _serve(api, prompt, max_new=4):
    req = api.submit(prompt, max_new_tokens=max_new)
    api.run_until_idle()
    assert req.state == RequestState.FINISHED, req.error
    return req.output_ids()


def _pressure(api, rng, n=2):
    """Cycle distinct prompts through the tiny arena so cold cached
    prefixes get evicted (spilled)."""
    for _ in range(n):
        _serve(api, _prompt(rng, 18))


# ------------------------------------------------------------ store units


def _fake_payload(fill, nbytes=256):
    return [(np.full(nbytes // 4, fill, np.float32),)]


def test_host_lru_byte_budget_drops_without_disk():
    store = HostKVCache(max_bytes=3 * 1024, disk_dir="")
    for i in range(6):
        store.put(bytes([i]) * 4, _fake_payload(i, 1024))
    st = store.stats()
    assert st["host_bytes"] <= 3 * 1024
    assert st["host_entries"] == 3
    # oldest dropped (no disk tier): a miss, recompute
    assert not store.has(bytes([0]) * 4)
    assert store.get(bytes([0]) * 4) == (None, None)
    # newest retained and LRU-touch keeps an old-but-hot entry alive
    assert store.has(bytes([5]) * 4)
    store.get(bytes([3]) * 4)  # touch
    store.put(b"new1" * 1, _fake_payload(9, 1024))
    assert store.has(bytes([3]) * 4)
    assert not store.has(bytes([4]) * 4)


def test_host_budget_overflows_to_disk_and_promotes(tmp_path):
    store = HostKVCache(max_bytes=2 * 1024, disk_dir=str(tmp_path))
    for i in range(4):
        store.put(bytes([i]) * 4, _fake_payload(i, 1024))
    # overflowed entries live on disk, still resident
    assert store.has(bytes([0]) * 4)
    assert store.tier_of(bytes([0]) * 4) == "disk"
    payload, tier = store.get(bytes([0]) * 4)
    assert tier == "disk"
    np.testing.assert_array_equal(payload[0][0],
                                  _fake_payload(0, 1024)[0][0])
    # a disk hit promotes back into the host tier
    assert store.tier_of(bytes([0]) * 4) == "host"


def test_disk_tier_byte_budget_deletes_oldest(tmp_path):
    from paddle_tpu.serving.tiered import DiskTier

    tier = DiskTier(str(tmp_path), max_bytes=3000)
    for i in range(5):
        tier.put(bytes([i]) * 4, _fake_payload(i, 1024))
    st = tier.stats()
    assert st["bytes"] <= 3000 and st["entries"] >= 1
    assert not tier.has(bytes([0]) * 4)  # oldest deleted
    assert tier.has(bytes([4]) * 4)      # newest kept
    # a fresh scan of the directory sees the same bounded population
    again = DiskTier(str(tmp_path), max_bytes=3000)
    assert again.stats()["entries"] == st["entries"]
    assert serving_metrics.stats().get("tier.disk_evictions", 0) > 0


def test_disk_crc_corruption_reads_as_miss(tmp_path):
    store = HostKVCache(max_bytes=1, disk_dir=str(tmp_path))
    store.put(b"key1key1", _fake_payload(7, 1024))
    store.put(b"key2key2", _fake_payload(8, 1024))  # pushes key1 to disk
    assert store.tier_of(b"key1key1") == "disk"
    files = list(tmp_path.glob("*.kv"))
    assert files
    for f in files:
        raw = bytearray(f.read_bytes())
        raw[40] ^= 0xFF  # flip a body byte: crc must catch it
        f.write_bytes(bytes(raw))
    before = serving_metrics.stats().get("tier.disk_corrupt", 0)
    assert store.get(b"key1key1") == (None, None)
    assert serving_metrics.stats().get("tier.disk_corrupt", 0) == before + 1
    # resilience dashboards see the corruption event too
    assert resilience.stats().get("tier.disk_corrupt", 0) >= 1
    # the corrupt file was deleted — no repeat alarms for a dead entry
    assert not store.has(b"key1key1")


def test_global_radix_index_residency():
    idx = GlobalRadixIndex()
    keys = [b"a", b"b", b"c"]
    idx.publish_insert(0, keys)
    idx.publish_insert(1, keys[:1])
    assert idx.resident_blocks(keys, 0) == 3
    assert idx.resident_blocks(keys, 1) == 1
    # chain-prefix semantics: losing the MIDDLE key truncates the match
    idx.publish_evict(0, b"b")
    assert idx.resident_blocks(keys, 0) == 1
    res = idx.residency(keys)
    assert res["device"] == {0: 1, 1: 1}
    idx.publish_reset(1)
    assert idx.resident_blocks(keys, 1) == 0
    assert idx.stats()["keys"] == 2  # a and c (held by replica 0)


# ---------------------------------------------------------- engine: spill


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_spill_restore_byte_exact_including_int8_scales(model, tier,
                                                        tmp_path):
    """An evicted prefix spilled to the host tier (or, with a host budget
    below one entry, through it to the disk tier) restores byte-identical
    — the int8 payload AND the f32 per-row scale pools — and the restore
    program never re-traces."""
    store = (HostKVCache(max_bytes=1 << 30, disk_dir="") if tier == "host"
             else HostKVCache(max_bytes=1, disk_dir=str(tmp_path)))
    api = _tiered_api(model, store, quant_kv=True)
    try:
        rng = np.random.default_rng(1)
        p1 = _prompt(rng, 18)  # 2 full blocks + private tail
        out1 = _serve(api, p1)
        np.testing.assert_array_equal(out1, _ref(model, p1, 4)[:len(out1)])
        eng = api.engine
        nodes = eng.prefix_cache.match(p1)
        assert len(nodes) == 2 and not any(n.spilled for n in nodes)
        before = [eng.arena.read_block(n.block) for n in nodes]
        assert all(len(entry) == 4 for blk in before for entry in blk), \
            "int8 arena entries must carry payload + scale rows"

        _pressure(api, rng)
        assert eng.prefix_cache.spills >= 2
        assert all(n.spilled and n.block == -1 for n in nodes)

        out2 = _serve(api, p1)
        np.testing.assert_array_equal(out2, out1)
        assert not any(n.spilled for n in nodes)
        after = [eng.arena.read_block(n.block) for n in nodes]
        for blk_before, blk_after in zip(before, after):
            for e_before, e_after in zip(blk_before, blk_after):
                assert len(e_before) == len(e_after) == 4
                for a, b in zip(e_before, e_after):
                    assert a.dtype == b.dtype
                    np.testing.assert_array_equal(a, b)
        assert eng.tier.restored_blocks == 2
        assert (eng.tier.disk_hits > 0) == (tier == "disk")
        assert eng.restore_traces == 1
        # churn more spill/restore cycles: ONE compiled restore, ever
        for _ in range(2):
            _pressure(api, rng)
            np.testing.assert_array_equal(_serve(api, p1), out1)
        assert eng.restore_traces == 1
        eng.check_invariants()
    finally:
        api.close()


def test_admit_sizing_counts_restore_cost_not_prefill_cost(model):
    """A matched-but-SPILLED block avoids prefill compute but still needs
    one fresh block (its restore target): admission sizing must keep it
    in the block budget while a device-resident match subtracts out."""
    store = HostKVCache(max_bytes=1 << 30, disk_dir="")
    api = _tiered_api(model, store, num_blocks=8)
    try:
        rng = np.random.default_rng(2)
        p1 = _prompt(rng, 18)
        _serve(api, p1)
        eng = api.engine
        resident_need, _ = eng.admit_sizing(18, 4, prompt=p1)
        # 3 blocks worst case, 2 resident matched -> reserve only 1
        assert resident_need == 1
        # spill the prefix: the same admission now budgets 3 (2 restore
        # targets + 1 private) — restore cost, not free attachment
        eng.prefix_cache.evict(2)
        assert eng.prefix_cache.spilled_nodes() == 2
        spilled_need, _ = eng.admit_sizing(18, 4, prompt=p1)
        assert spilled_need == 3
        # and the restored admission still avoids the prefill COMPUTE
        sm0 = serving_metrics.stats()
        _serve(api, p1)
        sm1 = serving_metrics.stats()
        avoided = (sm1.get("tokens.prefill_avoided", 0)
                   - sm0.get("tokens.prefill_avoided", 0))
        assert avoided == 16  # both restored blocks' tokens
    finally:
        api.close()


def test_disk_corruption_falls_back_to_recompute(model, tmp_path):
    """A spilled prefix whose disk entry is corrupted is pruned on the
    next walk and the admission recomputes — token output stays correct,
    nothing serves the damaged bytes."""
    # budget below one real entry: every spill lands on disk
    store = HostKVCache(max_bytes=1, disk_dir=str(tmp_path))
    api = _tiered_api(model, store)
    try:
        rng = np.random.default_rng(3)
        p1 = _prompt(rng, 18)
        out1 = _serve(api, p1)
        _pressure(api, rng)
        assert api.engine.prefix_cache.spilled_nodes() >= 2
        for f in tmp_path.glob("*.kv"):
            raw = bytearray(f.read_bytes())
            raw[len(raw) // 2] ^= 0xFF
            f.write_bytes(bytes(raw))
        before = api.engine.tier.misses
        out2 = _serve(api, p1)
        np.testing.assert_array_equal(out2, out1)
        assert api.engine.tier.misses > before  # lost entry, recomputed
        assert api.engine.tier.restored_blocks == 0
        api.engine.check_invariants()
    finally:
        api.close()


def test_flag_off_is_tier_free(model):
    """The default build (FLAGS_serving_kv_tiering=0) carries no tier:
    no store attached, no restore program ever built, eviction discards
    (PR 14 behavior), and outputs match the explicit kv_tiering=False
    build token-for-token."""
    rng = np.random.default_rng(4)
    p1 = _prompt(rng, 18)
    outs = []
    for kw in ({}, {"kv_tiering": False}):
        api = ServingAPI(model, num_slots=2, kv_block_size=BS,
                         max_model_len=MAX_LEN, num_blocks=6,
                         prefix_cache=True, **kw)
        try:
            eng = api.engine
            assert eng.tier is None
            assert eng.prefix_cache.tier is None
            out1 = _serve(api, p1)
            _pressure(api, np.random.default_rng(5))
            # eviction DISCARDED: no spilled nodes, nothing restorable
            assert eng.prefix_cache.spilled_nodes() == 0
            outs.append(np.concatenate([out1, _serve(api, p1)]))
            assert eng._restore_jit is None and eng.restore_traces == 0
            assert "tier.spilled_blocks" not in eng.stats()
        finally:
            api.close()
    np.testing.assert_array_equal(outs[0], outs[1])


# ------------------------------------------------------------ gateway


def test_cross_replica_host_hit_through_gateway(model):
    """A prefix prefilled on replica A is a host-tier hit on replica B:
    both engines attach to ONE HostKVCache, B's walk materializes the
    shared chunk keys and restores them — token-identical, and the
    GlobalRadixIndex reports true per-replica device residency."""
    store = HostKVCache(max_bytes=1 << 30, disk_dir="")
    pool = ReplicaPool(model, replicas=2, num_slots=2, kv_block_size=BS,
                       max_model_len=MAX_LEN, prefix_cache=True,
                       kv_tiering=True, tier_store=store,
                       affinity_slack=2)
    try:
        rng = np.random.default_rng(6)
        sysp = _prompt(rng, 16)
        p1 = np.concatenate([sysp, _prompt(rng, 4)])
        rr = pool.submit(p1, max_new_tokens=4)
        pool.run_until_idle()
        out1 = rr.output_ids()
        cache0 = pool._replicas[0].api.engine.prefix_cache
        keys = cache0.chunk_keys(p1)
        # replicas published their deltas: residency is per-replica truth
        assert pool.index.resident_blocks(keys, 0) == 2
        assert pool.index.resident_blocks(keys, 1) == 0
        res = pool.index.residency(keys,
                                   tier=pool._replicas[0].api.engine.tier)
        assert res["device"] == {0: 2} and res["host"] == 2
        # drive replica B directly: its tree has never seen the prompt,
        # but the shared host tier has — restore, not re-prefill
        rep_b = pool._replicas[1]
        req_b = rep_b.api.submit(p1, max_new_tokens=4)
        while rep_b.api.scheduler.has_work():
            rep_b.api.scheduler.step()
        np.testing.assert_array_equal(req_b.output_ids(), out1)
        eng_b = rep_b.api.engine
        assert eng_b.tier.host_hits == 2
        assert eng_b.tier.restored_blocks == 2
        assert eng_b.prefix_cache.hits == 1
        # B now serves from device too — the index shows both replicas
        assert pool.index.resident_blocks(keys, 1) == 2
        assert "tier" in pool.stats()
    finally:
        pool.close()


def test_gateway_affinity_consults_index(model):
    """Routing warmth comes from the shared index, not tree probes: a
    warm-on-replica-1 prompt wins the affinity override within slack."""
    pool = ReplicaPool(model, replicas=2, num_slots=2, kv_block_size=BS,
                       max_model_len=MAX_LEN, prefix_cache=True,
                       affinity_slack=2)
    try:
        rng = np.random.default_rng(7)
        sysp = _prompt(rng, 16)
        # seed replica 1's cache directly (replica 0 stays cold)
        rep1 = pool._replicas[1]
        req = rep1.api.submit(np.concatenate([sysp, _prompt(rng, 3)]),
                              max_new_tokens=2)
        while rep1.api.scheduler.has_work():
            rep1.api.scheduler.step()
        assert req.state == RequestState.FINISHED
        before = serving_metrics.stats().get("gateway.affinity_routes", 0)
        rr = pool.submit(np.concatenate([sysp, _prompt(rng, 3)]),
                         max_new_tokens=2)
        pool.run_until_idle()
        assert rr.state == RequestState.FINISHED
        assert (serving_metrics.stats().get("gateway.affinity_routes", 0)
                == before + 1)
        assert rr._replica_idx == 1  # the index steered it warm
    finally:
        pool.close()


# --------------------------------------------------------------- chaos


@pytest.mark.chaos
def test_chaos_rebuild_replays_warm_from_host_tier(model):
    """ISSUE 15 (c): a ``serving_device`` fault mid-decode rebuilds the
    arena, but the host tier is off-device and SURVIVES — the replay's
    admissions restore their prefix blocks from it instead of
    re-prefilling. Token-for-token parity, ``decode_traces`` frozen, and
    the restore program warm from before the crash."""
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    store = HostKVCache(max_bytes=1 << 30, disk_dir="")
    api = _tiered_api(model, store, num_blocks=8)
    try:
        rng = np.random.default_rng(8)
        shared = _prompt(rng, 16)  # 2 shared full blocks
        prompts = [np.concatenate([shared, _prompt(rng, n)])
                   for n in (2, 4)]
        # reference pass (also warms every program incl. one restore)
        reqs = [api.submit(p, max_new_tokens=6) for p in prompts]
        api.run_until_idle()
        refs = [r.output_ids() for r in reqs]
        _pressure(api, rng, n=4)  # spill, then restore: warm program
        assert api.engine.prefix_cache.spills > 0
        r = api.submit(prompts[0], max_new_tokens=6)
        api.run_until_idle()
        np.testing.assert_array_equal(r.output_ids(), refs[0])
        assert api.engine.restore_traces == 1

        d0 = api.engine.decode_traces
        restored0 = api.engine.tier.restored_blocks
        reqs2 = [api.submit(p, max_new_tokens=6) for p in prompts]
        for _ in range(2):
            api._pump_once()
        assert all(r2.state == RequestState.RUNNING for r2 in reqs2)
        resilience.inject_fault("serving_device", times=1)
        api.run_until_idle()
        for ref, r2 in zip(refs, reqs2):
            assert r2.state == RequestState.FINISHED
            np.testing.assert_array_equal(ref, r2.output_ids())
        assert api.supervisor.rebuild_count == 1
        assert api.engine.decode_traces == d0     # replay: no recompiles
        assert api.engine.restore_traces == 1     # restore program reused
        # warm-cache replay: the rebuilt (empty) tree pulled the crashed
        # arena's prefixes back from the surviving host tier
        assert api.engine.tier.restored_blocks > restored0
        api.engine.check_invariants()
        a = api.engine.arena.stats()
        assert a["blocks_reserved"] == 0
        assert a["blocks_in_use"] == a["blocks_cached"]
    finally:
        resilience.clear_faults()
        api.close()
        paddle.set_flags({"fault_injection": keep})


def test_tier_view_counters_and_entry_bytes(model):
    """The per-engine TierView counters EnginePredictor.close() reports
    match the store's ground truth (spilled bytes only counted when the
    write-through copy was already gone)."""
    store = HostKVCache(max_bytes=1 << 30, disk_dir="")
    api = _tiered_api(model, store)
    try:
        rng = np.random.default_rng(9)
        p1 = _prompt(rng, 18)
        _serve(api, p1)
        st = store.stats()
        # write-through: both full blocks host-resident while still on
        # device; per-entry bytes match the arena's row shapes
        assert st["host_entries"] == 2
        node = api.engine.prefix_cache.match(p1)[0]
        payload = api.engine.arena.read_block(node.block)
        assert st["host_bytes"] == 2 * _payload_bytes(payload)
        _pressure(api, rng)
        view = api.engine.tier
        assert view.spilled_blocks >= 2
        assert view.spilled_bytes == 0  # write-through made spills free
        _serve(api, p1)
        assert view.restored_blocks == 2
        assert view.restored_bytes == 2 * _payload_bytes(payload)
        assert view.stats()["tier.host_hits"] == view.host_hits
    finally:
        api.close()
