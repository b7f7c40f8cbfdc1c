"""Pallas paged-attention serving kernels (ISSUE 13): interpreter-mode
parity of :mod:`paddle_tpu.ops.paged_attention` against the XLA gather
baseline (``cache_views.gather_ctx`` + ``serving_seam.masked_attention``),
the launch shapes the kernels derive for themselves, and the engine
integration behind ``ServingConfig.paged_kernel``.

Parity policy (docs/performance.md "Paged attention kernels"): the
kernels' online softmax associates differently from the gather path's
full-width softmax, so raw attention output is compared under a small
f32 tolerance — while greedy DECODED TOKENS must match exactly, which the
engine-level tests assert across cache hits, chunked prefill, int8
arenas and speculative verify. Everything here runs the real kernel
bodies through the Pallas interpreter on the CPU mesh."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.ops import paged_attention as pk
from paddle_tpu.serving import ServingAPI, ServingConfig
from paddle_tpu.serving import metrics as serving_metrics

pytestmark = pytest.mark.serving

pytest.importorskip("jax.experimental.pallas")
if not pk.available():  # pragma: no cover - environment guard
    pytest.skip("Pallas scalar-prefetch unavailable", allow_module_level=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.quantization import quantize_kv  # noqa: E402
from paddle_tpu.serving.cache_views import gather_ctx  # noqa: E402
from paddle_tpu.models.serving_seam import masked_attention  # noqa: E402


# ------------------------------------------------------------- references


def _decode_ref(q, entry, bt, pos):
    """The XLA gather baseline, op-for-op what PagedCacheView does after
    the scatter: gather the whole logical context, mask to <= pos."""
    t_len = bt.shape[1] * entry[0].shape[1]
    k_all, v_all = gather_ctx(entry, bt, q.dtype)
    mask = (jnp.arange(t_len)[None, :] <= pos[:, None])[:, None, None, :]
    return masked_attention(q[:, None], k_all, v_all, mask)[:, 0]


def _prefill_ref(q, entry, bt_row, prefix_len):
    """The PrefixPrefillView baseline: one slot's suffix queries at
    global positions prefix_len + i over the gathered table."""
    t_len = bt_row.shape[0] * entry[0].shape[1]
    k_all, v_all = gather_ctx(entry, bt_row, q.dtype)
    gpos = prefix_len + jnp.arange(q.shape[0])
    mask = (jnp.arange(t_len)[None, :] <= gpos[:, None])[None, None]
    return masked_attention(q[None], k_all[None], v_all[None], mask)[0]


def _pools(rng, nb, bs, h, d, dtype="float32", quantized=False):
    kf = jnp.asarray(rng.standard_normal((nb, bs, h, d)), dtype)
    vf = jnp.asarray(rng.standard_normal((nb, bs, h, d)), dtype)
    if not quantized:
        return (kf, vf)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    return (kq, vq, ks, vs)


def _tol(dtype):
    # online vs full-width softmax association; bf16 rounds the operands
    return dict(atol=5e-6, rtol=5e-6) if dtype == "float32" \
        else dict(atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------- kernel parity


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["full", "int8"])
def test_decode_parity_permuted_partial_tables(dtype, quantized):
    """Kernel vs gather+masked_attention over permuted, partially-filled
    tables and mixed per-lane positions — bf16 and int8 entries."""
    rng = np.random.default_rng(0)
    S, H, D, NB, bs, MB = 5, 4, 32, 23, 8, 4
    entry = _pools(rng, NB, bs, H, D, dtype, quantized)
    q = jnp.asarray(rng.standard_normal((S, H, D)), dtype)
    # permuted physical blocks; lanes 3/4 share a "partial" look: table
    # tails still point at arbitrary blocks but positions mask them off
    bt = jnp.asarray(rng.permutation(np.arange(1, NB))[: S * MB].reshape(
        S, MB), jnp.int32)
    pos = jnp.asarray([0, 3, 17, 25, 31], jnp.int32)
    out = pk.paged_decode_attention(q, entry, bt, pos)
    ref = _decode_ref(q, entry, bt, pos)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


def test_decode_parity_every_tile_size():
    """The tile's page count is a pure launch parameter: every size
    computes the same attention (the autotuner can never change
    results), a table that is not a whole number of tiles included."""
    rng = np.random.default_rng(1)
    S, H, D, NB, bs, MB = 3, 4, 16, 23, 4, 5
    entry = _pools(rng, NB, bs, H, D)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, NB, (S, MB)), jnp.int32)
    pos = jnp.asarray([2, 7, 19], jnp.int32)
    ref = _decode_ref(q, entry, bt, pos)
    for pages in (1, 2, 4, 8):
        out = pk.paged_decode_attention(q, entry, bt, pos, pages=pages)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **_tol("float32"))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("quantized", [False, True], ids=["full", "int8"])
@pytest.mark.parametrize("heads", [16, 30])
def test_decode_tiles_ragged_lanes(heads, quantized, dtype):
    """Tiles of 2 pages over ragged lanes, at both head counts the
    benchmark serves (30 does not fill whole sublane tiles): a lane of one
    token, one ending exactly on a page edge, one on a tile edge, one at
    the table's full length, a permuted table with a block two lanes
    share, and a lane that is not active — its output is unused and its
    pages unread, shown by poisoning a block only it names."""
    rng = np.random.default_rng(7)
    S, H, D, NB, bs, MB, pages = 6, heads, 32, 40, 4, 6, 2
    entry = _pools(rng, NB, bs, H, D, dtype, quantized)
    q = jnp.asarray(rng.standard_normal((S, H, D)), dtype)
    bt = rng.permutation(np.arange(2, NB))[: S * MB].reshape(S, MB)
    bt[3, 0] = bt[2, 0]  # lanes 2 and 3 share their first block
    poisoned = 1         # named by the inactive lane 4 alone
    bt[4, :] = poisoned
    bt = jnp.asarray(bt, jnp.int32)
    if quantized:  # int8 cannot hold a NaN: poison the block's scales
        entry = entry[:2] + tuple(e.at[poisoned].set(jnp.nan)
                                  for e in entry[2:])
    else:
        entry = tuple(e.at[poisoned].set(jnp.nan) for e in entry)
    # lengths: 1 token; a page edge (4); a tile edge (8); mid-page; the
    # inactive lane; the whole table (24)
    pos = jnp.asarray([0, bs - 1, pages * bs - 1, 13, 9, MB * bs - 1],
                      jnp.int32)
    active = jnp.asarray([1, 1, 1, 1, 0, 1], bool)
    out = np.asarray(pk.paged_decode_attention(
        q, entry, bt, pos, active=active, pages=pages), np.float32)
    ref = np.asarray(_decode_ref(q, entry, bt, pos), np.float32)
    live = np.asarray(active)
    assert np.isfinite(out).all()  # the poisoned block was never read
    np.testing.assert_allclose(out[live], ref[live], **_tol(dtype))
    # the other tile sizes and the default agree (same lanes, same data)
    for n in (1, 4, None):
        again = np.asarray(pk.paged_decode_attention(
            q, entry, bt, pos, active=active, pages=n), np.float32)
        np.testing.assert_allclose(again[live], ref[live], **_tol(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("quantized", [False, True], ids=["full", "int8"])
@pytest.mark.parametrize("kv_heads", [2, 10])
def test_decode_grouped_queries(kv_heads, quantized, dtype):
    """Four query heads a K/V head (2: a token-major pool; 10: a
    head-major one, Phi-4-mini-flash's count): query head ``h`` reads K/V
    head ``h // 4`` through the tile's bias, ragged lanes, an inactive
    lane, every tile size."""
    rng = np.random.default_rng(11)
    S, H, D, NB, bs, MB = 5, kv_heads, 32, 40, 4, 6
    entry = _pools(rng, NB, bs, H, D, dtype, quantized)
    q = jnp.asarray(rng.standard_normal((S, 4 * H, D)), dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, NB))[: S * MB].reshape(
        S, MB), jnp.int32)
    pos = jnp.asarray([0, 3, 7, 13, MB * bs - 1], jnp.int32)
    active = jnp.asarray([1, 1, 0, 1, 1], bool)
    live = np.asarray(active)
    ref = np.asarray(_decode_ref(q, entry, bt, pos), np.float32)
    assert ref.shape == (S, 4 * H, D)
    for pages in (1, 2, None):
        out = np.asarray(pk.paged_decode_attention(
            q, entry, bt, pos, active=active, pages=pages), np.float32)
        np.testing.assert_allclose(out[live], ref[live], **_tol(dtype))
        assert not out[~live].any()


def test_grouped_reference_is_attention_with_repeated_heads():
    """What the grouped cases above are held to: ``masked_attention`` with
    fewer K/V heads equals the plain one with each K/V head repeated."""
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((2, 5, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 9, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 9, 2, 16)), jnp.float32)
    mask = jnp.asarray(rng.random((2, 1, 5, 9)) < 0.7).at[..., 0].set(True)
    got = masked_attention(q, k, v, mask)
    want = masked_attention(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                            mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("quantized", [False, True], ids=["full", "int8"])
def test_prefill_grouped_queries(dtype, quantized):
    """The prefill kernel at query heads 4 x K/V heads, several runtime
    prefixes, every query tile and head group."""
    rng = np.random.default_rng(13)
    sq, H, D, NB, bs, MB = 8, 2, 32, 19, 4, 6
    entry = _pools(rng, NB, bs, H, D, dtype, quantized)
    q = jnp.asarray(rng.standard_normal((sq, 4 * H, D)), dtype)
    bt_row = jnp.asarray(rng.permutation(np.arange(1, MB + 1)), jnp.int32)
    for prefix in (0, 5, 13):
        ref = np.asarray(_prefill_ref(q, entry, bt_row, prefix), np.float32)
        for blk_q, blk_h in ((None, None), (2, 1), (8, 2)):
            out = pk.paged_prefill_attention(q, entry, bt_row, prefix,
                                             block_q=blk_q, block_h=blk_h)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), ref,
                err_msg=f"prefix={prefix} tile=({blk_q},{blk_h})",
                **_tol(dtype))


@pytest.mark.parametrize("sq", [8, 12])
def test_full_prefill_grouped_queries(sq):
    rng = np.random.default_rng(14)
    H, D, bs = 2, 32, 8
    q = jnp.asarray(rng.standard_normal((sq, 4 * H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.float32)
    out = pk.paged_full_prefill_attention(q, k, v, bs)
    mask = (jnp.arange(sq)[None, :] <= jnp.arange(sq)[:, None])[None, None]
    ref = masked_attention(q[None], k[None], v[None], mask)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol("float32"))


@pytest.mark.parametrize("heads,dim", [(16, 128), (30, 128), (4, 32)])
def test_write_token_is_the_plain_scatter(heads, dim):
    """The kernel route's write of the new token (a head's row at a time
    into the pool's slab view, token- or head-major) leaves the pool as
    ``pool.at[block, offset].set`` does, a head dim under 128 included."""
    rng = np.random.default_rng(9)
    S, NB, bs = 5, 11, 4
    pool = jnp.asarray(rng.standard_normal((NB, bs, heads, dim)),
                       jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((S, heads, dim)), jnp.bfloat16)
    blocks = jnp.asarray(rng.permutation(NB)[:S], jnp.int32)
    offsets = jnp.asarray(rng.integers(0, bs, (S,)), jnp.int32)
    got = jax.jit(pk.write_token)(pool, blocks, offsets, new)
    assert got.shape == pool.shape and got.dtype == pool.dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(pool.at[blocks, offsets].set(new), np.float32))


def test_decode_shared_block_between_lanes():
    """Two lanes whose tables alias the same physical block (a radix-
    cache shared prefix) read identical context through the kernel."""
    rng = np.random.default_rng(2)
    S, H, D, NB, bs, MB = 2, 2, 16, 9, 4, 2
    entry = _pools(rng, NB, bs, H, D)
    q0 = rng.standard_normal((1, H, D))
    q = jnp.asarray(np.concatenate([q0, q0]), jnp.float32)  # same query
    bt = jnp.asarray([[5, 3], [5, 7]], jnp.int32)  # block 5 shared
    pos = jnp.asarray([3, 3], jnp.int32)  # both inside the shared block
    out = np.asarray(pk.paged_decode_attention(q, entry, bt, pos))
    np.testing.assert_array_equal(out[0], out[1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["full", "int8"])
def test_prefill_parity_mixed_prefix(dtype, quantized):
    """Chunked-prefill kernel vs the suffix-prefill baseline at several
    runtime prefix lengths (cache hits of different depths / successive
    chunks) — one compiled shape serves them all."""
    rng = np.random.default_rng(3)
    sq, H, D, NB, bs, MB = 16, 4, 32, 19, 8, 6
    entry = _pools(rng, NB, bs, H, D, dtype, quantized)
    q = jnp.asarray(rng.standard_normal((sq, H, D)), dtype)
    bt_row = jnp.asarray(rng.permutation(np.arange(1, MB + 1)), jnp.int32)
    for prefix in (0, 5, 11, 31):
        out = pk.paged_prefill_attention(q, entry, bt_row, prefix)
        ref = _prefill_ref(q, entry, bt_row, prefix)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            err_msg=f"prefix={prefix}", **_tol(dtype))


def test_prefill_parity_every_tile():
    rng = np.random.default_rng(4)
    sq, H, D, NB, bs, MB = 8, 2, 16, 9, 4, 3
    entry = _pools(rng, NB, bs, H, D)
    q = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.float32)
    bt_row = jnp.asarray([4, 1, 7], jnp.int32)
    ref = _prefill_ref(q, entry, bt_row, 2)
    for blk_q in (1, 2, 4, 8):
        for blk_h in (1, 2):
            out = pk.paged_prefill_attention(q, entry, bt_row, 2,
                                             block_q=blk_q, block_h=blk_h)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       **_tol("float32"))


@pytest.mark.parametrize("sq", [8, 12, 16])  # 12: non-divisible pad path
def test_full_prefill_pseudo_table_parity(sq):
    """The no-table entry (PR 13 open item): contiguous K/V through an
    arange pseudo-table with prefix 0 equals plain causal attention —
    including when sq doesn't divide the block size (pad keys sit above
    every query row and are masked off)."""
    rng = np.random.default_rng(6)
    H, D, bs = 4, 32, 8
    q = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.float32)
    out = pk.paged_full_prefill_attention(q, k, v, bs)
    mask = (jnp.arange(sq)[None, :] <= jnp.arange(sq)[:, None])[None, None]
    ref = masked_attention(q[None], k[None], v[None], mask)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol("float32"))


def test_kernel_runtime_data_one_trace():
    """Tables, positions and the active lanes are runtime data: one jit
    trace serves arbitrary admit/retire churn of all three."""
    rng = np.random.default_rng(5)
    S, H, D, NB, bs, MB = 4, 2, 16, 13, 4, 3
    entry = _pools(rng, NB, bs, H, D)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    traces = {"n": 0}

    @jax.jit
    def step(q, entry, bt, pos, active):
        traces["n"] += 1
        return pk.paged_decode_attention(q, entry, bt, pos, active=active,
                                         pages=2)

    for i in range(4):
        bt = jnp.asarray(rng.integers(1, NB, (S, MB)), jnp.int32)
        pos = jnp.asarray(rng.integers(0, MB * bs, (S,)), jnp.int32)
        active = np.ones(S, bool)
        active[rng.integers(0, S, (i,))] = False  # 0..3 lanes retired
        out = np.asarray(step(q, entry, bt, pos, jnp.asarray(active)))
        ref = np.asarray(_decode_ref(q, entry, bt, pos))
        np.testing.assert_allclose(out[active], ref[active],
                                   **_tol("float32"))
        assert not out[~active].any()  # a lane that is not active: zeros
    assert traces["n"] == 1


# ------------------------------ launch shapes: derived, in the kernel's file


@pytest.mark.parametrize("kind,seq,tiles", [
    ("TPU v5 lite", 512, None),            # under the measured range
    ("TPU v5 lite", 1024, (1024, 1024)),   # its first row (`train-1chip`)
    ("TPU v5 lite", 3000, (1024, 1024)),   # inside: the nearest row, 2048
    ("TPU v5 lite", 8192, (1024, 512)),    # its last row
    ("TPU v5 lite", 32768, (1024, 512)),   # past it: the last row still
    ("TPU v9000", 2048, None),             # a chip nobody measured
])
def test_flash_tuned_blocks_come_from_the_table_alone(kind, seq, tiles,
                                                      monkeypatch):
    """``_tuned_blocks``: the kernel module's own table by device kind,
    the nearest measured sequence length inside and past the measured
    range, nothing under it and nothing for a kind that is not there."""
    from types import SimpleNamespace

    from paddle_tpu.ops import pallas_ops

    monkeypatch.setattr(pallas_ops.jax, "devices",
                        lambda *a: [SimpleNamespace(device_kind=kind)])
    assert pallas_ops._tuned_blocks(seq) == tiles


#: one page of a bf16 pool at block 16: K (or V) rows of ``heads`` heads of
#: 128, or a latent pool's 8 rows of two 576-value tokens
_KV_PAGE = lambda heads: 16 * heads * 128 * 2  # noqa: E731
_LATENT_PAGE = 8 * (2 * 576) * 2


@pytest.mark.parametrize("page_bytes,max_blocks,tile_bytes,pages", [
    (_KV_PAGE(16), 2048 // 16, pk._TILE_BYTES, 16),
    (_KV_PAGE(30), 4096 // 16, pk._TILE_BYTES, 8),
    (_KV_PAGE(10), 16384 // 16, pk._TILE_BYTES, 16),
    (_KV_PAGE(8), 16384 // 16, pk._TILE_BYTES, 32),
    (_LATENT_PAGE, 10240 // 16, pk._LATENT_TILE_BYTES, 64),
    (_LATENT_PAGE, 7168 // 16, pk._LATENT_TILE_BYTES, 64),
    (_KV_PAGE(16), 3, pk._TILE_BYTES, 4),
], ids=["serve-batch-long", "serve-doc-hybrid", "serve-reason-flash",
        "serve-mixed-swa-moe", "serve-doc-latent-moe", "serve-agent-scmoe",
        "a_table_shorter_than_a_tile"])
def test_tile_pages_derived_at_the_cells_shapes(page_bytes, max_blocks,
                                                tile_bytes, pages):
    """With no ``pages`` argument a decode launch takes what fills its
    tile's bytes, a power of two: 16 pages at GPT-1.3B's 16 heads, 8 at
    Olmo-Hybrid's 30 (within 3% of the best of 4/8/16/32 on the chip,
    PERF.md PR 27), 64 of a latent pool's 18 KB; never more than covers
    the table."""
    assert pk._tile_pages(max_blocks, page_bytes,
                          tile_bytes=tile_bytes) == pages


_STRAY_STORE = """
import json, os, sys
from types import SimpleNamespace
import jax
from paddle_tpu.core import compile_cache
from paddle_tpu.ops import paged_attention as pk, pallas_ops

kind = str(jax.devices()[0].device_kind or jax.devices()[0].platform)
compile_cache.default_cache_dir = lambda: sys.argv[1]
rec = lambda **params: {"params": params, "measured_us": 1.0}
with open(os.path.join(sys.argv[1], "TUNED_KERNELS.json"), "w") as f:
    json.dump({"records": {kind: {
        "flash_fwd": {"s=2048": rec(blk_q=256, blk_k=512)},
        "paged_decode": {"bs=4,d=16,h=4,mb=3": rec(pages=2)}}}}, f)
pallas_ops._TUNED_BLOCKS = {kind: {2048: (512, 512)}}
asked = []
pk._decode_call = lambda q, entry, bt, lengths, pages: (
    asked.append(pages), q)[1]
import jax.numpy as jnp
pools = (jnp.zeros((9, 4, 4, 16)),) * 2
pk.paged_decode_attention(jnp.zeros((2, 4, 16)), pools,
                          jnp.zeros((2, 3), jnp.int32),
                          jnp.zeros((2,), jnp.int32))
print(json.dumps({"flash": pallas_ops._tuned_blocks(2048),
                  "pages": asked}))
"""


def test_a_stray_tuned_kernels_file_changes_no_launch(tmp_path):
    """A ``TUNED_KERNELS.json`` in the compile cache's directory (what
    the tuning store read until PR 47; nothing ever wrote one) changes
    neither the flash kernel's tiles nor the decode launch's tile: which
    program runs is in the diff, not in a checkout's cache directory. In
    a process of its own: the store kept what it read for the life of a
    process."""
    out = subprocess.run(
        [sys.executable, "-c", _STRAY_STORE, str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"flash": [512, 512], "pages": [0]}


def test_use_interpret_memoized():
    """Satellite: the backend probe resolves once per process, at module
    level — not once per pallas_call trace."""
    from paddle_tpu.ops import pallas_ops

    assert pallas_ops._use_interpret() is True  # CPU test mesh
    assert pallas_ops._INTERPRET_MEMO  # resolved and memoized
    memo = dict(pallas_ops._INTERPRET_MEMO)
    assert pallas_ops._use_interpret() is True
    assert pallas_ops._INTERPRET_MEMO == memo  # no re-probe growth


def test_gather_ctx_per_block_dequant_bitwise():
    """Satellite: the bf16 fallback dequant chunks per block (lax.map)
    but stays bitwise identical to the whole-context expression."""
    from paddle_tpu.quantization import dequantize_kv

    rng = np.random.default_rng(6)
    NB, bs, H, D, S, MB = 9, 4, 2, 16, 3, 3
    entry = _pools(rng, NB, bs, H, D, quantized=True)
    table = jnp.asarray(rng.integers(0, NB, (S, MB)), jnp.int32)
    k_all, v_all = gather_ctx(entry, table, "bfloat16")
    k_ref = dequantize_kv(entry[0][table], entry[2][table],
                          "bfloat16").reshape(S, MB * bs, H, D)
    v_ref = dequantize_kv(entry[1][table], entry[3][table],
                          "bfloat16").reshape(S, MB * bs, H, D)
    np.testing.assert_array_equal(np.asarray(k_all), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v_all), np.asarray(v_ref))


# ------------------------------------------------------- engine integration


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _serve(model, rng, workload, **cfg_kw):
    cfg = ServingConfig(num_slots=4, kv_block_size=16, max_model_len=128,
                        **cfg_kw)
    api = ServingAPI(model, cfg)
    try:
        reqs = [api.submit(p, max_new_tokens=n) for p, n in workload]
        api.run_until_idle()
        outs = [np.asarray(r.output_ids()) for r in reqs]
        stats = api.engine.stats()
    finally:
        api.close()
    return outs, stats


def _workload(rng, n=6):
    lens = [8, 12, 20, 7, 16, 9]
    return [(rng.integers(0, 1024, (lens[i % len(lens)],), dtype=np.int32),
             8) for i in range(n)]


def test_engine_token_parity_and_zero_recompile_churn(model):
    """The headline gate: a paged-kernel engine reproduces the gather
    engine token-for-token across admit/retire churn, with decode traced
    exactly ONCE (kernel.decode_traces mirrors it) — block-table and
    position churn never re-lowers the kernel."""
    off, _ = _serve(model, None, _workload(np.random.default_rng(0)),
                    paged_kernel=False)
    before = serving_metrics.stats()
    on, st = _serve(model, None, _workload(np.random.default_rng(0)),
                    paged_kernel=True)
    after = serving_metrics.stats()
    assert st["kernel.paged"] == 1
    assert st["decode_traces"] == 1
    assert after.get("kernel.decode_traces", 0) \
        - before.get("kernel.decode_traces", 0) == 1
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_engine_parity_int8_arena(model):
    """Fused in-kernel dequant: int8 arena + kernel reproduces the int8
    gather engine exactly (quantized serving never materializes f32
    context on the kernel path)."""
    w = _workload(np.random.default_rng(1))
    off, _ = _serve(model, None, w, paged_kernel=False, quant_kv=True)
    on, st = _serve(model, None, w, paged_kernel=True, quant_kv=True)
    assert st["arena.quantized"] is True
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_engine_parity_prefix_cache_suffix_prefill(model):
    """Cache-hit admissions route the suffix prefill through the paged
    prefill kernel (prefix_len runtime data — one program per bucket)."""
    rng = np.random.default_rng(2)
    sys_p = rng.integers(0, 1024, (32,), dtype=np.int32)
    w = [(np.concatenate([sys_p,
                          rng.integers(0, 1024, (6,), dtype=np.int32)]), 8)
         for _ in range(4)]
    off, _ = _serve(model, None, w, paged_kernel=False, prefix_cache=True)
    on, st = _serve(model, None, w, paged_kernel=True, prefix_cache=True)
    assert st["prefix.hits"] >= 3
    assert sum(st["prefix_prefill_traces"].values()) == 1
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_engine_parity_chunked_prefill(model):
    """Chunked admissions drive every chunk through the prefill kernel —
    same tokens, chunks actually taken."""
    rng = np.random.default_rng(7)
    w = [(rng.integers(0, 1024, (40,), dtype=np.int32), 6)
         for _ in range(3)]
    off, _ = _serve(model, None, w, paged_kernel=False, chunked_prefill=8)
    before = serving_metrics.stats()
    on, st = _serve(model, None, w, paged_kernel=True, chunked_prefill=8)
    after = serving_metrics.stats()
    assert after.get("chunk.chunks", 0) > before.get("chunk.chunks", 0)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_engine_parity_spec_verify(model):
    """Speculative decoding's draft/verify sub-steps read through the
    kernel too (the PagedCacheView route inside _spec_step): lockstep
    spec + kernel == plain greedy, acceptance structurally 1.0."""
    w = _workload(np.random.default_rng(3), n=4)
    off, _ = _serve(model, None, w, paged_kernel=False)
    on, st = _serve(model, None, w, paged_kernel=True, spec_k=2)
    assert st["spec.mode"] == "lockstep"
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


@pytest.mark.chaos
def test_engine_kernel_supervisor_replay_parity(model):
    """Standing invariant: supervisor rebuild/replay is unchanged under
    the kernel — a mid-decode device fault recovers with token-identical
    output, one rebuild, and the decode step never re-traced (the
    rebuilt arena has the same shapes, so the kernel programs are
    reused)."""
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    from paddle_tpu.core import resilience

    cfg = ServingConfig(num_slots=4, kv_block_size=16, max_model_len=128,
                        paged_kernel=True)
    api = ServingAPI(model, cfg)
    try:
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, 1024, (n,), dtype=np.int32)
                   for n in (5, 9, 12)]
        reqs = [api.submit(p, max_new_tokens=8) for p in prompts]
        api.run_until_idle()
        refs = [r.output_ids() for r in reqs]
        d0 = api.engine.decode_traces
        reqs2 = [api.submit(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            api._pump_once()
        resilience.inject_fault("serving_device", times=1)
        api.run_until_idle()
        for ref, r in zip(refs, reqs2):
            np.testing.assert_array_equal(ref, r.output_ids())
        assert api.engine.decode_traces == d0 == 1
        assert api.engine.stats()["kernel.paged"] == 1
    finally:
        api.close()
        paddle.set_flags({"fault_injection": keep})


def test_arena_kernel_layout_contract(model):
    """KVArena.kernel_layout() states the facts the kernels and the
    --paged-attention bench size launches from — it must match the live
    pool arrays exactly, quantized and not."""
    for quant in (False, True):
        cfg = ServingConfig(num_slots=2, kv_block_size=16,
                            max_model_len=64, paged_kernel=True,
                            quant_kv=quant)
        api = ServingAPI(model, cfg)
        try:
            arena = api.engine.arena
            lay = arena.kernel_layout()
            entry = arena.pools[0]
            assert lay["num_blocks"] == entry[0].shape[0]
            assert lay["block_size"] == entry[0].shape[1]
            assert lay["quantized"] == (len(entry) == 4)
            assert lay["scratch_block"] == 0
            if quant:
                assert tuple(entry[2].shape) == (lay["num_blocks"],
                                                 lay["block_size"])
        finally:
            api.close()


def test_engine_default_route_follows_the_device(model):
    """``paged_kernel=None`` (the default) is resolved from the device:
    here, where the kernel would run interpreted, the decode step takes
    the XLA gather — the bit-preserved baseline every parity test above
    compares against — and the record says so."""
    _, st = _serve(model, None, _workload(np.random.default_rng(4), n=2))
    assert st["kernel.paged"] == 0
    assert st["kernel.mesh"] == "gather@single"
    assert st["kernel.mesh"].startswith("gather@")


# ------------------------------------------------- SPMD partitioning (mesh)
#
# ISSUE 16: on a multi-device mesh the kernels run per model-shard
# through headwise_shard_map — head-sharded q/K/V pool operands,
# replicated block tables/positions/scales, the row-parallel output
# psum closing the attention output. Everything below runs on the 8
# virtual CPU devices conftest forces.

from paddle_tpu.distributed.mesh import serving_mesh  # noqa: E402


def _fresh():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("quantized", [False, True], ids=["full", "int8"])
def test_sharded_decode_parity(dtype, quantized):
    """The sharded decode kernel (4-way model split of 8 heads — each
    device runs its 2 local heads against replicated tables) matches the
    unsharded kernel: per-head attention is independent, so splitting
    the head dim changes nothing but placement."""
    mesh = serving_mesh(4, install=False)
    rng = np.random.default_rng(9)
    S, H, D, NB, bs, MB = 4, 8, 32, 17, 8, 4
    entry = _pools(rng, NB, bs, H, D, dtype, quantized)
    q = jnp.asarray(rng.standard_normal((S, H, D)), dtype)
    bt = jnp.asarray(rng.integers(1, NB, (S, MB)), jnp.int32)
    pos = jnp.asarray([0, 7, 19, 31], jnp.int32)
    ref = pk.paged_decode_attention(q, entry, bt, pos)
    out = pk.paged_decode_attention(q, entry, bt, pos, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("quantized", [False, True], ids=["full", "int8"])
def test_sharded_prefill_parity(quantized):
    """The sharded suffix-prefill kernel at several runtime prefix
    lengths — one shard_map'd program serves them all."""
    mesh = serving_mesh(4, install=False)
    rng = np.random.default_rng(10)
    sq, H, D, NB, bs, MB = 16, 8, 32, 19, 8, 6
    entry = _pools(rng, NB, bs, H, D, "float32", quantized)
    q = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.float32)
    bt_row = jnp.asarray(rng.permutation(np.arange(1, MB + 1)), jnp.int32)
    for prefix in (0, 5, 31):
        out = pk.paged_prefill_attention(q, entry, bt_row, prefix,
                                         mesh=mesh)
        ref = _prefill_ref(q, entry, bt_row, prefix)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            err_msg=f"prefix={prefix}", **_tol("float32"))


def test_sharded_nondivisible_heads_replicate():
    """Heads not divisible by the model degree degrade to replicated
    specs inside the wrapper — correct output, never a crash or a
    gather fallback."""
    mesh = serving_mesh(4, install=False)
    rng = np.random.default_rng(11)
    S, H, D, NB, bs, MB = 3, 6, 16, 9, 4, 3  # 6 % 4 != 0
    entry = _pools(rng, NB, bs, H, D)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, NB, (S, MB)), jnp.int32)
    pos = jnp.asarray([2, 7, 11], jnp.int32)
    out = pk.paged_decode_attention(q, entry, bt, pos, mesh=mesh)
    ref = _decode_ref(q, entry, bt, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol("float32"))


def test_mesh_engine_kernel_vs_gather_parity_one_trace():
    """The ISSUE 16 headline gate: on a live (model=4) mesh the kernel
    engine reproduces the mesh-gather engine token-for-token, decode is
    traced exactly ONCE (kernel.decode_traces mirrors it), and the
    route gauge reports kernel@model4 — admit/retire churn on the mesh
    re-lowers nothing."""
    serving_mesh(4)
    model = _fresh()
    w = _workload(np.random.default_rng(12))
    off, st0 = _serve(model, None, w, paged_kernel=False)
    assert st0["kernel.mesh"] == "gather@model4"
    before = serving_metrics.stats()
    on, st = _serve(model, None, w, paged_kernel=True)
    after = serving_metrics.stats()
    assert st["kernel.paged"] == 1
    assert st["kernel.mesh"] == "kernel@model4"
    assert st["decode_traces"] == 1
    assert after.get("kernel.decode_traces", 0) \
        - before.get("kernel.decode_traces", 0) == 1
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_mesh_engine_parity_int8_arena():
    """Fused in-kernel dequant per model-shard: int8 arena + kernel on
    the mesh reproduces the int8 mesh-gather engine exactly (the scale
    pools ride replicated next to the head-sharded payloads)."""
    serving_mesh(4)
    model = _fresh()
    w = _workload(np.random.default_rng(13), n=4)
    off, _ = _serve(model, None, w, paged_kernel=False, quant_kv=True)
    on, st = _serve(model, None, w, paged_kernel=True, quant_kv=True)
    assert st["arena.quantized"] is True
    assert st["kernel.mesh"] == "kernel@model4"
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_mesh_engine_parity_spec_verify():
    """Speculative draft/verify sub-steps ride the sharded kernel too:
    lockstep spec + kernel + mesh == plain mesh greedy decode."""
    serving_mesh(4)
    model = _fresh()
    w = _workload(np.random.default_rng(14), n=3)
    off, _ = _serve(model, None, w, paged_kernel=False)
    on, st = _serve(model, None, w, paged_kernel=True, spec_k=2)
    assert st["spec.mode"] == "lockstep"
    assert st["kernel.mesh"] == "kernel@model4"
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_mesh_mp1_kernel_bit_identity():
    """A 1-device mesh never takes the shard_map route (`_kernel_mesh`
    stays None): same tokens as no mesh at all — the PR 13 kernel path
    is bit-preserved, while the program key still differs (mesh_axes_key
    joins it)."""
    w = _workload(np.random.default_rng(15), n=3)
    ref, st0 = _serve(_fresh(), None, w, paged_kernel=True)
    assert st0["kernel.mesh"] == "kernel@single"
    serving_mesh(1)
    on, st = _serve(_fresh(), None, w, paged_kernel=True)
    assert st["kernel.paged"] == 1
    assert st["kernel.mesh"].startswith("kernel@")
    assert st["kernel.mesh"] != "kernel@single"  # keyed differently
    for a, b in zip(ref, on):
        np.testing.assert_array_equal(a, b)


@pytest.mark.chaos
def test_mesh_kernel_supervisor_replay_parity():
    """Supervisor rebuild/replay with mesh AND kernel on: a mid-decode
    device fault recovers token-identically, the rebuilt arena
    re-commits the same shardings, and the sharded decode program is
    reused (decode never re-traced)."""
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    from paddle_tpu.core import resilience

    serving_mesh(4)
    model = _fresh()
    cfg = ServingConfig(num_slots=4, kv_block_size=16, max_model_len=128,
                        paged_kernel=True)
    api = ServingAPI(model, cfg)
    try:
        rng = np.random.default_rng(17)
        prompts = [rng.integers(0, 1024, (n,), dtype=np.int32)
                   for n in (5, 9, 12)]
        reqs = [api.submit(p, max_new_tokens=8) for p in prompts]
        api.run_until_idle()
        refs = [r.output_ids() for r in reqs]
        d0 = api.engine.decode_traces
        reqs2 = [api.submit(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            api._pump_once()
        resilience.inject_fault("serving_device", times=1)
        api.run_until_idle()
        for ref, r in zip(refs, reqs2):
            np.testing.assert_array_equal(ref, r.output_ids())
        assert api.engine.decode_traces == d0 == 1
        assert api.engine.stats()["kernel.mesh"] == "kernel@model4"
    finally:
        api.close()
        paddle.set_flags({"fault_injection": keep})


# ------------------------------------------------ the latent decode kernel


def _latent_gather(q, pool, bt, pos, width, value_dim, scale, active):
    """The XLA form over the same pool: every lane's table gathered,
    masked softmax over the rows, the rows' first values weighted."""
    S, MB = bt.shape
    rows = pk.latent_rows(pool, width)[bt].reshape(S, -1, width)
    sc = jnp.einsum("shw,stw->sht", q, rows) * scale
    live = jnp.arange(rows.shape[1])[None, None] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(live, sc, -1e30), -1)
    out = jnp.einsum("sht,std->shd", p, rows[..., :value_dim])
    return out * active[:, None, None]


_LATENT_POS = [0, 16, 100, 191, 64, 15]


@pytest.mark.parametrize("width,value_dim,heads,pages,pos,dtype,tol", [
    (576, 512, 32, None, _LATENT_POS, jnp.float32, 1e-5),
    (576, 512, 32, 4, _LATENT_POS, jnp.float32, 1e-5),
    (40, 32, 4, 2, _LATENT_POS, jnp.float32, 1e-5),
    (256, 128, 5, 2, _LATENT_POS, jnp.float32, 1e-5),
    (576, 512, 32, 2, [2, 32, 78, 190, 64, 14], jnp.float32, 1e-5),
    (576, 512, 32, 4, [64, 128, 65, 63, 64, 129], jnp.float32, 1e-5),
    (256, 128, 5, 4, [64, 128, 65, 63, 64, 129], jnp.float32, 1e-5),
    (576, 512, 32, None, _LATENT_POS, jnp.bfloat16, 2e-2),
    (576, 512, 32, 2, [2, 33, 78, 191, 64, 14], jnp.bfloat16, 2e-2)],
    ids=["xing4-default-tile", "xing4-4-pages", "tiny", "unpacked-5-heads",
         "odd-lengths-end-on-position-0", "one-tile-plus-one-token",
         "unpacked-one-tile-plus-one-token", "bf16-32-heads",
         "bf16-32-heads-short-tiles"])
def test_latent_decode_kernel_matches_the_gather(width, value_dim, heads,
                                                 pages, pos, dtype, tol):
    """GPT-unlike shapes: one row of 576 a token for 32 heads, values its
    first 512, block 16 (two tokens a pool row of 1152 lanes); a width
    whose rows are not packed (256); lanes of one token, of a length that
    ends inside a pool row, at a block boundary and past a tile, and one
    that is not active. What the stacked softmax could get wrong: lengths
    that are odd, so the last pool row's packed position 1 is masked while
    its position 0 is live (in the last row of a block, of a tile, and
    the very first row); lanes of exactly one tile plus one token, whose
    last tile holds one live column of one row block and none of the
    other, next to a lane of one tile less a token and one of exactly a
    tile; and the cell's own precision, a bf16 pool and bf16 queries of
    32 heads held to the float32 gather over the same values. The kernel
    reads what the gather reads, and no block that a table names past a
    lane's length."""
    rng = np.random.default_rng(3)
    S, bs, MB, NB = 6, 16, 12, 80
    pack = pk.latent_pack(width)
    assert pack == (1 if width % 128 == 0 else 2)
    pool = jnp.asarray(rng.normal(size=(NB, bs // pack, pack * width)),
                       jnp.float32).astype(dtype)
    q = jnp.asarray(rng.normal(size=(S, heads, width)),
                    jnp.float32).astype(dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, NB))[:S * MB]
                     .reshape(S, MB), jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray([True, True, True, True, False, True])
    scale = 0.5 / math.sqrt(width)
    want = _latent_gather(q.astype(jnp.float32), pool.astype(jnp.float32),
                          bt, pos, width, value_dim, scale, active)
    # the kernel alone sees what a table holds past a lane's length (the
    # scratch block 0, and in it what must never be read)
    dead = jnp.arange(MB)[None, :] * bs > pos[:, None]
    got = pk.paged_latent_decode(q, pool.at[0].set(jnp.nan),
                                 jnp.where(dead, 0, bt), pos, value_dim,
                                 scale, active=active, pages=pages)
    assert got.shape == (S, heads, value_dim) and got.dtype == dtype
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < tol
    assert float(jnp.max(jnp.abs(got[4]))) == 0.0


def test_latent_token_write_replaces_one_tokens_lanes():
    rng = np.random.default_rng(4)
    width, bs, NB, S = 576, 16, 9, 4
    pool = jnp.asarray(rng.normal(size=(NB, bs // 2, 2 * width)),
                       jnp.float32)
    rows = jnp.asarray(rng.normal(size=(S, width)), jnp.float32)
    blocks = jnp.asarray([3, 5, 5, 0], jnp.int32)
    offsets = jnp.asarray([0, 7, 8, 15], jnp.int32)
    got = pk.latent_rows(pk.write_latent_token(pool, blocks, offsets, rows),
                         width)
    want = pk.latent_rows(pool, width).at[blocks, offsets].set(rows)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0


@pytest.mark.parametrize("s", [48, 256, 384])
def test_latent_prefill_flash_takes_keys_wider_than_values(s):
    """The flash forward kernel's body with 192-wide keys and 128-wide
    values against plain causal softmax (one block, two blocks of 128,
    three)."""
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.normal(size=(s, 4, 192)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(s, 4, 128)), jnp.float32)
    got = pk.latent_prefill_attention(q, k, v, 0.07, block=128)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * 0.07
    sc = jnp.where(jnp.arange(s)[None, :] <= jnp.arange(s)[:, None], sc,
                   -1e30)
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
