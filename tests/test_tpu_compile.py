"""Every Pallas entry point of the main path compiles for a v5e chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2.3),
so what Mosaic refuses — a contraction it cannot lower, an illegal block
shape, too much VMEM — fails in tier-1 instead of on the chip. Interpret
mode cannot show any of that: before this file existed the paged decode
kernel passed every interpreter parity test and did not lower.

Shapes are the smoke's (``chip_smoke.py``: 32 slots, block 16, 128 blocks
per slot, sq 512) at the 124M widths (H12/D64), the gpt_1p3b widths
(H16/D128) and the H/4 local-head shapes each device runs on the 4-way
mesh route. A compile that passes is not a chip run: numerics are held by
the interpret-mode parity tests (``tests/test_paged_kernel.py``).
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import compilation_cache  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.ops import pallas_ops  # noqa: E402

_DEVICES = []  # the four described v5e devices, filled by `_described`


@pytest.fixture(scope="module")
def _described():
    """Describe the chip once per process, at RUN time: a skip decided at
    import would make pytest-xdist workers collect different tests. The
    compiler library guards against two processes holding a chip; nothing
    here attaches one, so several workers may load it side by side."""
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    _DEVICES[:] = topo.devices


#: (heads, head_dim): 124M, gpt_1p3b, their H/4 mesh-local shapes, and
#: Olmo-Hybrid's 30 heads (not a whole number of sublane tiles)
WIDTHS = [(12, 64), (16, 128), (3, 64), (4, 128), (30, 128)]
_IDS = [f"H{h}D{d}" for h, d in WIDTHS]
S, BS, MB, NB, SQ = 32, 16, 128, 1024, 512


@pytest.fixture(autouse=True)
def _for_the_chip(monkeypatch, _described):
    """Steer the kernels off the interpreter (the program gets no option
    for this), compile at the chip's matmul precision (conftest pins
    "highest" for the float64 goldens; Mosaic refuses that on bf16
    operands), and keep the persistent cache out of it: an entry compiled
    for a described chip cannot be read back without one."""
    monkeypatch.setattr(pallas_ops, "_use_interpret", lambda: False)
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(_DEVICES[0]))


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the kernel itself, not a fallback
    return text


def _entry(h, d, quantized, nb=NB):
    dt = jnp.int8 if quantized else jnp.bfloat16
    pools = (_sds((nb, BS, h, d), dt),) * 2
    return pools + ((_sds((nb, BS), jnp.float32),) * 2 if quantized else ())


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("h,d", WIDTHS, ids=_IDS)
def test_paged_decode_compiles(h, d, quantized):
    _compile(lambda q, bt, pos, act, *entry:
             pa.paged_decode_attention(q, entry, bt, pos, active=act),
             _sds((S, h, d), jnp.bfloat16), _sds((S, MB), jnp.int32),
             _sds((S,), jnp.int32), _sds((S,), jnp.bool_),
             *_entry(h, d, quantized))


@pytest.mark.parametrize("hq,h,mb,nb", [(16, 16, 128, 2730),
                                        (30, 30, 256, 2560),
                                        (40, 10, 1024, 16384)],
                         ids=["serve-batch-long", "serve-doc-hybrid",
                              "serve-reason-flash"])
def test_paged_decode_compiles_at_the_cells_shapes(hq, h, mb, nb):
    """The serving cells' own decode shapes (32 lanes, bf16, block 16,
    head dim 128; the third: 40 query heads over a pool of 10 K/V heads):
    the kernel compiles, nothing shaped like the gathered tables
    ``[S*MB, block, H, D]`` is in the program, and the pools reach
    the kernel as they lie on the chip (``_head_major`` guessed the
    layout XLA gives them: a wrong guess shows as a copy of a pool)."""
    pools = (_sds((nb, BS, h, 128), jnp.bfloat16),) * 2
    text = _compile(lambda q, bt, pos, act, *entry:
                    pa.paged_decode_attention(q, entry, bt, pos, active=act),
                    _sds((S, hq, 128), jnp.bfloat16),
                    _sds((S, mb), jnp.int32),
                    _sds((S,), jnp.int32), _sds((S,), jnp.bool_), *pools)
    assert f"[{S * mb},{BS},{h},128]" not in text
    assert f"[{S},{mb * BS},{h},128]" not in text
    assert not re.search(rf"= bf16\[{nb},[0-9,]*128\]\S* (copy|transpose)\(",
                         text)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("nb,h,p", [(2730, 16, 2048), (2560, 30, 4096),
                                    (16384, 10, 8192), (24576, 8, 16384)],
                         ids=["serve-batch-long", "serve-doc-hybrid",
                              "serve-reason-flash", "serve-mixed-swa-moe"])
def test_prefill_pool_write_is_in_place_at_the_cells_shapes(nb, h, p,
                                                            quantized):
    """A prefill's write of its chunk into the donated pool entry
    (``cache_views.scatter_blocks``) at the four cells' pool shapes, two of
    them head-major on the chip (30 and 10 heads): no ``copy`` or
    ``transpose`` with the pool's leading dimension (``[NB, ...]`` or its
    ``[NB*16, ...]`` view) is in the program, payload or scale pool. The
    row scatter (``scatter_rows``) has six a head-major entry."""
    from paddle_tpu.serving.cache_views import scatter_blocks

    entry = _entry(h, 128, quantized, nb)
    kv = _sds((p, h, 128), jnp.bfloat16)
    text = jax.jit(
        lambda entry, rows, true_len, k, v: scatter_blocks(
            entry, rows, true_len, k, v, BS),
        donate_argnums=(0,)).lower(
            entry, _sds((p // BS,), jnp.int32), _sds((), jnp.int32), kv,
            kv).compile().as_text()
    assert "scatter" in text
    assert not re.search(rf"= \w+\[({nb}|{nb * BS})[0-9,]*\]\S* "
                         r"(copy|transpose)\(", text)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_grouped_query_kernels_compile(quantized):
    """40 query heads over 10 K/V heads of width 128 (the differential
    form of 40 heads of 64 over 20): decode and both prefill entries."""
    hq, h, d = 40, 10, 128
    entry = _entry(h, d, quantized)
    _compile(lambda q, bt, pos, act, *entry:
             pa.paged_decode_attention(q, entry, bt, pos, active=act),
             _sds((S, hq, d), jnp.bfloat16), _sds((S, MB), jnp.int32),
             _sds((S,), jnp.int32), _sds((S,), jnp.bool_), *entry)
    _compile(lambda q, bt, prefix, *entry:
             pa.paged_prefill_attention(q, entry, bt, prefix),
             _sds((SQ, hq, d), jnp.bfloat16), _sds((MB,), jnp.int32),
             _sds((), jnp.int32), *entry)
    if not quantized:
        kv = _sds((SQ, h, d), jnp.bfloat16)
        _compile(lambda q, k, v: pa.paged_full_prefill_attention(q, k, v,
                                                                 BS),
                 _sds((SQ, hq, d), jnp.bfloat16), kv, kv)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("h,d", WIDTHS, ids=_IDS)
def test_paged_prefill_compiles(h, d, quantized):
    _compile(lambda q, bt, prefix, *entry:
             pa.paged_prefill_attention(q, entry, bt, prefix),
             _sds((SQ, h, d), jnp.bfloat16), _sds((MB,), jnp.int32),
             _sds((), jnp.int32), *_entry(h, d, quantized))


@pytest.mark.parametrize("h,d", WIDTHS, ids=_IDS)
def test_paged_full_prefill_compiles(h, d):
    x = _sds((SQ, h, d), jnp.bfloat16)
    _compile(lambda q, k, v: pa.paged_full_prefill_attention(q, k, v, BS),
             x, x, x)


@pytest.mark.parametrize("blk", [128, 512], ids=["untuned", "tuned"])
@pytest.mark.parametrize("h,d", WIDTHS, ids=_IDS)
def test_flash_fwd_bwd_compiles(h, d, blk):
    """Forward and the one backward kernel, at the untuned tiling and at
    512 x 512 (the latent prefill's tiles)."""
    x = _sds((2, 1024, h, d), jnp.bfloat16)
    text = _compile(_flash_loss_and_grads(blk, blk), x, x, x)
    assert _flash_kernels(text) == ["flash_bwd", "flash_fwd"]


def _flash_loss_and_grads(blk_q=None, blk_k=None):
    def loss_and_grads(q, k, v):
        def loss(q, k, v):
            o = pallas_ops.flash_attention(q, k, v, causal=True,
                                           blk_q=blk_q, blk_k=blk_k)
            return o.astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    return loss_and_grads


def _flash_kernels(text):
    """The flash kernels of a compiled program, by their instructions'
    names (what ``flash_time_share_pct`` matches in a trace)."""
    return sorted(set(re.findall(
        r"%\w*?(flash_fwd|flash_bwd_dkv|flash_bwd_dq|flash_bwd)[\w.]* = ",
        text)))


def _kernel_vmem(text, name):
    """Bytes of scoped VMEM the compiler ALLOCATED to the kernel ``name``
    (``used_scoped_memory_configs`` on its custom call, memory space 1:
    ``memory_analysis()`` counts HBM only)."""
    line, = [l for l in text.splitlines()
             if re.search(rf"%{name}[\d.]* = .*custom-call\(", l)]
    used, = re.findall(r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                       r'"offset":"0","size":"(\d+)"\}\]', line)
    return int(used)


@pytest.mark.parametrize("batch,seq", [(4, 1024), (2, 2048), (1, 4096),
                                       (1, 8192)],
                         ids=["train-1chip", "s2048", "s4096", "s8192"])
def test_flash_pair_compiles_at_the_tables_tiles(batch, seq, monkeypatch):
    """``train-1chip``'s shapes (4 x 1,024 tokens, 16 heads of 128: 64
    batch-heads) and the table's longer rows, each at the tiles
    ``_TUNED_BLOCKS`` holds for a v5e: the fused backward holds dQ of a
    whole sequence in VMEM, inside ``_FUSED_BWD_VMEM`` (the budget that
    chooses the route), and what the compiler allocates to the kernel in
    all (dQ, the tiles twice, the score-sized values) lies under the
    ``_VMEM_LIMIT`` the launch states."""
    from paddle_tpu.core import compile_cache

    monkeypatch.setattr(jax, "devices", lambda *a: _DEVICES)
    tiles = pallas_ops._tuned_blocks(seq)
    assert tiles == pallas_ops._TUNED_BLOCKS["TPU v5 lite"][seq]
    x = _sds((batch, seq, 16, 128), jnp.bfloat16)
    dq_bytes = seq * 128 * (4 + 2 * 2)
    assert dq_bytes <= pallas_ops._FUSED_BWD_VMEM < pallas_ops._VMEM_LIMIT
    before = compile_cache.stats()
    text = _compile(_flash_loss_and_grads(), x, x, x)
    moved = compile_cache.stats_delta(before, compile_cache.stats(),
                                      drop_zero=True)
    assert _flash_kernels(text) == ["flash_bwd", "flash_fwd"]
    assert moved.get("flash.bwd_fused") == 1 and "flash.bwd_split" not in moved
    assert f"bf16[{batch * 16},{seq},128]" in text
    assert dq_bytes < _kernel_vmem(text, "flash_bwd") <= pallas_ops._VMEM_LIMIT
    assert _kernel_vmem(text, "flash_fwd") <= pallas_ops._VMEM_LIMIT


@pytest.mark.parametrize("dtype,seq", [(jnp.bfloat16, 16384),
                                       (jnp.float32, 8192)],
                         ids=["bf16-16384", "float32-8192"])
def test_flash_backward_beyond_the_budget_is_the_split_pair(dtype, seq):
    """dQ's accumulator and output block of 16,384 x 128 in bf16 (16 MiB)
    or 8,192 x 128 in float32 (12 MiB) pass the budget, so the two
    reduction kernels run (``flash.bwd_split``), at the table's tiles."""
    from paddle_tpu.core import compile_cache

    x = _sds((1, seq, 2, 128), dtype)
    before = compile_cache.stats()
    text = _compile(_flash_loss_and_grads(
        *pallas_ops._TUNED_BLOCKS["TPU v5 lite"][8192]), x, x, x)
    moved = compile_cache.stats_delta(before, compile_cache.stats(),
                                      drop_zero=True)
    assert _flash_kernels(text) == ["flash_bwd_dkv", "flash_bwd_dq",
                                    "flash_fwd"]
    assert moved.get("flash.bwd_split") == 1 and "flash.bwd_fused" not in moved


# ------------------------------------------------ four described devices


def _mesh(data, model):
    return Mesh(np.array(_DEVICES[:4]).reshape(data, model),
                ("data", "model"))


def _on(mesh, shape, dtype, *spec):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*spec)))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_sharded_paged_kernels_compile(quantized):
    """The ``headwise_shard_map`` route at gpt_1p3b widths on a 4-way
    model mesh: pools and queries split over heads as ``shard_kv_entry``
    commits them, tables / positions / scale pools replicated."""
    mesh, (h, d) = _mesh(1, 4), (16, 128)
    dt = jnp.int8 if quantized else jnp.bfloat16
    entry = (_on(mesh, (NB, BS, h, d), dt, None, None, "model", None),) * 2
    if quantized:
        entry += (_on(mesh, (NB, BS), jnp.float32),) * 2
    _compile(lambda q, bt, pos, *entry:
             pa.paged_decode_attention(q, entry, bt, pos, mesh=mesh),
             _on(mesh, (S, h, d), jnp.bfloat16, None, "model", None),
             _on(mesh, (S, MB), jnp.int32), _on(mesh, (S,), jnp.int32),
             *entry)
    _compile(lambda q, bt, prefix, *entry:
             pa.paged_prefill_attention(q, entry, bt, prefix, mesh=mesh),
             _on(mesh, (SQ, h, d), jnp.bfloat16, None, "model", None),
             _on(mesh, (MB,), jnp.int32), _on(mesh, (), jnp.int32), *entry)


@pytest.mark.parametrize("data,model", [(4, 1), (1, 4), (2, 2)],
                         ids=["dp4", "mp4", "dp2xmp2"])
def test_flash_under_a_mesh_compiles(data, model):
    """GSPMD refuses to partition a Mosaic kernel, so under an installed
    multi-device mesh the attention path maps the flash kernel per device
    (``flash_shard_map``); without that the dp=4 training step does not
    compile for the chip at all."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.nn.functional.attention import _sdpa

    mesh = _mesh(data, model)
    mesh_mod.set_mesh(mesh)  # conftest uninstalls it after the test
    x = _on(mesh, (4, 1024, 16, 128), jnp.bfloat16,
            "data", None, "model", None)

    def loss_and_grads(q, k, v):
        def loss(q, k, v):
            o = _sdpa(q, k, v, scale=128 ** -0.5, causal=True,
                      use_flash=True)
            return o.astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compile(loss_and_grads, x, x, x)
    assert _flash_kernels(text) == ["flash_bwd", "flash_fwd"]


# ------------------------------------------------- the latent / expert cell


def test_latent_decode_compiles_at_the_cells_shapes():
    """``serve-doc-latent-moe``'s decode shapes: 64 lanes, 32 heads, one
    row of 576 a token kept two to a pool row (1152 lanes), 640 blocks of
    16 a lane, 24,576 blocks. The pool reaches the kernel as it lies: no
    copy of it is in the program. What leaves the kernel is the combined
    ``[lanes, heads, 512]`` in the queries' dtype (the two packed
    positions' parts, the second from lane 64 of its tiles, are summed
    inside): no float32 ``[64, 2, 32, 1152]`` for XLA to slice and add."""
    text = _compile(
        lambda q, pool, bt, pos, act: pa.paged_latent_decode(
            q, pool, bt, pos, 512, 0.1, active=act),
        _sds((64, 32, 576), jnp.bfloat16),
        _sds((24576, 8, 1152), jnp.bfloat16), _sds((64, 640), jnp.int32),
        _sds((64,), jnp.int32), _sds((64,), jnp.bool_))
    assert not re.search(r"bf16\[24576,8,1152\][^\n]* (copy|transpose)\(",
                         text)
    assert re.search(r"= bf16\[64,32,512\][^\n]* custom-call\(", text)
    assert "f32[64,2,32,1152]" not in text


def test_a_576_lane_pool_row_is_refused_by_mosaic():
    """Why the pool packs two tokens a row: Mosaic copies whole 128-lane
    tiles, and a ``[blocks, 16, 576]`` pool lies 640 wide."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(
            lambda q, pool, bt, pos: pa._latent_call(
                q, pool, bt, pos, 576, 512, 0.1, 0),
            _sds((64, 32, 576), jnp.bfloat16),
            _sds((24576, 16, 576), jnp.bfloat16), _sds((64, 640), jnp.int32),
            _sds((64,), jnp.int32))


@pytest.mark.parametrize("s", [1024, 10240])
def test_latent_prefill_flash_compiles(s):
    _compile(lambda q, k, v: pa.latent_prefill_attention(q, k, v, 0.1),
             _sds((s, 32, 192), jnp.bfloat16),
             _sds((s, 32, 192), jnp.bfloat16),
             _sds((s, 32, 128), jnp.bfloat16))


@pytest.mark.parametrize("tokens", [64, 1024, 10240],
                         ids=["decode-step", "bucket-1024", "bucket-10240"])
def test_expert_grouped_matmul_compiles(tokens, monkeypatch):
    """Sort, megablox grouped matmul over 64 stacked experts of width 1024,
    unsort, at a decode step's 256 assignments and at the smallest and the
    largest prefill bucket's: each row tile of ``_TILES`` fits VMEM."""
    from paddle_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    _compile(lambda x, idx, w, up, down: gm.expert_ffn(x, idx, w, up, down,
                                                       64),
             _sds((tokens, 3584), jnp.bfloat16), _sds((tokens, 4), jnp.int32),
             _sds((tokens, 4), jnp.float32),
             _sds((64, 3584, 2048), jnp.bfloat16),
             _sds((64, 1024, 3584), jnp.bfloat16))


# ----------------------------------- the shortcut-connected expert cell


def test_latent_decode_compiles_at_64_heads_and_128_lanes():
    """``serve-agent-scmoe``'s decode shapes: 128 lanes, 64 heads (twice
    the other latent cell's: the tiles and the float32 softmax state at
    ``[64, 576]`` queries fit VMEM), 448 blocks of 16 a lane, 20,480
    blocks. The pool reaches the kernel as it lies."""
    text = _compile(
        lambda q, pool, bt, pos, act: pa.paged_latent_decode(
            q, pool, bt, pos, 512, 192 ** -0.5, active=act),
        _sds((128, 64, 576), jnp.bfloat16),
        _sds((20480, 8, 1152), jnp.bfloat16), _sds((128, 448), jnp.int32),
        _sds((128,), jnp.int32), _sds((128,), jnp.bool_))
    assert not re.search(r"bf16\[20480,8,1152\][^\n]* (copy|transpose)\(",
                         text)
    assert re.search(r"= bf16\[128,64,512\][^\n]* custom-call\(", text)


def test_latent_prefill_flash_compiles_at_64_heads():
    _compile(lambda q, k, v: pa.latent_prefill_attention(q, k, v, 0.1),
             _sds((7168, 64, 192), jnp.bfloat16),
             _sds((7168, 64, 192), jnp.bfloat16),
             _sds((7168, 64, 128), jnp.bfloat16))


@pytest.mark.parametrize("tokens, rows", [(128, 64), (256, 128),
                                          (4096, 2048), (7168, 3584)],
                         ids=["decode-step", "bucket-256", "bucket-4096",
                              "bucket-7168"])
def test_small_share_grouped_matmul_compiles(tokens, rows, monkeypatch):
    """A sixteenth of a share: 16 held experts of width 2048 among 768
    columns, 12 a token. A pass of the grouped matmuls takes ``rows`` of
    the ``12 tokens`` assignments (twice the share's even load) through
    ``[rows, 6144] x [16, 6144, 4096]`` and ``[rows, 2048] x [16, 2048,
    6144]``; ``_tiling`` picks a ``tk`` that divides 6144 and 2048, and
    each row tile fits scoped VMEM with both buffers. The passes run under
    one ``while`` (their count is the step's own), the kernel inside it."""
    from paddle_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    assert gm.row_cap(12 * tokens, 16, 768) == rows
    for k, n in ((6144, 4096), (2048, 6144)):
        tm, tk, tn = gm._tiling(rows, k, n)
        assert rows % tm == 0 and k % tk == 0 and n % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        assert 2 * 2 * (tm * tk + tk * tn) + 4 * tm * tn < 16 * 2 ** 20
    text = _compile(
        lambda x, idx, w, up, down: gm.expert_ffn(x, idx, w, up, down, 768),
        _sds((tokens, 6144), jnp.bfloat16), _sds((tokens, 12), jnp.int32),
        _sds((tokens, 12), jnp.float32),
        _sds((16, 6144, 4096), jnp.bfloat16),
        _sds((16, 2048, 6144), jnp.bfloat16))
    assert " while(" in text
    # no array of every assignment's row: the largest gather is a pass's
    assert f"[{12 * tokens},6144]" not in text



# ------------------------------------ the window-and-experts cell (Trinity)


@pytest.mark.parametrize("s", [1024, 16384])
@pytest.mark.parametrize("window", [4096, None], ids=["banded", "causal"])
def test_swa_prefill_flash_compiles(s, window):
    """``serve-mixed-swa-moe``'s prefill attention at its smallest and its
    largest bucket: 48 query heads over 8 K/V heads of 128 through the
    index map, tiles of 512, the sliding layers' band of 9 key tiles and
    the full layer's causal half."""
    text = _compile(lambda q, k, v: pa.swa_prefill_attention(q, k, v, window),
                    _sds((s, 48, 128), jnp.bfloat16),
                    _sds((s, 8, 128), jnp.bfloat16),
                    _sds((s, 8, 128), jnp.bfloat16))
    assert "swa_prefill_flash" in text


@pytest.mark.parametrize("tokens, rows", [(32, 64), (1024, 1024),
                                          (16384, 16384)],
                         ids=["decode-step", "bucket-1024", "bucket-16384"])
def test_eighth_share_grouped_matmul_compiles(tokens, rows, monkeypatch):
    """An eighth of the experts: 32 held of 256 routed, width 3072, 4 a
    token: ``[rows, 3072] x [32, 3072, 6144]`` and ``[rows, 3072] x [32,
    3072, 3072]``, a pass taking ``rows`` of the ``4 tokens`` assignments;
    ``_tiling`` gives whole tiles (``tk`` 1536 for a decode step, 768
    from 2,048 rows on, ``tn`` 1024) that fit scoped VMEM with both
    buffers."""
    from paddle_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    assert gm.row_cap(4 * tokens, 32, 256) == rows
    for k, n in ((3072, 6144), (3072, 3072)):
        tm, tk, tn = gm._tiling(rows, k, n)
        assert k % tk == 0 and n % tn == 0 and tn == 1024
        assert tk == (1536 if rows < 2048 else 768)
        assert 2 * 2 * (tm * tk + tk * tn) + 4 * tm * tn < 16 * 2 ** 20
    _compile(
        lambda x, idx, w, up, down: gm.expert_ffn(x, idx, w, up, down, 256),
        _sds((tokens, 3072), jnp.bfloat16), _sds((tokens, 4), jnp.int32),
        _sds((tokens, 4), jnp.float32),
        _sds((32, 3072, 6144), jnp.bfloat16),
        _sds((32, 3072, 3072), jnp.bfloat16))


# ------------------------------------------ the fused hyper-connection

_HC_N, _HC_H = 4, 3584
_HC_KW = dict(n=_HC_N, iters=20, rms_eps=1e-6, hc_eps=1e-6,
              clamp=(-30.0, 30.0), out_dtype=jnp.bfloat16)


def _hc_chain(sublayers):
    """``sublayers`` hyper-connections of Xing4.0's widths chained as a
    layer stack chains them (each kernel updates the streams behind the
    sublayer before it, the last update alone), a ``[3584, 3584]`` bf16
    matmul standing in for each sublayer; every second one hands out the
    unrounded read-out as well, as an expert layer's does."""
    from paddle_tpu.ops import hyper_connection as hc

    def chain(x, ws, abs_, gains, mats):
        prev = None
        for i, (w, ab, g, m) in enumerate(zip(ws, abs_, gains, mats)):
            x, u, _, mix = hc.hyper_connection(
                x, hc.MixerParams(w, ab), g, prev=prev, want_f32=i % 2 == 1,
                **_HC_KW)
            prev = (jnp.dot(u, m), mix)
        return hc.hyper_connection_update(x, *prev, n=_HC_N)

    def args(lead):
        k = _HC_N * _HC_H
        return (_sds(lead + (k,), jnp.float32),
                [_sds((k, 128), jnp.bfloat16)] * sublayers,
                [_sds((48, 1), jnp.float32)] * sublayers,
                [_sds((_HC_H,), jnp.bfloat16)] * sublayers,
                [_sds((_HC_H, _HC_H), jnp.bfloat16)] * sublayers)
    return chain, args


@pytest.fixture
def _hc_for_the_chip(monkeypatch):
    from paddle_tpu.ops import hyper_connection as hc

    monkeypatch.setattr(hc, "_use_interpret", lambda: False)
    hc._call.clear_cache()  # the launch is jitted: lower it for the chip
    yield
    hc._call.clear_cache()


@pytest.mark.parametrize("lead", [(1, 8192), (1, 10240), (64, 1)],
                         ids=["bucket-8192", "bucket-10240", "decode-step"])
def test_hyper_connection_compiles_at_the_cells_shapes(lead,
                                                       _hc_for_the_chip):
    """``serve-doc-latent-moe``'s shapes: the two largest prefill buckets
    and the 64-lane step; 4 float32 streams of 3584, bf16 sublayers."""
    chain, args = _hc_chain(2)
    text = _compile(chain, *args(lead))
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # the streams lie [positions, n h] throughout: no T(4,128) relayout
    assert not re.search(r"f32\[[0-9,]*,4,3584\]", text)


def test_hyper_connection_chain_by_the_compilers_own_count(
        _hc_for_the_chip):
    """A four-sublayer chain at 4,096 positions, by ``cost_analysis()``
    (the kernel states what it moves: ``pl.CostEstimate``): under 3 passes
    over the streams a sublayer once the stand-in matmuls are taken off
    (the ``jax.numpy`` form: 12.0), and under 8 kernels a sublayer (101)."""
    positions, sublayers = 4096, 4
    chain, args = _hc_chain(sublayers)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(chain, donate_argnums=0).lower(
            *args((1, positions))).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    streams = positions * _HC_N * _HC_H * 4
    matmuls = sublayers * 2 * _HC_H * (2 * positions + _HC_H)
    passes = (cost["bytes accessed"] - matmuls) / streams / sublayers
    assert 2.0 < passes < 3.0, passes
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    kernels = len(re.findall(
        r" (?:fusion|custom-call|copy|convolution|dot)\(", entry))
    assert sublayers <= kernels < 8 * sublayers, kernels


# -------------------------------- the chunkwise gated delta rule's kernel

_GDN_H, _GDN_DK, _GDN_DV = 30, 96, 192


@pytest.fixture
def _gdn_route(monkeypatch):
    """Steers ``ops/gated_delta.py`` onto its kernel (``True``) or its
    ``jax.numpy`` form; the launch is jitted, so lower it anew."""
    from paddle_tpu.ops import gated_delta as gd

    def route(kernel: bool):
        monkeypatch.setattr(gd, "_use_interpret", lambda: not kernel)
        gd._gdn_call.clear_cache()
    yield route
    gd._gdn_call.clear_cache()


@pytest.mark.parametrize("positions", [1024, 4096])
def test_gdn_chunk_compiles_at_the_cells_shapes(positions, _gdn_route):
    """``serve-doc-hybrid``'s linear-attention layers: 30 heads of ``dk``
    96 (neither a whole lane tile nor a whole sublane tile of heads) and
    ``dv`` 192, bf16 operands, the smallest and the largest prefill
    bucket: Mosaic takes the blocks, the float32 matmuls of the inverse
    and the state update's transposed product, and the VMEM fits."""
    from paddle_tpu.ops import gated_delta as gd

    _gdn_route(True)
    lead = (1, positions, _GDN_H)
    text = _compile(
        lambda *a: gd.gated_delta_chunked(*a, mm_dtype=jnp.bfloat16),
        _sds(lead + (_GDN_DK,), jnp.float32),
        _sds(lead + (_GDN_DK,), jnp.float32),
        _sds(lead + (_GDN_DV,), jnp.float32),
        _sds(lead, jnp.float32), _sds(lead, jnp.float32),
        _sds((1, _GDN_H, _GDN_DV, _GDN_DK), jnp.float32),
        _sds((), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"%gdn_chunk[.\d]* = ", text)


def test_gdn_core_holds_no_more_with_the_kernel(_gdn_route):
    """Everything of a linear-attention mixer between its projections
    (``models/olmo_hybrid.py`` ``_gdn_core``) at the 4,096 bucket, by the
    compiler's own analysis: the kernel's route keeps no more temporaries
    alive than the ``jax.numpy`` form's (whose float32 chunk arrays it
    removes), so the largest prefill program does not grow."""
    import functools

    from paddle_tpu.models.olmo_hybrid import _gdn_core

    t, width = 4096, _GDN_H * (2 * _GDN_DK + _GDN_DV)
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (_sds((1, t, width), bf16), _sds((1, t, _GDN_H), bf16),
            _sds((1, t, _GDN_H), bf16),
            _sds((1, t, _GDN_H * _GDN_DV), bf16), _sds((4, width), bf16),
            _sds((_GDN_H,), bf16), _sds((_GDN_H,), bf16),
            _sds((_GDN_DV,), bf16),
            _sds((1, _GDN_H, _GDN_DV, _GDN_DK), f32),
            _sds((1, 3, width), bf16), _sds((), jnp.int32))
    temps = {}
    for kernel in (True, False):
        _gdn_route(kernel)
        core = functools.partial(  # a new function: jit traces it anew
            _gdn_core, heads=_GDN_H, dk=_GDN_DK, dv=_GDN_DV, eps=1e-6,
            beta_scale=2.0)
        compiled = jax.jit(core).lower(*args).compile()
        assert bool(re.search(r"%gdn_chunk[.\d]* = ",
                              compiled.as_text())) == kernel
        temps[kernel] = compiled.memory_analysis().temp_size_in_bytes
    assert temps[True] <= temps[False], temps


# -------------------------------- the sparse-attention-and-experts cell (Keye)


@pytest.fixture
def _sparse_route(monkeypatch):
    """``ops/sparse_attention.py`` off the interpreter; its launches are
    jitted, so what another route traced is dropped before and after."""
    from paddle_tpu.ops import sparse_attention as sa

    calls = (sa._paged_scores_call, sa._scores_call, sa._flash_call)
    monkeypatch.setattr(sa, "_use_interpret", lambda: False)
    for call in calls:
        call.clear_cache()
    yield sa
    for call in calls:
        call.clear_cache()


def test_paged_index_scores_compiles_at_the_cells_shapes(_sparse_route):
    """``serve-longctx-sparse-moe``'s decode shapes: 32 lanes, 16 index
    heads of 64, index keys packed two to a pool row of 128 lanes, 1,920
    blocks of 16 a lane, 32,768 blocks. The pool reaches the kernel as it
    lies: no copy of it is in the program."""
    sa = _sparse_route
    text = _compile(
        lambda qi, w, pool, bt, pos, act: sa.paged_index_scores(
            qi, w, pool, bt, pos, act, kernel=True),
        _sds((32, 16, 64), jnp.bfloat16), _sds((32, 16), jnp.float32),
        _sds((32768, 8, 128), jnp.bfloat16), _sds((32, 1920), jnp.int32),
        _sds((32,), jnp.int32), _sds((32,), jnp.bool_))
    assert re.search(r"%paged_index_scores[.\d]* = ", text)
    assert not re.search(r"bf16\[32768,8,128\][^\n]* (copy|transpose)\(",
                         text)


def test_sparse_decode_gathers_topk_rows_and_no_more(_sparse_route):
    """The decode view's attention at the cell's shapes (4 K/V heads of
    128 under 32 query heads): what it reads of the K and V pools is one
    gather each of ``[32, 2048, 4, 128]``, never a lane's whole table."""
    sa = _sparse_route
    pool = _sds((32768, 16, 4, 128), jnp.bfloat16)
    text = jax.jit(sa.gathered_attention).lower(
        _sds((32, 1, 32, 128), jnp.bfloat16), pool, pool,
        _sds((32, 1920), jnp.int32), _sds((32, 2048), jnp.int32),
        _sds((32, 2048), jnp.bool_)).compile().as_text()
    assert len(re.findall(r"= bf16\[32,2048,4,128\][^\n]* gather\(",
                          text)) == 2
    assert "bf16[32,30720,4,128]" not in text
    assert not re.search(r"bf16\[32768,16,4,128\][^\n]* (copy|transpose)\(",
                         text)


@pytest.mark.parametrize("s", [4096, 30720])
def test_sparse_prefill_kernels_compile(s, _sparse_route):
    """A prefill's two kernels at the cell's smallest and largest bucket:
    32 query heads over 4 K/V heads of 128, 16 index heads of 64, query
    tiles of 128 and key tiles of 512; the thresholds a chunk of queries
    at a time, 1,024 or as many as leave the chunk's scores in VMEM
    between the 32 passes of the search (``S(1)`` in the compiler's text:
    512 at 30,720 positions, where 1,024 queries' 126 MB made every pass
    read HBM)."""
    sa = _sparse_route
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (_sds((s, 32, 128), bf16), _sds((s, 4, 128), bf16),
            _sds((s, 4, 128), bf16), _sds((s, 16, 64), bf16),
            _sds((s, 64), bf16), _sds((s, 16), f32))

    def prefill(q, k, v, qi, ki, w):
        tau = sa.index_thresholds(qi, ki, w, 2048, kernel=True)
        return sa.sparse_prefill_attention(q, k, v, qi, ki, w, tau,
                                           kernel=True)

    compiled = jax.jit(prefill).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(r"%index_scores[.\d]* = ", text)
    assert re.search(r"%sparse_prefill_flash[.\d]* = ", text)
    assert f"f32[{s},{s}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
    rows = {4096: 1024, 30720: 512}[s]
    assert re.search(r"s32\[%d,%d\]\{[^}]*S\(1\)\}" % (rows, s), text)
