"""Pallas flash-attention kernel tests (interpret mode on CPU).

Forward and backward are compared against the straightforward XLA softmax
attention (the same contract the reference's flash kernels are tested
against, ref:paddle/phi/kernels/gpu/flash_attn_kernel.cu).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_ops as po

RNG = np.random.RandomState(3)


def _qkv(b, s, h, d, sk=None):
    sk = sk or s
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, sk, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, sk, h, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    q, k, v = _qkv(2, 256, 2, 64)
    scale = 1.0 / np.sqrt(64)
    got = po._flash_attention(q, k, v, scale, causal)
    exp = po._attention_reference(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    q, k, v = _qkv(1, 256, 2, 64)
    scale = 1.0 / np.sqrt(64)

    def loss_flash(q, k, v):
        return (po._flash_attention(q, k, v, scale, causal) ** 2).sum()

    def loss_ref(q, k, v):
        return (po._attention_reference(q, k, v, scale, causal) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-2, atol=5e-2,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_flash_backward_causal_shorter_kv():
    """sq > sk with causal: early query rows attend to NOTHING (lse=-inf);
    their grads must be exactly zero (regression: exp(-inf - -inf) = 1)."""
    q, k, v = _qkv(1, 256, 1, 64, sk=128)
    scale = 1.0 / np.sqrt(64)

    valid = 128  # rows sq-sk .. sq-1 see >=1 key; earlier rows see none

    def loss_flash(q, k, v):
        # masked rows output 0, so summing all rows == summing valid rows
        return (po._flash_attention(q, k, v, scale, True) ** 2).sum()

    def loss_ref(q, k, v):
        # the plain softmax reference produces NaN (0/0) on fully-masked
        # rows; restrict its loss to the valid rows for a fair comparison
        out = po._attention_reference(q, k, v, scale, True)
        return (out[:, -valid:] ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    dq = np.asarray(g1[0])
    assert np.abs(dq[0, :-valid]).max() == 0.0, "masked-row dq must be 0"
    assert np.isfinite(np.asarray(g1[1])).all() and np.isfinite(np.asarray(g1[2])).all()
    for a, b, name in zip(g1, g2, "qkv"):
        a, b = np.asarray(a), np.asarray(b)
        if name == "q":
            a, b = a[:, -valid:], b[:, -valid:]
        np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-2,
                                   err_msg=f"d{name} mismatch")


F32, BF16 = jnp.float32, jnp.bfloat16

#: id: (sq, sk, key width, value width, blk_q, blk_k, causal, dtype, the
#: fused backward's budget or None for the module's). Tiles in a 2 x 2 or
#: 4 x 4 grid hold one wholly under the diagonal (one unmasked step),
#: diagonal ones (masked; by rows of sub-blocks, 128 rows here, where a
#: square tile holds four or more a side, nothing right of the diagonal
#: sub-block) and ones above it (skipped, not copied).
_PAIR_CASES = {
    "causal-2x2-tiles-of-4x4-sub-blocks": (1024, 1024, 64, 64, 512, 512, True, F32, None),
    "causal-one-tile-of-4x4-sub-blocks-bf16": (512, 512, 128, 128, 512, 512, True, BF16, None),
    "causal-2x2-masked-diagonal-tiles": (512, 512, 64, 64, 256, 256, True, F32, None),
    "causal-4x4": (512, 512, 64, 64, 128, 128, True, F32, None),
    "causal-4x4-head128-bf16": (512, 512, 128, 128, 128, 128, True, BF16, None),
    "causal-blk_q-over-blk_k": (512, 512, 64, 64, 256, 128, True, F32, None),
    "causal-blk_q-under-blk_k": (512, 512, 128, 128, 128, 256, True, F32, None),
    "causal-longer-kv-sub-blocks": (512, 1024, 64, 64, 512, 512, True, F32, None),
    "causal-longer-kv": (256, 512, 64, 64, 128, 256, True, F32, None),
    "causal-longer-kv-off-the-tiles": (256, 384, 64, 64, 256, 128, True, BF16, None),
    "causal-shorter-kv-masked-rows": (512, 256, 64, 64, 256, 128, True, F32, None),
    "causal-shorter-kv-sub-blocks": (1024, 512, 64, 64, 512, 512, True, F32, None),
    "full": (256, 256, 64, 64, 128, 128, False, F32, None),
    "full-longer-kv-bf16": (256, 512, 128, 128, 128, 256, False, BF16, None),
    "split-route-causal-sub-blocks": (512, 512, 64, 64, 512, 512, True, F32, 0),
    "split-route-causal-shorter-kv": (512, 256, 64, 64, 128, 128, True, F32, 0),
    "split-route-full": (256, 256, 128, 128, 128, 128, False, F32, 0),
    "fused-route-at-its-budget": (256, 256, 64, 64, 128, 128, True, F32,
                                  256 * 64 * 12),
    "split-route-a-byte-under": (256, 256, 64, 64, 128, 128, True, F32,
                                 256 * 64 * 12 - 1),
    "keys-192-values-128-2x2-tiles": (512, 512, 192, 128, 256, 256, True, F32, None),
    "keys-192-values-128-bf16": (512, 512, 192, 128, 128, 128, True, BF16, None),
}


@pytest.fixture
def sub_blocks_of_128(monkeypatch):
    """``_DIAG_SUB`` = 128, so that a 512-row tile goes by sub-block rows.
    The launches are jitted: trace them anew, and let no later test meet
    these traces."""
    monkeypatch.setattr(po, "_DIAG_SUB", 128)
    po._forward_call.clear_cache(), po._backward_call.clear_cache()
    yield
    po._forward_call.clear_cache(), po._backward_call.clear_cache()


@pytest.mark.parametrize("case", list(_PAIR_CASES), ids=list(_PAIR_CASES))
def test_flash_pair_matches_reference(case, monkeypatch, sub_blocks_of_128):
    """The causal pair (``_causal_tile``: unmasked tiles under the diagonal,
    sub-blocks of a diagonal tile, nothing above it) and the ONE backward
    kernel against ``_attention_reference``: values and all three
    gradients; keys wider than values forward only (the latent prefill,
    which keeps a body of its own). ``flash.bwd_fused`` / ``flash.bwd_split`` say
    which backward was traced: the route follows dQ's bytes."""
    from paddle_tpu.core import compile_cache
    from paddle_tpu.ops import paged_attention as pa

    sq, sk, dk, dv, blk_q, blk_k, causal, dtype, budget = _PAIR_CASES[case]
    if budget is not None:
        monkeypatch.setattr(po, "_FUSED_BWD_VMEM", budget)
    rng = np.random.RandomState(len(case))
    q, k, v = (jnp.asarray(rng.standard_normal((1, s, 2, d)), dtype)
               for s, d in ((sq, dk), (sk, dk), (sk, dv)))
    scale = 1.0 / np.sqrt(dk)
    tol = dict(rtol=2e-3, atol=2e-3) if dtype == F32 else \
        dict(rtol=3e-2, atol=3e-2)
    gtol = dict(rtol=5e-2, atol=5e-2) if dtype == F32 else \
        dict(rtol=1e-1, atol=1e-1)
    valid = min(sq, sk)  # rows sq - valid .. see a key; earlier ones none
    f32 = [t.astype(F32) for t in (q, k, v)]

    if dk != dv:
        assert blk_q == blk_k
        got = pa.latent_prefill_attention(q[0], k[0], v[0], scale,
                                          block=blk_q)[None]
        want = po._attention_reference(*f32, scale, causal)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), **tol)
        return

    def loss(attn, rows):
        # the plain softmax is 0/0 on rows that see no key; the kernel
        # gives them 0, so summing its every row is summing the valid ones
        return lambda q, k, v: (attn(q, k, v)[:, rows].astype(F32) ** 2).sum()

    flash = lambda q, k, v: po._flash_attention(q, k, v, scale, causal,
                                                blk_q, blk_k)
    ref = lambda q, k, v: po._attention_reference(q, k, v, scale, causal)
    before = compile_cache.stats()
    got, g1 = jax.value_and_grad(loss(flash, slice(None)),
                                 argnums=(0, 1, 2))(q, k, v)
    moved = compile_cache.stats_delta(before, compile_cache.stats(),
                                      drop_zero=True)
    fused = sq * dk * (4 + 2 * q.dtype.itemsize) <= po._FUSED_BWD_VMEM
    assert moved.get("flash.bwd_fused", 0) == int(fused)
    assert moved.get("flash.bwd_split", 0) == int(not fused)

    want, g2 = jax.value_and_grad(loss(ref, slice(sq - valid, None)),
                                  argnums=(0, 1, 2))(*f32)
    out = np.asarray(flash(q, k, v), np.float32)
    np.testing.assert_allclose(out[:, sq - valid:],
                               np.asarray(ref(*f32))[:, sq - valid:], **tol)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-2)
    if valid < sq:  # lse = NEG_INF there: no output and no gradient
        assert np.abs(out[:, :sq - valid]).max() == 0.0
        assert np.abs(np.asarray(g1[0], np.float32)[:, :sq - valid]).max() == 0.0
    for a, b, name in zip(g1, g2, "qkv"):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(
            a, b, err_msg=f"d{name} mismatch ({case})",
            **{**gtol, "atol": gtol["atol"] * max(1.0, np.abs(b).max())})


@pytest.mark.parametrize("seq,want", [(1024, (1024, 1024)),
                                      (1536, (512, 512)),
                                      (3072, (1024, 1024)),
                                      (1152, (128, 128))])
def test_a_measured_tile_halves_to_fit_the_sequence(monkeypatch, seq, want):
    """The table's 1,024-row tiles at a length they do not divide: the
    largest half of them that does, never the fall back to the 128s that a
    512-row table never needed at 1,536."""
    seen = []
    monkeypatch.setattr(po, "_default_blocks", lambda seq=None: (1024, 1024))
    monkeypatch.setattr(po, "_flash_attention",
                        lambda q, k, v, scale, causal, bq, bk:
                        seen.append((bq, bk)))
    x = jnp.zeros((1, seq, 1, 64), jnp.bfloat16)
    po.flash_attention(x, x, x, causal=True)
    assert seen == [want]


def test_flash_odd_shapes_fall_back():
    # non-multiple-of-128 seq len must route to the XLA reference path
    q, k, v = _qkv(1, 100, 2, 32)
    scale = 1.0 / np.sqrt(32)
    out = po.flash_attention(q, k, v, scale=scale, causal=True)
    exp = po._attention_reference(q, k, v, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4)


class _Dev:
    """What ``jax.devices()[0]`` has to be for the kernel to see a chip
    of this kind."""

    def __init__(self, kind):
        self.platform, self.device_kind = "tpu", kind


def _on_a(monkeypatch, kind):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(kind)])


def _block_flags(q=128, k=128, thr=-1):
    return {"flash_block_q": q, "flash_block_k": k,
            "flash_attention_min_seqlen": thr}


@pytest.fixture
def restore_flash_flags():
    from paddle_tpu.core import flags

    keep = {name: flags.flag(name) for name in _block_flags()}
    yield flags
    flags.set_flags(keep)


@pytest.mark.parametrize("kind,seq,want", [
    ("TPU v5 lite", 1024, (1024, 1024)), ("TPU v5 lite", 2048, (1024, 1024)),
    ("TPU v5 lite", 4096, (1024, 1024)), ("TPU v5 lite", 8192, (1024, 512)),
    ("TPU v5 lite", 512, None), ("TPU v4", 2048, None)])
def test_measured_tiles_by_device_kind_and_seq(monkeypatch, kind, seq, want):
    """The table as committed: what the benchmark's training cell runs at
    1024 on a v5e, nothing under the smallest measured length, nothing
    for a chip kind that was never measured."""
    _on_a(monkeypatch, kind)
    assert po._tuned_blocks(seq) == want
    assert po._default_blocks(seq=seq) == (want or (128, 128))


@pytest.mark.parametrize("kind,set_flags,want", [
    ("TPU v5 lite", _block_flags(), 1024),
    ("TPU v4", _block_flags(), 4608),
    ("TPU v5 lite", _block_flags(q=256), 4608),
    ("TPU v5 lite", _block_flags(thr=2000), 2000)],
    ids=["measured-kind", "other-kind", "explicit-blocks",
         "explicit-threshold"])
def test_flash_threshold_follows_the_table(monkeypatch, restore_flash_flags,
                                           kind, set_flags, want):
    """Auto routes from 1024 only where the table's tiles will be the
    ones that run; an explicit threshold always wins."""
    from paddle_tpu.nn.functional.attention import _effective_min_seqlen

    _on_a(monkeypatch, kind)
    restore_flash_flags.set_flags(set_flags)
    assert _effective_min_seqlen(1024) == want


def test_tuned_blocks_precedence(monkeypatch, restore_flash_flags):
    """Measured tiles apply when the block flags sit at their 128
    defaults; explicit flags always win; nothing measured -> defaults."""
    kind = jax.devices()[0].device_kind
    monkeypatch.setattr(po, "_TUNED_BLOCKS",
                        {kind: {4096: (256, 512), 8192: (512, 512)}})
    assert po._default_blocks(seq=5000) == (256, 512)  # nearest measured
    assert po._default_blocks(seq=8192) == (512, 512)
    assert po._default_blocks() == (128, 128)  # no seq context
    # below the measured range: a tiling verified at 4096+ was never
    # lowered at short seqs -> safe defaults
    assert po._default_blocks(seq=1024) == (128, 128)
    restore_flash_flags.set_flags({"FLAGS_flash_block_q": 256})
    assert po._default_blocks(seq=8192) == (256, 128)  # explicit wins
    restore_flash_flags.set_flags({"FLAGS_flash_block_q": 128})
    monkeypatch.setattr(po, "_TUNED_BLOCKS", {})
    assert po._default_blocks(seq=8192) == (128, 128)


def test_tuned_blocks_loader_device_kind_gate(monkeypatch):
    """Tiles measured on one chip generation are not adopted on another
    (tiles verified on v5e must not run on v4); the kind that was
    measured gets them."""
    monkeypatch.setattr(po, "_TUNED_BLOCKS",
                        {"TPU v99": {4096: (256, 512)}})
    assert po._tuned_blocks(4096) is None  # this process is on "cpu"
    _on_a(monkeypatch, "TPU v99")
    assert po._tuned_blocks(4096) == (256, 512)
    _on_a(monkeypatch, "TPU v98")
    assert po._tuned_blocks(4096) is None


def test_effective_min_seqlen_auto(monkeypatch, restore_flash_flags):
    """FLAGS_flash_attention_min_seqlen=-1 (auto): 1024 with tiles
    measured for this chip, 4608 without; an explicit value always wins."""
    from paddle_tpu.nn.functional.attention import _effective_min_seqlen

    flags = restore_flash_flags
    kind = jax.devices()[0].device_kind
    flags.set_flags({"flash_attention_min_seqlen": -1})
    # nothing measured for this chip -> the 128-tile break-even
    assert _effective_min_seqlen(2048) == 4608
    # tiles for this chip covering the seq -> their break-even
    monkeypatch.setattr(po, "_TUNED_BLOCKS", {kind: {1024: (512, 512)}})
    assert _effective_min_seqlen(2048) == 1024
    # explicit flag wins over auto
    flags.set_flags({"flash_attention_min_seqlen": 9999})
    assert _effective_min_seqlen(2048) == 9999
    flags.set_flags({"flash_attention_min_seqlen": 0})
    assert _effective_min_seqlen(2048) == 0


@pytest.mark.parametrize("data,model", [(4, 1), (1, 4), (2, 2)],
                         ids=["dp4", "mp4", "dp2xmp2"])
def test_flash_under_a_mesh_matches_one_device(data, model):
    """On a multi-device mesh the attention path maps the flash kernel per
    device (``sharding_util.flash_shard_map`` — GSPMD cannot partition a
    Mosaic kernel): batch over the data axis, heads over "model". Outputs
    and gradients equal the unmapped kernel's (each device runs whole
    sequences of whole heads, so nothing is reassociated); a batch the
    data axis does not divide replicates instead."""
    from jax.sharding import NamedSharding, PartitionSpec

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.nn.functional.attention import _sdpa

    kw = dict(scale=1.0 / np.sqrt(64), causal=True, use_flash=True)

    def loss_and_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: (_sdpa(q, k, v, **kw) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)

    # 3: a batch the data axis does not divide (replicates instead)
    for batch in ((4, 3) if data == 4 else (4,)):
        q, k, v = _qkv(batch, 128, 4, 64)
        want = jax.jit(loss_and_grads)(q, k, v)  # no mesh: the bare kernel
        mesh = mesh_mod.serving_mesh(model, data=data)
        placed = [jax.device_put(t, NamedSharding(mesh, PartitionSpec(
            "data" if batch % data == 0 and data > 1 else None, None,
            "model" if model > 1 else None, None))) for t in (q, k, v)]
        got = jax.jit(loss_and_grads)(*placed)
        mesh_mod.clear_mesh()
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
