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


def test_flash_odd_shapes_fall_back():
    # non-multiple-of-128 seq len must route to the XLA reference path
    q, k, v = _qkv(1, 100, 2, 32)
    scale = 1.0 / np.sqrt(32)
    out = po.flash_attention(q, k, v, scale=scale, causal=True)
    exp = po._attention_reference(q, k, v, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4)


def test_tuned_blocks_precedence(monkeypatch):
    """FLASH_TUNED.json winners apply when the block flags sit at their
    128 defaults; explicit flags always win; no tune record -> defaults."""
    from paddle_tpu.core import flags
    from paddle_tpu.ops import pallas_ops as po

    monkeypatch.setattr(po, "_TUNED_BLOCKS",
                        {4096: (256, 512), 8192: (512, 512)})
    assert po._default_blocks(seq=5000) == (256, 512)  # nearest measured
    assert po._default_blocks(seq=8192) == (512, 512)
    assert po._default_blocks() == (128, 128)  # no seq context
    # below the measured range: a tiling verified at 4096+ was never
    # lowered at short seqs -> safe defaults
    assert po._default_blocks(seq=1024) == (128, 128)
    flags.set_flags({"FLAGS_flash_block_q": 256})
    try:
        assert po._default_blocks(seq=8192) == (256, 128)  # explicit wins
    finally:
        flags.set_flags({"FLAGS_flash_block_q": 128})
    # the documented escape hatch: force defaults despite a tune record
    flags.set_flags({"FLAGS_flash_use_tuned": False})
    try:
        assert po._default_blocks(seq=8192) == (128, 128)
    finally:
        flags.set_flags({"FLAGS_flash_use_tuned": True})
    monkeypatch.setattr(po, "_TUNED_BLOCKS", {})
    assert po._default_blocks(seq=8192) == (128, 128)


def test_tuned_blocks_loader_device_kind_gate(tmp_path, monkeypatch):
    """A tune record stamped with a different chip generation is ignored
    (tiles verified on v5e must not load on v4); matching stamp loads;
    malformed records degrade to defaults instead of raising."""
    import json

    import jax

    from paddle_tpu.ops import pallas_ops as po

    kind = getattr(jax.devices()[0], "device_kind", "")
    path = tmp_path / "FLASH_TUNED.json"
    monkeypatch.setattr(po, "_TUNED_PATH", str(path))

    path.write_text(json.dumps(
        {"device_kind": kind, "blocks": {"4096": [256, 512]}}))
    monkeypatch.setattr(po, "_TUNED_BLOCKS", None)
    assert po._tuned_blocks(4096) == (256, 512)

    path.write_text(json.dumps(
        {"device_kind": "TPU v99", "blocks": {"4096": [256, 512]}}))
    monkeypatch.setattr(po, "_TUNED_BLOCKS", None)
    assert po._tuned_blocks(4096) is None

    path.write_text("[128, 128]")  # malformed: old/other format
    monkeypatch.setattr(po, "_TUNED_BLOCKS", None)
    assert po._tuned_blocks(4096) is None


def test_effective_min_seqlen_auto(tmp_path, monkeypatch):
    """FLAGS_flash_attention_min_seqlen=-1 (auto): 1024 with a tune record
    for this chip, 4608 without; an explicit value always wins."""
    import json

    import jax

    from paddle_tpu.core import flags
    from paddle_tpu.nn.functional.attention import _effective_min_seqlen
    from paddle_tpu.ops import pallas_ops as po

    kind = getattr(jax.devices()[0], "device_kind", "")
    path = tmp_path / "FLASH_TUNED.json"
    monkeypatch.setattr(po, "_TUNED_PATH", str(path))
    old = flags.flag("flash_attention_min_seqlen")
    try:
        flags.set_flags({"flash_attention_min_seqlen": -1})
        # no tune record -> conservative untuned break-even
        monkeypatch.setattr(po, "_TUNED_BLOCKS", None)
        assert _effective_min_seqlen(2048) == 4608
        # record for this chip covering the seq -> tuned break-even
        path.write_text(json.dumps(
            {"device_kind": kind, "blocks": {"1024": [512, 512]}}))
        monkeypatch.setattr(po, "_TUNED_BLOCKS", None)
        assert _effective_min_seqlen(2048) == 1024
        # explicit flag wins over auto
        flags.set_flags({"flash_attention_min_seqlen": 9999})
        assert _effective_min_seqlen(2048) == 9999
        flags.set_flags({"flash_attention_min_seqlen": 0})
        assert _effective_min_seqlen(2048) == 0
    finally:
        flags.set_flags({"flash_attention_min_seqlen": old})


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
