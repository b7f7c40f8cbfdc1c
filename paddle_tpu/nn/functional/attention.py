"""Attention functionals.

Parity surface: ``paddle.nn.functional.flash_attention`` /
``scaled_dot_product_attention`` (ref:python/paddle/nn/functional/
flash_attention.py wrapping the CUDA flash kernels,
ref:paddle/phi/kernels/gpu/flash_attn_kernel.cu:213).

TPU-native: on TPU the hot path is a Pallas blockwise-flash kernel
(paddle_tpu.ops.pallas_ops); elsewhere (CPU tests) a numerically-stable XLA
softmax attention — same math, fused by XLA. Layout is [batch, seq, heads,
head_dim] (paddle flash_attn contract).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...core import rng
from ...core.dispatch import apply
from ...core.tensor import Tensor


def _prob_dropout(probs, key, p):
    # paddle contract: dropout acts on the post-softmax probability matrix
    keep = jax.random.bernoulli(key, 1.0 - p, probs.shape)
    return jnp.where(keep, probs / (1.0 - p), 0.0).astype(probs.dtype)


def _sdpa_reference(q, k, v, *, scale, causal, dropout_p=0.0, key=None):
    # [b, s, h, d] -> [b, h, s, d]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p:
        probs = _prob_dropout(probs, key, dropout_p)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def _effective_min_seqlen(sk: int) -> int:
    """Resolve the flash-routing threshold. FLAGS default -1 = auto:
    with the tiles measured for this chip (``pallas_ops._TUNED_BLOCKS``)
    the kernel measured FASTER than XLA at every seqlen >= 1024 (1.30x
    @1k, 1.56x @2k, 2.58x @4k, 18.4x @8k; v5e bf16 fwd+bwd d=64), so auto
    routes from 1024; with the 128-blocks the kernel loses below ~4.6k
    (0.64-0.80x of XLA at 1k-4.6k), so auto stays at 4608. An explicit
    flag value always wins; 0 = always flash.

    The 1024 threshold applies only when the measured tiles will actually
    be ADOPTED: the same gate _default_blocks uses, flash_block_q/_k at
    their 128 defaults."""
    from ...core import flags

    thr = int(flags.flag("flash_attention_min_seqlen"))
    if thr >= 0:
        return thr
    from ...ops.pallas_ops import _tuned_blocks

    blocks_at_default = (int(flags.flag("flash_block_q")),
                         int(flags.flag("flash_block_k"))) == (128, 128)
    if blocks_at_default and _tuned_blocks(sk):
        return 1024
    return 4608


def _use_pallas(sk: int) -> bool:
    """Backend + measured-profitability gate (both trace-static)."""
    if jax.default_backend() != "tpu":
        return False
    thr = _effective_min_seqlen(sk)
    return thr == 0 or sk >= thr


def _sdpa(q, k, v, *, scale, causal, use_flash, seq_parallel="none"):
    if seq_parallel in ("ring", "ulysses"):
        from ...distributed.context_parallel import ring_attention, ulysses_attention

        fn = ring_attention if seq_parallel == "ring" else ulysses_attention
        return fn(q, k, v, scale=scale, causal=causal)
    if use_flash:
        from ...distributed import mesh as mesh_mod
        from ...ops.pallas_ops import flash_attention as pallas_flash

        fn = functools.partial(pallas_flash, scale=scale, causal=causal)
        mesh = mesh_mod.get_mesh()
        # on a multi-device mesh the kernel runs per device under a manual
        # map (GSPMD refuses to partition it) — unless this is already the
        # body of one (a pipeline stage: the value is manual-axis-varying)
        if (mesh is not None and mesh.devices.size > 1
                and not getattr(getattr(q, "aval", None), "vma", None)):
            from ...distributed.sharding_util import flash_shard_map

            fn = flash_shard_map(fn, mesh, q.shape[0], q.shape[2])
        return fn(q, k, v)
    return _sdpa_reference(q, k, v, scale=scale, causal=causal)


def _sdpa_dropout(q, k, v, key, *, scale, causal, dropout_p):
    # dropout on the probability matrix isn't expressible in the Pallas flash
    # kernel; the XLA path materializes probs anyway
    return _sdpa_reference(q, k, v, scale=scale, causal=causal,
                           dropout_p=dropout_p, key=key)


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p: float = 0.0,
    is_causal: bool = False,
    training: bool = True,
    name=None,
):
    """paddle.nn.functional.scaled_dot_product_attention parity.
    Layout [batch, seq, num_heads, head_dim]."""
    d = query.shape[-1]
    scale = 1.0 / math.sqrt(d)
    drop = float(dropout_p) if (dropout_p and training) else 0.0
    if attn_mask is not None:
        # masked variant stays on the XLA path (mask shapes are arbitrary)
        def _masked(q, k, v, m, rkey=None, *, scale, dropout_p):
            qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
            logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
            if m.dtype == jnp.bool_:
                logits = jnp.where(m, logits, jnp.finfo(logits.dtype).min)
            else:
                logits = logits + m
            p = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
            if dropout_p:
                p = _prob_dropout(p, rkey, dropout_p)
            return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)

        args = (query, key, value, attn_mask)
        if drop:  # consume an rng key only when dropout is live
            args += (Tensor(rng.next_key()),)
        out = apply(_masked, args, {"scale": scale, "dropout_p": drop}, name="sdpa")
    elif drop:
        out = apply(
            _sdpa_dropout,
            (query, key, value, Tensor(rng.next_key())),
            {"scale": scale, "causal": bool(is_causal), "dropout_p": drop},
            name="sdpa",
        )
    else:
        try:
            sk = int(key.shape[1])
        except Exception:  # symbolic dim (jit.save export) — jax raises
            sk = -1        # InconclusiveDimensionOperation, not TypeError
        use_flash = sk >= 0 and _use_pallas(sk)
        out = apply(
            _sdpa,
            (query, key, value),
            {"scale": scale, "causal": bool(is_causal), "use_flash": use_flash,
             "seq_parallel": _seq_parallel_mode()},
            name="sdpa",
        )
    return out


def _seq_parallel_mode() -> str:
    """Context-parallel dispatch: 'ring' (default when the mesh has an active
    "sep" axis), 'ulysses', or 'none'; FLAGS_sequence_parallel_mode
    overrides (the reference has no SP at all — SURVEY.md §5.7)."""
    from ...core import flags
    from ...distributed import mesh as mesh_mod

    mode = flags.flag("sequence_parallel_mode")
    if mode in ("ring", "ulysses", "none"):
        return mode
    m = mesh_mod.get_mesh()
    return "ring" if m is not None and m.shape.get("sep", 1) > 1 else "none"


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(
        query, key, value, dropout_p=dropout, is_causal=causal, training=training
    )
    return out, None  # (out, softmax); softmax only materialized on request
