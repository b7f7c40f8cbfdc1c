"""Pallas TPU kernels — the hot-op fusion zoo.

Replaces the reference's CUDA fusion layer (flash_attn integration
ref:paddle/phi/kernels/gpu/flash_attn_kernel.cu:213, fused_attention/
fused_feedforward ref:paddle/phi/kernels/fusion/) with TPU-native Pallas:
blockwise flash attention with online softmax streaming K/V through VMEM,
grid over (batch*heads, q-blocks, k-blocks), fp32 accumulation on the MXU.

Backward is fused Pallas too (≈ ref:paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu):
the forward emits a lane-broadcast log-sum-exp residual; dK/dV come from a
kernel gridded over k-blocks reducing across q-blocks into VMEM scratch, dQ
from the transposed grid — O(S) memory, the S×S matrix is never materialized.

Falls back to a pure-XLA reference path for awkward shapes; on CPU the
kernels run in the Pallas interpreter, so the same code path is exercised by
the CPU test mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

NEG_INF = -1e30
_LANES = 128  # residuals (lse, delta) are stored lane-broadcast [.., s, 128]


#: memoized _use_interpret() answers, keyed on (backend, device_count):
#: the default backend is fixed for a process's lifetime (JAX_PLATFORMS),
#: and the probe (jax.default_backend() resolves the backend registry)
#: used to re-run inside every pallas_call trace — three call sites here
#: alone, plus every paged kernel. The device count is PART of the key
#: (ISSUE 16): a forced ``xla_force_host_platform_device_count`` mesh is
#: a different runtime than the single-device probe that may have
#: resolved first — the blind "one entry, reuse it" fast path reused the
#: single-device answer there. Clear it (tests only) after swapping
#: platforms mid-process.
_INTERPRET_MEMO: Dict[tuple, bool] = {}


def _use_interpret() -> bool:
    """Run kernels in the Pallas interpreter off-TPU (the CPU test mesh:
    the CPU backend has no Mosaic lowering). On a TPU backend the answer is
    always False — the chip path never interprets — and a backend that
    fails to initialize raises here instead of quietly interpreting.
    Memoized per (backend, device_count) at module level
    (``_INTERPRET_MEMO``); both probes are answered from jax's own cached
    backend object, so a memo hit never re-resolves the backend
    registry."""
    key = (jax.default_backend(), jax.device_count())
    hit = _INTERPRET_MEMO.get(key)
    if hit is None:
        hit = _INTERPRET_MEMO[key] = key[0] != "tpu"
    return hit


def _attention_reference(q, k, v, scale, causal):
    """XLA fallback, [b, s, h, d] layout, fp32 softmax."""
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", probs, vt), 1, 2)


def _causal_mask(s, qi, ki, blk_q, blk_k, offset):
    rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return jnp.where(rows + offset >= cols, s, NEG_INF)


# --------------------------------------------------------------- forward


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
                      blk_q, blk_k, offset, with_lse):
    """One (bh, qi, ki) step of blockwise attention with online softmax.
    ``offset = sk - sq`` aligns the causal diagonal when kv is longer than q
    (decode): query i attends keys j <= i + offset."""
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(1)
    run = True
    if causal:
        # whole k-block strictly above the (offset) diagonal contributes nothing
        run = (ki * blk_k) <= (qi * blk_q + blk_q - 1 + offset)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0]  # [blk_q, d]
        k = k_ref[0]  # [blk_k, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [blk_q, blk_k]
        if causal:
            s = _causal_mask(s, qi, ki, blk_q, blk_k, offset)
        m_prev = m_scr[:, 0:1]  # [blk_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [blk_q, blk_k] f32
        correction = jnp.exp(m_prev - m_new)  # [blk_q, 1]
        l_new = correction * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_q, d]
        acc_scr[:] = acc_scr[:] * correction + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        denom = l_scr[:, 0:1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        if lse_ref is not None:
            safe_l = jnp.where(l_scr[:] > 0.0, l_scr[:], 1.0)
            lse_ref[0] = jnp.where(l_scr[:] > 0.0,
                                   m_scr[:] + jnp.log(safe_l), NEG_INF)


def _flash_forward(q, k, v, scale, causal, blk_q=128, blk_k=128,
                   with_lse=False):
    """q,k,v: [bh, s, d] (batch*heads flattened). Returns o, or (o, lse)
    where lse is the lane-broadcast [bh, sq, 128] log-sum-exp residual."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    grid = (bh, sq // blk_q, sk // blk_k)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, blk_q=blk_q,
        blk_k=blk_k, offset=sk - sq, with_lse=with_lse,
    )
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, blk_q, _LANES), lambda b, i, j: (b, i, 0)))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # running max (lane-bcast)
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((blk_q, d), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return (res[0], res[1]) if with_lse else res[0]


# --------------------------------------------------------------- backward
#
# Standard flash-attention backward split into two reduction kernels:
#   delta_i = rowsum(dO_i * O_i)                       (XLA, cheap)
#   P_ij    = exp(S_ij - lse_i)
#   dV_j    = sum_i P_ij^T dO_i
#   dS_ij   = P_ij * (dO_i V_j^T - delta_i) * scale
#   dK_j    = sum_i dS_ij^T Q_i
#   dQ_i    = sum_j dS_ij K_j
# dK/dV reduce over q-blocks (grid (bh, kj, qi), qi innermost/arbitrary),
# dQ reduces over k-blocks (grid (bh, qi, ki)).


def _bwd_common(q, k, v, do, lse, di, qi, ki, scale, causal, blk_q, blk_k,
                offset):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [blk_q, blk_k]
    if causal:
        s = _causal_mask(s, qi, ki, blk_q, blk_k, offset)
    reps = blk_k // _LANES
    lse_b = jnp.tile(lse, (1, reps)) if reps > 1 else lse[:, :blk_k]
    di_b = jnp.tile(di, (1, reps)) if reps > 1 else di[:, :blk_k]
    # fully-masked query rows store lse = NEG_INF; exp(NEG_INF - NEG_INF)
    # would be 1, so force their probabilities (and thus grads) to zero
    p = jnp.where(lse_b > NEG_INF * 0.5, jnp.exp(s - lse_b), 0.0)  # [blk_q, blk_k] f32
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [blk_q, blk_k]
    ds = p * (dp - di_b) * scale
    return p, ds


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                          blk_q, blk_k, offset):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        # q-block entirely above the diagonal of this k-block: no contribution
        run = (qi * blk_q + blk_q - 1 + offset) >= (kj * blk_k)

    @pl.when(run if causal else True)
    def _step():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _bwd_common(q, k, v, do, lse_ref[0], di_ref[0], qi, kj,
                            scale, causal, blk_q, blk_k, offset)
        dv_scr[:] += jax.lax.dot_general(  # P^T dO -> [blk_k, d]
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(  # dS^T Q -> [blk_k, d]
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                         dq_ref, dq_scr, *, scale, causal, blk_q, blk_k,
                         offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = (ki * blk_k) <= (qi * blk_q + blk_q - 1 + offset)

    @pl.when(run if causal else True)
    def _step():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _bwd_common(q, k, v, do, lse_ref[0], di_ref[0], qi, ki,
                            scale, causal, blk_q, blk_k, offset)
        dq_scr[:] += jax.lax.dot_general(  # dS K -> [blk_q, d]
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, scale, causal, blk_q=128, blk_k=128):
    """All operands [bh, s, d] except lse [bh, sq, 128]."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    offset = sk - sq

    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di[:, :, None], (bh, sq, _LANES))

    q_spec_i = pl.BlockSpec((1, blk_q, d), lambda b, j, i: (b, i, 0))
    kv_spec_j = pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0))
    lm_spec_i = pl.BlockSpec((1, blk_q, _LANES), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, offset=offset),
        grid=(bh, sk // blk_k, sq // blk_q),
        in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, lm_spec_i,
                  lm_spec_i],
        out_specs=[kv_spec_j, kv_spec_j],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk_k, d), jnp.float32),
                        pltpu.VMEM((blk_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, di)

    q_spec_q = pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0))
    kv_spec_k = pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0))
    lm_spec_q = pl.BlockSpec((1, blk_q, _LANES), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, offset=offset),
        grid=(bh, sq // blk_q, sk // blk_k),
        in_specs=[q_spec_q, kv_spec_k, kv_spec_k, q_spec_q, lm_spec_q,
                  lm_spec_q],
        out_specs=q_spec_q,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# ------------------------------------------------------------- public op


def _shapes_ok(q, k, blk=128):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    return (
        sq % min(blk, sq) == 0
        and sk % min(blk, sk) == 0
        and sq >= 8
        and sk >= 8
        and d in (64, 128, 256)
    )


def _flatten_heads(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _unflatten_heads(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, scale, causal, blk_q=128, blk_k=128):
    b, sq, h, d = q.shape
    of = _flash_forward(_flatten_heads(q), _flatten_heads(k),
                        _flatten_heads(v), scale, causal,
                        blk_q=blk_q, blk_k=blk_k)
    return _unflatten_heads(of, b, h)


def _flash_fwd_rule(q, k, v, scale, causal, blk_q=128, blk_k=128):
    b, sq, h, d = q.shape
    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    of, lse = _flash_forward(qf, kf, vf, scale, causal, blk_q=blk_q,
                             blk_k=blk_k, with_lse=True)
    return _unflatten_heads(of, b, h), (qf, kf, vf, of, lse)


def _flash_bwd_rule(scale, causal, blk_q, blk_k, res, do):
    qf, kf, vf, of, lse = res
    b, sq, h, d = do.shape
    dq, dk, dv = _flash_backward(qf, kf, vf, of, lse, _flatten_heads(do),
                                 scale, causal, blk_q=blk_q, blk_k=blk_k)
    return (_unflatten_heads(dq, b, h), _unflatten_heads(dk, b, h),
            _unflatten_heads(dv, b, h))


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


#: Flash tiles by device kind and kv sequence length: (blk_q, blk_k).
#: Measured on a v5e in bf16, forward and backward, each candidate checked
#: against the reference before it was timed (CHANGES.md, PR 1). The
#: benchmark's `train-1chip` cell runs the 1024 row; its ledger lines read
#: it as `flash_time_share_pct`. A kind that is not here keeps the 128s:
#: tiles verified on one TPU generation are not adopted on another (VMEM
#: limits differ; Mosaic may reject them).
_TUNED_BLOCKS = {
    "TPU v5 lite": {1024: (512, 512), 2048: (512, 512),
                    4096: (512, 512), 8192: (512, 512)},
}


def _tuned_blocks(seq):
    """Measured tiles for this chip at the nearest measured seqlen, or
    None. The shared kernel-tuning store (:mod:`paddle_tpu.ops.tuning`,
    kernel ``"flash_fwd"``, bucketed by seqlen, device-kind gated) is
    consulted first; ``_TUNED_BLOCKS`` second."""
    from . import tuning

    rec = tuning.lookup("flash_fwd", tuning.bucket_key(s=seq))
    if rec and "blk_q" in rec and "blk_k" in rec:
        return int(rec["blk_q"]), int(rec["blk_k"])
    table = _TUNED_BLOCKS.get(jax.devices()[0].device_kind)
    # only adopt within the measured range: a tiling verified at 8192 was
    # never lowered at 512 (different VMEM footprint; Mosaic may reject
    # it), and short seqs route through XLA attention anyway
    if not table or seq < min(table):
        return None
    return table[min(table, key=lambda s: abs(s - seq))]


def _default_blocks(seq=None):
    """Kernel tiling: FLAGS_flash_block_q/_k. 128 matches the MXU/lane
    width and is the safe default; larger k-blocks amortize grid overhead
    at long context. With the flags at their defaults the tiles measured
    for this chip (``_tuned_blocks``) are taken; any other flag value
    wins."""
    from ..core import flags

    bq = int(flags.flag("flash_block_q"))
    bk = int(flags.flag("flash_block_k"))
    if (bq, bk) == (128, 128) and seq is not None:
        tuned = _tuned_blocks(seq)
        if tuned:
            return tuned
    return bq, bk


def flash_attention(q, k, v, scale: Optional[float] = None, causal: bool = False,
                    blk_q: Optional[int] = None, blk_k: Optional[int] = None):
    """Blockwise flash attention, layout [batch, seq, heads, head_dim]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if not _HAS_PALLAS or not _shapes_ok(q, k):
        return _attention_reference(q, k, v, scale, causal)
    dq, dk = _default_blocks(seq=k.shape[1])
    blk_q = blk_q or dq
    blk_k = blk_k or dk
    # block sizes must tile the sequence, and the backward's lane-broadcast
    # lse/delta tiling (reps = blk_k // 128 in _bwd_common) needs blk_k to
    # be <=128 or a multiple of 128; fall back to the safe 128s otherwise
    sq, sk = q.shape[1], k.shape[1]
    if (sq % min(blk_q, sq) or sk % min(blk_k, sk)
            or (blk_k > _LANES and blk_k % _LANES)
            or blk_q % 8):
        blk_q = blk_k = 128
    return _flash_attention(q, k, v, scale, causal, blk_q, blk_k)
