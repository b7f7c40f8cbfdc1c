"""Pallas paged-attention kernels over KV-arena block tables.

The serving engine's XLA path pays a *gather tax* on every decode step:
``engine._gather_ctx`` materializes each lane's whole logical context
(``kp[table]`` — ``[S, max_blocks*block_size, H, D]`` of mostly-masked
rows, dequantized from int8 first when the arena is quantized) before
``masked_attention`` reads a single useful element. These kernels read
K/V **directly through the block tables instead**: the table rides as a
scalar-prefetch operand and each grid step's BlockSpec ``index_map``
resolves one logical block to its physical pool row, so HBM traffic is
the pool blocks themselves — no contiguous copy, no f32 materialization
of an int8 arena (per-block scales stream alongside the payload and fold
into the scores and probabilities in VMEM —
:func:`paddle_tpu.quantization.dequantize_kv`'s math, see
:func:`_attend_block`).

Two kernels, same online-softmax core as the training flash kernel
(:mod:`paddle_tpu.ops.pallas_ops`):

* :func:`paged_decode_attention` — one new token per slot. Grid
  ``(slots, head-groups, logical blocks)``; each lane's ``positions``
  entry masks keys past its own context (``start_pos`` semantics of
  ``engine._PagedCacheView``), and whole blocks past the position are
  predicated off with ``pl.when``.
* :func:`paged_prefill_attention` — a suffix/chunk of queries for ONE
  slot against its table (the ``engine._PrefixPrefillView`` contract):
  query ``i`` sits at global position ``prefix_len + i`` and attends
  keys at global index ``<= prefix_len + i``. ``prefix_len`` is runtime
  data (scalar prefetch), so every chunk of every admission reuses one
  compiled program per suffix bucket.

Block tables, positions and prefix lengths are *runtime data*
(scalar-prefetch operands): admit/retire/accept/reject churn never
recompiles — the same invariant the XLA path holds. Launch parameters
(``block_h`` head grouping, ``block_q`` query tiling) come from the
shared per-(kernel, chip, shape-bucket) tuning store
(:mod:`paddle_tpu.ops.tuning`); absent a record the safe defaults run.

Numerics: the online softmax is mathematically identical to the gather
path's full-width softmax but associates differently, so parity is
*tolerance*, not bitwise — see docs/performance.md ("Paged attention
kernels") for the documented bound and the greedy token-parity gate.
Off-TPU the kernels run in the Pallas interpreter
(:func:`~paddle_tpu.ops.pallas_ops._use_interpret`), so tier-1 exercises
this exact code path on the CPU mesh.

SPMD partitioning (ISSUE 16): every public entry takes ``mesh=``. On a
multi-device mesh the call routes through
:func:`~paddle_tpu.distributed.sharding_util.headwise_shard_map` —
``shard_kv_entry`` already committed the K/V payload pools heads-sharded
over the "model" axis, so each device runs this SAME kernel on its local
head shard (the grid's head-group math sees the local ``H``) through the
replicated per-slot block tables, with zero cross-chip K/V traffic; the
heads-sharded output hands straight to the row-parallel output
projection's psum. Launch params resolve from the tuning store under the
mesh-topology key (:func:`paddle_tpu.ops.tuning.lookup` with ``mesh=``)
BEFORE the manual region, against the local head count. A 1-device mesh
(or ``mesh=None``) skips the wrapper entirely — bit-identical to PR 13.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .pallas_ops import NEG_INF, _HAS_PALLAS, _LANES, _use_interpret

if _HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

__all__ = ["available", "paged_decode_attention", "paged_prefill_attention",
           "paged_full_prefill_attention"]


def available() -> bool:
    """Whether the paged kernels can run here (Pallas importable with
    scalar-prefetch support). The engine checks ONCE at construction and
    refuses to build a kernel engine without them — never a traced
    branch, never a silent gather path."""
    return _HAS_PALLAS and hasattr(pltpu, "PrefetchScalarGridSpec")


def _head_group(num_heads: int, block_h) -> int:
    """Clamp a (tuned) head-group size to a divisor of ``num_heads``.
    Default: all heads in one grid step (fewest steps — the right call
    for small pools and the interpreter; a chip tune may prefer smaller
    groups to fit VMEM at large head_dim)."""
    g = int(block_h) if block_h else num_heads
    g = max(1, min(g, num_heads))
    while num_heads % g:
        g -= 1
    return g


def _query_block(sq: int, block_q) -> int:
    """Clamp a (tuned) query tile to a divisor of the (bucketed) suffix
    length."""
    b = int(block_q) if block_q else min(sq, 128)
    b = max(1, min(b, sq))
    while sq % b:
        b -= 1
    return b


def _mesh_routes(mesh) -> bool:
    """Whether ``mesh`` routes a call through the manual shard_map wrapper:
    only a MULTI-device mesh does — a 1-device mesh (the default
    deployment posture) or no mesh calls pallas directly, so those two
    stay bit-identical by construction."""
    return mesh is not None and int(mesh.devices.size) > 1


def _local_heads(num_heads: int, mesh) -> int:
    """The per-device head count inside the manual region: ``H // mp``
    when the payload pools shard (``shard_kv_entry``'s divisibility rule),
    else the full ``H`` (replicated pools, replicated kernel)."""
    from ..distributed.sharding_util import MODEL_AXIS

    mp = mesh.shape.get(MODEL_AXIS, 1)
    return num_heads // mp if (mp > 1 and num_heads % mp == 0) \
        else num_heads


#: query rows per decode tile. Mosaic lowers the heads-batched contraction
#: only when the lhs has a free (row) dimension, so the slot's single
#: query is broadcast IN VMEM to one f32 sublane tile; row 0 is written
#: back. Decode attention is bound by the K/V block reads, not these rows.
_DECODE_ROWS = 8

#: int8 scale pools stream in whole f32 sublane tiles: a ``(1, bs)`` block
#: of a ``[num_blocks, bs]`` array is not a legal TPU block shape, an
#: ``(8, bs)`` one is. The kernel picks its row out of the tile.
_SCALE_ROWS = 8


def _scale_spec(bs, block_of):
    """BlockSpec of one scale pool: the sublane tile holding the physical
    block that ``block_of(*grid_and_prefetch_args)`` names."""
    return pl.BlockSpec(
        (_SCALE_ROWS, bs), lambda *a: (block_of(*a) // _SCALE_ROWS, 0))


def _scale_row(ref, block):
    """``[1, bs]`` per-token scales of physical ``block`` out of its
    tile (keys on lanes — the layout of a score row)."""
    return ref[pl.ds(block % _SCALE_ROWS, 1), :]


def _attend_block(q, k, v, k_scale, v_scale, visible, scale,
                  m_scr, l_scr, acc_scr):
    """Online-softmax update of ``[blk_h, rows, ...]`` scratch with one
    KV block: ``q`` ``[blk_h, rows, D]`` (head-major — the batch dim leads
    the lhs and the lhs keeps a free dim, the one form of this contraction
    Mosaic lowers), ``k``/``v`` ``[bs, blk_h, D]``, ``visible`` a mask
    broadcastable to ``[blk_h, rows, bs]``.

    An int8 block is NOT dequantized element-wise: its per-token scales
    (``[1, bs]``, keys on lanes) fold into the score columns and the
    probabilities — ``q.(k*s) == (q.k)*s`` and ``p@(v*s) == (p*s)@v`` —
    which is :func:`paddle_tpu.quantization.dequantize_kv`'s math without
    a relayout of the scale row across the block's leading axis (int8
    values are exact in every compute dtype)."""
    if k_scale is not None:
        k, v = k.astype(q.dtype), v.astype(q.dtype)
    sc = jax.lax.dot_general(  # [blk_h, rows, bs]
        q, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        sc = sc * k_scale
    sc = jnp.where(visible, sc, NEG_INF)
    m_prev = m_scr[:, :, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
    p = jnp.exp(sc - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    pv = jax.lax.dot_general(  # [blk_h, rows, D]
        p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * corr + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _normalized(l_scr, acc_scr):
    denom = l_scr[:, :, 0:1]
    return acc_scr[:] / jnp.where(denom == 0.0, 1.0, denom)


# ---------------------------------------------------------------- decode


def _decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest, bs, blk_h,
                   scale, quantized):
    """One (slot, head-group, logical-block) step: online softmax of the
    slot's single query against one physical KV block, masked to keys at
    global index ``<= positions[slot]``."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        (o_ref, m_scr, l_scr, acc_scr), ks_ref, vs_ref = rest, None, None
    s = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = pos_ref[s]

    # whole blocks past the lane's position contribute nothing — skip the
    # math (the masked-lane/garbage-query cases still produce finite
    # output: key 0 is always <= pos, so the denominator never zeroes)
    @pl.when(j * bs <= pos)
    def _step():
        q = q_ref[0]  # [blk_h, 1, D]
        q = jnp.broadcast_to(q, (blk_h, _DECODE_ROWS, q.shape[-1]))
        block = bt_ref[s, j]
        gk = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        _attend_block(
            q, k_ref[0], v_ref[0],
            _scale_row(ks_ref, block) if quantized else None,
            _scale_row(vs_ref, block) if quantized else None,
            gk <= pos, scale, m_scr, l_scr, acc_scr)

    @pl.when(j == nj - 1)
    def _fin():
        o_ref[0] = _normalized(l_scr, acc_scr)[:, 0:1, :].astype(o_ref.dtype)


def paged_decode_attention(q, entry, block_tables, positions,
                           block_h=None, mesh=None):
    """Decode attention straight through the block tables.

    ``q`` is ``[S, H, D]`` (each slot's new token, heads unflattened);
    ``entry`` is one layer's whole arena pool entry — ``(k, v)`` pools
    shaped ``[num_blocks, block_size, H, D]``, or int8
    ``(k, v, k_scale, v_scale)`` with ``[num_blocks, block_size]`` scale
    pools (dequantized in-kernel — the f32 full-width context of the
    gather path is never materialized). ``block_tables`` is ``[S, MB]``
    int32, ``positions`` ``[S]`` int32 (the new token's write position —
    keys at global index ``<= positions[s]`` are attended, matching
    ``masked_attention``'s mask in ``_PagedCacheView``). Returns
    ``[S, H, D]`` in ``q.dtype``. All table/position operands are
    runtime data: one compiled program serves every churn pattern.
    On a multi-device ``mesh`` the call runs per model-shard (module
    docstring, "SPMD partitioning")."""
    if _mesh_routes(mesh):
        return _sharded_decode(q, entry, block_tables, positions,
                               block_h, mesh)
    S, H, D = q.shape
    quantized = len(entry) == 4
    kp, vp = entry[0], entry[1]
    bs = kp.shape[1]
    MB = block_tables.shape[1]
    if block_h is None:
        from . import tuning

        rec = tuning.lookup("paged_decode",
                            tuning.bucket_key(h=H, d=D, bs=bs, mb=MB))
        block_h = rec.get("block_h") if rec else None
    blk_h = _head_group(H, block_h)
    grid = (S, H // blk_h, MB)
    kern = functools.partial(_decode_kernel, bs=bs, blk_h=blk_h,
                             scale=1.0 / math.sqrt(D), quantized=quantized)
    # [S, H, 1, D] view: the (1, D) trailing block dims equal the array's,
    # so any head grouping is a legal block, and the kernel reads its
    # query head-major without a transpose
    q_spec = pl.BlockSpec((1, blk_h, 1, D),
                          lambda s, g, j, bt, pos: (s, g, 0, 0))
    kv_spec = pl.BlockSpec((1, bs, blk_h, D),
                           lambda s, g, j, bt, pos: (bt[s, j], 0, g, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [block_tables, positions, q[:, :, None, :], kp, vp]
    if quantized:
        in_specs += [_scale_spec(
            bs, lambda s, g, j, bt, pos: bt[s, j])] * 2
        args += [entry[2], entry[3]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((blk_h, _DECODE_ROWS, _LANES), jnp.float32),  # max
            pltpu.VMEM((blk_h, _DECODE_ROWS, _LANES), jnp.float32),  # denom
            pltpu.VMEM((blk_h, _DECODE_ROWS, D), jnp.float32),  # out acc
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
        name="paged_decode",
    )(*args)
    return out[:, :, 0, :]


def _sharded_decode(q, entry, block_tables, positions, block_h, mesh):
    """Per-shard decode: resolve launch params OUTSIDE the manual region
    under the mesh-topology tuning key (against the LOCAL head count each
    device actually launches with), then map the plain kernel over the
    mesh — heads-sharded q/K/V in, replicated tables/positions/scales
    through, heads-sharded output back."""
    from ..distributed.sharding_util import (headwise_shard_map,
                                             mesh_axes_key)

    S, H, D = q.shape
    if block_h is None:
        from . import tuning

        rec = tuning.lookup(
            "paged_decode",
            tuning.bucket_key(h=_local_heads(H, mesh), d=D,
                              bs=entry[0].shape[1],
                              mb=block_tables.shape[1]),
            mesh=mesh_axes_key(mesh))
        block_h = (rec or {}).get("block_h") or 0
    n = len(entry)

    def kernel(q, *rest):
        # block_h=0 means "safe default, no store lookup" to the plain
        # entry point — the mesh-keyed lookup above already ran
        return paged_decode_attention(q, rest[:n], rest[n], rest[n + 1],
                                      block_h=block_h or 0)

    mapped = headwise_shard_map(
        kernel, mesh,
        in_head_dims=(1, 2, 2) + (None,) * (n - 2) + (None, None),
        out_head_dim=1, num_heads=H)
    return mapped(q, *entry, block_tables, positions)


# --------------------------------------------------------------- prefill


def _prefill_kernel(bt_ref, meta_ref, q_ref, k_ref, v_ref, *rest, bs,
                    blk_q, blk_h, scale, quantized):
    """One (head-group, query-tile, logical-block) step of suffix/chunk
    prefill: flash-style causal attention at global positions
    ``prefix_len + i`` (``meta_ref[0]`` = the runtime prefix length)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        (o_ref, m_scr, l_scr, acc_scr), ks_ref, vs_ref = rest, None, None
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    prefix = meta_ref[0]

    # a block strictly past this tile's last global row is fully masked
    @pl.when(j * bs <= prefix + (qi + 1) * blk_q - 1)
    def _step():
        block = bt_ref[j]
        rows = prefix + qi * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (1, blk_q, bs), 1)
        cols = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, blk_q, bs), 2)
        _attend_block(  # q is [blk_h, blk_q, D], head-major (the wrapper)
            q_ref[:], k_ref[0], v_ref[0],
            _scale_row(ks_ref, block) if quantized else None,
            _scale_row(vs_ref, block) if quantized else None,
            cols <= rows, scale, m_scr, l_scr, acc_scr)

    @pl.when(j == nj - 1)
    def _fin():
        o_ref[:] = _normalized(l_scr, acc_scr).astype(o_ref.dtype)


def paged_prefill_attention(q, entry, bt_row, prefix_len,
                            block_q=None, block_h=None, mesh=None):
    """Suffix/chunk prefill attention for ONE slot through its table.

    ``q`` is ``[sq, H, D]`` (the padded suffix bucket — padded rows
    produce garbage the caller discards, exactly like the XLA path);
    ``bt_row`` is ``[MB]`` int32, ``prefix_len`` a (traced) scalar: query
    ``i`` attends keys at global index ``<= prefix_len + i``, the
    ``_PrefixPrefillView`` mask verbatim. The suffix's own K/V must
    already be scattered into the pools (same call order as the XLA
    path: scatter, then attend). Returns ``[sq, H, D]``. On a
    multi-device ``mesh`` the call runs per model-shard (module
    docstring, "SPMD partitioning")."""
    if _mesh_routes(mesh):
        return _sharded_prefill(q, entry, bt_row, prefix_len,
                                block_q, block_h, mesh)
    sq, H, D = q.shape
    quantized = len(entry) == 4
    kp, vp = entry[0], entry[1]
    bs = kp.shape[1]
    MB = bt_row.shape[0]
    if block_q is None and block_h is None:
        from . import tuning

        rec = tuning.lookup(
            "paged_prefill",
            tuning.bucket_key(sq=sq, h=H, d=D, bs=bs, mb=MB))
        if rec:
            block_q, block_h = rec.get("block_q"), rec.get("block_h")
    blk_q = _query_block(sq, block_q)
    blk_h = _head_group(H, block_h)
    grid = (H // blk_h, sq // blk_q, MB)
    kern = functools.partial(_prefill_kernel, bs=bs, blk_q=blk_q,
                             blk_h=blk_h, scale=1.0 / math.sqrt(D),
                             quantized=quantized)
    # head-major query/output layout so neither the kernel nor Mosaic
    # transposes inside VMEM; the swapaxes below stay in XLA
    q_hm = jnp.swapaxes(q, 0, 1)  # [H, sq, D]
    in_specs = [
        pl.BlockSpec((blk_h, blk_q, D),
                     lambda g, qi, j, bt, meta: (g, qi, 0)),
        pl.BlockSpec((1, bs, blk_h, D),
                     lambda g, qi, j, bt, meta: (bt[j], 0, g, 0)),
        pl.BlockSpec((1, bs, blk_h, D),
                     lambda g, qi, j, bt, meta: (bt[j], 0, g, 0)),
    ]
    args = [bt_row, jnp.reshape(jnp.asarray(prefix_len, jnp.int32), (1,)),
            q_hm, kp, vp]
    if quantized:
        in_specs += [_scale_spec(
            bs, lambda g, qi, j, bt, meta: bt[j])] * 2
        args += [entry[2], entry[3]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk_h, blk_q, D),
                               lambda g, qi, j, bt, meta: (g, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((blk_h, blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_h, blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_h, blk_q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, sq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
        name="paged_prefill",
    )(*args)
    return jnp.swapaxes(out, 0, 1)


def _sharded_prefill(q, entry, bt_row, prefix_len, block_q, block_h, mesh):
    """Per-shard suffix/chunk prefill — same structure as
    :func:`_sharded_decode`; ``prefix_len`` rides replicated like the
    table (runtime data, identical on every device)."""
    from ..distributed.sharding_util import (headwise_shard_map,
                                             mesh_axes_key)

    sq, H, D = q.shape
    if block_q is None and block_h is None:
        from . import tuning

        rec = tuning.lookup(
            "paged_prefill",
            tuning.bucket_key(sq=sq, h=_local_heads(H, mesh), d=D,
                              bs=entry[0].shape[1], mb=bt_row.shape[0]),
            mesh=mesh_axes_key(mesh))
        block_q = (rec or {}).get("block_q") or 0
        block_h = (rec or {}).get("block_h") or 0
    n = len(entry)

    def kernel(q, *rest):
        return paged_prefill_attention(q, rest[:n], rest[n], rest[n + 1],
                                       block_q=block_q or 0,
                                       block_h=block_h or 0)

    mapped = headwise_shard_map(
        kernel, mesh,
        in_head_dims=(1, 2, 2) + (None,) * (n - 2) + (None, None),
        out_head_dim=1, num_heads=H)
    return mapped(q, *entry, bt_row,
                  jnp.asarray(prefix_len, jnp.int32))


def paged_full_prefill_attention(q, k, v, block_size,
                                 block_q=None, block_h=None, mesh=None):
    """Full (no-table) causal prefill through the SAME kernel — the PR 13
    open item: a cache-miss admission has no resident prefix and no block
    table yet, but the flash-style kernel above is exactly the right
    attention for it too. Contiguous ``k``/``v`` (``[sq, H, D]``, the
    chunk's own keys/values) are viewed as ``ceil(sq/bs)`` **pseudo-blocks**
    and addressed through an identity (``arange``) pseudo-table with
    ``prefix_len = 0``: query ``i`` attends keys ``<= i`` — the
    ``_CapturePrefillView`` causal mask verbatim. The pad rows a non-divisible
    ``sq`` adds sit at key positions ``>= sq``, above every query row, so
    the mask discards them like the XLA path's padding. One reshape/pad in
    XLA; no gather, no ``[sq, sq]`` materialized probability matrix —
    kernel-on engines have no gather-path prefill left."""
    sq, H, D = q.shape
    bs = int(block_size)
    nb = -(-sq // bs)
    pad = nb * bs - sq
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    entry = (k.reshape(nb, bs, H, D), v.reshape(nb, bs, H, D))
    table = jnp.arange(nb, dtype=jnp.int32)
    return paged_prefill_attention(q, entry, table, jnp.int32(0),
                                   block_q=block_q, block_h=block_h,
                                   mesh=mesh)
