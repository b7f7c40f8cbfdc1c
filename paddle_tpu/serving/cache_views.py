"""The server's side of the engine<->model seam, and the only module that
names a KIND of layer state: the cache views a layer of each kind drives
(their protocols: the head of ``models/serving_seam.py``), the writers of
the arena's pools, and one table by kind (:data:`KINDS`) that says what
else the server must know. The engine builds every program's views,
commits a prefill, sizes the arena, refuses options and reports gauges by
asking the table; ``serving/disagg/pool.py`` asks it what it cannot hand
over. A new kind is a dataclass in the seam and one entry here
(docs/serving_model_seam.md, "A new kind of layer state").

The views are trace-time objects: built inside a compiled program from its
traced arguments, they emit their operations when the layer drives them
and hand back a successor whose ``entry`` the program returns. The route a
view takes (``kernel``, ``mesh``, an int8 entry's tuple length) is
structure, fixed when the engine is built, never a traced branch.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..models.serving_seam import SharedRef, masked_attention
from ..ops import paged_attention as pa
from ..ops import sparse_attention as sa
from ..quantization import dequantize_kv, quantize_kv


def _raw(*ts):
    """The arrays of what a layer hands over (Tensors or bare arrays)."""
    return tuple(t._data if isinstance(t, Tensor) else t for t in ts)


def scatter_rows(entry, row, off, kc, vc):
    """Scatter one chunk's k/v rows at ``(row, off)`` into a pool entry.
    A full-precision ``(k, v)`` entry writes the rows as-is (op-for-op
    the pre-quantization path); an int8 ``(k, v, k_scale, v_scale)``
    entry quantizes-on-scatter: each token row is symmetric-int8 quantized
    (:func:`paddle_tpu.quantization.quantize_kv`) and its per-row scale
    lands in the scale pools at the SAME (row, off) — payload and scale
    can never go out of step. The entry-length branch is tuple structure
    (static at trace time), never traced data."""
    if len(entry) == 2:
        kp, vp = entry
        return (kp.at[row, off].set(kc), vp.at[row, off].set(vc))
    kp, vp, ks, vs = entry
    qk, sk = quantize_kv(kc)
    qv, sv = quantize_kv(vc)
    return (kp.at[row, off].set(qk), vp.at[row, off].set(qv),
            ks.at[row, off].set(sk), vs.at[row, off].set(sv))


def scatter_blocks(entry, table_rows, true_len, kc, vc, block_size: int):
    """A whole prompt's k/v ``[p, H, D]`` into the slot's blocks
    (``table_rows``: the block of each ``block_size`` positions), in
    whole BLOCKS: the scatter's window then covers every minor dimension
    of a pool, so the chip writes it in place whichever way it lays the
    pool out (a window of one position's ``(H, D)`` is not minor in a
    head-major pool, ``paged_attention._head_major``, and
    :func:`scatter_rows` there costs three copies of the whole pool). A
    full prefill starts at position 0 of blocks the slot owns alone. A
    block whose first position is at or past ``true_len`` is padding and
    lands in scratch block 0; the one that straddles ``true_len`` carries
    padded positions behind real ones, each of which the decode step that
    writes that position (``write_token``) replaces before any mask lets
    it be read (``<= pos``). A chunk that is not a whole number of blocks
    is padded up to one, behind ``true_len``. An int8 ``(k, v, k_scale,
    v_scale)`` entry quantizes as :func:`scatter_rows` does, payload and
    scale pools written by the same block ids."""
    n_blk = -(-kc.shape[0] // block_size)
    blk = jnp.where(jnp.arange(n_blk) * block_size < true_len,
                    table_rows[:n_blk], 0)
    pad = ((0, n_blk * block_size - kc.shape[0]), (0, 0), (0, 0))
    kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
    if len(entry) == 4:
        (kc, sk), (vc, sv) = quantize_kv(kc), quantize_kv(vc)
        chunks = (kc, vc, sk, sv)
    else:
        chunks = (kc, vc)

    def write(pool, chunk):
        chunk = chunk.reshape((n_blk, block_size) + chunk.shape[1:])
        if pool.ndim == 2:
            # a scale pool's row is narrower than a lane tile: to scatter
            # whole rows the compiler transposes the pool and back, so
            # each scale goes where it lies, by the same block ids
            return pool.at[blk[:, None], jnp.arange(block_size)].set(chunk)
        return pool.at[blk].set(chunk)

    return tuple(write(p, c) for p, c in zip(entry, chunks))


@jax.named_scope("kv_gather")  # metadata on the device's operations
def gather_ctx(entry, table, dtype):
    """Gather a block table's logical context from one pool entry:
    ``table`` is ``[..., max_blocks]`` int32; returns ``(k_all, v_all)``
    shaped ``[..., max_blocks*block_size, heads, dim]``. Int8 entries
    dequantize-on-attend through their per-row scales in f32 before the
    cast to the attention compute ``dtype`` — per table ROW (``lax.map``
    over the lanes) when the compute dtype is narrower than f32, so the
    f32 intermediate is one lane's context, never a second full-width
    copy of the whole batch's. Per-element math is identical either way
    (one f32 multiply, one cast), so the output is bitwise the same."""
    kp, vp = entry[0], entry[1]
    if len(entry) == 4:
        ks, vs = entry[2], entry[3]
        if jnp.dtype(dtype).itemsize >= 4:
            # f32 compute: the dequant output IS the f32 buffer — nothing
            # to save by chunking
            k_all = dequantize_kv(kp[table], ks[table], dtype)
            v_all = dequantize_kv(vp[table], vs[table], dtype)
        else:
            def _deq_lane(row):  # row: one lane's [max_blocks] table
                return (dequantize_kv(kp[row], ks[row], dtype),
                        dequantize_kv(vp[row], vs[row], dtype))

            lanes = table.reshape(-1, table.shape[-1])
            k_all, v_all = jax.lax.map(_deq_lane, lanes)
            k_all = k_all.reshape(table.shape + kp.shape[1:])
            v_all = v_all.reshape(table.shape + vp.shape[1:])
    else:
        k_all = kp[table]
        v_all = vp[table]  # [..., mb, bs, H, D]
    shp = k_all.shape
    out_shape = shp[:-4] + (shp[-4] * shp[-3],) + shp[-2:]
    return k_all.reshape(out_shape), v_all.reshape(out_shape)


class PagedCacheView:
    """One ``"kv"`` layer's decode-step view of the paged arena: write the
    new token's k/v at each lane's (block, offset), gather the lane's
    block table, and attend under the per-lane position mask. ``entry`` is
    the layer's whole arena pool entry — ``(k, v)`` or, with
    ``FLAGS_serving_quant_kv``, ``(k, v, k_scale, v_scale)``
    (quantize-on-scatter / dequant-on-attend via :func:`scatter_rows` /
    :func:`gather_ctx`).

    With ``kernel=True`` (the engine's ``decode_kernel``) the attend side
    routes through the Pallas paged-decode kernel
    (:func:`paddle_tpu.ops.paged_attention.paged_decode_attention`):
    K/V are read directly through the block table — no gather into a
    contiguous ``[S, max_blocks*bs, H, D]`` buffer, int8 dequant fused
    in-kernel, a lane costing the pages it has live and a lane that is
    not ``active`` none. The scatter of the new token stays in XLA either
    way (one row per lane). On a multi-device ``mesh`` the kernel call
    runs per model-shard through ``headwise_shard_map``; None keeps the
    direct pallas path."""

    def __init__(self, entry, block_tables, positions, active,
                 block_size: int, kernel: bool = False, mesh=None):
        self.entry = entry
        self.block_tables = block_tables  # [S, max_blocks] int32
        self.positions = positions        # [S] int32: write pos of new token
        self.active = active              # [S] bool
        self.block_size = block_size
        self.kernel = kernel
        self.mesh = mesh

    def update_and_attend(self, q, k, v):
        qa, ka, va = _raw(q, k, v)
        s_lanes = qa.shape[0]
        bs = self.block_size
        pos = self.positions
        # physical write target; inactive lanes are routed to scratch block
        # 0 so their (garbage) writes never touch live cache state
        row = self.block_tables[jnp.arange(s_lanes), pos // bs]
        row = jnp.where(self.active, row, 0)
        off = pos % bs
        if self.kernel and len(self.entry) == 2 and self.mesh is None:
            # the same write as scatter_rows, made in place whichever
            # way the chip lays the pool out, as the kernel reads it (an
            # int8 entry quantizes on scatter, and a pool sharded over
            # heads keeps its head axis, below)
            entry = tuple(pa.write_token(pool, row, off, new[:, 0])
                          for pool, new in zip(self.entry, (ka, va)))
        else:
            entry = scatter_rows(self.entry, row, off, ka[:, 0], va[:, 0])
        o = paged_attend(self, qa, entry)
        return o, PagedCacheView(entry, self.block_tables, pos, self.active,
                                 bs, kernel=self.kernel, mesh=self.mesh)

    def reader(self):
        """A view for a ``"shared"`` layer: it attends this pool as it is
        now (this call's token already written) and writes nothing."""
        return PagedReadView(self)


def paged_attend(view, qa, entry):
    """The attend side of the decode step's paged views: each lane's one
    query ``qa`` ``[S, 1, heads, D]`` against its block table's context in
    ``entry``, up to and including its write position."""
    pos, bs = view.positions, view.block_size
    if view.kernel:
        return pa.paged_decode_attention(
            qa[:, 0], entry, view.block_tables, pos, active=view.active,
            mesh=view.mesh)[:, None]
    # gather each lane's logical context [S, max_blocks*bs, H, D]
    t_len = view.block_tables.shape[1] * bs
    k_all, v_all = gather_ctx(entry, view.block_tables, qa.dtype)
    mask = (jnp.arange(t_len)[None, :] <= pos[:, None])[:, None, None, :]
    return masked_attention(qa, k_all, v_all, mask)


class PagedReadView:
    """A ``"shared"`` layer's decode-step view: the pool entry of the layer
    it names, read through the same block tables, never written."""

    def __init__(self, source: "PagedCacheView"):
        self.source = source

    def attend(self, q):
        qa, = _raw(q)
        return paged_attend(self.source, qa, self.source.entry)


class CapturePrefillView:
    """One ``"kv"`` layer's full-prefill view: plain causal attention over
    the (padded) prompt chunk, returning the chunk's k/v as the successor
    for the commit to write into the slot's blocks (:func:`scatter_blocks`).

    With ``kernel=True`` a whole prompt (every query row present) goes
    through the flash prefill kernel
    (:func:`paddle_tpu.ops.paged_attention.swa_prefill_attention` with no
    window: tiles of up to 512 rows, grouped queries through the index
    map, the tiles above the diagonal neither multiplied nor copied); on a
    mesh of several chips through the paged prefill kernel's no-table
    entry (:func:`~paddle_tpu.ops.paged_attention.paged_full_prefill_attention`,
    which shards over heads and walks the keys a block of the pool at a
    time). ``kernel=False``: ``masked_attention``."""

    def __init__(self, block_size: int = 0, kernel: bool = False,
                 mesh=None, last=None):
        self.block_size = block_size
        self.kernel = kernel
        self.mesh = mesh
        #: a prefill whose later layers run on the last valid row alone
        #: (``ServingSpec.prefill_tail``): that row's index, traced. The
        #: layer may then hand over one query row (that one) with every
        #: row's K/V
        self.last = last

    def update_and_attend(self, q, k, v):
        qa, ka, va = _raw(q, k, v)
        captured = CapturedKV(ka, va, self.last)
        if self.kernel and qa.shape[1] == ka.shape[1]:  # not one row alone
            if self.mesh is None:
                o = pa.swa_prefill_attention(qa[0], ka[0], va[0])[None]
            else:
                o = pa.paged_full_prefill_attention(
                    qa[0], ka[0], va[0], self.block_size,
                    mesh=self.mesh)[None]
            return o, captured
        return captured.attend(qa), captured


class CapturedKV:
    """What a ``"kv"`` layer's prefill leaves behind: the chunk's K and V,
    which the commit writes into the slot's blocks and a ``"shared"``
    layer of the same call reads (:meth:`reader`)."""

    def __init__(self, ka, va, last=None):
        self.k, self.v, self.last = ka, va, last

    def reader(self):
        return self

    def attend(self, q):
        """Causal attention of ``q`` over the captured rows: as many rows
        as were captured, each at its own position, or one row, at
        ``last``."""
        qa, = _raw(q)
        p = self.k.shape[1]
        cols = jnp.arange(p)[None, :]
        rows = (jnp.arange(p)[:, None] if qa.shape[1] == p
                else jnp.reshape(self.last, (1, 1)))
        mask = (cols <= rows)[None, None]
        return masked_attention(qa, self.k, self.v, mask)


class PrefixPrefillView:
    """Suffix-only prefill over a slot whose prefix KV is already resident
    (matched radix-cache blocks attached to the block table by reference):
    scatter only the suffix chunk's k/v at global positions
    ``prefix_len + i`` via the slot's table, then attend each suffix query
    against the full gathered context — prefix blocks are read, never
    recomputed. ``prefix_len`` is a traced scalar and the table is runtime
    int32 data, so every (cache hit, prefix length) reuses ONE compiled
    program per suffix-length bucket.

    With ``kernel=True`` the attend side routes through the Pallas
    chunked-prefill kernel
    (:func:`paddle_tpu.ops.paged_attention.paged_prefill_attention`):
    same scatter-then-attend order, same global-position mask, the
    resident prefix streamed block by block through the table. Chunked
    prefill rides this view too. Every layer is a ``"kv"`` layer here:
    the options that reach this view refuse any other kind."""

    def __init__(self, entry, bt_row, prefix_len, true_len,
                 block_size: int, kernel: bool = False, mesh=None):
        self.entry = entry            # the layer's whole arena pool entry
        self.bt_row = bt_row          # [max_blocks] int32: the slot's table
        self.prefix_len = prefix_len  # scalar int32: resident context length
        self.true_len = true_len      # scalar int32: real (unpadded) suffix
        self.block_size = block_size
        self.kernel = kernel
        self.mesh = mesh

    def update_and_attend(self, q, k, v):
        qa, ka, va = _raw(q, k, v)
        p = qa.shape[1]
        bs = self.block_size
        p_idx = jnp.arange(p)
        gpos = self.prefix_len + p_idx  # global write positions
        bi = jnp.clip(gpos // bs, 0, self.bt_row.shape[0] - 1)
        # padded suffix positions scatter into the scratch block, exactly
        # like full prefill's padding — bucketing never pollutes live state
        row = jnp.where(p_idx < self.true_len, self.bt_row[bi], 0)
        off = gpos % bs
        entry = scatter_rows(self.entry, row, off, ka[0], va[0])
        if self.kernel:
            o = pa.paged_prefill_attention(qa[0], entry, self.bt_row,
                                           self.prefix_len,
                                           mesh=self.mesh)[None]
        else:
            t_len = self.bt_row.shape[0] * bs
            k_all, v_all = gather_ctx(entry, self.bt_row, qa.dtype)
            k_all, v_all = k_all[None], v_all[None]
            mask = (jnp.arange(t_len)[None, :] <= gpos[:, None])[None, None]
            o = masked_attention(qa, k_all, v_all, mask)
        return o, PrefixPrefillView(entry, self.bt_row, self.prefix_len,
                                    self.true_len, bs, kernel=self.kernel,
                                    mesh=self.mesh)


class SlotStateDecodeView:
    """One ``"recurrent"`` layer's decode-step view of the slot-indexed
    store: ``entry`` is that layer's ``[S, ...]`` state arrays. The layer
    reads every lane's state, advances it one token and writes it back;
    an inactive lane keeps what it had."""

    valid_len = None  # one real token a lane: nothing is padded

    def __init__(self, entry, active):
        self.entry = entry
        self.active = active  # [S] bool

    def read(self):
        return self.entry

    def write(self, new):
        def keep(n, old):
            act = self.active.reshape((-1,) + (1,) * (old.ndim - 1))
            return jnp.where(act, n.astype(old.dtype), old)

        return SlotStateDecodeView(
            tuple(keep(n, o) for n, o in zip(new, self.entry)), self.active)


class SlotStatePrefillView:
    """One ``"recurrent"`` layer's prefill view: the admitted request
    starts from a ZERO state (the lane is reset, whatever its last tenant
    left), runs over the true length of the padded prompt (``valid_len``),
    and its final state is written into lane ``slot``."""

    def __init__(self, entry, slot, true_len):
        self.entry = entry
        self.slot = slot          # scalar int32: the lane being admitted
        self.valid_len = true_len  # scalar int32: real (unpadded) length

    def read(self):
        return tuple(jnp.zeros((1,) + a.shape[1:], a.dtype)
                     for a in self.entry)

    def write(self, new):
        entry = tuple(
            jax.lax.dynamic_update_slice_in_dim(
                old, n.astype(old.dtype), self.slot, axis=0)
            for n, old in zip(new, self.entry))
        return SlotStatePrefillView(entry, self.slot, self.valid_len)


class WindowDecodeView:
    """One ``"window"`` layer's decode-step view: ``entry`` is that layer's
    ``[S, kv_heads, window, D]`` K and V rings. The token at position ``p``
    overwrites row ``p % window`` of its lane's ring, which then holds
    positions ``p - window + 1 .. p`` (fewer while the context is shorter:
    the rows past it are masked; the order inside the ring is free, see
    the seam). A lane that is not active writes into its own ring, which
    the prefill that next admits a request to it fills anew. The attention
    is XLA's over the whole ring, ``window`` rows a lane whatever is live.

    The ring lies head-major, as the attention reads it (the layout the
    chip's compiler gives a ``[S, window, kv_heads, D]`` ring on its own,
    after which it relaid all of it out and back around the one-row
    write: 16 copies of 42 MB a step at Phi-4-mini-flash's sizes), and
    each head's row is scattered on its own into the ``[S, kv_heads *
    window, D]`` view, where one row is minor-most: in place, as
    :func:`paddle_tpu.ops.paged_attention.write_token` does for a
    head-major pool."""

    def __init__(self, entry, positions, window: int):
        self.entry = entry
        self.positions = positions  # [S] int32: write pos of new token
        self.window = int(window)

    def update_and_attend(self, q, k, v):
        qa, ka, va = _raw(q, k, v)
        w, pos = self.window, self.positions
        s_lanes, heads = qa.shape[0], ka.shape[2]
        lanes = jnp.arange(s_lanes)[:, None]
        at = jnp.arange(heads)[None, :] * w + (pos % w)[:, None]  # [S, H]

        def write(ring, new):  # ring [S, H, w, D], new [S, 1, H, D]
            slab = ring.reshape(s_lanes, heads * w, ring.shape[-1])
            slab = slab.at[lanes, at].set(new[:, 0].astype(ring.dtype))
            return slab.reshape(ring.shape)

        entry = tuple(write(ring, new)
                      for ring, new in zip(self.entry, (ka, va)))
        live = jnp.minimum(pos + 1, w)
        mask = (jnp.arange(w)[None, :] < live[:, None])[:, None, None, :]
        o = masked_attention(qa, jnp.swapaxes(entry[0], 1, 2),
                             jnp.swapaxes(entry[1], 1, 2), mask)
        return o, WindowDecodeView(entry, pos, w)


class WindowPrefillView:
    """One ``"window"`` layer's prefill view: query ``t`` of the (padded)
    prompt attends keys ``t - window + 1 .. t``, and the last ``window``
    rows before ``true_len`` go into lane ``slot``'s ring (``[kv_heads,
    window, D]``), row ``t`` at ``t % window`` (the keys as the model
    handed them over: rotated, where it rotates). Key tiles wholly outside
    the window are never computed, so the work grows linearly with the
    prompt, by either route: ``kernel=True`` (the engine's prefill route
    is the kernel, ``ServingConfig.paged_kernel``) through the banded
    flash kernel
    (:func:`paddle_tpu.ops.paged_attention.swa_prefill_attention`), which
    holds a tile of scores at a time; ``kernel=False`` in XLA, a chunk of
    ``window`` queries at a time against its own chunk and the one before,
    whose scores ``[heads, window, 2 window]`` are materialised (84 MB at
    a window of 512, 6.4 GB at 4,096: a model of that window is served
    with the kernel route)."""

    def __init__(self, entry, slot, true_len, window: int,
                 kernel: bool = False):
        self.entry = entry
        self.slot = slot          # scalar int32: the lane being admitted
        self.true_len = true_len  # scalar int32: real (unpadded) length
        self.window = int(window)
        self.kernel = kernel

    def update_and_attend(self, q, k, v):
        qa, ka, va = _raw(q, k, v)
        if self.kernel:
            o = pa.swa_prefill_attention(qa[0], ka[0], va[0],
                                         self.window)[None]
        else:
            o = self._attend_chunks(qa, ka, va)
        # ring row r takes the last position t < true_len with t % w == r
        w, last = self.window, self.true_len - 1
        t_r = last - (last - jnp.arange(w)) % w
        entry = tuple(
            jax.lax.dynamic_update_slice_in_dim(
                ring, jnp.swapaxes(new[0][jnp.maximum(t_r, 0)], 0, 1)[None]
                .astype(ring.dtype), self.slot, axis=0)
            for ring, new in zip(self.entry, (ka, va)))
        return o, WindowPrefillView(entry, self.slot, self.true_len, w,
                                    kernel=self.kernel)

    def _attend_chunks(self, qa, ka, va):
        w, p = self.window, qa.shape[1]
        n = -(-p // w)

        def chunks(a, lead):  # [1, p, H, D] -> [n, w, H, D], `lead` rows on
            a = jnp.pad(a[0], ((lead, n * w - p), (0, 0), (0, 0)))
            return a[:n * w].reshape((n, w) + a.shape[1:])

        qi = jnp.arange(w)[:, None] + w        # a query's column in 2w keys
        ki = jnp.arange(2 * w)[None, :]
        band = (ki <= qi) & (qi - ki < w)      # [w, 2w]
        first = ki >= w                        # chunk 0 has no chunk before

        def one(xs):
            qc, k0, k1, v0, v1, c = xs
            mask = band & (first | (c > 0))
            return masked_attention(
                qc[None], jnp.concatenate([k0, k1])[None],
                jnp.concatenate([v0, v1])[None], mask[None, None])[0]

        o = jax.lax.map(one, (chunks(qa, 0), chunks(ka, w), chunks(ka, 0),
                              chunks(va, w), chunks(va, 0), jnp.arange(n)))
        return o.reshape((1, n * w) + o.shape[2:])[:, :p]


class LatentDecodeView:
    """One ``"latent"`` layer's decode-step view: ``entry`` is its pool
    entry ``(rows,)`` (:meth:`KVArena._fresh_latent`). The token's row is
    written at the lane's (block, offset) (a lane that is not active
    writes scratch block 0) and every head's ABSORBED query attends the
    lane's rows up to and including it: scores ``q . row * scale``, output
    the probabilities' sum of the rows' first ``latent_dim`` values.
    ``kernel``: through the Pallas latent decode kernel
    (:func:`paddle_tpu.ops.paged_attention.paged_latent_decode`: the live
    pages alone, each read once); else the XLA gather of the tables."""

    absorbed = True

    def __init__(self, entry, block_tables, positions, active,
                 block_size: int, latent_dim: int, kernel: bool = False):
        self.entry = entry
        self.block_tables = block_tables
        self.positions = positions
        self.active = active
        self.block_size = block_size
        self.latent_dim = int(latent_dim)
        self.kernel = kernel

    def write_and_attend(self, q, rows, scale, kv=None):
        qa, ra = _raw(q, rows)
        bs, pos = self.block_size, self.positions
        blk = self.block_tables[jnp.arange(qa.shape[0]), pos // bs]
        blk = jnp.where(self.active, blk, 0)
        pool = pa.write_latent_token(self.entry[0], blk, pos % bs, ra[:, 0])
        if self.kernel:
            o = pa.paged_latent_decode(qa[:, 0], pool, self.block_tables,
                                       pos, self.latent_dim, scale,
                                       active=self.active)[:, None]
        else:
            with jax.named_scope("kv_gather"):
                ctx = pa.latent_rows(pool, ra.shape[-1])[self.block_tables]
            ctx = ctx.reshape(qa.shape[0], -1, ra.shape[-1])  # [S, T, W]
            sc = jnp.einsum("shw,stw->sht", qa[:, 0], ctx) * scale
            mask = jnp.arange(ctx.shape[1])[None, :] <= pos[:, None]
            sc = jnp.where(mask[:, None, :], sc, -1e30)
            pr = jax.nn.softmax(sc.astype(jnp.float32), -1).astype(qa.dtype)
            o = jnp.einsum("sht,std->shd", pr,
                           ctx[..., :self.latent_dim])[:, None]
        return o, LatentDecodeView((pool,), self.block_tables, pos,
                                   self.active, bs, self.latent_dim,
                                   self.kernel)


class LatentPrefillView:
    """One ``"latent"`` layer's prefill view: the prompt's rows are kept
    for the commit to scatter into the slot's blocks
    (:func:`scatter_latent`), and the attention is causal over the keys
    and values EXPANDED from them (keys wider than values): the flash
    forward kernel
    (:func:`paddle_tpu.ops.paged_attention.latent_prefill_attention`) where
    ``kernel``, else plain XLA (the CPU's tiny prompts: its scores are
    ``[heads, s, s]``)."""

    absorbed = False

    def __init__(self, kernel: bool = False, rows=None):
        self.kernel = kernel
        self.rows = rows

    def write_and_attend(self, q, rows, scale, kv=None):
        qa, ra, ka, va = _raw(q, rows, *kv)
        if self.kernel:
            o = pa.latent_prefill_attention(qa[0], ka[0], va[0], scale)[None]
        else:
            p = qa.shape[1]
            sc = jnp.einsum("bqhd,bkhd->bhqk", qa, ka) * scale
            mask = jnp.arange(p)[None, :] <= jnp.arange(p)[:, None]
            sc = jnp.where(mask[None, None], sc, -1e30)
            pr = jax.nn.softmax(sc.astype(jnp.float32), -1).astype(qa.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", pr, va)
        return o, LatentPrefillView(self.kernel, ra)


def scatter_latent(entry, table_rows, true_len, rows, block_size: int):
    """A prompt's latent rows ``[p, W]`` into the slot's blocks
    (``table_rows``: the block of each ``block_size`` positions). Rows go
    in whole POOL rows (``pack`` consecutive tokens): a pool row whose
    first token is at or past ``true_len`` is padding and lands in scratch
    block 0; one that straddles ``true_len`` carries a padding token
    behind a real one, which the decode step that writes that position
    replaces before any mask lets it be read."""
    pool = entry[0]
    p, width = rows.shape
    pack = pool.shape[2] // width
    first = jnp.arange(p // pack) * pack            # each pool row's token
    blk = jnp.where(first < true_len, table_rows[first // block_size], 0)
    return (pool.at[blk, (first % block_size) // pack].set(
        rows.reshape(p // pack, pack * width).astype(pool.dtype)),)


class SparseDecodeView:
    """One ``"sparse"`` layer's decode-step view: ``entry`` is its pool
    entry ``(k, v, index_rows)``. The token's three rows are written at
    the lane's (block, offset) (a lane that is not active writes scratch
    block 0); the lane's live index keys are scored (``kernel``: the Pallas
    kernel :func:`paddle_tpu.ops.sparse_attention.paged_index_scores`
    through the block tables, the live pages alone; else the XLA gather of
    the tables), the ``topk`` largest kept (``sparse_attention.
    select_topk``: ``lax.top_k`` over the lane's scores; only the set is
    used), and the attention reads those rows of K and of V alone, gathered
    through the table: ``topk`` rows of each a lane whatever its context.
    What it scored and what it read it counts itself, where it reads
    (``counts``: the lanes' live index keys handed to the scoring and the
    live rows handed to the gather), for the layer to add to the step's
    counters."""

    def __init__(self, entry, block_tables, positions, active,
                 block_size: int, topk: int, kernel: bool = False,
                 counts=None):
        self.entry = entry
        self.block_tables = block_tables
        self.positions = positions
        self.active = active
        self.block_size = block_size
        self.topk = int(topk)
        self.kernel = kernel
        #: a successor's: what the call that made it scored and read
        self.counts = counts

    def select_and_attend(self, q, k, v, qi, ki, w):
        qa, ka, va, qia, kia, wa = _raw(q, k, v, qi, ki, w)
        bs, pos = self.block_size, self.positions
        blk = self.block_tables[jnp.arange(qa.shape[0]), pos // bs]
        blk = jnp.where(self.active, blk, 0)
        kp, vp = (pa.write_token(pool, blk, pos % bs, new[:, 0])
                  for pool, new in zip(self.entry, (ka, va)))
        ip = pa.write_latent_token(self.entry[2], blk, pos % bs, kia[:, 0])
        with jax.named_scope("indexer"):
            scores = sa.paged_index_scores(
                qia[:, 0], wa[:, 0], ip, self.block_tables, pos,
                active=self.active, kernel=self.kernel)
        with jax.named_scope("select"):
            idx, live = sa.select_topk(scores, self.topk)
        with jax.named_scope("sparse_attn"):
            o = sa.gathered_attention(qa, kp, vp, self.block_tables, idx,
                                      live)
        counts = {
            # the keys the scoring was told are live (the kernel copies
            # their pages alone) and the rows of K and of V the gather was
            # handed that hold one: a view that attended every live row
            # under a mask would count them all here
            "sparse.index_rows_scored": jnp.sum(
                jnp.where(self.active, pos + 1, 0), dtype=jnp.int32),
            "sparse.rows_read": jnp.sum(live, dtype=jnp.int32)}
        return o, SparseDecodeView((kp, vp, ip), self.block_tables, pos,
                                   self.active, bs, self.topk, self.kernel,
                                   counts)


class SparsePrefillView:
    """One ``"sparse"`` layer's prefill view: the prompt's K, V and index
    keys are kept for the commit to write into the slot's blocks
    (:func:`scatter_blocks`, :func:`scatter_index_blocks`), and every query
    attends the ``topk`` keys before it of largest index score: a chunk of
    queries at a time is scored against every key for each row's threshold
    (its ``topk``-th largest), then ONE flash pass keeps what stands at or
    over it (:mod:`paddle_tpu.ops.sparse_attention`; ``kernel``: its two
    Pallas kernels, else ``jax.numpy`` on the CPU's small prompts). No
    ``[positions, positions]`` array is made by either route."""

    def __init__(self, topk: int, kernel: bool = False, kept=None):
        self.topk = int(topk)
        self.kernel = kernel
        self.kept = kept  # (k, v, index keys) of the prompt, for the commit

    def select_and_attend(self, q, k, v, qi, ki, w):
        qa, ka, va, qia, kia, wa = _raw(q, k, v, qi, ki, w)
        with jax.named_scope("select"):
            tau = sa.index_thresholds(qia[0], kia[0], wa[0], self.topk,
                                      kernel=self.kernel)
        with jax.named_scope("sparse_attn"):
            o = sa.sparse_prefill_attention(
                qa[0], ka[0], va[0], qia[0], kia[0], wa[0], tau,
                kernel=self.kernel)[None]
        return o, SparsePrefillView(self.topk, self.kernel, (ka, va, kia))


def scatter_index_blocks(pool, table_rows, true_len, rows, block_size: int):
    """A prompt's index keys ``[p, W]`` into the slot's blocks of a packed
    index pool, in whole blocks as :func:`scatter_blocks` writes K and V
    (a block at or past ``true_len`` lands in scratch block 0)."""
    n_blk = -(-rows.shape[0] // block_size)
    blk = jnp.where(jnp.arange(n_blk) * block_size < true_len,
                    table_rows[:n_blk], 0)
    rows = jnp.pad(rows, ((0, n_blk * block_size - rows.shape[0]), (0, 0)))
    return pool.at[blk].set(
        rows.reshape((n_blk,) + pool.shape[1:]).astype(pool.dtype))


def _commit_sparse(view, entry, rows, c):
    k, v, ki = view.kept
    return scatter_blocks(entry[:2], rows, c.true_len, k[0], v[0],
                          c.block_size) + (scatter_index_blocks(
                              entry[2], rows, c.true_len, ki[0],
                              c.block_size),)


# ------------------------------------------------------ the table by kind

#: where a kind's state lies (:attr:`Kind.store`): rows in the arena's
#: block pools, or a fixed size a lane in its slot-indexed store
PAGED, SLOT = "paged", "slot"

#: the options whose bookkeeping keeps, shares or rewinds a request's
#: state as K/V BLOCKS, and the handoff that publishes them
_BLOCK_OPTIONS = ("prefix_cache", "kv_tiering",
                  "spec_k (speculative decoding)", "chunked_prefill")
HANDOFF = "disaggregated prefill/decode handoff"


class DecodeContext(NamedTuple):
    """What the decode step hands every layer's view."""

    block_tables: object  # [S, max_blocks] int32
    positions: object     # [S] int32: write position of the new token
    active: object        # [S] bool
    block_size: int
    kernel: bool          # the engine's decode route (``decode_kernel``)
    mesh: object          # the mesh its kernel calls shard over, or None


class PrefillContext(NamedTuple):
    """What a full prefill hands every layer's view."""

    slot: object          # scalar int32: the lane being admitted
    true_len: object      # scalar int32: real (unpadded) length
    block_size: int
    kernel: bool          # the prefill route (``paged_kernel`` asked for)
    any_kernel: bool      # ... or, failing that, the decode step's route:
    #                       for a kind whose prompt attention has no XLA
    #                       form that fits a long prompt
    mesh: object
    last: object = None   # the last valid row: for the layer before a
    #                       ``prefill_tail`` alone


@dataclass(frozen=True)
class Kind:
    """Everything the server knows of one kind of layer state. ``st`` is
    the layer's declared state, ``entry`` its stored arrays (None where
    it owns none), ``c`` the program's context."""

    decode_view: Callable   # (st, entry, DecodeContext) -> the step's view
    prefill_view: Callable  # (st, entry, PrefillContext) -> a prefill's
    store: Optional[str] = None  # PAGED, SLOT, or None: it owns no state
    #: ``(successor view, entry, table rows, PrefillContext)`` -> the entry
    #: as the prefill leaves it
    commit: Callable = lambda view, entry, rows, c: None
    #: PAGED: ``st`` -> the arena's ``(heads, head_dim, latent_width,
    #: index_width)`` (:class:`~.kv_arena.KVArena`'s arguments), and
    #: ``st`` -> a pool row's minor dimension (the decode kernel reads the
    #: pool where it lies if ``paged_attention.decode_in_place`` of it)
    pool_row: Optional[Callable] = None
    minor: Optional[Callable] = None
    #: PAGED: the commit writes every array of the entry in whole blocks
    #: (``prefill.block_writes`` counts them)
    block_writes: bool = False
    #: SLOT: ``(st, dtype)`` -> the store's ``((name, shape, dtype), ...)``,
    #: and the gauge its bytes report to
    arrays: Optional[Callable] = None
    bytes_gauge: Optional[str] = None
    #: the gauges that count a model's layers of this kind, and the one
    #: that is 1 when such a layer decodes through a kernel of its own
    counted_in: Tuple[str, ...] = ()
    kernel_gauge: Optional[str] = None
    #: the options a model with such a layer cannot be served with, what
    #: the refusal calls its layers, and the one clause that says why
    refuses: Tuple[str, ...] = ()
    called: str = ""
    why: str = ""


_SLOT = dict(
    store=SLOT, refuses=_BLOCK_OPTIONS + (HANDOFF,),
    commit=lambda view, entry, rows, c: view.entry,  # it wrote its lane
    called="recurrent-state layers (a fixed-size state or window per lane)",
    # each would need a snapshot per cached prefix, per chunk, per rollback
    why="it assumes every layer's state is paged blocks")

#: one entry for each kind ``models/serving_seam.py`` declares
KINDS = {
    "kv": Kind(
        store=PAGED, block_writes=True,
        decode_view=lambda st, entry, c: PagedCacheView(
            entry, c.block_tables, c.positions, c.active, c.block_size,
            kernel=c.kernel, mesh=c.mesh),
        prefill_view=lambda st, entry, c: CapturePrefillView(
            c.block_size, kernel=c.kernel, mesh=c.mesh, last=c.last),
        commit=lambda view, entry, rows, c: scatter_blocks(
            entry, rows, c.true_len, view.k[0], view.v[0], c.block_size),
        pool_row=lambda st: (st.kv_heads, st.head_dim, 0, 0),
        minor=lambda st: st.head_dim,
        counted_in=("arena.paged_layers", "arena.kv_readers")),
    "latent": Kind(
        store=PAGED,
        decode_view=lambda st, entry, c: LatentDecodeView(
            entry, c.block_tables, c.positions, c.active, c.block_size,
            st.latent_dim, kernel=c.kernel),
        # its prompt attention has no XLA form that fits a long prompt
        prefill_view=lambda st, entry, c: LatentPrefillView(c.any_kernel),
        commit=lambda view, entry, rows, c: scatter_latent(
            entry, rows, c.true_len, view.rows[0], c.block_size),
        pool_row=lambda st: (1, 1, st.width, 0),
        minor=lambda st: pa.latent_pack(st.width) * st.width,
        counted_in=("arena.paged_layers",),
        kernel_gauge="kernel.paged_latent",
        # each attends a resident prefix or verifies drafts through "kv"
        # views, stores int8 K and V, or shards heads
        refuses=_BLOCK_OPTIONS + ("quant_kv", "mesh (more than one chip)",
                                  HANDOFF),
        called="latent-attention layers (one shared row a token in the "
               "paged pool)",
        why="it assumes per-head K and V pools"),
    "sparse": Kind(
        store=PAGED, block_writes=True,
        decode_view=lambda st, entry, c: SparseDecodeView(
            entry, c.block_tables, c.positions, c.active, c.block_size,
            st.topk, kernel=c.kernel),
        # as a latent layer's: no XLA form that fits a long prompt
        prefill_view=lambda st, entry, c: SparsePrefillView(
            st.topk, c.any_kernel),
        commit=_commit_sparse,
        # K and V rows of (kv_heads, head_dim) and, beside them, ONE row of
        # index_dim values a token; read in place where both fill whole
        # lane tiles (the index row two tokens to a pool row)
        pool_row=lambda st: (st.kv_heads, st.head_dim, 0, st.index_dim),
        minor=lambda st: math.gcd(
            st.head_dim, pa.latent_pack(st.index_dim) * st.index_dim),
        counted_in=("arena.paged_layers",),
        kernel_gauge="kernel.paged_index",
        # each keeps, shares, rewinds, quantizes or shards K and V blocks
        refuses=_BLOCK_OPTIONS + ("quant_kv", "mesh (more than one chip)",
                                  HANDOFF),
        called="sparse-attention layers (an index key a token beside its "
               "K and V rows)",
        why="it knows K and V pools alone, and the index keys would have "
            "to be carried, quantized or sharded beside them"),
    "recurrent": Kind(
        decode_view=lambda st, entry, c: SlotStateDecodeView(entry, c.active),
        prefill_view=lambda st, entry, c: SlotStatePrefillView(
            entry, c.slot, c.true_len),
        arrays=lambda st, dtype: st.arrays,
        bytes_gauge="state.ssm_bytes", **_SLOT),
    "window": Kind(
        decode_view=lambda st, entry, c: WindowDecodeView(
            entry, c.positions, st.window),
        prefill_view=lambda st, entry, c: WindowPrefillView(
            entry, c.slot, c.true_len, st.window, kernel=c.kernel),
        arrays=lambda st, dtype: st.arrays(dtype),
        bytes_gauge="state.window_bytes", **_SLOT),
    # its real view is ``reader()`` of the successor of the layer it names,
    # which exists only once that layer has run (``forward_cached``)
    "shared": Kind(
        decode_view=lambda st, entry, c: SharedRef(st.source),
        prefill_view=lambda st, entry, c: SharedRef(st.source),
        counted_in=("arena.kv_readers",)),
    "none": Kind(decode_view=lambda st, entry, c: None,
                 prefill_view=lambda st, entry, c: None),
}


def layer_entries(states, pools, slot_state):
    """Each layer's stored entry, in layer order: the next of ``pools``
    (the arena's block pools) or of ``slot_state`` (its slot-indexed
    store), None for a layer that owns neither."""
    its = {PAGED: iter(pools), SLOT: iter(slot_state)}
    return [next(its[store]) if store else None
            for store in (KINDS[st.kind].store for st in states)]


def by_store(states, items):
    """``items`` (one a layer) of the layers that own state, by where it
    lies: ``(those of the block pools, those of the slot store)``."""
    return tuple([x for st, x in zip(states, items)
                  if KINDS[st.kind].store == store]
                 for store in (PAGED, SLOT))


def pool_row(layers):
    """The ONE shape of row the arena's block pools hold for a model's
    ``layers``, as :class:`~.kv_arena.KVArena` takes it: ``(heads,
    head_dim, latent_width, index_width)``. Raises for a model that
    declares two, and
    for a ``"shared"`` layer that names no ``"kv"`` layer before it."""
    for i, st in enumerate(layers):
        if st.kind == "shared" and not (
                0 <= st.source < i and layers[st.source].kind == "kv"):
            raise ValueError(
                f"layer {i} shares the cache of layer {st.source}, "
                "which is no paged kv layer before it")
    rows = {KINDS[st.kind].pool_row(st) for st in by_store(layers, layers)[0]}
    if len(rows) > 1:
        raise ValueError(
            "the paged arena holds one shape of row: a model's kv layers "
            "share one (heads, head_dim), its latent layers one width, its "
            "sparse layers one (heads, head_dim, index width), and it has "
            f"layers of one of the three alone (declared: {sorted(rows)})")
    return rows.pop() if rows else (1, 1, 0, 0)


def refuse_options(layers, asked) -> None:
    """Raise for the first option of ``asked`` (its name -> whether it is
    on) that a kind among ``layers`` cannot be served with, by the
    option's name and the kind's."""
    for kind in dict.fromkeys(KINDS[st.kind] for st in layers):
        for option in kind.refuses:
            if asked.get(option):
                raise ValueError(
                    f"{option} is not supported for a model with "
                    f"{kind.called}: {kind.why}")
