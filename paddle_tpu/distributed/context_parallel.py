"""Context (sequence) parallelism — ring attention and Ulysses.

The reference has NO sequence parallelism (SURVEY.md §5.7: exhaustive grep —
it scales long sequences only via flash attention + recompute). This module
fills that gap TPU-first, as first-class mesh-axis parallelism over "sep":

* **Ring attention** (`ring_attention`): Q stays put; K/V blocks rotate
  around the ICI ring via ``lax.ppermute`` while a flash-style online
  softmax (running max/sum) accumulates partial attention — blockwise
  attention for sequences that don't fit one chip's HBM. Causality is
  enforced per block pair from global positions, so fully-masked future
  blocks contribute nothing.
* **Ulysses** (`ulysses_attention`): all_to_all re-shards sequence-sharded
  activations to head-sharded, runs *local* full-sequence attention (which
  can use the Pallas flash kernel on the MXU), and all_to_alls back.
  Preferable when num_heads >= sep degree and seq fits after gathering.

Both are differentiable (scan + ppermute/all_to_all transpose) and run
inside partial-manual shard_map: only "sep" is manual, so data/model-axis
GSPMD sharding inside (e.g. TP-sharded heads) is preserved.
"""
from __future__ import annotations


from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from . import mesh as mesh_mod

SEP_AXIS = "sep"
_NEG_INF = -1e30  # finite: keeps exp(m_old - m_new) well-defined for empty rows


def _block_attn(q, k, v, bias_mask, scale):
    """One Q-block x KV-block flash partial: returns (m, l, o) contributions.
    q,k,v: [b, h, s, d]; bias_mask: [sq, sk] bool (True = attend)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(bias_mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)  # [b,h,sq]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(bias_mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m, l, o


def _ring_body(q, k0, v0, *, scale, causal, R, s_local):
    """Runs the R-step ring on [b, h, s_local, d] blocks (already manual)."""
    rank = jax.lax.axis_index(SEP_AXIS)
    b, h, sq, d = q.shape
    def pvary(x):
        return jax.lax.pcast(x, (SEP_AXIS,), to="varying")
    m = pvary(jnp.full((b, h, sq), _NEG_INF, jnp.float32))
    l = pvary(jnp.zeros((b, h, sq), jnp.float32))
    o = pvary(jnp.zeros((b, h, sq, d), jnp.float32))
    # send K/V to the NEXT rank each step => after r steps this rank holds
    # the block of rank (rank - r) mod R
    perm = [(i, (i + 1) % R) for i in range(R)]
    qpos = rank * s_local + jnp.arange(sq)

    def step(carry, r):
        m, l, o, k, v = carry
        src = (rank - r) % R
        kpos = src * s_local + jnp.arange(s_local)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
        else:
            mask = jnp.ones((sq, s_local), bool)
        bm, bl, bo = _block_attn(q, k, v, mask, scale)
        m_new = jnp.maximum(m, bm)
        corr_old = jnp.exp(m - m_new)
        corr_new = jnp.exp(bm - m_new)
        l = l * corr_old + bl * corr_new
        o = o * corr_old[..., None] + bo * corr_new[..., None]
        k = jax.lax.ppermute(k, SEP_AXIS, perm)
        v = jax.lax.ppermute(v, SEP_AXIS, perm)
        return (m_new, l, o, k, v), None

    (m, l, o, _, _), _ = jax.lax.scan(step, (m, l, o, k0, v0), jnp.arange(R))
    return (o / jnp.maximum(l, 1e-38)[..., None]).astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    *,
    scale: float,
    causal: bool = True,
    mesh: Optional[Mesh] = None,
):
    """Blockwise ring attention over the "sep" axis.

    q/k/v: [batch, seq, heads, head_dim], seq sharded over "sep" (the paddle
    flash_attn layout). Returns same layout/sharding. Falls back to plain
    attention when the mesh has no sep axis."""
    mesh = mesh or mesh_mod.ensure_mesh()
    R = mesh.shape.get(SEP_AXIS, 1)
    if R <= 1:
        from ..nn.functional.attention import _sdpa_reference

        return _sdpa_reference(q, k, v, scale=scale, causal=causal)
    s_local = q.shape[1] // R

    def f(q, k, v):
        # [b, s_l, h, d] -> [b, h, s_l, d]
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        out = _ring_body(qt, kt, vt, scale=scale, causal=causal, R=R, s_local=s_local)
        return jnp.swapaxes(out, 1, 2)

    spec = PartitionSpec(None, SEP_AXIS, None, None)
    fn = jax.shard_map(
        f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={SEP_AXIS}, check_vma=True,
    )
    return fn(q, k, v)


def ulysses_attention(
    q,
    k,
    v,
    *,
    scale: float,
    causal: bool = True,
    mesh: Optional[Mesh] = None,
):
    """Ulysses/DeepSpeed-style: all_to_all seq-shard -> head-shard, local
    full-sequence attention, all_to_all back. heads must divide by sep."""
    mesh = mesh or mesh_mod.ensure_mesh()
    R = mesh.shape.get(SEP_AXIS, 1)
    if R <= 1:
        from ..nn.functional.attention import _sdpa_reference

        return _sdpa_reference(q, k, v, scale=scale, causal=causal)
    if q.shape[2] % R:
        raise ValueError(f"num_heads {q.shape[2]} not divisible by sep degree {R}")

    def f(q, k, v):
        # local [b, s_l, h, d] -> gather seq, scatter heads: [b, s, h_l, d]
        def fwd(t):
            return jax.lax.all_to_all(t, SEP_AXIS, split_axis=2, concat_axis=1, tiled=True)

        def rev(t):
            return jax.lax.all_to_all(t, SEP_AXIS, split_axis=1, concat_axis=2, tiled=True)

        from ..nn.functional.attention import _sdpa_reference

        out = _sdpa_reference(fwd(q), fwd(k), fwd(v), scale=scale, causal=causal)
        return rev(out)

    spec = PartitionSpec(None, SEP_AXIS, None, None)
    fn = jax.shard_map(
        f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={SEP_AXIS}, check_vma=True,
    )
    return fn(q, k, v)
