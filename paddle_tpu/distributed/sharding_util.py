"""Sharding helpers shared by TP/PP/ZeRO layers — the mesh execution core.

The reference moves data with explicit collective ops (c_allreduce/c_concat/
c_split, ref:paddle/fluid/operators/collective/); TPU-native we *annotate*:
parameters are device_put with a NamedSharding, activations get
``with_sharding_constraint`` under trace, and XLA's SPMD partitioner inserts
the ICI collectives (SURVEY.md §7: "GSPMD sharding annotations give DP/TP/
sharding for free").

ISSUE 14 makes this module the ONE sharding home for the compiled
execution core: :func:`shard_kv_entry` states the KV-arena pool placement
rule (payload heads-sharded over "model", per-block scale pools
replicated), and
:func:`mesh_axes_key` is the hashable mesh fingerprint that joins every
compiled program key (engine builds, ``generate()``'s runner cache)
exactly like the quant/donation flags already do.

ISSUE 16 adds :func:`headwise_shard_map` — the manual-partitioning rule
that runs the Pallas paged-attention kernels per model-shard over the
pools :func:`shard_kv_entry` committed (local head counts in, replicated
block tables through, heads-sharded output back to GSPMD).
:func:`flash_shard_map` is the same rule for the training flash kernel
(batch over the data-like axes, heads over "model").
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tensor import Tensor
from . import mesh as mesh_mod


def _mesh() -> Mesh:
    return mesh_mod.ensure_mesh()


def headwise_shard_map(fn, mesh, in_head_dims, out_head_dim: int,
                       num_heads: int):
    """Manual-partitioning wrapper for a head-parallel Pallas kernel
    (ISSUE 16) — the SPMD rule the paged-attention kernels run under.

    ``fn`` is a per-device kernel body over positional args;
    ``in_head_dims[i]`` names the heads dimension of argument ``i``, or
    ``None`` for replicated runtime data (block tables, positions,
    per-block scale pools — exactly the operands
    :func:`shard_kv_entry` keeps replicated). The returned callable maps
    ``fn`` over the WHOLE mesh via ``jax.shard_map``: head-carrying
    operands split over the "model" axis (so ``fn`` sees the LOCAL head
    count, ``num_heads // mp``, and reads only its own K/V shard — zero
    cross-chip traffic), everything else replicates, and the single output
    re-assembles its ``out_head_dim`` over "model" — handing GSPMD a
    heads-sharded activation that the row-parallel output projection's
    psum contracts, same as the gather path.

    When ``num_heads`` doesn't divide the model degree the pools were
    committed replicated (:func:`shard_kv_entry`'s divisibility guard), so
    every spec replicates and each device runs the full-head kernel —
    correct, just not compute-scaled; a data-only mesh degenerates the
    same way. Replicated operands are passed through ``jax.lax.pcast``
    inside the body so a vma-checking shard_map types them against the
    sharded ones."""
    mp = mesh.shape.get(MODEL_AXIS, 1)
    split = mp > 1 and num_heads % mp == 0

    def spec(dim):
        if dim is None or not split:
            return PartitionSpec()
        return PartitionSpec(*([None] * dim), MODEL_AXIS)

    in_specs = tuple(spec(d) for d in in_head_dims)

    def body(*local):
        if split:
            local = [jax.lax.pcast(a, (MODEL_AXIS,), to="varying")
                     if d is None else a
                     for a, d in zip(local, in_head_dims)]
        return fn(*local)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=spec(out_head_dim), check_vma=False)


#: mesh axes a training batch is split over (``shard_batch`` / the hybrid
#: mesh's data-like axes), outermost first
BATCH_AXES = ("data", "sharding")


def flash_shard_map(fn, mesh, batch: int, num_heads: int):
    """Manual-partitioning wrapper for the flash-attention kernel on
    ``[batch, seq, heads, head_dim]`` operands. GSPMD cannot partition a
    Mosaic kernel ("Mosaic kernels cannot be automatically partitioned"),
    so on a multi-device mesh the compiled step would be refused; attention
    is independent per sequence and per head, so each device runs ``fn`` on
    its own block: batch over the data-like axes, heads over "model" —
    the placement :func:`constraint` already gives q/k/v in the GPT block,
    hence no resharding. An axis that does not divide its dimension
    replicates instead (correct, just not scaled)."""
    batch_axes = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if batch % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    mp = mesh.shape.get(MODEL_AXIS, 1)
    heads = MODEL_AXIS if mp > 1 and num_heads % mp == 0 else None
    spec = PartitionSpec(batch_axes or None, None, heads, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def _prune_spec(mesh: Mesh, spec):
    """Drop axis names that aren't on the mesh or have size 1."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if mesh.shape.get(a, 1) > 1)
            out.append(kept if kept else None)
        else:
            out.append(entry if mesh.shape.get(entry, 1) > 1 else None)
    return tuple(out)


def shard_parameter(p: Tensor, *spec, mesh: Optional[Mesh] = None) -> Tensor:
    """Place a parameter on the mesh with the given PartitionSpec (eager).
    jit infers in_shardings from committed arrays, so this single device_put
    is all the 'dist_attr annotation' a compiled step needs.

    No-op when no mesh was installed (single-chip eager mode) — placing
    params on an implicit mesh would strand them away from host inputs."""
    mesh = mesh or mesh_mod.get_mesh()
    if mesh is None:
        return p
    spec = _prune_spec(mesh, spec)
    if not p._is_traced():
        p._data = jax.device_put(p._data, NamedSharding(mesh, PartitionSpec(*spec)))
    return p


def constraint(x, *spec, mesh: Optional[Mesh] = None):
    """Activation sharding constraint: under trace emits
    with_sharding_constraint; eager re-places the array."""
    mesh = mesh or mesh_mod.get_mesh()
    if mesh is None:
        return x
    spec = _prune_spec(mesh, spec)
    t = isinstance(x, Tensor)
    arr = x._data if t else x
    ns = NamedSharding(mesh, PartitionSpec(*spec))
    if isinstance(arr, jax.core.Tracer):
        # inside a shard_map manual region (e.g. the pipeline stage body)
        # the value is manual-axis-varying; a full-mesh constraint is
        # ill-typed there — let GSPMD propagate from the operands instead
        if getattr(getattr(arr, "aval", None), "vma", None):
            return x
        out = jax.lax.with_sharding_constraint(arr, ns)
    else:
        out = jax.device_put(arr, ns)
    if t:
        x._data = out
        return x
    return out


def replicate(x, mesh: Optional[Mesh] = None):
    return constraint(x, mesh=mesh)


def replicate_unplaced(x, mesh: Mesh):
    """Commit ``x`` (a Tensor, in place; or an array, returned) replicated
    over ``mesh`` unless it already spans the mesh's devices. What no layer
    placed — norm weights, position embeddings, fresh optimizer state, a
    host array — sits uncommitted on one device; a compiled call would
    re-broadcast it every time, and a training step whose outputs come
    back on the mesh would compile a second time for them."""
    arr = x._data if isinstance(x, Tensor) else x
    sh = getattr(arr, "sharding", None)  # None: a host (numpy) array
    if sh is not None and len(sh.device_set) == mesh.devices.size:
        return x
    return replicate(x, mesh)


# ------------------------------------------------ mesh-aware program keys

MODEL_AXIS = "model"


def mesh_axes_key(mesh: Optional[Mesh] = None) -> Optional[Tuple]:
    """Hashable fingerprint of a mesh — ``((axis, size), ...)`` in device
    order, or ``None`` off-mesh. This is the value that joins compiled
    program keys (the serving engine's build config, ``generate()``'s
    runner cache) exactly like the quant/donation flags: a different mesh
    shape or axis layout is a different executable, never a reused one.
    A 1-device mesh keys differently from no mesh on purpose — the
    programs are bit-identical but the committed shardings are not."""
    m = mesh if mesh is not None else mesh_mod.get_mesh()
    if m is None:
        return None
    return tuple((str(a), int(m.shape[a])) for a in m.axis_names)


def shard_kv_entry(entry, mesh: Optional[Mesh] = None):
    """Place one KV-arena pool entry on the mesh — the ONE statement of
    the arena's sharding rule (ISSUE 14):

    * K/V payload pools ``[num_blocks, block_size, heads, head_dim]``
      shard their HEADS dim over the "model" axis (the same axis the
      attention weights shard over, so the decode step's scatter/gather
      stay local per shard). Heads that don't divide the model degree
      replicate instead — correct, just not memory-scaled.
    * per-block scale pools ``[num_blocks, block_size]`` (the int8
      arena's 4-tuple entries) replicate: they are read by every head's
      dequant, and at 2 floats per token row they are noise next to the
      payload.

    Block tables, positions, refcounts and COW bookkeeping stay host-side
    numpy — layout-agnostic by construction. No-op without a mesh (the
    single-chip path is byte-identical to PR 13)."""
    mesh = mesh if mesh is not None else mesh_mod.get_mesh()
    if mesh is None:
        return tuple(entry)
    mp = mesh.shape.get(MODEL_AXIS, 1)
    out = []
    for i, arr in enumerate(entry):
        if (i < 2 and mp > 1 and arr.ndim >= 3
                and arr.shape[2] % mp == 0):
            spec = PartitionSpec(None, None, MODEL_AXIS, None)
        else:
            spec = PartitionSpec()
        out.append(jax.device_put(arr, NamedSharding(mesh, spec)))
    return tuple(out)
