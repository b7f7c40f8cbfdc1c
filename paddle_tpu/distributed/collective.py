"""Collective communication API.

Replaces the reference's ProcessGroup stack
(ref:paddle/fluid/distributed/collective/process_group.h:53 — AllReduce/
AllGather/AllToAll/Broadcast/Reduce/ReduceScatter/Send/Recv — and the Python
wrappers ref:python/paddle/distributed/communication/). There is no runtime
comm library on TPU: collectives are XLA ops. This module keeps the paddle
API meaningful in three regimes:

1. **Traced** (inside ``shard_map``/jit with the group's mesh axis bound):
   calls lower to ``jax.lax.psum``/``all_gather``/``ppermute`` — the compiled
   hybrid-parallel path.
2. **Eager over a sharded array** (single-controller, array sharded along the
   group axis): the call jits a tiny ``shard_map`` program — the "eager
   collective = one-op XLA computation" design from SURVEY.md §5.8.
3. **Degenerate** (group size 1, the single-process unit-test regime): the
   paddle-contract identity behavior.
"""
from __future__ import annotations

import functools
import threading
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tensor import Tensor
from . import mesh as mesh_mod


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


def _pprod(x, axis):
    # no lax.pprod primitive: gather the axis then reduce locally
    return jnp.prod(jax.lax.all_gather(x, axis, tiled=False), axis=0)


_REDUCE_FNS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
    ReduceOp.PROD: _pprod,
}


class Group:
    """A communication group = a mesh axis (or the whole mesh).

    ``ranks`` is kept for API parity; the operative identity is
    (mesh, axis_name).
    """

    _next_gid = 0

    def __init__(self, mesh: Mesh, axis: str, ranks: Optional[List[int]] = None, pg_name: str = ""):
        self.mesh = mesh
        self.axis = axis
        self.nranks = mesh.shape.get(axis, 1) if axis else 1
        if ranks is None:
            ranks = _axis_rank_list(mesh, axis) if axis and self.nranks > 1 else list(range(self.nranks))
        self.ranks = ranks
        Group._next_gid += 1
        self.id = Group._next_gid
        self.name = pg_name or f"pg_{self.id}"

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(axis={self.axis!r}, nranks={self.nranks})"


def _axis_rank_list(mesh: Mesh, axis: str) -> List[int]:
    """Global (device-id) ranks of this process's group along a mesh axis:
    hold the local device's other coordinates fixed, vary the axis."""
    devs = mesh.devices
    names = list(mesh.axis_names)
    if axis not in names:
        return [0]
    ax = names.index(axis)
    local = jax.local_devices()[0]
    coords = np.argwhere(devs == local)
    base = list(coords[0]) if coords.size else [0] * devs.ndim
    ranks = []
    for i in range(devs.shape[ax]):
        base[ax] = i
        ranks.append(int(devs[tuple(base)].id))
    return ranks


_lock = threading.Lock()
_default_group: Optional[Group] = None
_groups: List[Group] = []


def _get_default_group() -> Group:
    global _default_group
    with _lock:
        if _default_group is None:
            m = mesh_mod.ensure_mesh()
            axis = m.axis_names[0] if m.axis_names else ""
            _default_group = Group(m, axis)
        return _default_group


def get_group(gid: Optional[int] = None) -> Group:
    if gid is None:
        return _get_default_group()
    for g in _groups:
        if g.id == gid:
            return g
    default = _get_default_group()
    if gid == default.id:
        return default
    raise ValueError(f"no communication group with id {gid} (was it destroyed?)")


def new_group(ranks: Optional[Sequence[int]] = None, backend: Optional[str] = None, axis: Optional[str] = None) -> Group:
    """Create a group. TPU-native extension: pass ``axis=`` to bind the group
    to a mesh axis (the common case — per-axis groups of the hybrid topology,
    ref:topology.py get_*_parallel_group). Plain rank lists build a sub-mesh
    over those devices on a fresh axis."""
    m = mesh_mod.ensure_mesh()
    if axis is not None:
        g = Group(m, axis, list(ranks) if ranks is not None else None)
    elif ranks is None or len(ranks) >= len(jax.devices()):
        g = Group(m, m.axis_names[0] if m.axis_names else "", list(ranks) if ranks else None)
    else:
        devs = [jax.devices()[r] for r in ranks]
        sub = Mesh(np.array(devs), ("sub",))
        g = Group(sub, "sub", list(ranks))
    with _lock:
        _groups.append(g)
    return g


def is_initialized() -> bool:
    return _default_group is not None


def destroy_process_group(group: Optional[Group] = None):
    global _default_group
    with _lock:
        if group is None:
            _default_group = None
            _groups.clear()
        elif group in _groups:
            _groups.remove(group)


def get_rank(group: Optional[Group] = None) -> int:
    from . import env

    if group is not None:
        return group.get_group_rank(env.get_rank())
    return env.get_rank()


def get_world_size(group: Optional[Group] = None) -> int:
    if group is not None:
        return group.nranks
    from . import env

    return env.get_world_size()


# ---------------------------------------------------------------------------
# helpers


def _is_traced(arr) -> bool:
    return isinstance(arr, jax.core.Tracer)


def _axis_in_sharding(arr, axis: str) -> bool:
    sh = getattr(arr, "sharding", None)
    if sh is None or not isinstance(sh, NamedSharding):
        return False
    for entry in sh.spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        if axis in names:
            return True
    return False


@functools.lru_cache(maxsize=256)
def _shard_map_collective(mesh, axis, kind, op, shape, dtype, spec):
    """Build a jitted shard_map program for an eager collective."""
    P = PartitionSpec
    reduced_spec = _drop_axis(spec, axis)

    def _wrap(f, out_spec):
        return jax.jit(
            jax.shard_map(f, mesh=mesh, in_specs=(P(*spec),),
                          out_specs=P(*out_spec), check_vma=False)
        )

    if kind == "all_reduce":
        def f(x):
            return _REDUCE_FNS.get(op, jax.lax.psum)(x, axis) if op != ReduceOp.AVG else jax.lax.pmean(x, axis)

        return _wrap(f, reduced_spec)
    if kind == "all_gather":
        return _wrap(lambda x: jax.lax.all_gather(x, axis, tiled=False), (None,) + tuple(reduced_spec))
    if kind == "broadcast":
        # op carries src: every shard takes src's block
        return _wrap(lambda x: jax.lax.all_gather(x, axis, tiled=False)[op], reduced_spec)
    if kind == "reduce_scatter":
        return _wrap(lambda x: jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True), spec)
    if kind == "alltoall":
        return _wrap(
            lambda x: jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True), spec
        )
    if kind == "shift":
        n = mesh.shape[axis]
        perm = [(i, (i + op) % n) for i in range(n)]  # op carries offset
        return _wrap(lambda x: jax.lax.ppermute(x, axis, perm), spec)
    raise ValueError(kind)


def _drop_axis(spec, axis):
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(n for n in entry if n != axis)
            out.append(kept if kept else None)
        else:
            out.append(None if entry == axis else entry)
    return tuple(out)


def _spec_of(arr):
    sh = arr.sharding
    return tuple(sh.spec) + (None,) * (arr.ndim - len(sh.spec))


def _data(t):
    return t._data if isinstance(t, Tensor) else t


def _is_per_process(g: Group, x) -> bool:
    """Regime 4: multi-process eager (launcher-spawned, one group member per
    jax process) with a process-local tensor — the reference's ProcessGroup
    semantics, where each rank holds its own full tensor."""
    if jax.process_count() <= 1 or g.nranks != jax.process_count():
        return False
    # the tensor must actually be process-local — a global array sharded
    # along some OTHER mesh axis must not be np.asarray'd here
    if isinstance(x, jax.Array) and (
        not x.is_fully_addressable or len(x.sharding.device_set) > 1
    ):
        return False
    # each group member must live on a distinct process, or the per-process
    # local block handed to make_array_from_process_local_data is wrong
    devs = g.mesh.devices
    names = list(g.mesh.axis_names)
    if g.axis not in names:
        return False
    ax = names.index(g.axis)
    idx = [0] * devs.ndim
    procs = set()
    for i in range(devs.shape[ax]):
        idx[ax] = i
        procs.add(devs[tuple(idx)].process_index)
    return len(procs) == g.nranks


def _per_process_collective(g: Group, x, kind, op):
    """Assemble a (nranks, *shape) global array from each process's local
    tensor, run the one-op shard_map program over the group axis, and return
    the (replicated) result array of shape (k, *shape)."""
    spec = (g.axis,) + (None,) * x.ndim
    sharding = NamedSharding(g.mesh, PartitionSpec(*spec))
    garr = jax.make_array_from_process_local_data(sharding, np.asarray(x)[None])
    fn = _shard_map_collective(g.mesh, g.axis, kind, op, garr.shape, str(garr.dtype), spec)
    out = fn(garr)
    # output is replicated along the group axis: this process's shard is the
    # whole value
    return jnp.asarray(out.addressable_shards[0].data)


# ---------------------------------------------------------------------------
# collectives


def all_reduce(tensor, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op: bool = True):
    """In-place allreduce (paddle contract: mutates ``tensor``)."""
    g = group or _get_default_group()
    x = _data(tensor)
    if _is_traced(x):
        red = _REDUCE_FNS.get(op, jax.lax.psum) if op != ReduceOp.AVG else jax.lax.pmean
        out = red(x, g.axis)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if _is_per_process(g, x):
        out = _per_process_collective(g, x, "all_reduce", op)[0]
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if g.nranks <= 1 or not _axis_in_sharding(x, g.axis):
        return tensor
    fn = _shard_map_collective(g.mesh, g.axis, "all_reduce", op, x.shape, str(x.dtype), _spec_of(x))
    out = fn(x)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def all_gather(tensor_list: list, tensor, group: Optional[Group] = None, sync_op: bool = True):
    """Gather ``tensor`` from all ranks into ``tensor_list`` (paddle contract)."""
    g = group or _get_default_group()
    x = _data(tensor)
    if _is_traced(x):
        out = jax.lax.all_gather(x, g.axis, tiled=False)
        tensor_list.extend(Tensor(out[i]) for i in range(g.nranks))
        return tensor_list
    if _is_per_process(g, x):
        out = _per_process_collective(g, x, "all_gather", ReduceOp.SUM)
        tensor_list.extend(Tensor(out[i, 0]) for i in range(out.shape[0]))
        return tensor_list
    if g.nranks <= 1 or not _axis_in_sharding(x, g.axis):
        tensor_list.append(tensor if isinstance(tensor, Tensor) else Tensor(x))
        return tensor_list
    fn = _shard_map_collective(g.mesh, g.axis, "all_gather", ReduceOp.SUM, x.shape, str(x.dtype), _spec_of(x))
    out = fn(x)
    for i in range(out.shape[0]):
        tensor_list.append(Tensor(out[i]))
    return tensor_list


def broadcast(tensor, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    g = group or _get_default_group()
    x = _data(tensor)
    # src is a global rank (paddle contract); the gather index is the
    # position along the group's axis
    src_idx = g.get_group_rank(src)
    if src_idx < 0:
        raise ValueError(f"src rank {src} is not a member of {g}")
    if _is_traced(x):
        # broadcast from src along the bound axis: select src's value
        out = jax.lax.all_gather(x, g.axis, tiled=False)[src_idx]
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if _is_per_process(g, x):
        out = _per_process_collective(g, x, "broadcast", src_idx)[0]
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if g.nranks <= 1 or not _axis_in_sharding(x, g.axis):
        return tensor  # degenerate / replicated
    fn = _shard_map_collective(g.mesh, g.axis, "broadcast", src_idx, x.shape, str(x.dtype), _spec_of(x))
    out = fn(x)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def reduce(tensor, dst: int = 0, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op: bool = True):
    # single-controller: reduce == all_reduce (every "rank" holds the result)
    return all_reduce(tensor, op, group, sync_op)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op: bool = True):
    g = group or _get_default_group()
    x = _data(tensor if tensor_list is None else jnp.stack([_data(t) for t in tensor_list]))
    if _is_traced(x):
        out = jax.lax.psum_scatter(x, g.axis, scatter_dimension=0, tiled=True)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if g.nranks <= 1:
        if tensor_list is not None and isinstance(tensor, Tensor):
            tensor._data = _data(tensor_list[0])
        return tensor
    if not _axis_in_sharding(x, g.axis):
        raise NotImplementedError(
            "eager reduce_scatter needs the input sharded along the group "
            "axis (or group size 1); got an unsharded array"
        )
    fn = _shard_map_collective(g.mesh, g.axis, "reduce_scatter", op, x.shape, str(x.dtype), _spec_of(x))
    out = fn(x)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def scatter(tensor, tensor_list=None, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    g = group or _get_default_group()
    if g.nranks <= 1:
        if tensor_list:
            src_t = tensor_list[src]
            tensor._data = _data(src_t)
        return tensor
    x = _data(tensor)
    if _is_traced(x) and tensor_list is not None:
        stacked = jnp.stack([_data(t) for t in tensor_list])
        idx = jax.lax.axis_index(g.axis)
        tensor._data = jnp.take(stacked, idx, axis=0)
        return tensor
    raise NotImplementedError(
        "eager scatter over a group of size > 1 is only expressible inside a "
        "traced (shard_map) program in the single-controller model"
    )


def alltoall(in_tensor_list, out_tensor_list=None, group: Optional[Group] = None, sync_op: bool = True):
    g = group or _get_default_group()
    if isinstance(in_tensor_list, (list, tuple)):
        x = jnp.stack([_data(t) for t in in_tensor_list])
    else:
        x = _data(in_tensor_list)
    if _is_traced(x):
        out = jax.lax.all_to_all(x, g.axis, split_axis=0, concat_axis=0, tiled=False)
        if out_tensor_list is not None:
            out_tensor_list.extend(Tensor(out[i]) for i in range(out.shape[0]))
            return out_tensor_list
        return Tensor(out)
    if g.nranks <= 1:
        if out_tensor_list is not None:
            out_tensor_list.extend(
                t if isinstance(t, Tensor) else Tensor(t) for t in in_tensor_list
            )
            return out_tensor_list
        return in_tensor_list
    if _axis_in_sharding(x, g.axis):
        fn = _shard_map_collective(g.mesh, g.axis, "alltoall", 0, x.shape, str(x.dtype), _spec_of(x))
        out = Tensor(fn(x))
        if out_tensor_list is not None:
            chunk = out._data.shape[0] // g.nranks
            out_tensor_list.extend(Tensor(out._data[i * chunk:(i + 1) * chunk]) for i in range(g.nranks))
            return out_tensor_list
        return out
    raise NotImplementedError(
        "eager alltoall needs the input sharded along the group axis "
        "(or group size 1); got an unsharded array"
    )


def alltoall_single(in_tensor, out_tensor=None, group: Optional[Group] = None, sync_op: bool = True, **kw):
    g = group or _get_default_group()
    x = _data(in_tensor)
    if _is_traced(x):
        out = jax.lax.all_to_all(x, g.axis, split_axis=0, concat_axis=0, tiled=True)
        if out_tensor is not None:
            out_tensor._data = out
            return out_tensor
        return Tensor(out)
    if g.nranks <= 1:
        return in_tensor
    if _axis_in_sharding(x, g.axis):
        fn = _shard_map_collective(g.mesh, g.axis, "alltoall", 0, x.shape, str(x.dtype), _spec_of(x))
        out = fn(x)
        if out_tensor is not None:
            out_tensor._data = out
            return out_tensor
        return Tensor(out)
    raise NotImplementedError(
        "eager alltoall_single needs the input sharded along the group axis"
    )


def shift(tensor, offset: int = 1, group: Optional[Group] = None):
    """SPMD point-to-point: every rank i sends its value to rank
    (i+offset) mod n — ONE valid permutation over the axis (the compiled
    form of the reference's partial_send/recv PP hops,
    ref:python/paddle/distributed/fleet/meta_parallel/pp_utils/
    p2p_communication.py). Use this inside shard_map'd pipeline schedules."""
    g = group or _get_default_group()
    x = _data(tensor)
    if g.nranks <= 1:
        return tensor
    if _is_traced(x):
        perm = [(i, (i + offset) % g.nranks) for i in range(g.nranks)]
        out = jax.lax.ppermute(x, g.axis, perm)
        if isinstance(tensor, Tensor):
            return Tensor(out, stop_gradient=tensor.stop_gradient)
        return out
    if not _axis_in_sharding(x, g.axis):
        raise NotImplementedError(
            "eager shift needs the input sharded along the group axis "
            "(or group size 1); got an unsharded array"
        )
    fn = _shard_map_collective(g.mesh, g.axis, "shift", offset, x.shape, str(x.dtype), _spec_of(x))
    out = fn(x)
    if isinstance(tensor, Tensor):
        return Tensor(out, stop_gradient=tensor.stop_gradient)
    return out


def send(tensor, dst: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """Per-rank p2p send. In the single-controller SPMD model a rank-local
    send has no meaning inside a traced program — pipeline hops are uniform
    shifts; use :func:`shift`. Degenerate (world 1) is a no-op."""
    g = group or _get_default_group()
    if g.nranks <= 1:
        return tensor
    if _is_traced(_data(tensor)):
        raise NotImplementedError(
            "per-rank send/recv inside a traced program is not expressible in "
            "SPMD; use paddle_tpu.distributed.shift(tensor, offset, group) "
            "for pipeline p2p hops"
        )
    return tensor


def recv(tensor, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    g = group or _get_default_group()
    if g.nranks <= 1:
        return tensor
    if _is_traced(_data(tensor)):
        raise NotImplementedError(
            "per-rank send/recv inside a traced program is not expressible in "
            "SPMD; use paddle_tpu.distributed.shift(tensor, offset, group) "
            "for pipeline p2p hops"
        )
    return tensor


def barrier(group: Optional[Group] = None):
    """Host-level barrier: block until all pending device work completes; in
    multi-process mode also syncs via the coordination service."""
    (jnp.zeros(()) + 0).block_until_ready()
    if jax.process_count() > 1:
        try:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("paddle_tpu_barrier")
        except Exception:
            pass


def all_gather_object(object_list: list, obj, group: Optional[Group] = None):
    """Host-side object gather: pickle → padded uint8 arrays →
    process_allgather over DCN → unpickle per rank (the TCPStore-object
    exchange of ref:python/paddle/distributed/communication/all_gather.py,
    rebuilt on the coordination service). Identity in single-process."""
    import pickle

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        payload = np.frombuffer(pickle.dumps(obj, protocol=4), dtype=np.uint8)
        lengths = multihost_utils.process_allgather(np.asarray([payload.size], np.int64))
        max_len = int(lengths.max())
        padded = np.zeros((max_len,), np.uint8)
        padded[: payload.size] = payload
        gathered = multihost_utils.process_allgather(padded)  # [nproc, max_len]
        for r in range(gathered.shape[0]):
            object_list.append(pickle.loads(gathered[r, : int(lengths[r][0])].tobytes()))
        return object_list
    object_list.append(obj)
    return object_list


def stream_all_reduce(*a, **k):  # paddle.distributed.stream.* parity hooks
    return all_reduce(*a, **k)
