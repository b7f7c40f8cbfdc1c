"""Device mesh & hybrid-parallel topology.

Replaces the reference's rank-cartesian topology
(``CommunicateTopology``/``HybridCommunicateGroup``,
ref:python/paddle/distributed/fleet/base/topology.py:54,140) and the C++
``ProcessMesh``/``DeviceMesh`` dist-attr structs
(ref:paddle/fluid/distributed/auto_parallel/process_mesh.h, device_mesh.h).

TPU-native: ONE ``jax.sharding.Mesh`` with named axes is the whole topology.
Axis names follow the reference's hybrid order ["data", "pipe", "sharding",
"model"] extended with "sep" (sequence/context parallel — a gap in the
reference, SURVEY.md §5.7) and "expert" (MoE). Per-axis "communication
groups" are just axis names; XLA lowers collectives onto the ICI torus.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# canonical axis order (outer → inner on the device array); inner axes get the
# fastest ICI links, so "model" (highest traffic) sits innermost, like the
# reference puts mp innermost in its topology order.
HYBRID_AXES = ("data", "pipe", "sharding", "sep", "expert", "model")

_state = threading.local()
_global_mesh: Optional[Mesh] = None
_global_lock = threading.Lock()


def build_mesh(
    axis_dims: Dict[str, int],
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Create a named mesh. ``axis_dims`` maps axis name -> degree; axes not
    given default to 1 and are dropped. Degrees must multiply to #devices."""
    devices = list(devices if devices is not None else jax.devices())
    names = [a for a in HYBRID_AXES if axis_dims.get(a, 1) > 1]
    extra = [a for a in axis_dims if a not in HYBRID_AXES and axis_dims[a] > 1]
    names += extra
    dims = [axis_dims[a] for a in names]
    if not names:
        names, dims = ["data"], [len(devices)]
    total = int(np.prod(dims))
    if total != len(devices):
        raise ValueError(
            f"mesh axis dims {dict(zip(names, dims))} multiply to {total}, "
            f"but {len(devices)} devices are available"
        )
    dev_array = np.array(devices).reshape(dims)
    return Mesh(dev_array, tuple(names))


def serving_mesh(model_parallel: int, data: int = 1,
                 devices: Optional[Sequence] = None,
                 install: bool = True) -> Mesh:
    """The serving topology of ISSUE 14: a ``("data", "model")`` mesh —
    batch/replica axis outer, tensor-parallel axis innermost (fastest ICI).
    ``model_parallel`` shards attention/MLP weights and the KV arena's
    head dim; ``data`` replicates the engine and shards the slot batch.
    ``install=True`` (default) also makes it the global mesh so models
    built afterwards commit their parameters with the right shardings —
    the serving engine captures whatever mesh is installed at construction
    as part of its program key. ``devices`` defaults to all; pass a
    one-device slice to build the 1-device mesh whose compiled programs
    are bit-identical to the no-mesh path (tests assert this). When
    ``data * model_parallel`` covers fewer devices than exist, the mesh is
    built over the first ``data * model_parallel`` of them (a sub-mesh is
    a legal serving topology — the rest of the chips belong to other
    replicas)."""
    if devices is None:
        devices = list(jax.devices())[:int(data) * int(model_parallel)]
    mesh = build_mesh({"data": int(data), "model": int(model_parallel)},
                      devices)
    if install:
        set_mesh(mesh)
    return mesh


def set_mesh(mesh: Mesh):
    global _global_mesh
    with _global_lock:
        _global_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _global_mesh


def clear_mesh() -> None:
    """Uninstall the global mesh (tests that interleave mesh and
    single-device builds; models constructed afterwards commit unsharded)."""
    global _global_mesh
    with _global_lock:
        _global_mesh = None


def ensure_mesh() -> Mesh:
    """Current mesh; lazily builds a 1-axis data mesh over all devices."""
    global _global_mesh
    if _global_mesh is None:
        set_mesh(build_mesh({"data": len(jax.devices())}))
    return _global_mesh


def axis_size(axis: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or ensure_mesh()
    return mesh.shape.get(axis, 1)


def named_sharding(*spec, mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or ensure_mesh()
    return NamedSharding(mesh, PartitionSpec(*spec))


class HybridCommunicateGroup:
    """Parity object for fleet topology queries
    (ref:python/paddle/distributed/fleet/base/topology.py:140).

    In the single-controller model "rank" means the current process; per-axis
    rank/world queries answer from the mesh shape and process index.
    """

    def __init__(self, mesh: Mesh):
        self._mesh = mesh
        self._shape = dict(mesh.shape)

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    def get_parallel_mode(self):
        if self._shape.get("model", 1) > 1 or self._shape.get("pipe", 1) > 1:
            return "hybrid"
        if self._shape.get("sharding", 1) > 1:
            return "sharding_parallel"
        return "data_parallel"

    # degree queries (paddle names)
    def get_data_parallel_world_size(self) -> int:
        return self._shape.get("data", 1)

    def get_model_parallel_world_size(self) -> int:
        return self._shape.get("model", 1)

    def get_pipe_parallel_world_size(self) -> int:
        return self._shape.get("pipe", 1)

    def get_sharding_parallel_world_size(self) -> int:
        return self._shape.get("sharding", 1)

    def get_sep_parallel_world_size(self) -> int:
        return self._shape.get("sep", 1)

    def get_expert_parallel_world_size(self) -> int:
        return self._shape.get("expert", 1)

    def _axis_rank(self, axis: str) -> int:
        # process-level rank along an axis: derive from the coordinates of
        # this process's first addressable device in the mesh device array.
        if self._shape.get(axis, 1) <= 1:
            return 0
        local = jax.local_devices()[0]
        coords = np.argwhere(self._mesh.devices == local)
        if coords.size == 0:
            return 0
        return int(coords[0][list(self._mesh.axis_names).index(axis)])

    def get_data_parallel_rank(self) -> int:
        return self._axis_rank("data")

    def get_model_parallel_rank(self) -> int:
        return self._axis_rank("model")

    def get_stage_id(self) -> int:
        return self._axis_rank("pipe")

    def get_sharding_parallel_rank(self) -> int:
        return self._axis_rank("sharding")

    def topology(self):
        return self._shape


def init_hybrid_mesh(
    dp: int = 1,
    mp: int = 1,
    pp: int = 1,
    sharding: int = 1,
    sep: int = 1,
    expert: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build + install the global hybrid mesh (fleet hybrid_configs analog)."""
    ndev = len(devices) if devices is not None else len(jax.devices())
    given = dp * mp * pp * sharding * sep * expert
    if given != ndev:
        if dp == 1 and ndev % (mp * pp * sharding * sep * expert) == 0:
            dp = ndev // (mp * pp * sharding * sep * expert)  # auto-fill data axis
        else:
            raise ValueError(f"degrees {given} != device count {ndev}")
    mesh = build_mesh(
        {"data": dp, "pipe": pp, "sharding": sharding, "sep": sep, "expert": expert, "model": mp},
        devices,
    )
    set_mesh(mesh)
    return mesh
