"""Eager Tensor.

Replaces the reference's ``phi::DenseTensor`` + eager ``paddle.Tensor``
(ref:paddle/phi/core/dense_tensor.h, ref:paddle/fluid/pybind/eager_method.cc).
A Tensor wraps a ``jax.Array`` (device buffer, XLA-managed HBM) or — under a
``jax.jit`` trace — a JAX tracer, so the same user code runs eagerly and
inside compiled programs.

Autograd state (``stop_gradient``, ``grad``, the producing tape node) lives on
the Tensor, mirroring paddle's dygraph contract: new tensors default to
``stop_gradient=True``; parameters set it to False.
"""
from __future__ import annotations

import weakref
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod
from .device import Place, current_place


class Tensor:
    __slots__ = (
        "_data",
        "stop_gradient",
        "grad",
        "_node",
        "_hooks",
        "name",
        "persistable",
        "_retain_grad",
        "_version",
        # static-graph capture: set only on symbolic placeholders/outputs
        # (static.data / captured ops); unset on eager tensors so
        # getattr(t, "_sym_id", None) stays the cheap discriminator
        "_sym_id",
        "_feed_shape",
        "__weakref__",
    )

    def __init__(self, data, stop_gradient: bool = True, name: Optional[str] = None):
        self._data = data  # jax.Array or tracer
        self.stop_gradient = stop_gradient
        self.grad: Optional[Tensor] = None
        self._node = None  # TapeNode that produced this tensor (autograd)
        self._hooks = None
        self.name = name
        self.persistable = False
        self._retain_grad = False
        # bumped by in-place mutation; tape nodes snapshot it so backward can
        # reject stale reads (the reference's inplace version check,
        # ref:paddle/fluid/eager/tensor_wrapper.h inplace_version)
        self._version = 0

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self) -> Place:
        d = getattr(self._data, "devices", None)
        if d:
            dev = next(iter(self._data.devices()))
            return Place(dev.platform, dev.id)
        return current_place()

    @property
    def is_leaf(self) -> bool:
        return self._node is None

    def _no_concrete(self):
        if getattr(self, "_sym_id", None) is not None:
            raise RuntimeError(
                "this Tensor is a static-graph placeholder (static.data / a "
                "captured op output) — it has no value until Executor.run; "
                "fetch it via fetch_list instead of reading it directly")

    def numpy(self) -> np.ndarray:
        self._no_concrete()
        return np.asarray(self._data)

    def item(self):
        self._no_concrete()
        return self._data.item()

    def tolist(self):
        self._no_concrete()
        return np.asarray(self._data).tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (
            f"Tensor(shape={self.shape}, dtype={dtype_mod.dtype_name(self.dtype)}"
            f"{grad_info},\n       {np.asarray(jax.device_get(self._data)) if not self._is_traced() else self._data!r})"
        )

    def _is_traced(self) -> bool:
        return isinstance(self._data, jax.core.Tracer)

    def __bool__(self):
        self._no_concrete()
        return bool(self._data)

    def __int__(self):
        self._no_concrete()
        return int(self._data)

    def __float__(self):
        self._no_concrete()
        return float(self._data)

    def __array__(self, dtype=None):
        self._no_concrete()
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- autograd ----------------------------------------------------------
    def backward(self, grad_tensor: Optional["Tensor"] = None, retain_graph: bool = False):
        from . import autograd

        autograd.backward_from(self, grad_tensor, retain_graph)

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def retain_grads(self):
        self._retain_grad = True

    def register_hook(self, hook):
        """Register a cotangent hook (applied to this tensor's incoming grad)."""
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)

        class _Removable:
            def __init__(self, hooks, h):
                self._hooks, self._h = hooks, h

            def remove(self):
                if self._h in self._hooks:
                    self._hooks.remove(self._h)

        return _Removable(self._hooks, hook)

    def detach(self) -> "Tensor":
        return Tensor(self._data, stop_gradient=True, name=self.name)

    def clone(self) -> "Tensor":
        from ..ops import math as _m

        return _m.assign(self)

    # -- conversion / placement -------------------------------------------
    def astype(self, dtype) -> "Tensor":
        from ..ops import manipulation as _mm

        return _mm.cast(self, dtype)

    cast = astype

    def to(self, *args, **kwargs) -> "Tensor":
        dtype = None
        device = None
        for a in args:
            if isinstance(a, str) and a in dtype_mod._STR_TO_DTYPE:
                dtype = a
            elif isinstance(a, str):
                device = a
        dtype = kwargs.get("dtype", dtype)
        device = kwargs.get("device", device)
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            from .device import set_device  # noqa: F401  (parse-only)

            t, _, i = device.partition(":")
            place = Place(t, int(i) if i else 0)
            out = Tensor(jax.device_put(out._data, place.jax_device()), out.stop_gradient)
        return out

    def cpu(self):
        return self.to(device="cpu")

    def _copy_to(self, place, blocking=True):
        return Tensor(jax.device_put(self._data, place.jax_device()), self.stop_gradient)

    # -- in-place mutation (eager only) -----------------------------------
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        self._data = jnp.asarray(value, dtype=self.dtype)
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        return self

    def zero_(self):
        return self.fill_(0)

    def scale_(self, scale):
        self._data = self._data * scale
        return self

    def __setitem__(self, idx, value):
        """Differentiable in-place assignment (the reference's set_value op),
        recorded through run_inplace: the vjp zeroes the overwritten region,
        so gradients no longer flow through replaced entries; the value
        tensor (if any) receives its gradient."""
        from .dispatch import apply, run_inplace

        from . import autograd as _ag

        idx_u = _unwrap_index(idx)
        val_t = value if isinstance(value, Tensor) else Tensor(jnp.asarray(value))

        if _index_is_static(idx_u):
            hidx = _hashable_index(idx_u)
            run_inplace(
                lambda t, v: apply(_setitem_static, (t, v), {"idx": hidx},
                                   name="set_value"), self, val_t)
        elif _index_has_bool_mask(idx_u) and not isinstance(idx_u, tuple):
            # mask assignment: where() is only valid when the value applies
            # identically at every selected position — a scalar, or a value
            # broadcasting over the dims the mask does NOT index. A value
            # mapped per-nonzero has a data-dependent layout: gather the
            # nonzero coordinates on the host (eager-only, the bool-mask
            # __getitem__ contract) and scatter in nonzero order.
            mask = idx_u
            k = getattr(mask, "ndim", 0)
            trail = tuple(self._data.shape)[k:]
            vshape = tuple(val_t._data.shape)
            pos_independent = val_t._data.size == 1
            if not pos_independent and len(vshape) <= len(trail):
                try:
                    np.broadcast_shapes(trail, vshape)
                    pos_independent = True
                except ValueError:
                    pass
            if pos_independent:
                mask_e = jnp.asarray(mask)
                mask_e = mask_e.reshape(tuple(mask_e.shape) + (1,) * len(trail))
                run_inplace(
                    lambda t, m, v: apply(_setitem_mask, (t, m, v), {},
                                          name="set_value"),
                    self, Tensor(mask_e), val_t)
            else:
                if (self._is_traced() or val_t._is_traced()
                        or isinstance(mask, jax.core.Tracer)):
                    raise NotImplementedError(
                        "mask assignment with a per-nonzero value has a "
                        "data-dependent mapping and cannot be jitted")
                coords = np.nonzero(np.asarray(mask))
                run_inplace(
                    lambda t, v, *ii: apply(_setitem_coords, (t, v) + ii, {},
                                            name="set_value"),
                    self, val_t, *(Tensor(jnp.asarray(c)) for c in coords))
        elif not isinstance(idx_u, tuple):
            run_inplace(
                lambda t, i, v: apply(_setitem_dynamic, (t, i, v), {},
                                      name="set_value"),
                self, Tensor(jnp.asarray(idx_u)), val_t)
        else:  # mixed dynamic tuple index: rare; functional update, no tape
            if (_ag.is_grad_enabled()
                    and (not self.stop_gradient or not val_t.stop_gradient)):
                raise NotImplementedError(
                    "gradient through a mixed dynamic tuple index assignment "
                    "is not supported; index with a single array or static "
                    "slices, or assign under paddle.no_grad()")
            arr = val_t._data
            self._data = self._data.at[idx_u].set(
                arr.astype(self._data.dtype) if hasattr(arr, "astype") else arr)
            self._version += 1

    def __getitem__(self, idx):
        from .dispatch import apply

        idx = _unwrap_index(idx)
        if _index_is_static(idx):
            # slices encode hashably (slice.__hash__ is 3.12+ only)
            return apply(_getitem_static, (self,),
                         {"idx": _hashable_index(idx)})
        if _index_has_bool_mask(idx):
            # data-dependent output shape: host round-trip, eager only
            # (same contract as nonzero/masked_select)
            if self._is_traced():
                raise ValueError("boolean-mask indexing has a data-dependent shape and cannot be jitted")
            return Tensor(jnp.asarray(np.asarray(self._data)[idx]))
        if isinstance(idx, tuple):
            # mixed advanced indexing (arrays + slices/ints): numpy
            # COORDINATE semantics — index arrays broadcast and pair up
            # (the reference lowers this to gather_nd over the broadcast
            # index grid, ref:python/paddle/fluid/variable_index.py:147
            # SliceInfo.get_item). Collapsing the tuple into one array
            # would instead gather each list along axis 0.
            arrays, spec = [], []
            for i in idx:
                if isinstance(i, (int, slice, type(None), type(Ellipsis))):
                    spec.append(("s", _hashable_index(i)))
                else:
                    # _unwrap_index already replaced Tensors with arrays
                    spec.append(("a", len(arrays)))
                    arrays.append(Tensor(jnp.asarray(i)))
            return apply(_getitem_mixed, (self, *arrays),
                         {"spec": tuple(spec)})
        # dynamic integer index: direct gather, no static-arg jit
        return apply(_getitem_dynamic, (self, Tensor(jnp.asarray(idx))), {})

    # -- method registry (ops patch themselves on, like monkey_patch_varbase) --
    @classmethod
    def _register_method(cls, name, fn):
        setattr(cls, name, fn)


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return np.asarray(idx._data) if not idx._is_traced() else idx._data
    if isinstance(idx, tuple):
        return tuple(_unwrap_index(i) for i in idx)
    return idx


def _index_is_static(idx):
    if isinstance(idx, tuple):
        return all(_index_is_static(i) for i in idx)
    return isinstance(idx, (int, slice, type(None), type(Ellipsis), bool))


def _index_has_bool_mask(idx):
    if isinstance(idx, tuple):
        return any(_index_has_bool_mask(i) for i in idx)
    if isinstance(idx, list):  # python bool lists are masks too (numpy)
        a = np.asarray(idx)
        return a.dtype == np.bool_
    return hasattr(idx, "dtype") and jnp.dtype(idx.dtype) == jnp.dtype(jnp.bool_)


def _hashable_index(idx):
    if isinstance(idx, slice):
        return ("slice", idx.start, idx.stop, idx.step)
    if isinstance(idx, tuple):
        return tuple(_hashable_index(i) for i in idx)
    return idx


def _unhash_index(idx):
    if isinstance(idx, tuple):
        if len(idx) == 4 and idx and idx[0] == "slice":
            return slice(idx[1], idx[2], idx[3])
        return tuple(_unhash_index(i) for i in idx)
    return idx


def _getitem_static(x, *, idx):
    return x[_unhash_index(idx)]


def _getitem_dynamic(x, idx):
    return x[idx]


def _getitem_mixed(x, *arrays, spec):
    sel = tuple(arrays[v] if kind == "a" else _unhash_index(v)
                for kind, v in spec)
    return x[sel]


def _fit_assign(v, slot_shape, dtype):
    """numpy assignment broadcasting: surplus leading length-1 dims drop."""
    v = v.astype(dtype)
    while v.ndim > len(slot_shape) and v.shape[0] == 1:
        v = v[0]
    return v


def _setitem_static(x, v, *, idx):
    i = _unhash_index(idx)
    return x.at[i].set(_fit_assign(v, x[i].shape, x.dtype))


def _setitem_dynamic(x, idx, v):
    return x.at[idx].set(_fit_assign(v, x[idx].shape, x.dtype))


def _setitem_mask(x, mask, v):
    return jnp.where(mask, v.astype(x.dtype), x)


def _setitem_coords(x, v, *idx):
    sel = tuple(idx)
    return x.at[sel].set(_fit_assign(v, x[sel].shape, x.dtype))


def to_tensor(data, dtype=None, place: Optional[Place] = None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor equivalent."""
    dtype = dtype_mod.convert_dtype_arg(dtype)
    if isinstance(data, Tensor):
        arr = data._data
        if dtype is not None and arr.dtype != jnp.dtype(dtype):
            arr = arr.astype(dtype)
        if place is not None:
            arr = jax.device_put(arr, place.jax_device())
        return Tensor(arr, stop_gradient=stop_gradient)
    if isinstance(data, (list, tuple)) and any(isinstance(x, Tensor) for x in data):
        data = [np.asarray(x._data) if isinstance(x, Tensor) else x for x in data]
    arr = np.asarray(data)
    if dtype is None and arr.dtype == np.float64:
        arr = arr.astype(np.float32)  # paddle default dtype contract
    if dtype is not None:
        arr = np.asarray(arr, dtype=jnp.dtype(dtype))
    from . import device as device_mod

    if place is None and device_mod._current_device is not None:
        place = device_mod._current_device  # user called set_device: honor it
    if place is not None:
        # explicit placement commits the array to that device
        return Tensor(jax.device_put(arr, place.jax_device()), stop_gradient=stop_gradient)
    # no explicit place: leave the array uncommitted so jit/pjit may reshard
    # it freely (a device-0-committed input poisons multi-device programs)
    return Tensor(jnp.asarray(arr), stop_gradient=stop_gradient)


def _unwrap(x):
    return x._data if isinstance(x, Tensor) else x


def _wrap(x, stop_gradient=True):
    return Tensor(x, stop_gradient=stop_gradient)


# Register Tensor as a JAX pytree so Tensors flow through jax.jit / jax.grad /
# shard_map transparently (the functional_call path relies on this).
jax.tree_util.register_pytree_node(
    Tensor,
    lambda t: ((t._data,), t.stop_gradient),
    lambda aux, children: Tensor(children[0], stop_gradient=aux),
)
