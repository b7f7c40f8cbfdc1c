"""Paged multi-LoRA adapter arena for the compiled decode step.

One engine, N fine-tunes: every gateway tenant can carry its own LoRA
adapter over the SHARED (possibly int8) base weights, and a single batch
mixes adapters freely. The design mirrors :mod:`~.kv_arena` — a fixed
paged arena addressed by per-slot indices that are pure runtime data:

* Per targeted linear (the matmuls the model declares through
  ``serving_linears()``, the same ones the int8 quantizer targets — for
  GPT ``attn.qkv`` / ``attn.proj`` / ``mlp.up`` / ``mlp.down``, per layer)
  the arena holds
  stacked pools ``A [cap+1, in, r]`` / ``B [cap+1, r, out]`` float32.
  **Row 0 is the identity adapter** (all zeros — the LoRA scratch block):
  a slot with ``adapter_id = 0`` runs the base model, token-identical to
  an engine without the arena.
* :meth:`AdapterArena.register` takes a row from a LIFO free list and
  writes the adapter's matrices (``alpha/r`` scaling folded into ``B`` at
  registration — no per-step scaling math); :meth:`unregister` returns
  the row. Registration changes pool *values*, never shapes, so it costs
  zero recompiles — like admit/retire.
* Inside the compiled step every slot gathers its adapter by index:
  ``delta = (x @ A[ids]) @ B[ids]`` in float32, added to the base
  matmul's output inside :func:`models.serving_seam.serving_linear` (the one
  attention/MLP matmul entry point — with ``FLAGS_serving_quant_weights``
  the base matmul streams int8 and the adapter stays f32: int8 base +
  f32 adapters, see docs/quantization.md). The pools ride into every
  program as arguments (runtime data) and the per-slot ``adapter_ids``
  thread exactly like ``start_pos``.

The binding between the traced pools and the model's linears is a
trace-time context (:meth:`AdapterArena.bind`): the engine's compiled
bodies enter it around the model's forward, ``serving_linear`` consults
it per layer. No context (training, plain ``generate()``, the spec-decode
verify program) ⇒ the hook is inert and the trace is unchanged.

Counters/gauges (``lora.*`` in ``serving.metrics``): ``registered`` /
``unregistered`` / ``admits`` (slots admitted with a non-zero adapter),
gauges ``lora.slots`` / ``lora.live`` / ``lora.arena_bytes``.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import metrics

__all__ = ["LoraAdapter", "AdapterArena"]

_tls = threading.local()  # .ctx — the active trace-time binding


class AdapterExhaustedError(RuntimeError):
    """No free adapter row left — the arena's ``capacity`` is live.
    Unregister an adapter (or size ``FLAGS_serving_lora_adapters`` up)."""


class LoraAdapter:
    """One adapter's weights: ``{"<layer>.<target>": (A [in, r],
    B [r, out])}``, a site being one the model's ``serving_linears()``
    names (GPT: ``attn.qkv``, ``attn.proj``, ``mlp.up``, ``mlp.down``). Missing sites stay
    identity (zeros). ``alpha`` is the usual LoRA scaling — folded into
    ``B`` as ``alpha / rank`` at registration time."""

    def __init__(self, weights: Dict[str, Tuple[np.ndarray, np.ndarray]],
                 alpha: float = 1.0, name: str = ""):
        self.weights = {str(k): (np.asarray(a, np.float32),
                                 np.asarray(b, np.float32))
                        for k, (a, b) in weights.items()}
        self.alpha = float(alpha)
        self.name = name

    @classmethod
    def random(cls, cfg, rank: int, seed: int = 0, scale: float = 0.02,
               name: str = "") -> "LoraAdapter":
        """A dense random adapter over every site (test/bench helper)."""
        rng = np.random.default_rng(seed)
        dims = {"attn.qkv": (cfg.hidden_size, 3 * cfg.hidden_size),
                "attn.proj": (cfg.hidden_size, cfg.hidden_size),
                "mlp.up": (cfg.hidden_size, cfg.intermediate_size),
                "mlp.down": (cfg.intermediate_size, cfg.hidden_size)}
        weights = {}
        for li in range(cfg.num_layers):
            for tgt, (fi, fo) in dims.items():
                weights[f"{li}.{tgt}"] = (
                    rng.normal(0, scale, (fi, rank)),
                    rng.normal(0, scale, (rank, fo)))
        return cls(weights, name=name)


class _TraceCtx:
    """The trace-time binding ``_serving_linear``'s hook reads: traced
    pool arrays per site, the per-lane adapter-id tracer, and the
    id(linear) → site index map."""

    __slots__ = ("pools", "ids", "site_by_layer")

    def __init__(self, pools, ids, site_by_layer):
        self.pools = pools
        self.ids = ids
        self.site_by_layer = site_by_layer


def _lora_hook(layer, x, y):
    """``models.serving_seam.serving_linear``'s adapter hook: add the per-lane
    low-rank update when a trace context is bound, identity otherwise.
    The gather (``A[ids]`` / ``B[ids]``) and both matmuls are all-array
    math over static shapes — the adapter mix is runtime data."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return y
    site = ctx.site_by_layer.get(id(layer))
    if site is None:
        return y
    import jax.numpy as jnp

    from ..core.tensor import Tensor

    a_pool, b_pool = ctx.pools[site]
    xa = x._data if isinstance(x, Tensor) else x
    ya = y._data if isinstance(y, Tensor) else y
    a = a_pool[ctx.ids]  # [S, in, r]
    b = b_pool[ctx.ids]  # [S, r, out]
    # f32 adapter math over (possibly bf16 / int8-dequant) base output:
    # the delta is computed in f32 and cast once at the add
    delta = jnp.einsum("sti,sir->str", xa.astype(jnp.float32), a)
    delta = jnp.einsum("str,sro->sto", delta, b)
    return Tensor(ya + delta.astype(ya.dtype))


class AdapterArena:
    """The paged LoRA store of one :class:`~.engine.ServingEngine`.

    ``rank`` and ``capacity`` are static (part of the engine's program
    key, like the quant/donation flags); which adapters are live and
    which slot wears which are runtime data. Host-side numpy pools with a
    memoized device copy — invalidated only on register/unregister, so
    steady-state steps re-use the same device arrays with zero transfer."""

    def __init__(self, model, rank: int, capacity: int):
        if rank < 1:
            raise ValueError("AdapterArena needs rank >= 1 "
                             "(FLAGS_serving_lora_rank)")
        if capacity < 1:
            raise ValueError("AdapterArena needs capacity >= 1 "
                             "(FLAGS_serving_lora_adapters)")
        self.rank = int(rank)
        self.capacity = int(capacity)
        self._a: List[np.ndarray] = []
        self._b: List[np.ndarray] = []
        self._site_names: List[str] = []
        self._site_by_layer: Dict[int, int] = {}
        for site, lin in model.serving_linears():
            fi, fo = (int(d) for d in lin.weight.shape)
            self._site_by_layer[id(lin)] = len(self._site_names)
            self._site_names.append(site)
            self._a.append(np.zeros((capacity + 1, fi, rank), np.float32))
            self._b.append(np.zeros((capacity + 1, rank, fo), np.float32))
        # LIFO free list over rows 1..capacity (row 0 = identity, never
        # allocatable — the kv_arena scratch-block discipline). Seeded
        # descending so pop() hands out 1, 2, ... in registration order:
        # replicas replaying the same registration sequence (gateway
        # respawn) assign identical ids.
        self._free: List[int] = list(range(capacity, 0, -1))
        self._live: Dict[int, str] = {}   # id -> name
        self._names: Dict[str, int] = {}  # name -> id
        self._dev = None  # memoized device pools
        self._engine = None  # bound by ServingEngine: the liveness guard
        # the hook is process-global and inert without a bound context
        from ..models.serving_seam import set_lora_hook

        set_lora_hook(_lora_hook)
        metrics.set_gauge("lora.slots", self.capacity)
        metrics.set_gauge("lora.live", 0)
        metrics.set_gauge("lora.arena_bytes", self.bytes_total())

    # ---------------------------------------------------------- lifecycle

    def register(self, adapter: LoraAdapter,
                 name: Optional[str] = None) -> int:
        """Install ``adapter`` into a free arena row; returns its id (the
        per-slot index requests decode with). Shape-preserving — zero
        recompiles. Raises :class:`AdapterExhaustedError` at capacity."""
        if not self._free:
            metrics.bump("lora.register_failed")
            raise AdapterExhaustedError(
                f"all {self.capacity} adapter rows are live; unregister "
                "one or raise FLAGS_serving_lora_adapters")
        name = name or adapter.name or f"adapter-{len(self._names)}"
        if name in self._names:
            raise ValueError(f"adapter name {name!r} already registered "
                             f"(id {self._names[name]})")
        idx = self._free.pop()
        scale = adapter.alpha / self.rank
        known = set(self._site_names)
        for key in adapter.weights:
            if key not in known:
                self._free.append(idx)
                raise ValueError(
                    f"adapter site {key!r} does not exist in this model "
                    f"(sites are '<layer>.<target>', such as "
                    f"{self._site_names[0]!r})")
        for si, site in enumerate(self._site_names):
            ab = adapter.weights.get(site)
            if ab is None:
                self._a[si][idx] = 0.0
                self._b[si][idx] = 0.0
                continue
            a, b = ab
            if a.shape != self._a[si].shape[1:] \
                    or b.shape != self._b[si].shape[1:]:
                self._free.append(idx)
                raise ValueError(
                    f"adapter site {site!r} shapes {a.shape}/{b.shape} do "
                    f"not match arena {self._a[si].shape[1:]}/"
                    f"{self._b[si].shape[1:]} (rank {self.rank})")
            self._a[si][idx] = a
            self._b[si][idx] = b * scale
        self._live[idx] = name
        self._names[name] = idx
        self._dev = None
        metrics.bump("lora.registered")
        metrics.set_gauge("lora.live", len(self._live))
        return idx

    def bind_engine(self, engine) -> None:
        """Adopt the owning engine as the unregister liveness authority
        (called by ``ServingEngine.__init__``)."""
        self._engine = engine

    def unregister(self, adapter) -> None:
        """Free one adapter row (by id or name): zero its matrices (a
        stale per-slot index must decode as the identity, not a ghost)
        and return the row to the free list. Refuses while any occupied
        slot decodes with the row — zeroing (or LIFO-recycling to the
        NEXT registrant) weights a live stream is wearing would silently
        corrupt its output, or worse bleed another tenant's fine-tune
        into it."""
        idx = self._names.get(adapter) if isinstance(adapter, str) \
            else int(adapter)
        if idx is None or idx not in self._live:
            raise KeyError(f"adapter {adapter!r} is not registered")
        eng = self._engine
        if eng is not None:
            wearing = np.flatnonzero(eng._occupied
                                     & (eng._adapter == idx))
            if wearing.size:
                raise RuntimeError(
                    f"adapter {self._live[idx]!r} (id {idx}) is in use by "
                    f"slot(s) {wearing.tolist()}; retire those requests "
                    "before unregistering")
        name = self._live.pop(idx)
        del self._names[name]
        for si in range(len(self._site_names)):
            self._a[si][idx] = 0.0
            self._b[si][idx] = 0.0
        self._free.append(idx)
        self._dev = None
        metrics.bump("lora.unregistered")
        metrics.set_gauge("lora.live", len(self._live))

    def check_live(self, adapter_id: int) -> None:
        """Admission-time validation: a request naming an unregistered
        adapter fails at submit, not with silent identity output."""
        if int(adapter_id) == 0:
            return
        if int(adapter_id) not in self._live:
            raise ValueError(
                f"adapter id {adapter_id} is not registered "
                f"(live: {sorted(self._live)})")

    def adapter_id(self, name: str) -> int:
        return self._names[name]

    def live(self) -> Dict[int, str]:
        return dict(self._live)

    # ------------------------------------------------------------ tracing

    def device_pools(self):
        """The stacked pools as device arrays (memoized; invalidated only
        by register/unregister — steady-state decode passes the SAME
        arrays every step, so there is no per-step transfer). On a device
        mesh the pools commit REPLICATED (sharding_util.replicate): the
        per-lane gather `A[ids]` reads a whole adapter row per slot, and
        at rank r the rows are noise next to the model-axis-sharded base
        weights — replication keeps the gather local on every shard, and
        an explicit committed placement means mesh installs never churn
        the program's input shardings between steps. The OWNING engine's
        captured mesh wins over the installed global (bind_engine), so an
        explicit ServingConfig.mesh keeps adapters coherent with the
        weights/arena."""
        if self._dev is None:
            import jax.numpy as jnp

            from ..distributed.sharding_util import replicate

            mesh = getattr(getattr(self, "_engine", None), "mesh", None)
            self._dev = [(replicate(jnp.asarray(a), mesh=mesh),
                          replicate(jnp.asarray(b), mesh=mesh))
                         for a, b in zip(self._a, self._b)]
        return self._dev

    @contextmanager
    def bind(self, pools, adapter_ids):
        """Enter the trace-time binding for one compiled body: ``pools``
        and ``adapter_ids`` are the program's traced arguments. Tracing
        is single-threaded per call, so a thread-local is sufficient."""
        prev = getattr(_tls, "ctx", None)
        _tls.ctx = _TraceCtx(pools, adapter_ids, self._site_by_layer)
        try:
            yield
        finally:
            _tls.ctx = prev

    # -------------------------------------------------------------- stats

    def bytes_total(self) -> int:
        return sum(a.nbytes + b.nbytes for a, b in zip(self._a, self._b))

    def stats(self) -> dict:
        return {"lora.rank": self.rank,
                "lora.slots": self.capacity,
                "lora.live": len(self._live),
                "lora.free": len(self._free),
                "lora.arena_bytes": self.bytes_total(),
                "lora.names": dict(self._names)}
