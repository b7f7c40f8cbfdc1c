"""Slot-based continuous-batching decode engine (Orca/vLLM-style, XLA-first).

``GPT.generate()`` compiles one decode loop per *batch*: every sequence in
the call starts together and the whole batch runs to the slowest member. A
serving endpoint sees the opposite workload — requests arrive and finish
continuously. The TPU-idiomatic answer is **iteration-level scheduling over
a fixed slot arena**:

* The engine owns ONE compiled decode step over ``[num_slots]`` lanes. Each
  slot holds (at most) one in-flight request: its last token, its write
  position, and a block table into the paged KV arena
  (:mod:`paddle_tpu.serving.kv_arena`).
* Admitting a request = prefill its prompt (compiled per
  ``compile_cache.prefill_bucket`` length bucket), scatter the prompt K/V
  into the slot's blocks, and flip the slot's lane in the ``active`` mask.
  Retiring = flip the mask back and return the blocks. **Neither touches
  the compiled step** — all per-request state is runtime *data* (masking,
  gather indices), never trace-time *structure*, so admit/retire causes
  zero recompiles after warmup. The trace counters
  (``serving.decode_compiles`` / ``serving.prefill_compiles`` in
  ``compile_cache.stats()``) make that invariant assertable.
* Inactive lanes still run the model (the step is shape-fixed) but their
  writes are routed to the arena's scratch block 0 and their outputs are
  discarded by the scheduler — the standard masked-lane trick that keeps
  one executable serving every occupancy pattern.

**The engine<->model seam** (``models/serving_seam.py``,
docs/serving_model_seam.md): the engine names no model and no kind of
layer state. A model declares its vocabulary, its longest context and, per
layer, the KIND of per-request state that layer keeps (``serving_spec()``).
What a kind needs of the server is :mod:`paddle_tpu.serving.cache_views`'s
to say, in one table by kind: where its state lies (rows in the paged
arena's block pools, a fixed size a lane in the arena's slot-indexed store,
nowhere), the cache view a program hands the layer, how a prefill commits
what the view left behind, the options it cannot be served with. Every
compiled program here runs the model one way,
:func:`~paddle_tpu.models.serving_seam.forward_cached`: embed -> layers,
each handed the view the table builds for ITS kind -> final norm; then
``serving_head``. A model that declares a ``prefill_tail`` has its prefill
run the layers from there on each request's last valid token alone. State
in the slot store is started anew by the prefill that admits a request,
advanced in place by the decode step, and untouched while its lane is
inactive. Blocks are counted for the layers that OWN a pool: a model with
one such layer and seven readers of it pages one layer.

Decode numerics deliberately share
``models.serving_seam.masked_attention`` and the model's ``serving_head``
with ``GPTForCausalLM.generate()``, so a greedy GPT request served through
the engine reproduces ``generate(stop_token_id=...)`` token-for-token.

Under ``FLAGS_decode_donate`` the KV pools are donated into every compiled
prefill/decode call: XLA updates the arena in place instead of
double-buffering what is by far the engine's largest allocation.

**Quantized serving** (``FLAGS_serving_quant_weights`` /
``FLAGS_serving_quant_kv`` / ``FLAGS_serving_quant_draft`` — see
docs/quantization.md) rides the same data path: weights stream int8 and
dequantize in-kernel (:func:`paddle_tpu.models.serving_seam.serving_linear`),
the KV arena stores int8 with per-block scale pools carried inside every
pool entry (quantize-on-scatter and dequant-on-attend in
:mod:`~paddle_tpu.serving.cache_views`), and
each mode is captured at construction as part of the engine's program key
exactly like the donation flag. All default off — the unquantized path is
bit-identical.

**Scenario diversity** (ISSUE 12) rides the same runtime-data contract:
per-slot sampling params + positional PRNG seeds
(:mod:`paddle_tpu.serving.sampling`), the per-slot constrained-decoding
vocab mask (:mod:`paddle_tpu.serving.constrain`), and the per-slot LoRA
adapter index into a paged adapter arena
(:mod:`paddle_tpu.serving.adapters`, gathered inside
``serving_seam.serving_linear``) all thread through the one compiled step like
``start_pos`` — a batch mixing greedy, sampled, constrained, and
N-adapter slots never recompiles, and the greedy/mask-off/adapter-0
paths are token-identical to the classic engine.

**Mesh-sharded execution** (ISSUE 14 — docs/distributed.md
"Tensor-parallel serving"): the engine captures the installed device
mesh at construction exactly like the quant/donation flags — the mesh's
``(axis, size)`` fingerprint (``sharding_util.mesh_axes_key``) is part
of its program key. On a ``("data", "model")`` mesh
(``distributed.mesh.serving_mesh``) the model's weights arrive with
committed model-axis shardings, the KV arena's pools (every namespace,
int8 scale 4-tuples included) shard their heads dim over the model axis
(``sharding_util.shard_kv_entry`` via ``KVArena``), and ALL slot/block
bookkeeping stays host-side numpy — so admit/retire churn on a live
mesh is still pure runtime data with zero recompiles, and supervisor
rebuilds re-commit identical placements through ``_arena_args``.
Greedy tokens are parity-asserted against the single-device engine;
a 1-device mesh is bit-identical to no mesh
(tests/test_mesh_serving.py).

**Tiered KV cache** (ISSUE 15 — ``FLAGS_serving_kv_tiering``,
:mod:`paddle_tpu.serving.tiered`): with the prefix cache on, an evicted
refcount-zero cached block spills its pool rows to a shared host-RAM tier
(overflowing to disk) keyed by the radix cache's content hashes instead
of discarding them; a later radix hit restores the rows into a fresh
block through ONE compiled scatter (:meth:`ServingEngine._get_restore` —
the ``_cow_copy`` template, dst block id as runtime data, zero new
compiles per restore). The tiers are off-device, so they survive
supervisor rebuilds (warm-cache replay) and are shared across gateway
replicas (a prefill on replica A is a host-tier hit on replica B).
Default off — eviction then discards exactly as before.

Two flag-gated multi-token extensions ride the same no-recompile
contract: **speculative decoding** (``FLAGS_serving_spec_k`` —
:mod:`paddle_tpu.serving.spec_decode`: a draft model proposes k tokens
into a second arena namespace, the target verifies all k in one fused
compiled call, bit-identical to plain greedy) and **chunked prefill**
(``FLAGS_serving_chunked_prefill`` — :meth:`ServingEngine.admit_begin` /
:meth:`ServingEngine.admit_chunk`: long prompts scatter one chunk per
scheduler iteration through the suffix-prefill programs, bounding the
decode stall of running streams to one chunk). Both default off,
reproducing the plain engine exactly.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from contextlib import contextmanager, nullcontext as _null_ctx
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from ..core import compile_cache, flags, resilience
from ..core.tensor import Tensor
from . import cache_views, metrics, telemetry
# benchmark/tests/test_hybrid_cell.py and test_flash_cell.py patch these two
# classes through this module, and this PR may not edit them; nothing here
# reads the names (ROADMAP.md C9: the line goes with the next `benchmark` PR)
from .cache_views import (  # noqa: F401
    SlotStatePrefillView as _SlotStatePrefillView,
    WindowDecodeView as _WindowDecodeView)
from .kv_arena import ArenaExhaustedError, KVArena, Reservation
from .prefix_cache import PrefixCache
from .spec_decode import SpecDecoder


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# rows of the decode step's packed ``[8, num_slots]`` int32 slot state (the
# two float rows travel bit-cast; the adapter row is read only when the
# LoRA arena is on)
_ST_ROWS = 8
(_ST_POS, _ST_TOK, _ST_ACT, _ST_TEMP, _ST_TOP_K, _ST_TOP_P, _ST_SEED,
 _ST_ADAPTER) = range(_ST_ROWS)


@dataclass
class ServingConfig:
    """Engine sizing. Zeros/None defer to flags / the model config:
    ``num_slots`` -> ``FLAGS_serving_slots``, ``kv_block_size`` ->
    ``FLAGS_kv_block_size``, ``max_model_len`` ->
    ``cfg.max_position_embeddings``, ``num_blocks`` -> one full-length
    context per slot (+ scratch), ``prefill_bucket_min`` ->
    ``FLAGS_serving_prefill_bucket_min``, ``donate`` ->
    ``FLAGS_decode_donate``."""

    num_slots: int = 0
    kv_block_size: int = 0
    max_model_len: int = 0
    num_blocks: int = 0
    prefill_bucket_min: int = 0
    donate: Optional[bool] = None
    # radix prefix cache (content-addressed KV block sharing); None defers
    # to FLAGS_serving_prefix_cache
    prefix_cache: Optional[bool] = None
    # tiered KV cache (ISSUE 15 — serving.tiered / docs/serving.md
    # "Tiered KV cache"): None defers to FLAGS_serving_kv_tiering
    # (default off = PR 14 eviction behavior bit-for-bit). Requires the
    # prefix cache; evicted refcount-zero cached blocks spill to a
    # host-RAM/disk tier keyed by content hash and restore via one
    # compiled scatter on the next radix hit.
    kv_tiering: Optional[bool] = None
    # the shared tiered.HostKVCache to attach to (gateway replicas pass
    # ONE store so a prefix prefilled on replica A is a host-tier hit on
    # replica B); None = the process-global store when tiering is on
    tier_store: Optional[object] = None
    # retry transient (OSError/timeout) step failures — only honored with
    # donation OFF: a donated call that died may have consumed its buffers,
    # so retrying it would replay invalidated state
    retry_policy: Optional[resilience.RetryPolicy] = None
    # speculative decoding: tokens proposed per iteration (None defers to
    # FLAGS_serving_spec_k; 0 = off). With a draft_model the draft
    # proposes into its own arena namespace and the target verifies k in
    # one batched call; without one the engine self-drafts (lockstep
    # fused multi-token decode). Captured at construction — like the
    # donation flag, it is part of the engine's program key: a different
    # k builds different executables, never reuses old ones.
    spec_k: Optional[int] = None
    draft_model: Optional[object] = None
    # chunked prefill: chunk size in tokens (None defers to
    # FLAGS_serving_chunked_prefill; 0 = off). Long prompts prefill one
    # chunk per scheduler iteration through the suffix-prefill programs,
    # bounding the decode stall of running streams to one chunk.
    chunked_prefill: Optional[int] = None
    # quantized serving (None defers to the FLAGS_serving_quant_* trio;
    # all default off = bit-identical to the unquantized engine).
    # Captured at construction like the donation flag — each mode is part
    # of the engine's program key: toggling builds fresh executables over
    # the new dtypes, never reuses old ones. quant_weights: int8
    # weight-only decode (per-channel, dequant-in-kernel); quant_kv: int8
    # KV arena with per-block scale pools; quant_draft: int8-quantize the
    # draft model's weights (speed/acceptance knob, never correctness).
    quant_weights: Optional[bool] = None
    quant_kv: Optional[bool] = None
    quant_draft: Optional[bool] = None
    # multi-LoRA adapter arena (None defers to FLAGS_serving_lora_rank /
    # FLAGS_serving_lora_adapters; rank 0 = off). Rank and capacity are
    # static (program key, like quant/donation); which adapters are live
    # and which slot wears which are runtime data — registration and
    # per-slot adapter churn never recompile. Adapter id 0 is the
    # identity (base weights, token-identical to an arena-less engine).
    lora_rank: Optional[int] = None
    lora_adapters: Optional[int] = None
    # Pallas paged-attention kernels. None (the default) is resolved at
    # construction from the device: the decode step's "kv" layers read
    # K/V through the block tables with the paged decode kernel where it
    # compiles natively (a TPU backend) and reads the pools where they
    # lie (head_dim a multiple of 128), and take the XLA gather where it
    # would run interpreted (CPU); prefill keeps the XLA path, whose scores
    # are materialised ([heads, s, s] for a "kv" layer, [heads, window,
    # 2 window] a chunk for a "window" layer: a model whose prompts or
    # window make that gigabytes asks for True). True: every kernel route
    # (decode; prefill: the flash kernel over whole tiles for "kv" and
    # "window" layers alike, banded for the latter; suffix/chunked
    # prefill through the block tables), raising when Pallas is missing.
    # False: XLA everywhere. Captured at construction
    # like the quant trio — part of the engine's program key; the route
    # the decode step was built with is `kernel.paged` / kernel_route().
    paged_kernel: Optional[bool] = None
    # device mesh (ISSUE 14): None defers to the globally installed mesh
    # (distributed.mesh.get_mesh() — e.g. serving_mesh(mp, dp)). Captured
    # at construction EXACTLY like quant/donation: the mesh's
    # (axis, size) fingerprint is part of the engine's program key — a
    # different mesh is a different set of executables. Everything the
    # ENGINE places follows this mesh (KV-arena pools via
    # sharding_util.shard_kv_entry, int8 weight re-placement, adapter
    # pools); the BASE float weights commit at model construction, so
    # an explicit mesh here must be the mesh the model was built under
    # (normally just the installed global — mixing device sets makes
    # jit reject the step). All block-table/refcount/COW bookkeeping
    # stays host-side. A 1-device mesh is bit-identical to no mesh.
    mesh: Optional[object] = None


class _StepInFlight(NamedTuple):
    """A dispatched decode step whose tokens the host has not read: the
    device array they will be in, the lanes the step ran, each slot's
    tenancy count when it was dispatched (a lane retired since, whoever
    holds the slot now, no longer owns what this step computed for it),
    and the engine's count of compiled calls once this one was made (the
    step is the newest program on the device while they are equal)."""

    tokens: jax.Array
    lanes: np.ndarray
    tenancy: np.ndarray
    seq: int


@dataclass
class _AdmitState:
    """Everything an in-flight admission carries between its setup
    (slot + blocks + shared refs claimed) and its finish (first token
    emitted, slot activated) — the unit of progress for chunked prefill."""

    slot: int
    prompt: np.ndarray
    ctx: np.ndarray
    plen: int
    clen: int
    max_new: int
    res: Reservation
    shared: List[int] = field(default_factory=list)
    n_attached: int = 0
    cow: bool = False
    prefix_len: int = 0
    done: int = 0  # context positions already scattered (chunk progress)
    sampling: Optional[object] = None  # SamplingParams (None = greedy)
    adapter: int = 0                   # LoRA arena row (0 = base)
    skip_draft: bool = False  # spec-ineligible: no draft prefill/blocks
    trace_id: str = ""  # the owning request's trace (RESTORED spans)


class ServingEngine:
    """The compiled slot runtime. Host-side responsibilities only: slot
    bookkeeping, block-table growth, and dispatching the two compiled
    programs (per-bucket prefill, the single decode step). Queueing and
    finish policy live in :class:`paddle_tpu.serving.scheduler.Scheduler`.
    """

    def __init__(self, model, config: Optional[ServingConfig] = None, **kw):
        cfg = config or ServingConfig(**kw)
        if config is not None and kw:
            raise TypeError("pass either a ServingConfig or kwargs, not both")
        self._model = model
        model.eval()

        # the mesh is captured FIRST: weight quantization re-places int8
        # payloads on it, the arena shards its pools over it, and its
        # fingerprint joins the program key like quant/donation below
        from ..distributed import mesh as mesh_mod
        from ..distributed.sharding_util import mesh_axes_key

        self.mesh = cfg.mesh if cfg.mesh is not None else mesh_mod.get_mesh()
        self.mesh_key = mesh_axes_key(self.mesh) if self.mesh is not None \
            else None
        self._mesh_model = (self.mesh.shape.get("model", 1)
                            if self.mesh is not None else 1)
        self._mesh_data = (self.mesh.shape.get("data", 1)
                           if self.mesh is not None else 1)
        self._mesh_devices = (int(self.mesh.devices.size)
                              if self.mesh is not None else 1)

        self.quant_weights = (bool(flags.flag("serving_quant_weights"))
                              if cfg.quant_weights is None
                              else bool(cfg.quant_weights))
        self.quant_kv = (bool(flags.flag("serving_quant_kv"))
                         if cfg.quant_kv is None else bool(cfg.quant_kv))
        self.quant_draft = (bool(flags.flag("serving_quant_draft"))
                            if cfg.quant_draft is None
                            else bool(cfg.quant_draft))
        if self.quant_weights:
            # in-place, idempotent (gateway replicas share one model):
            # must run BEFORE the functional_state snapshot below so the
            # compiled programs stream the int8 payload + scale buffers.
            # The captured mesh is threaded through so an explicit
            # ServingConfig.mesh re-places the int8 payloads on THIS
            # engine's mesh, not whatever global happens to be installed
            from ..models.serving_seam import quantize_serving_weights

            n = quantize_serving_weights(model, mesh=self.mesh)
            if n:
                metrics.bump("quant.weight_layers", n)
        # multi-LoRA adapter arena: rank/capacity are static (program key,
        # like the quant trio); registration and per-slot adapter ids are
        # runtime data. Built before the snapshot only for symmetry — the
        # adapter pools are program ARGUMENTS, not buffers.
        lora_rank = int(cfg.lora_rank if cfg.lora_rank is not None
                        else flags.flag("serving_lora_rank"))
        lora_cap = int(cfg.lora_adapters if cfg.lora_adapters is not None
                       else flags.flag("serving_lora_adapters"))
        if lora_rank > 0:
            from .adapters import AdapterArena

            self.lora = AdapterArena(model, lora_rank, lora_cap)
            self.lora.bind_engine(self)  # unregister liveness guard
        else:
            self.lora = None
        if hasattr(model, "serving_prepare"):
            model.serving_prepare()  # derived buffers: before the snapshot
        params, buffers = model.functional_state()
        self._objs = list(params.values()) + list(buffers.values())
        if self._mesh_devices > 1:
            # norms and position embeddings no layer placed: every compiled
            # call reads them in place on each chip, not from device 0
            from ..distributed.sharding_util import replicate_unplaced

            for p in self._objs:
                replicate_unplaced(p, self.mesh)
        self._arrays = [p._data for p in self._objs]

        # what the model declares (the seam): sizes, and per layer the kind
        # of state it keeps. What each kind needs of this engine is the
        # table's to say (cache_views.KINDS): the block pools hold ONE shape
        # of row for the layers that own one, the slot-indexed store the
        # state of those with a fixed size per lane
        spec = model.serving_spec()
        self._layer_states = tuple(spec.layers)
        self._layer_kinds = tuple(cache_views.KINDS[st.kind]
                                  for st in spec.layers)
        self._prefill_tail = spec.prefill_tail
        kv_heads, kv_dim, latent_width, index_width = cache_views.pool_row(
            spec.layers)
        paged_layers, self._slot_layers = cache_views.by_store(
            spec.layers, spec.layers)
        self.recurrent = bool(self._slot_layers)
        self.num_slots = int(cfg.num_slots or flags.flag("serving_slots"))
        self.block_size = int(cfg.kv_block_size or flags.flag("kv_block_size"))
        self.max_model_len = int(cfg.max_model_len or spec.max_positions)
        if self.max_model_len > spec.max_positions:
            raise ValueError("max_model_len exceeds the model's "
                             "max_position_embeddings")
        self.blocks_per_slot = _ceil_div(self.max_model_len, self.block_size)
        spec_k = int(cfg.spec_k if cfg.spec_k is not None
                     else flags.flag("serving_spec_k"))
        self.chunk_size = int(cfg.chunked_prefill
                              if cfg.chunked_prefill is not None
                              else flags.flag("serving_chunked_prefill"))
        # draft mode doubles the default arena: every slot carries a
        # second (draft-namespace) block table of the same worst case
        draft_on = spec_k > 0 and cfg.draft_model is not None
        num_blocks = int(cfg.num_blocks
                         or self.num_slots * self.blocks_per_slot
                         * (2 if draft_on else 1) + 1)
        self.prefill_bucket_min = int(cfg.prefill_bucket_min
                                      or flags.flag("serving_prefill_bucket_min"))
        self.donate = (bool(flags.flag("decode_donate"))
                       if cfg.donate is None else bool(cfg.donate))
        # `paged_kernel`: the prefill routes (asked for outright only);
        # `decode_kernel`: the decode step's route, which the default
        # takes from the device (see ServingConfig.paged_kernel)
        self.paged_kernel = bool(cfg.paged_kernel)
        if cfg.paged_kernel is None:
            from ..ops import paged_attention, pallas_ops

            # natively compiled, and reading the pools where they lie
            self.decode_kernel = not pallas_ops._use_interpret() and all(
                paged_attention.decode_in_place(
                    cache_views.KINDS[st.kind].minor(st))
                for st in paged_layers)
        else:
            self.decode_kernel = self.paged_kernel
        # the mesh the kernel calls route through (ISSUE 16): on a
        # multi-device mesh every kernel call runs per model-shard via
        # paged_attention's headwise_shard_map wrapper — the pools are
        # already heads-sharded by shard_kv_entry, the block tables ride
        # replicated. None on a 1-device mesh / no mesh: the direct
        # pallas path there is bit-identical to PR 13 by construction.
        # Trace-time STRUCTURE like `kernel` itself, never a traced branch.
        self._kernel_mesh = None
        if self.decode_kernel:
            from ..ops import paged_attention

            if not paged_attention.available():
                # the kernel is this engine's route (asked for, or the
                # default on this device): serving the gather path
                # instead would hide that from every counter and every
                # measurement made of this engine
                raise RuntimeError(
                    "the paged-attention kernel route (paged_kernel="
                    f"{cfg.paged_kernel!r} on this device) needs the "
                    "Pallas paged-attention kernels, which are "
                    "unavailable here (no scalar-prefetch support); the "
                    "engine does not fall back to the XLA gather path")
            if self._mesh_devices > 1:
                self._kernel_mesh = self.mesh
        self._retry = cfg.retry_policy
        if self._retry is None and not self.donate:
            self._retry = resilience.io_policy()

        from ..models.serving_seam import serving_compute_dtype

        kv_dtype = serving_compute_dtype(model)
        # kept so the supervisor can rebuild an identically-shaped arena
        # after a transient device failure (same shapes => zero recompiles);
        # the quant-kv mode rides along so the rebuilt arena keeps its
        # int8 pools + scale pools
        # the mesh rides along so the rebuilt arena re-commits the SAME
        # pool shardings (identical shapes AND placements => the
        # supervisor's rebuild/replay path stays zero-recompile on a mesh)
        self.use_prefix_cache = (bool(flags.flag("serving_prefix_cache"))
                                 if cfg.prefix_cache is None
                                 else bool(cfg.prefix_cache))
        self.kv_tiering = (bool(flags.flag("serving_kv_tiering"))
                           if cfg.kv_tiering is None
                           else bool(cfg.kv_tiering))
        # the options a kind of state among the model's cannot be served
        # with are refused by name, not served with a silently wrong answer
        cache_views.refuse_options(spec.layers, {
            "prefix_cache": self.use_prefix_cache,
            "kv_tiering": self.kv_tiering,
            "spec_k (speculative decoding)": spec_k > 0,
            "chunked_prefill": self.chunk_size > 0,
            "quant_kv": self.quant_kv,
            "mesh (more than one chip)": self._mesh_devices > 1})
        self._arena_args = (len(paged_layers), kv_heads, kv_dim, num_blocks,
                            self.block_size, kv_dtype, self.quant_kv,
                            # a latent row and an index row have no
                            # heads to shard
                            None if latent_width or index_width
                            else self.mesh,
                            self.num_slots,
                            tuple(cache_views.KINDS[st.kind].arrays(
                                st, kv_dtype) for st in self._slot_layers),
                            latent_width, index_width)
        self.arena = KVArena(*self._arena_args)
        # pool arrays a full prefill writes in whole blocks (an admission)
        self._block_writes = sum(
            len(entry) for st, entry in zip(paged_layers, self.arena.pools)
            if cache_views.KINDS[st.kind].block_writes)
        # tiered KV cache (ISSUE 15): the TierView survives rebuild()
        # untouched — host/disk tiers are off-device by construction, so
        # crash recovery replays against a warm cache. The view's arena
        # signature (shape facts + quant mode + mesh fingerprint) keeps
        # incompatible engines from ever exchanging entries through a
        # shared store.
        self.tier = None
        if self.kv_tiering and self.use_prefix_cache:
            from .tiered import TierView, get_tier_store

            store = (cfg.tier_store if cfg.tier_store is not None
                     else get_tier_store())
            self.tier = TierView(store, signature=(
                len(paged_layers), kv_heads, kv_dim, self.block_size,
                kv_dtype, self.quant_kv, self.mesh_key))
        self.prefix_cache = (PrefixCache(self.arena, self.block_size,
                                         tier=self.tier)
                             if self.use_prefix_cache else None)

        s = self.num_slots
        self._bt_host = np.zeros((s, self.blocks_per_slot), np.int32)
        self._bt_dev = None  # invalidated whenever _bt_host changes
        self._positions = np.zeros(s, np.int32)
        self._last_tok = np.zeros(s, np.int32)
        self._active = np.zeros(s, np.bool_)
        # the decode step's device copy of the per-slot vectors (positions,
        # last token, active mask, sampling params, adapter ids: one packed
        # array, see _pack_slot_state). The step hands the next step's
        # state back, so the host mirrors above and below are uploaded only
        # after a host write: None = stale, set through _touch_slot_state,
        # which also keeps who wrote last (engine.restarts.<why>)
        self._state_dev = None
        self._stale_why = "admit"
        # compiled calls made (_call). When a blocking read of the newest
        # one has returned, nothing this engine dispatched is unfinished:
        # _empty is the open `device.empty` phase from then to the next
        # _call; _idle says the scheduler ran out of work meanwhile
        self._dispatched = 0
        self._empty: Optional[telemetry.phase] = None
        self._idle = False
        # decode steps dispatched and not yet read, oldest first (see
        # decode_dispatch / decode_collect); _tenancy counts each slot's
        # retirements, so a step read after a lane changed hands neither
        # mirrors nor reports that lane; _ahead is set for the length of
        # one decode_turn call; lanes_read: the lanes of the step read last
        # whose token belongs to the request that holds the slot now
        self._flight: deque = deque()
        self._tenancy = np.zeros(s, np.int64)
        self._ahead = False
        self.lanes_read = np.zeros(s, np.bool_)
        # occupied ⊇ active: a slot mid-chunked-prefill holds blocks and
        # must not be re-picked, but its lane stays masked out of the
        # decode step until its first token exists
        self._occupied = np.zeros(s, np.bool_)
        # per-slot context-length cap (prompt + max_new): the runtime clamp
        # speculation depth respects so block reservations and the model's
        # position budget are never overrun
        self._slot_limit = np.zeros(s, np.int32)
        # per-slot sampling / constraint / adapter state — ALL runtime
        # data threaded into the one compiled step exactly like start_pos
        # (see serving.sampling): temperature 0 = greedy (bit-identical
        # to the classic path), the [S, vocab] mask defaults all-True
        # (mask-off identity), adapter 0 = base weights. The mask's
        # device copy is memoized and invalidated only on change, so
        # unconstrained workloads re-pass one cached array per step.
        self.vocab = int(spec.vocab_size)
        self._temp = np.zeros(s, np.float32)
        self._top_k = np.zeros(s, np.int32)
        self._top_p = np.ones(s, np.float32)
        self._seed = np.zeros(s, np.int32)
        self._adapter = np.zeros(s, np.int32)
        self._sampled = np.zeros(s, np.bool_)      # temp > 0
        self._constrained = np.zeros(s, np.bool_)  # mask row not all-True
        # STICKY spec-ineligibility: once a slot has sampled, worn a
        # mask, or carried an adapter this request, it stays on the
        # plain-decode path even if the constraint later lifts — during
        # the fallback iterations the draft namespace saw none of the
        # slot's tokens, so handing the lane back to speculation would
        # propose from a holed draft cache (silent acceptance collapse)
        self._scenario_once = np.zeros(s, np.bool_)
        self._mask_host = np.ones((s, self.vocab), np.bool_)
        self._mask_dev = None
        self._mask_dirty: set = set()  # rows stale on device (see
        #                                _mask_arg: one batched row
        #                                scatter per step, not per update)
        # lifetime per-engine admission counters (EnginePredictor.close()
        # summaries must not read the process-global metrics)
        self.sampled_admits = 0
        self.constrained_admits = 0
        self.adapter_admits = 0
        self._chunk: Dict[int, _AdmitState] = {}
        self._slot_res: List[Optional[Reservation]] = [None] * s
        # per-slot sharing state: block ids attached by reference from the
        # radix cache (deref'd at retire, NOT owned by the reservation) and
        # the count of filled block-table entries (shared + private) that
        # decode growth compares against
        self._slot_shared: List[List[int]] = [[] for _ in range(s)]
        self._slot_filled = np.zeros(s, np.int32)
        # trace counters: incremented at TRACE time inside the compiled
        # functions — the assertable "admit/retire never recompiles" number
        self.decode_traces = 0
        self.prefill_traces: Dict[int, int] = {}
        self.prefix_prefill_traces: Dict[int, int] = {}
        self.cow_traces = 0
        self.restore_traces = 0  # tier restore: one trace per arena shape
        self._step_jit = None
        #: names of the counters the step returns behind its tokens
        self._step_counters: Tuple[str, ...] = ()
        self._prefill_jits: Dict[int, object] = {}
        self._prefix_jits: Dict[int, object] = {}
        self._cow_jit = None
        self._restore_jit = None
        # speculative decoding sidecar (draft or lockstep self-draft);
        # built after the arena so the draft namespace can bind to it
        self.spec = (SpecDecoder(self, cfg.draft_model, spec_k)
                     if spec_k > 0 else None)
        self._meter = metrics.Meter()  # sliding-window tokens/s gauge
        # per-replica latency histograms (ISSUE 17): every observe() below
        # records into BOTH the process-global set (pool-merged view,
        # survives replica ejection) and this one (`/v1/metrics` labels it
        # by replica index); timestamps are taken AROUND compiled calls,
        # never inside them — see docs/observability.md "Overhead policy"
        self.hists = telemetry.HistogramSet()
        self._trace_ctx = ""  # the in-flight admission's trace id
        self._prefill_ph: Optional[telemetry.phase] = None  # and its phase
        metrics.set_gauge("slots.total", s)
        # mesh/axis gauges (ISSUE 14): the live topology next to the mode
        # gauges — tools/serving_stats.py --run reports them per run
        metrics.set_gauge("mesh.devices", self._mesh_devices)
        metrics.set_gauge("mesh.model_axis", self._mesh_model)
        metrics.set_gauge("mesh.data_axis", self._mesh_data)
        metrics.set_gauge("kernel.paged", int(self.decode_kernel))
        for row in cache_views.KINDS.values():
            if row.kernel_gauge:  # a kind with a decode kernel of its own
                metrics.set_gauge(row.kernel_gauge, int(
                    self.decode_kernel and row in self._layer_kinds))
        if spec.kernels:  # the model's own (serving_seam.ServingSpec)
            from ..ops.pallas_ops import _use_interpret

            for name in spec.kernels:
                metrics.set_gauge(f"kernel.{name}",
                                  int(not _use_interpret()))
        # the EFFECTIVE attention route x mesh topology (ISSUE 16), per
        # arena namespace: "kernel@data1.model4", "gather@single", ... A
        # fallback (Pallas unavailable, flag off) is observable here
        # instead of inferred from step times — every namespace (primary
        # + the spec-decode draft) rides the same engine-level route.
        metrics.set_gauge("kernel.mesh", self.kernel_route())
        for ns in ["primary"] + self.arena.namespaces():
            metrics.set_gauge(f"kernel.mesh.{ns}", self.kernel_route())
        metrics.set_gauge("tier.enabled", int(self.tier is not None))
        metrics.set_gauge("quant.weights", int(self.quant_weights))
        metrics.set_gauge("quant.kv", int(self.quant_kv))
        metrics.set_gauge("quant.draft", int(self.quant_draft
                                             and self.spec is not None
                                             and self.spec.draft_mode))
        self._publish_arena_bytes()
        self._refresh_gauges()

    # ----------------------------------------------------------- capacity

    def free_slots(self) -> int:
        # occupied, not active: a slot mid-chunked-prefill is taken
        return int((~self._occupied).sum())

    def active_slots(self) -> int:
        return int(self._active.sum())

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        need = _ceil_div(prompt_len + max_new_tokens, self.block_size)
        if self.spec is not None:
            # draft mode reserves a second (draft-namespace) table's worst
            # case per slot; lockstep adds nothing
            need += self.spec.blocks_needed(prompt_len, max_new_tokens)
        return need

    def _target_blocks_needed(self, prompt_len: int,
                              max_new_tokens: int) -> int:
        """The primary (target-cache) table's worst case alone — what the
        prefix cache's matched blocks subtract from."""
        return _ceil_div(prompt_len + max_new_tokens, self.block_size)

    def reserved_blocks(self, slot: int) -> int:
        """Admission-time block budget held by ``slot`` (0 if empty),
        draft-namespace reservation included. Retiring the slot returns
        this whole budget to the arena's grantable pool — the quantity
        preemption feasibility sums."""
        res = self._slot_res[slot]
        n = res.total if res is not None else 0
        if self.spec is not None:
            n += self.spec.reserved_blocks(slot)
        return n

    def validate(self, prompt_len: int, max_new_tokens: int,
                 adapter: int = 0) -> None:
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if int(adapter) != 0:
            # fail at submit, not with silent base-weight output mid-decode
            if self.lora is None:
                raise ValueError(
                    f"request names adapter {adapter} but the engine has "
                    "no adapter arena (FLAGS_serving_lora_rank is 0)")
            self.lora.check_live(adapter)
        total = prompt_len + max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"prompt+new tokens {total} exceeds engine max_model_len "
                f"{self.max_model_len}")
        # a request whose worst case exceeds the WHOLE arena could never be
        # admitted — reject at submit instead of parking it at the FCFS head
        # forever (it would starve everything queued behind it)
        need = self.blocks_needed(prompt_len, max_new_tokens)
        cap = self.arena.num_blocks - 1
        if need > cap:
            raise ValueError(
                f"request needs {need} KV blocks but the arena has only "
                f"{cap} allocatable; it could never be admitted")

    def admit_blocks_needed(self, prompt_len: int, max_new_tokens: int,
                            prompt=None, journal_len: int = 0) -> int:
        """Blocks an admission would actually RESERVE: the worst-case
        budget minus full prompt blocks resident in the radix cache (those
        attach by reference). A fully-cached block-aligned prompt still
        reserves one private block — the copy-on-write target its last
        block is recomputed into. Conservative when ``prompt`` is None or
        the cache is off (plain worst case)."""
        return self.admit_sizing(prompt_len, max_new_tokens, prompt,
                                 journal_len=journal_len)[0]

    def admit_sizing(self, prompt_len: int, max_new_tokens: int,
                     prompt=None, keys=None, journal_len: int = 0):
        """Both admission-feasibility numbers from ONE radix walk:
        (blocks this admission would reserve, matched-but-unpinned blocks
        that ``grantable()`` counts evictable but admit() will pin).
        ``keys`` — a precomputed ``PrefixCache.chunk_keys`` chain — makes
        the walk hash-free for per-step scheduler probes.

        ``journal_len`` is the request's replay-journal length (re-route /
        replay / disagg-handoff admissions): admit prefills
        ``prompt + journal``, so the copy-on-write trigger — "the whole
        PREFILLED context is cache-matched" — compares against
        ``prompt_len + journal_len``, not the bare prompt. Without it a
        handed-off request whose published chain exactly covers its
        block-aligned prompt would be billed a phantom COW block: the
        published chain is restore cost (one fresh block each, already in
        the worst-case budget), never a COW."""
        need = self.blocks_needed(prompt_len, max_new_tokens)
        if self.prefix_cache is None or (prompt is None and keys is None):
            return need, 0
        # matched prefix blocks attach by reference to the TARGET table
        # only (the draft namespace, when present, always prefills its own
        # private blocks — its budget in `need` is untouched)
        resident, spilled, unpinned = self.prefix_cache.match_stats(
            prompt, keys=keys)
        matched = resident + spilled
        if matched:
            # only DEVICE-resident blocks are free (attach by reference);
            # a matched-but-SPILLED block avoids the prefill compute but
            # still consumes one fresh block as its restore target —
            # restore cost, not prefill cost — so it stays in the budget
            need -= resident
            if matched * self.block_size >= prompt_len + int(journal_len):
                need += 1  # COW copy of the last fully-matched block
        return need, unpinned

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  prompt=None, keys=None, journal_len: int = 0) -> bool:
        if self.free_slots() <= 0:
            return False
        need, pinned = self.admit_sizing(prompt_len, max_new_tokens,
                                         prompt, keys=keys,
                                         journal_len=journal_len)
        return self.arena.grantable() - pinned >= need

    def prefetch(self, prompt, trace_id: str = "") -> int:
        """Restore-ahead (disagg, ISSUE 19): pull the spilled/published
        tail of ``prompt``'s radix chain into fresh arena blocks NOW —
        the same one-scatter ``_restore_nodes`` path admission uses, with
        no slot claimed and no references taken — so a QUEUED request's
        later admission finds the whole chain device-resident and skips
        the restore wait. Bounded by the arena's free refcount-zero
        headroom ABOVE what eviction could already reclaim
        (``grantable() - evictable``): a prefetch converts free blocks
        into evictable cached blocks, which leaves ``grantable()``
        unchanged — prefetch can never starve admission — and the bound
        additionally keeps it from evicting warmer prefixes to make room
        for colder ones. Returns how many blocks were restored."""
        if self.prefix_cache is None or self.tier is None:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        walked = self.prefix_cache.match(prompt)
        split = next((i for i, n in enumerate(walked) if n.spilled),
                     len(walked))
        tail = walked[split:]
        if not tail:
            return 0
        # free - reserved headroom (grantable counts evictable on top)
        headroom = self.arena.grantable() \
            - self.prefix_cache.evictable_blocks()
        if headroom <= 0:
            return 0
        self._trace_ctx = trace_id
        restored = self._restore_nodes(tail[:headroom])
        if restored:
            metrics.bump("disagg.prefetched_blocks", restored)
            telemetry.span(trace_id, telemetry.PREFETCHED,
                           blocks=restored)
        return restored

    # ------------------------------------------------------------ compile

    def _get_prefill(self, p_bucket: int):
        fn = self._prefill_jits.get(p_bucket)
        if fn is not None:
            return fn
        from ..core import rng as prng
        from ..jit import _swap_data
        from ..models.serving_seam import forward_cached
        from .sampling import sample_tokens

        model = self._model
        lora = self.lora
        states, kinds = self._layer_states, self._layer_kinds
        tail = self._prefill_tail
        bs = self.block_size
        use_kernel = self.paged_kernel
        # a kind whose prompt attention has no XLA form that fits a long
        # prompt follows the decode step's route
        any_kernel = self.paged_kernel or self.decode_kernel
        kmesh = self._kernel_mesh

        def prefill(arrays, ids, true_len, pools, rows, samp, rec, slot,
                    *lora_args):
            # trace-time bookkeeping (runs once per bucket, not per call)
            self.prefill_traces[p_bucket] = \
                self.prefill_traces.get(p_bucket, 0) + 1
            compile_cache.bump("serving.prefill_compiles")
            if use_kernel:
                # trace-time: the full-prefill (pseudo-table) kernel twin
                # of prefill_traces — admission churn never re-lowers it
                metrics.bump("kernel.prefill_traces")
            # a "kv" layer hands back its chunk's k/v to scatter below; a
            # "recurrent" layer starts lane `slot` from zeros and writes
            # its final state there itself (through its view), a "window"
            # layer the last rows of the prompt; a "shared" layer reads
            # what the layer it names captured; a model with a prefill
            # tail runs its last layers on row `last` alone
            last = None if tail is None else true_len - 1
            ctx = cache_views.PrefillContext(slot, true_len, bs, use_kernel,
                                             any_kernel, kmesh)
            entries = cache_views.layer_entries(states, pools, rec)
            views = [kind.prefill_view(st, entry, ctx._replace(last=last)
                                       if i + 1 == tail else ctx)
                     for i, (kind, st, entry)
                     in enumerate(zip(kinds, states, entries))]
            with _swap_data(self._objs, list(arrays)):
                with prng.key_guard(jax.random.key(0)):
                    with (lora.bind(*lora_args) if lora is not None
                          else _null_ctx()):
                        h, new_views = forward_cached(
                            model, Tensor(ids), views, 0, last=last,
                            prefill_tail=tail)
                with jax.named_scope("head_sample"):
                    if tail is None:
                        h_last = jax.lax.dynamic_index_in_dim(
                            h._data, true_len - 1, axis=1, keepdims=False)
                    else:
                        h_last = h._data[:, 0]
                    logits = model.serving_head(h_last)
            # each kind commits what its view left behind: blocks (pool
            # rows) wholly past the true prompt length land in the scratch
            # block, so bucketing never pollutes another lane's cache state
            new_pools, new_rec = cache_views.by_store(states, [
                kind.commit(view, entry, rows, ctx) for kind, view, entry
                in zip(kinds, new_views, entries)])
            # the first generated token goes through the SAME sampling
            # core as the decode step ([1, V] and [S, V] rows are
            # bit-identical per row); greedy/unmasked slots reproduce
            # the classic argmax exactly
            temp, k, p, seed, spos, vmask = samp
            with jax.named_scope("head_sample"):
                nxt = sample_tokens(logits, temp, k, p, seed, spos,
                                    allowed=vmask)
            return nxt[0], new_pools, new_rec

        fn = (jax.jit(prefill, donate_argnums=(3, 6)) if self.donate
              else jax.jit(prefill))
        self._prefill_jits[p_bucket] = fn
        return fn

    def _get_prefix_prefill(self, p_bucket: int):
        """Compiled suffix-only prefill for a cache-hit admission: run the
        model over the unmatched suffix (padded to ``p_bucket``) while
        attending to — not recomputing — the resident prefix blocks.
        One program per suffix-length bucket; prefix length and the block
        table are runtime data, so hits of any depth share it. Every
        layer is a ``"kv"`` layer here: the prefix cache and chunked
        prefill, which alone reach this program, refuse any other kind."""
        fn = self._prefix_jits.get(p_bucket)
        if fn is not None:
            return fn
        import jax.numpy as jnp

        from ..core import rng as prng
        from ..jit import _swap_data
        from ..models.serving_seam import forward_cached
        from .sampling import sample_tokens

        model = self._model
        lora = self.lora
        bs = self.block_size
        use_kernel = self.paged_kernel
        kmesh = self._kernel_mesh

        def prefix_prefill(arrays, ids, true_len, prefix_len, pools,
                           bt_row, samp, *lora_args):
            self.prefix_prefill_traces[p_bucket] = \
                self.prefix_prefill_traces.get(p_bucket, 0) + 1
            compile_cache.bump("serving.prefill_compiles")
            if use_kernel:
                # trace-time: the paged-kernel twin of prefill_traces —
                # asserts chunk/hit churn never re-lowers the kernel
                metrics.bump("kernel.prefill_traces")
            views = [cache_views.PrefixPrefillView(
                entry, bt_row, prefix_len, true_len, bs, kernel=use_kernel,
                mesh=kmesh) for entry in pools]
            with _swap_data(self._objs, list(arrays)):
                with prng.key_guard(jax.random.key(0)):
                    with (lora.bind(*lora_args) if lora is not None
                          else _null_ctx()):
                        h, new_views = forward_cached(
                            model, Tensor(ids), views, prefix_len)
                with jax.named_scope("head_sample"):
                    h_last = jax.lax.dynamic_index_in_dim(
                        h._data, true_len - 1, axis=1, keepdims=False)
                    logits = model.serving_head(h_last)
            temp, k, p, seed, spos, vmask = samp
            with jax.named_scope("head_sample"):
                nxt = sample_tokens(logits, temp, k, p, seed, spos,
                                    allowed=vmask)
            new_pools = [v.entry for v in new_views]
            return nxt[0], new_pools

        fn = (jax.jit(prefix_prefill, donate_argnums=(4,)) if self.donate
              else jax.jit(prefix_prefill))
        self._prefix_jits[p_bucket] = fn
        return fn

    def _cow_copy(self, src: int, dst: int) -> None:
        """Copy one physical block's K/V rows (every layer, both pools)
        into a privately taken block — the copy-on-write that keeps shared
        blocks read-only when a slot must write inside its matched prefix
        (a fully-cached block-aligned prompt recomputing its last token).
        One compiled gather/scatter per arena shape; src/dst are runtime
        scalars, so COW never recompiles either. Copies EVERY array of
        each pool entry — with the int8 arena that includes the per-block
        scale pools (a COW that copied KV but not scales would silently
        dequantize the copy with the victim block's scales; the arena's
        ``check_invariants`` audits the entry structure)."""
        if self._cow_jit is None:
            import jax

            def cow(pools, src, dst):
                self.cow_traces += 1
                compile_cache.bump("serving.cow_compiles")
                return [tuple(p.at[dst].set(p[src]) for p in entry)
                        for entry in pools]

            self._cow_jit = (jax.jit(cow, donate_argnums=(0,))
                             if self.donate else jax.jit(cow))
        import jax.numpy as jnp

        new_pools = self._call(self._cow_jit, self.arena.pools,
                               jnp.int32(src), jnp.int32(dst),
                               name="serving.cow_copy")
        self.arena.set_pools(new_pools)
        metrics.bump("prefix.cow_copies")

    def _get_restore(self):
        """Compiled tier-restore scatter (ISSUE 15): write a whole
        spilled CHAIN's host rows — every layer, EVERY array of the pool
        entry, so an int8 arena's payload and its per-row scales land
        together — into their destination blocks in one call. The
        :meth:`_cow_copy` gather/scatter is the template scaled to a
        fixed batch: ``dsts`` is a runtime ``[blocks_per_slot]`` id
        vector and the stacked payload rows are runtime data of fixed
        per-arena shapes (shorter chains pad with zero rows scattered
        into scratch block 0, exactly like padded prefill positions), so
        every restore of every admission reuses ONE program — zero new
        compiles per restore, trace-asserted via ``restore_traces``."""
        if self._restore_jit is None:
            import jax

            def restore(pools, rows, dsts):
                self.restore_traces += 1
                compile_cache.bump("serving.restore_compiles")
                return [tuple(p.at[dsts].set(r) for p, r in zip(entry, row))
                        for entry, row in zip(pools, rows)]

            self._restore_jit = (jax.jit(restore, donate_argnums=(0,))
                                 if self.donate else jax.jit(restore))
        return self._restore_jit

    def _restore_nodes(self, nodes) -> int:
        """Restore a spilled radix chain's KV into fresh arena blocks:
        load the host rows from the tier, take cached refcount-zero
        blocks (evicting colder prefixes under pressure), scatter ALL of
        them through the one compiled restore program, and re-point each
        node at its block — from there they are indistinguishable from
        prefix blocks that never left the device. Stops at the first
        node whose tier entry was lost (pruned — the caller's match
        truncates there and the remainder prefills: recompute, never
        garbage) or when the arena has no headroom for another restore
        target. Returns how many leading nodes of ``nodes`` were
        restored."""
        with telemetry.phase("restore", self.hists) as ph:
            restored = self._restore_chain(nodes)
            if not restored:
                ph.discard()  # nothing restored is no restore sample
        return restored

    def _restore_chain(self, nodes) -> int:
        cache = self.prefix_cache
        payloads, live = [], []
        for node in nodes:
            if len(live) >= self.blocks_per_slot:
                break  # a chain can never exceed one slot's table anyway
            payload = self.tier.lookup(node.key)
            if payload is None:
                cache.prune_lost(node)
                break
            payloads.append(payload)
            live.append(node)
        if not live:
            return 0
        blks: List[int] = []
        for _ in live:
            try:
                blks.append(self.arena.take_cached_block())
            except ArenaExhaustedError:
                break  # restore what fits; the tail prefills normally
        if not blks:
            return 0
        live, payloads = live[:len(blks)], payloads[:len(blks)]
        batch = self.blocks_per_slot
        dsts = np.zeros(batch, np.int32)
        dsts[:len(blks)] = blks
        rows = []
        for li in range(len(payloads[0])):
            entry_rows = []
            for ai in range(len(payloads[0][li])):
                base = [pl[li][ai] for pl in payloads]
                pad = np.zeros_like(base[0])
                entry_rows.append(
                    np.stack(base + [pad] * (batch - len(base))))
            rows.append(tuple(entry_rows))
        import jax.numpy as jnp

        try:
            new_pools = self._call(self._get_restore(), self.arena.pools,
                                   rows, jnp.asarray(dsts),
                                   name="serving.tier_restore")
        # analysis: allow(broad-except) — cleanup-and-reraise: a failed
        # restore scatter must return the taken blocks before the error
        # reaches the admission unwind / supervisor
        except Exception:
            for blk in blks:
                self.arena.uncache(blk)
            raise
        self.arena.set_pools(new_pools)
        for node, blk in zip(live, blks):
            cache.mark_restored(node, blk)
        self.tier.note_restored(payloads)
        # the restore ran inside an admission's radix walk: its span lands
        # on the admitting request's timeline (the engine is serialized
        # under the api lock, so _trace_ctx is exactly that admission's)
        telemetry.span(self._trace_ctx, telemetry.RESTORED,
                       blocks=len(live))
        return len(live)

    def _get_step(self):
        if self._step_jit is not None:
            return self._step_jit
        import jax.numpy as jnp

        from ..core import rng as prng
        from ..distributed.sharding_util import replicate
        from ..jit import _swap_data
        from ..models.serving_seam import forward_cached
        from .sampling import sample_tokens

        model = self._model
        lora = self.lora
        states, kinds = self._layer_states, self._layer_kinds
        bs = self.block_size
        use_kernel = self.decode_kernel
        kmesh = self._kernel_mesh
        mesh = self.mesh

        def step(arrays, pools, block_tables, state, vmask, rec,
                 *lora_pools):
            self.decode_traces += 1  # trace-time: the no-recompile counter
            compile_cache.bump("serving.decode_compiles")
            if use_kernel:
                # trace-time: the paged-kernel twin of decode_traces —
                # asserts admit/retire churn never re-lowers the kernel
                metrics.bump("kernel.decode_traces")
            # the packed slot state (see _pack_slot_state): whether it was
            # uploaded this step or is the last step's output is invisible
            # here — one program either way
            positions, last_tok = state[_ST_POS], state[_ST_TOK]
            active = state[_ST_ACT] != 0
            temp = jax.lax.bitcast_convert_type(state[_ST_TEMP], jnp.float32)
            top_p = jax.lax.bitcast_convert_type(state[_ST_TOP_P],
                                                 jnp.float32)
            ctx = cache_views.DecodeContext(block_tables, positions, active,
                                            bs, use_kernel, kmesh)
            views = [kind.decode_view(st, entry, ctx)
                     for kind, st, entry in zip(
                         kinds, states,
                         cache_views.layer_entries(states, pools, rec))]
            # the step carry's seed: the lanes that hold a request
            carry = {"lanes": active[:, None]}
            with _swap_data(self._objs, list(arrays)):
                with prng.key_guard(jax.random.key(0)):
                    with (lora.bind(*lora_pools, state[_ST_ADAPTER])
                          if lora is not None else _null_ctx()):
                        h, new_views = forward_cached(
                            model, Tensor(last_tok[:, None]), views,
                            positions, carry=carry)
                with jax.named_scope("head_sample"):
                    logits = model.serving_head(h._data[:, 0])
            # per-slot sampling over the constrained logits: temperature /
            # top-k / top-p / seed / mask are all runtime data (greedy
            # lanes reproduce the classic argmax bit-for-bit); the
            # emitted token sits at context index positions+1 — its
            # positional PRNG key (see serving.sampling)
            with jax.named_scope("head_sample"):
                nxt = sample_tokens(logits, temp, state[_ST_TOP_K], top_p,
                                    state[_ST_SEED], positions + 1,
                                    allowed=vmask)
            new_pools, new_rec = (
                [v.entry for v in owned]
                for owned in cache_views.by_store(states, new_views))
            # the next step's state, as the host's mirrors will read after
            # this one: active lanes advance a position and hold `nxt`
            new_state = state.at[_ST_POS].set(
                jnp.where(active, positions + 1, positions)
            ).at[_ST_TOK].set(jnp.where(active, nxt, last_tok))
            # on a mesh the state goes round replicated, the placement
            # _step_args uploads it in: one signature, one executable
            if mesh is not None:
                new_state = replicate(new_state, mesh=mesh)
            # what the layers counted this step rides behind the tokens
            # (trace time: the names; a model that counts nothing leaves
            # the token vector as it was)
            counted = carry.get("counters", {})
            self._step_counters = tuple(counted)
            # analysis: allow(traced-branch) — `counted` is a dict by
            # counter name: trace-time structure, whatever its values are
            if counted:
                nxt = jnp.concatenate([nxt] + [
                    v.astype(nxt.dtype)[None] for v in counted.values()])
            return nxt, new_pools, new_rec, new_state

        self._step_jit = (jax.jit(step, donate_argnums=(1, 5))
                          if self.donate else jax.jit(step))
        return self._step_jit

    def _call(self, fn, *args, name: str, cause: str = "admit"):
        """Dispatch one compiled call. Donation makes a failed call
        non-retryable (its buffers may already be consumed), so the retry
        policy only wraps the copying build. ``cause`` is what this call
        is, for the account of the empty device it may end
        (``device.empty``): ``admit`` (a prefill and what it needs: the
        copy of a shared block, a tier restore), ``restart`` or ``sync``
        (a decode step, :meth:`decode_dispatch`)."""
        self._dispatched += 1
        empty, self._empty = self._empty, None
        if empty is not None:
            empty.stop("idle" if self._idle else cause)
        self._idle = False

        def attempt(*a):
            # the fault probes sit inside the retried callable so injected
            # transient failures exercise the same recovery path real ones
            # would. serving_step raises caller-chosen (typically IO-class,
            # retried) errors; serving_device/arena_corrupt raise the
            # supervisor-recoverable classes (rebuild + replay).
            resilience.maybe_fault("serving_step")
            resilience.maybe_fault("serving_device")
            resilience.maybe_fault("arena_corrupt")
            return fn(*a)

        with warnings.catch_warnings():
            # donation is best-effort: XLA warns about lanes it could not
            # alias (expected on CPU) — not actionable here
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            if self._retry is not None and not self.donate:
                return resilience.call_with_retry(attempt, *args, name=name,
                                                  policy=self._retry)
            return attempt(*args)

    def _device_drained(self, seq: int) -> None:
        """A blocking read of compiled call number ``seq`` has returned.
        If that call is the newest, nothing this engine dispatched is
        unfinished: ``device.empty`` begins, and the next :meth:`_call`
        ends it with its cause. The host learns of a program's end a
        millisecond or two after the device (docs/observability.md), so
        the account is a lower bound."""
        if seq == self._dispatched and self._empty is None:
            self._empty = telemetry.phase("device.empty", self.hists).begin()

    def note_idle(self) -> None:
        """The scheduler has neither running nor waiting work: whatever
        the device stands empty for until the next compiled call is
        nobody's delay (``time_us.device.empty.idle``, kept out of every
        share). A step dropped unread may still be running, so here the
        phase may begin up to a step early."""
        self._idle = True
        self._device_drained(self._dispatched)

    # ----------------------------------------------------- slot lifecycle

    @contextmanager
    def _admission(self, trace_id: str):
        """The ``prefill`` phase around one admission or one chunk, and
        the lane-time it takes from the requests that could have decoded
        meanwhile: its elapsed microseconds times the lanes active when it
        began (``prefill.lane_us_blocked``)."""
        self._trace_ctx = trace_id
        lanes = int(self._active.sum())
        t0 = time.perf_counter()
        with telemetry.phase("prefill", self.hists,
                             trace_id=trace_id) as ph:
            self._prefill_ph = ph
            try:
                yield
            finally:
                self._prefill_ph = None
                metrics.bump("prefill.lane_us_blocked",
                             round((time.perf_counter() - t0) * 1e6) * lanes)

    def admit(self, prompt: np.ndarray, max_new_tokens: int,
              tokens=None, sampling=None, adapter: int = 0,
              mask=None, spec_exclude: bool = False,
              trace_id: str = "") -> Tuple[int, int]:
        """Prefill ``prompt`` (plus an optional already-generated token
        journal) into a free slot. Returns ``(slot, next_token)`` — the
        token comes out of the prefill program itself (the context's last
        hidden state is already there).

        ``tokens`` is the request's journal when this admission is a
        *replay* (supervisor recovery) or *re-admission after preemption*:
        the prefill runs over ``prompt + tokens`` and emits the journal's
        next token, leaving the slot in exactly the state an uninterrupted
        decode would have reached (position ``len(prompt+tokens)``, last
        token = the newly emitted one) — token-for-token identical output.
        With speculation on, the draft cache is reconstructed here too
        (one draft prefill over the same context), so replay resumes with
        a warm draft. ``max_new_tokens`` stays the request's ORIGINAL
        budget (the journal counts toward it), so the block reservation is
        unchanged.

        ``sampling`` / ``adapter`` / ``mask`` install the slot's scenario
        state (all runtime data — see :meth:`admit`); replay passes the
        same values and resumes bit-identically.

        Raises if no capacity; callers gate on :meth:`can_admit`."""
        with self._admission(trace_id):
            with telemetry.phase("prefill.setup", self.hists):
                st = self._admit_setup(prompt, max_new_tokens, tokens,
                                       sampling=sampling, adapter=adapter,
                                       mask=mask, spec_exclude=spec_exclude)
            return st.slot, self._admit_prefill_all(st)

    def admit_begin(self, prompt: np.ndarray, max_new_tokens: int,
                    tokens=None, sampling=None, adapter: int = 0,
                    mask=None, spec_exclude: bool = False,
                    trace_id: str = "") -> Tuple[int, Optional[int]]:
        """Chunked admission entry point: claim a slot + block budget now,
        prefill incrementally. Returns ``(slot, first_token)`` when the
        whole context fits one chunk (identical to :meth:`admit`), or
        ``(slot, None)`` with a chunked prefill left in progress — the
        scheduler then calls :meth:`admit_chunk` once per iteration until
        the first token appears. The slot is *occupied* (its blocks are
        held) but not *active* (its lane stays masked out of the decode
        step), so running streams keep decoding between chunks."""
        with self._admission(trace_id):
            with telemetry.phase("prefill.setup", self.hists):
                st = self._admit_setup(prompt, max_new_tokens, tokens,
                                       sampling=sampling, adapter=adapter,
                                       mask=mask, spec_exclude=spec_exclude)
            chunk = self.chunk_size
            if chunk <= 0 or st.clen - st.prefix_len <= chunk:
                return st.slot, self._admit_prefill_all(st)
            # the claim alone (a `prefill` sample that holds its setup and
            # no compiled call): admit_chunk times each chunk
            with telemetry.phase("prefill.finish", self.hists):
                st.trace_id = trace_id  # admit_chunk restores the context
                st.done = st.prefix_len
                self._chunk[st.slot] = st
                metrics.bump("chunk.admits")
                self._refresh_gauges()
        return st.slot, None

    def admit_chunk(self, slot: int) -> Optional[int]:
        """Advance one chunked prefill by one chunk (one compiled
        suffix-prefill call over ``ctx[done:done+chunk]`` — prefix length
        and the block table are runtime data, so every chunk of every
        admission reuses the chunk-size bucket's ONE program). Returns the
        first generated token when the context is fully scattered (the
        final chunk's last-position logits), else None."""
        st = self._chunk.get(slot)
        if st is None:
            raise RuntimeError(f"slot {slot} has no chunked prefill "
                               "in progress")
        with self._admission(st.trace_id):
            take = min(self.chunk_size, st.clen - st.done)
            try:
                nxt, new_pools = self._suffix_prefill_call(
                    st.ctx, st.done + take, st.done, slot, chunked=True)
                seq = self._dispatched
                with telemetry.phase("prefill.finish", self.hists):
                    self.arena.set_pools(new_pools)
                    st.done += take
                    metrics.bump("chunk.chunks")
                    metrics.bump("chunk.tokens", take)
                    # incremental publish (FLAGS_serving_publish_chunks):
                    # every prompt block this chunk finished scattering
                    # becomes a radix node NOW — and, via the insert
                    # path's write_through (+ FLAGS_serving_tier_publish),
                    # tier/disk-resident — so a disagg prefill worker's
                    # partial chain is restorable the moment it exists.
                    # insert() is idempotent over the already-inserted
                    # prefix (resident nodes are skipped), and the new
                    # nodes' blocks are marked cached, so even an abort of
                    # the remaining chunks leaves them valid (cached
                    # blocks survive the reservation release).
                    if (self.prefix_cache is not None
                            and flags.flag("serving_publish_chunks")):
                        full = min(st.done, st.plen) // self.block_size
                        if full > 0:
                            self.prefix_cache.insert(
                                st.prompt, self._bt_host[slot], full)
                if (st.done >= st.clen and self.spec is not None
                        and not st.skip_draft):
                    with telemetry.phase("prefill.draft", self.hists):
                        self.spec.prefill(slot, st.ctx)
            # analysis: allow(broad-except) — cleanup-and-reraise: a
            # failed chunk must not leak the admission's blocks/refs/slot
            except Exception:
                self._chunk.pop(slot, None)
                self._admit_abort(st)
                raise
            if st.done < st.clen:
                # nothing waits for a chunk that yields no token: the
                # decode steps between the chunks queue behind it
                return None
            self._chunk.pop(slot, None)
            return self._admit_first_token(st, nxt, seq)

    def _admit_setup(self, prompt: np.ndarray, max_new_tokens: int,
                     tokens, sampling=None, adapter: int = 0,
                     mask=None, spec_exclude: bool = False) -> _AdmitState:
        """Claim everything an admission needs before any prefill work:
        the slot, the shared-prefix references, the target + draft block
        reservations, the filled block table, the COW copy, and the
        slot's sampling/constraint/adapter state (installed BEFORE the
        prefill calls — the prefill programs sample their first token
        under it). On ANY failure the claim unwinds completely."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = int(prompt.shape[0])
        self.validate(plen, max_new_tokens, adapter=adapter)
        journal = np.asarray(tokens if tokens is not None else [], np.int32)
        ctx = (np.concatenate([prompt, journal.reshape(-1)])
               if journal.size else prompt)
        clen = int(ctx.shape[0])
        if clen >= plen + max_new_tokens:
            raise ValueError(
                f"journal of {journal.size} tokens already exhausts the "
                f"max_new_tokens={max_new_tokens} budget; nothing to resume")
        slot = int(np.argmin(self._occupied))
        if self._occupied[slot]:
            raise RuntimeError("no free slot")

        # ---- radix-cache walk: attach resident full PROMPT blocks by
        # reference (refcount++, zero prefill work for the matched prefix).
        # The refs are taken BEFORE reserve() so its eviction pass can
        # never reclaim the very blocks this admission is about to share.
        # With tiering the chain is a resident prefix followed by a
        # SPILLED tail (a resident node's ancestors are resident by
        # construction): each resident node is pinned the moment it is
        # reached — so the evictions a restore may trigger can never
        # reclaim it — and each spilled node is first restored into a
        # fresh cached block (ONE compiled scatter, _restore_node), then
        # pinned identically. A restore that fails (tier lost the entry /
        # no headroom) truncates the match there: the remainder prefills
        # normally — recompute, never garbage.
        cache = self.prefix_cache
        walked = cache.match(prompt) if cache is not None else []
        chain = []
        try:
            split = next((i for i, n in enumerate(walked) if n.spilled),
                         len(walked))
            for node in walked[:split]:
                self.arena.ref(node.block)
                chain.append(node)
            if split < len(walked) and self.tier is not None:
                restored = self._restore_nodes(walked[split:])
                for node in walked[split:split + restored]:
                    self.arena.ref(node.block)
                    chain.append(node)
        # analysis: allow(broad-except) — cleanup-and-reraise: a restore
        # dying mid-chain must drop every ref taken so far
        except Exception:
            for node in chain:
                self.arena.deref(node.block)
            raise
        # a fully-matched block-aligned context has no suffix to prefill,
        # but the last token must still be recomputed for its logits: the
        # last matched block is copied into a private block (COW) and the
        # final token re-scattered there — shared blocks stay read-only
        cow = bool(chain) and len(chain) * self.block_size == clen
        attached = chain[:-1] if cow else chain
        shared = [node.block for node in attached]
        # the COW source is read, not attached — but it must stay pinned
        # across reserve() too, or the eviction pass could reclaim (and a
        # recycled take() could overwrite) the block _cow_copy is about to
        # read. Every chain node already holds this admission's ref from
        # the loop above: `shared` names the ones retire dereferences,
        # `cow_src`'s ref is the COW pin released right after the copy;
        # admit_sizing's unpinned count already budgets for these pins
        cow_src: Optional[int] = chain[-1].block if cow else None
        try:
            res = self.arena.reserve(
                self._target_blocks_needed(plen, max_new_tokens)
                - len(attached))
        # analysis: allow(broad-except) — cleanup-and-reraise: any
        # reservation failure must drop the refs taken above
        except Exception:
            for node in chain:
                self.arena.deref(node.block)
            raise
        # a spec-ineligible lane (sampled/constrained/adapter — sticky,
        # see spec_ineligible) never reads its draft cache: skip the
        # draft prefill AND its block reservation entirely. Admission
        # FEASIBILITY (blocks_needed/can_admit) stays conservative —
        # it doesn't know the scenario — so this only under-consumes.
        skip_draft = (self.spec is not None
                      and (bool(spec_exclude) or int(adapter) != 0
                           or mask is not None
                           or (sampling is not None
                               and sampling.temperature > 0)))
        if self.spec is not None and not skip_draft:
            try:
                self.spec.alloc_slot(slot, plen, max_new_tokens)
            # analysis: allow(broad-except) — cleanup-and-reraise: the
            # draft budget failing must return the target's too
            except Exception:
                res.release()
                for blk in shared:
                    self.arena.deref(blk)
                if cow_src is not None:
                    self.arena.deref(cow_src)
                raise
        n_attached = len(attached)
        prefix_len = clen - 1 if cow else n_attached * self.block_size
        st = _AdmitState(slot=slot, prompt=prompt, ctx=ctx, plen=plen,
                         clen=clen, max_new=int(max_new_tokens), res=res,
                         shared=shared, n_attached=n_attached, cow=cow,
                         prefix_len=prefix_len, sampling=sampling,
                         adapter=int(adapter), skip_draft=skip_draft)
        self._occupied[slot] = True
        self._slot_res[slot] = res
        self._slot_shared[slot] = shared
        try:
            # inside the unwind: a bad constraint mask (wrong vocab size,
            # empty) must release the slot/reservation/refs like any
            # other admission failure, not leak them
            self._install_slot_scenario(slot, sampling, int(adapter),
                                        mask, spec_exclude=spec_exclude)
            for i, blk in enumerate(shared):
                self._bt_host[slot, i] = blk
            # private blocks covering the suffix [prefix blocks, clen)
            for bi in range(n_attached, _ceil_div(clen, self.block_size)):
                self._bt_host[slot, bi] = res.take()
            self._slot_filled[slot] = _ceil_div(clen, self.block_size)
            self._bt_dev = None
            if cow:
                self._cow_copy(cow_src, res.taken[0])
                self.arena.deref(cow_src)
                cow_src = None  # pin released: the copy is private now
        except Exception:
            # analysis: allow(broad-except) — cleanup-and-reraise: a failed
            # admission must not leak capacity whatever the cause — drop
            # the shared refs, return the private blocks, clear the row.
            # (Under donation the pools may already be consumed — the
            # engine is then dead and every later call fails loudly; the
            # scheduler fails requests cleanly.)
            if cow_src is not None:
                self.arena.deref(cow_src)
            self._admit_abort(st)
            raise
        return st

    def _install_slot_scenario(self, slot: int, sampling, adapter: int,
                               mask, spec_exclude: bool = False) -> None:
        """Install the slot's per-request scenario state — sampling
        params, constraint mask, adapter id — as runtime data. Runs at
        claim time (before any prefill call: the prefill programs sample
        their first token under it)."""
        sp = sampling
        greedy = sp is None or sp.temperature <= 0.0
        self._temp[slot] = 0.0 if sp is None else float(sp.temperature)
        self._top_k[slot] = 0 if sp is None else int(sp.top_k)
        self._top_p[slot] = 1.0 if sp is None else float(sp.top_p)
        self._seed[slot] = 0 if sp is None else int(sp.seed)
        self._sampled[slot] = not greedy
        self._adapter[slot] = adapter
        self._touch_slot_state("slot_update")
        if mask is not None:
            row = np.asarray(mask, bool).reshape(-1)
            if row.shape[0] != self.vocab:
                raise ValueError(
                    f"constraint mask covers {row.shape[0]} tokens, "
                    f"vocab is {self.vocab}")
            if not row.any():
                raise ValueError("constraint mask allows no token")
            self._mask_host[slot, :] = row
            self._constrained[slot] = True
            self._update_mask_row(slot)
            metrics.bump("constrain.admits")
        if not greedy:
            self.sampled_admits += 1
            metrics.bump("sampling.admits")
        if mask is not None:
            self.constrained_admits += 1
        if adapter:
            self.adapter_admits += 1
            metrics.bump("lora.admits")
        self._scenario_once[slot] = (not greedy or mask is not None
                                     or bool(adapter) or bool(spec_exclude))

    def _clear_slot_scenario(self, slot: int) -> None:
        """Reset the slot's scenario state to the greedy/unmasked/base
        defaults (retire and admission unwind)."""
        self._temp[slot] = 0.0
        self._top_k[slot] = 0
        self._top_p[slot] = 1.0
        self._seed[slot] = 0
        self._sampled[slot] = False
        self._adapter[slot] = 0
        self._scenario_once[slot] = False
        self._touch_slot_state("slot_update")
        if self._constrained[slot]:
            self._mask_host[slot, :] = True
            self._constrained[slot] = False
            self._update_mask_row(slot)

    def _update_mask_row(self, slot: int) -> None:
        """Mark one mask row stale on device. The refresh is DEFERRED and
        batched: ``_mask_arg`` applies every dirty row in one scatter
        per decode step — neither a full [S, vocab] re-upload per step
        (the walker advances every token) nor one dispatch per update."""
        if self._mask_dev is not None:
            self._mask_dirty.add(int(slot))

    def set_slot_mask(self, slot: int, mask) -> None:
        """Scatter a constrained slot's new allowed-vocab row (the host
        walker advanced one token): pure runtime data — one device row
        updates, never the compiled step. ``None`` lifts the constraint
        (all-True, the mask-off identity)."""
        if mask is None:
            if self._constrained[slot]:
                self._mask_host[slot, :] = True
                self._constrained[slot] = False
                self._update_mask_row(slot)
            return
        row = np.asarray(mask, bool).reshape(-1)
        if row.shape[0] != self.vocab:
            raise ValueError(
                f"constraint mask covers {row.shape[0]} tokens, vocab "
                f"is {self.vocab}")
        if not row.any():
            raise ValueError("constraint mask allows no token")
        self._mask_host[slot, :] = row
        self._constrained[slot] = True
        self._scenario_once[slot] = True  # sticky: see spec_ineligible
        self._update_mask_row(slot)
        metrics.bump("constrain.mask_updates")

    def spec_ineligible(self) -> np.ndarray:
        """Per-slot mask of lanes speculative decoding must NOT cover:
        sampled (verify-against-sampled-distribution is follow-up work),
        constrained (the verify program applies no vocab mask), and
        adapter-wearing (the verify program binds no adapter context)
        slots fall back to the plain decode step per-slot — see
        :meth:`~.spec_decode.SpecDecoder.step`. STICKY per request
        (``_scenario_once``): a constraint that lifts mid-stream must
        not hand the lane back — its draft cache missed every token of
        the fallback phase."""
        return (self._sampled | self._constrained
                | (self._adapter != 0) | self._scenario_once)

    def _admit_abort(self, st: _AdmitState) -> None:
        """Unwind a claimed admission (setup succeeded, a later prefill /
        chunk / draft call failed): drop the shared refs, release both
        reservations, clear the slot row."""
        for blk in st.shared:
            self.arena.deref(blk)
        st.shared = []
        st.res.release()
        if self.spec is not None:
            self.spec.release_slot(st.slot)
        self._slot_res[st.slot] = None
        self._slot_shared[st.slot] = []
        self._slot_filled[st.slot] = 0
        self._bt_host[st.slot, :] = 0
        self._bt_dev = None
        self._occupied[st.slot] = False
        self._clear_slot_scenario(st.slot)
        self._refresh_gauges()

    def _admit_prefill_all(self, st: _AdmitState) -> int:
        """The one-shot (non-chunked) prefill path: whole-context bucketed
        prefill (or suffix-only on a cache hit), then the draft prefill
        when speculation runs a draft model."""
        try:
            if st.n_attached or st.cow:
                nxt, new_pools = self._suffix_prefill_call(
                    st.ctx, st.clen, st.prefix_len, st.slot)
                new_rec = None
            else:
                nxt, new_pools, new_rec = self._full_prefill_call(
                    st.ctx, st.clen, st.res, st.slot)
            seq = self._dispatched
            with telemetry.phase("prefill.finish", self.hists):
                if new_rec is not None:
                    self.arena.set_slot_state(new_rec)
                self.arena.set_pools(new_pools)
            if self.spec is not None and not st.skip_draft:
                with telemetry.phase("prefill.draft", self.hists):
                    self.spec.prefill(st.slot, st.ctx)
        # analysis: allow(broad-except) — cleanup-and-reraise: a failed
        # prefill must not leak the admission's blocks/refs/slot
        except Exception:
            self._admit_abort(st)
            raise
        return self._admit_first_token(st, nxt, seq)

    def _admit_first_token(self, st: _AdmitState, nxt, seq: int) -> int:
        """The end of an admission: wait for the prefill's token
        (``prefill.wait``: the device runs the step that was in flight
        ahead of the prefill, then the prefill), then activate the slot
        (``prefill.finish``). ``seq`` numbers the prefill call."""
        with telemetry.phase("prefill.wait", self.hists):
            first = int(nxt)
        self._device_drained(seq)
        with telemetry.phase("prefill.finish", self.hists):
            return self._admit_finish(st, first)

    def _admit_finish(self, st: _AdmitState, first: int) -> int:
        """Activate the slot: the whole context is scattered and its next
        token exists. From here the slot decodes like any other."""
        cache = self.prefix_cache
        slot = st.slot
        if cache is not None:
            cache.note_hit(st.prefix_len if (st.n_attached or st.cow)
                           else 0)
            # make this prompt's freshly scattered FULL blocks shareable;
            # the trailing partial block (still written mid-stream) and
            # journal/generated tokens stay private to the slot
            cache.insert(st.prompt, self._bt_host[slot],
                         st.plen // self.block_size)
            if st.n_attached or st.cow:
                metrics.bump("tokens.prefill_avoided", st.prefix_len)

        self._positions[slot] = st.clen  # next write position
        self._last_tok[slot] = first
        self._slot_limit[slot] = st.plen + st.max_new
        self._active[slot] = True
        self._touch_slot_state("admit")
        metrics.bump("engine.admits")
        if self.recurrent:
            # the prefill started the lane from zeros (whatever its last
            # tenant left) and wrote this request's state into it
            metrics.bump("state.resets")
        metrics.bump("tokens.prefill", st.clen - st.prefix_len)
        # tokens through the layers before the model's prefill tail and
        # through those of it (the last valid token alone; a model that
        # declares no tail runs every layer on every token)
        metrics.bump("prefill.body_tokens", st.clen - st.prefix_len)
        metrics.bump("prefill.tail_tokens", st.clen - st.prefix_len
                     if self._prefill_tail is None else 1)
        metrics.bump("tokens.generated")  # the next token, out of prefill
        self._refresh_gauges()
        return first

    def _full_prefill_call(self, ctx: np.ndarray, clen: int,
                           res: Reservation, slot: int):
        """Dispatch the whole-context bucketed prefill (the cache-miss and
        cache-off path — byte-identical to the pre-cache engine). The
        emitted first token sits at context index ``clen`` — it samples
        under the slot's params at that positional key."""
        import jax.numpy as jnp

        with telemetry.phase("prefill.upload", self.hists):
            p_bucket = compile_cache.prefill_bucket(
                clen, self.max_model_len, self.prefill_bucket_min)
            ids = np.zeros((1, p_bucket), np.int32)
            ids[0, :clen] = ctx
            mbp = _ceil_div(p_bucket, self.block_size)
            rows = np.zeros(mbp, np.int32)
            rows[:len(res.taken)] = res.taken
            up = (jnp.asarray(ids), jnp.int32(clen), jnp.asarray(rows),
                  self._samp_row(slot, clen), jnp.int32(slot))
            lora = self._lora_args(slot)
            self._count_prefill_call(p_bucket, clen, up, lora[1:])
            if self._block_writes:
                metrics.bump("prefill.block_writes", self._block_writes)
        with telemetry.phase("prefill.dispatch", self.hists):
            fn = self._get_prefill(p_bucket)
            out = self._call(
                fn, self._arrays, up[0], up[1], self.arena.pools, up[2],
                up[3], self.arena.slot_state, up[4], *lora,
                name="serving.prefill")
            # the uploaded arrays die inside the phase, not between two
            # (a device array's destructor gives the GIL away)
            del up, lora
            return out

    def _count_prefill_call(self, bucket: int, tokens: int, *sent) -> None:
        """One compiled prefill call: the positions it computes (its
        bucket; the real ones are ``tokens.prefill``), the bytes of the
        arrays made for it on the device (``sent``), and both lengths as
        arguments of the ``pt.prefill`` interval."""
        metrics.bump("prefill.calls")
        metrics.bump("prefill.positions_computed", bucket)
        metrics.bump("prefill.upload_bytes",
                     sum(a.nbytes for a in jax.tree_util.tree_leaves(sent)))
        if self._prefill_ph is not None:
            self._prefill_ph.note(bucket=bucket, tokens=tokens)

    def _suffix_prefill_call(self, ctx: np.ndarray, clen: int,
                             prefix_len: int, slot: int,
                             chunked: bool = False):
        """Dispatch the suffix-only prefill for a cache-hit admission (or
        one chunk of a chunked admission — same programs, different
        accounting): only ``ctx[prefix_len:clen]`` runs through the model;
        everything before ``prefix_len`` is attended via the slot's
        (already filled) block table, never recomputed."""
        import jax.numpy as jnp

        with telemetry.phase("prefill.upload", self.hists):
            slen = clen - prefix_len
            s_bucket = compile_cache.prefill_bucket(
                slen, self.max_model_len, self.prefill_bucket_min)
            ids = np.zeros((1, s_bucket), np.int32)
            ids[0, :slen] = ctx[prefix_len:clen]
            # the emitted token sits at context index `clen`; only the
            # FINAL chunk of a chunked admission consumes it, where clen
            # == the full context length — the same positional key either
            # way
            up = (jnp.asarray(ids), jnp.int32(slen), jnp.int32(prefix_len),
                  jnp.asarray(self._bt_host[slot]),
                  self._samp_row(slot, clen))
            lora = self._lora_args(slot)
            self._count_prefill_call(s_bucket, slen, up, lora[1:])
        with telemetry.phase("prefill.dispatch", self.hists):
            fn = self._get_prefix_prefill(s_bucket)
            if not chunked:
                metrics.bump("prefix.suffix_prefills")
            out = self._call(
                fn, self._arrays, up[0], up[1], up[2], self.arena.pools,
                up[3], up[4], *lora, name="serving.prefix_prefill")
            del up, lora  # as in _full_prefill_call
            return out

    def retire(self, slot: int, why: str = "retire") -> None:
        """Free a slot: deactivate its lane, drop its shared-prefix
        references (refcount--; a shared block returns to the free list
        only when the last sharer lets go — or stays resident if the radix
        cache holds it), and release its private blocks (draft namespace
        included) through the same refcount layer. Also covers a slot
        mid-chunked-prefill (occupied but not yet active) — a cancelled
        long admission frees everything it claimed. Purely host-side
        state — never recompiles. ``why`` (``retire``, or ``preempt`` from
        the scheduler) names the writer of the slot state."""
        if not self._occupied[slot]:
            return
        self._occupied[slot] = False
        self._active[slot] = False
        self._chunk.pop(slot, None)
        res = self._slot_res[slot]
        self._slot_res[slot] = None
        if res is not None:
            res.release()
        for blk in self._slot_shared[slot]:
            self.arena.deref(blk)
        if self.spec is not None:
            self.spec.release_slot(slot)
        self._slot_shared[slot] = []
        self._slot_filled[slot] = 0
        self._bt_host[slot, :] = 0
        self._bt_dev = None
        self._positions[slot] = 0
        self._last_tok[slot] = 0
        self._slot_limit[slot] = 0
        self._tenancy[slot] += 1  # a step in flight no longer owns the lane
        self._clear_slot_scenario(slot)
        self._touch_slot_state(why)
        metrics.bump("engine.retires")
        if flags.flag("serving_arena_invariants"):
            self.check_invariants()
        self._refresh_gauges()

    def check_invariants(self) -> None:
        """Audit the refcount layer against the live slot tables: free
        blocks must be refcount-zero/uncached, and each block's refcount
        must equal the number of ACTIVE table entries referencing it
        (shared prefixes may appear in several tables — but only as many
        times as the refcount says). Gated behind
        ``FLAGS_serving_arena_invariants`` on the release paths; callable
        directly from tests."""
        tables = []
        # occupied, not just active: a slot mid-chunked-prefill already
        # holds (and may share) blocks
        for slot in np.flatnonzero(self._occupied):
            n = int(self._slot_filled[slot])
            tables.append([int(b) for b in self._bt_host[slot, :n]])
        if self.spec is not None:
            # the second (draft-namespace) block tables: privately owned,
            # so each entry must account for exactly one refcount
            tables.extend(self.spec.slot_tables())
        self.arena.check_invariants(tables)

    def rebuild(self) -> None:
        """Throw away the KV arena and every slot's runtime state and start
        from an empty, identically-shaped arena. This is the supervisor's
        recovery primitive after a transient device/arena failure: the old
        pools may be corrupt or consumed (a donated call died holding
        them), but the COMPILED programs only depend on shapes, so a
        rebuilt engine re-serves without a single recompile — live
        requests are re-prefilled from their journals by the supervisor.
        """
        self.arena = KVArena(*self._arena_args)
        # the radix tree indexed the OLD arena's blocks: reset it with the
        # fresh arena — journal replays re-populate it (and re-share) as
        # they re-prefill. Lifetime counters carry over: stats()/close()
        # summaries cover the engine's whole life, not just post-rebuild.
        # The TIER VIEW survives untouched: host/disk entries are
        # off-device by construction, so replay walks hit the tier and
        # RESTORE the crashed arena's prefixes instead of re-prefilling
        # them — warm-cache replay for free.
        if self.use_prefix_cache:
            old = self.prefix_cache
            self.prefix_cache = PrefixCache(self.arena, self.block_size,
                                            tier=self.tier)
            if old is not None:
                for k in ("hits", "misses", "hit_tokens",
                          "inserted_blocks", "evictions", "spills",
                          "restores"):
                    setattr(self.prefix_cache, k, getattr(old, k))
                if old._index is not None:
                    # rebind the cross-replica residency index; binding
                    # resets this replica's published device residency
                    # (the fresh tree is empty — replays republish)
                    self.prefix_cache.bind_index(old._index, old._replica)
        self._bt_host[:] = 0
        self._bt_dev = None
        self._touch_slot_state("error")
        # a step in flight died with the old arena: its tokens were never
        # emitted, so the journals replay it
        self.decode_drop()
        self._positions[:] = 0
        self._last_tok[:] = 0
        self._active[:] = False
        self._occupied[:] = False
        self._slot_limit[:] = 0
        self._chunk.clear()
        # scenario state dies with the slots; journal replays re-install
        # each request's sampling/mask/adapter at re-admission (the LoRA
        # arena itself is host-owned and survives — registered adapters
        # need no re-registration after a rebuild)
        self._temp[:] = 0.0
        self._top_k[:] = 0
        self._top_p[:] = 1.0
        self._seed[:] = 0
        self._adapter[:] = 0
        self._sampled[:] = False
        self._constrained[:] = False
        self._scenario_once[:] = False
        self._mask_host[:] = True
        self._mask_dev = None
        self._slot_res = [None] * self.num_slots
        self._slot_shared = [[] for _ in range(self.num_slots)]
        self._slot_filled[:] = 0
        if self.spec is not None:
            # bind a fresh draft namespace to the fresh arena; journal
            # replays reconstruct each slot's draft cache as they re-admit
            self.spec.rebuild()
        metrics.bump("engine.rebuilds")
        self._publish_arena_bytes()
        self._refresh_gauges()

    # --------------------------------------------------------- decode step

    def _grow_slot_to(self, slot: int, pos_max: int) -> None:
        """Take private blocks until the slot's table covers ``pos_max``
        (the reservation guarantees take() cannot fail). Growth compares
        against FILLED table entries — shared prefix blocks count, so a
        cache-hit slot grows past its attached prefix seamlessly, and
        decode never writes a shared block: the write position is always
        past the last full (sharable) block of the context."""
        res = self._slot_res[slot]
        need = pos_max // self.block_size + 1
        while int(self._slot_filled[slot]) < need:
            bi = int(self._slot_filled[slot])
            self._bt_host[slot, bi] = res.take()
            self._slot_filled[slot] = bi + 1
            self._bt_dev = None

    def spec_decode_step(self):
        """One speculative iteration (``FLAGS_serving_spec_k`` > 0):
        up to k accepted tokens per active slot from one compiled call —
        see :class:`~.spec_decode.SpecDecoder.step`. Returns
        ``{slot: [tokens]}``."""
        with telemetry.phase("spec_step", self.hists):
            return self.spec.step()

    def _touch_slot_state(self, why: str) -> None:
        """A host write to any per-slot vector the decode step carries
        (positions, last token, active mask, sampling params, adapter
        ids): the device copy is stale and the next step re-sends the
        mirrors, all in one upload. ``why`` names the writer (``admit``,
        ``retire``, ``preempt``, ``slot_update``, ``override``,
        ``error``); the dispatch that restarts from the mirrors counts
        the last one (``engine.restarts.<why>``)."""
        self._state_dev = None
        self._stale_why = why

    def _pack_slot_state(self, act) -> np.ndarray:
        """The host mirrors as the decode step's one ``[8, S]`` int32
        argument (rows ``_ST_*``; float rows bit-cast, unpacked again
        inside the compiled step)."""
        st = np.empty((_ST_ROWS, self.num_slots), np.int32)
        st[_ST_POS] = self._positions
        st[_ST_TOK] = self._last_tok
        st[_ST_ACT] = act
        st[_ST_TEMP] = self._temp.view(np.int32)
        st[_ST_TOP_K] = self._top_k
        st[_ST_TOP_P] = self._top_p.view(np.int32)
        st[_ST_SEED] = self._seed
        st[_ST_ADAPTER] = self._adapter
        return st

    def _mask_arg(self):
        """The decode step's [S, vocab] constraint mask. The device mask
        is memoized; rows the walkers changed since the last step refresh
        in ONE batched scatter here (unconstrained steady state re-passes
        the cached array with zero transfer; constrained slots cost one
        small dispatch/step)."""
        import jax.numpy as jnp

        if self._mask_dev is None:
            self._mask_dev = jnp.asarray(self._mask_host)
            self._mask_dirty.clear()
            self._count_step_upload(self._mask_host)
        elif self._mask_dirty:
            rows = np.fromiter(self._mask_dirty, np.int32,
                               len(self._mask_dirty))
            new = self._mask_host[rows]
            self._mask_dev = self._mask_dev.at[jnp.asarray(rows)].set(
                jnp.asarray(new))
            self._mask_dirty.clear()
            self._count_step_upload(rows, new)  # row ids and rows
        return self._mask_dev

    @staticmethod
    def _count_step_upload(*sent: np.ndarray) -> None:
        """The transfers a decode step's preparation made, and their
        bytes."""
        metrics.bump("engine.step_uploads", len(sent))
        metrics.bump("engine.step_upload_bytes",
                     sum(a.nbytes for a in sent))

    def _samp_row(self, slot: int, pos: int):
        """One slot's sampling pytree for a prefill call ([1] shapes;
        ``pos`` = the context index where the emitted token will sit —
        its positional PRNG key)."""
        import jax.numpy as jnp

        return (jnp.asarray(self._temp[slot:slot + 1]),
                jnp.asarray(self._top_k[slot:slot + 1]),
                jnp.asarray(self._top_p[slot:slot + 1]),
                jnp.asarray(self._seed[slot:slot + 1]),
                jnp.full((1,), pos, jnp.int32),
                jnp.asarray(self._mask_host[slot:slot + 1]))

    def _lora_args(self, slot: int) -> tuple:
        """The adapter-arena args of a prefill call — ``()`` when the
        arena is off (the programs are built without the parameters), else
        ``(pools, adapter_ids)``: the memoized device pools plus the
        slot's adapter index. (The decode step reads its per-lane ids from
        the packed slot state.)"""
        if self.lora is None:
            return ()
        import jax.numpy as jnp

        return (self.lora.device_pools(),
                jnp.asarray(self._adapter[slot:slot + 1]))

    def _step_args(self, act) -> tuple:
        """The decode step's arguments at the current slot state. Only what
        the host changed since the last step is sent again (the block
        table after a lane grew, the packed slot state after a host
        write, stale mask rows); ``engine.step_uploads`` counts the
        transfers and ``engine.step_upload_bytes`` their bytes."""
        import jax.numpy as jnp

        if self._bt_dev is None:
            self._bt_dev = jnp.asarray(self._bt_host)
            self._count_step_upload(self._bt_host)
        if self._state_dev is None:
            # placed as the step hands it back (replicated on a mesh,
            # uncommitted without one), so that an uploaded and a carried
            # state are one call signature
            state = self._pack_slot_state(act)
            if self.mesh is None:
                self._state_dev = jnp.asarray(state)
            else:
                from ..distributed.sharding_util import replicate

                self._state_dev = replicate(state, mesh=self.mesh)
            self._count_step_upload(state)
        lora = () if self.lora is None else (self.lora.device_pools(),)
        return (self._arrays, self.arena.pools, self._bt_dev,
                self._state_dev, self._mask_arg(), self.arena.slot_state,
                *lora)

    def lower_decode_step(self):
        """``jax.stages.Lowered`` of the one compiled decode step at this
        engine's shapes and placements — for reading what the step
        contains (a ``tpu_custom_call`` when the paged kernel is in it,
        collectives on a mesh; ``.compile()`` gives the memory analysis).
        Lowering re-traces the step, so it counts in ``decode_traces`` /
        ``serving.decode_compiles`` like any other trace: read those
        counters first."""
        return self._get_step().lower(*self._step_args(self._active))

    def decode_dispatch(self, active=None) -> _StepInFlight:
        """The first half of a decode step: grow the block tables, send
        what the host changed, dispatch the compiled step and let its
        arguments go. Nothing here waits for the device. The step's
        write positions advance now (they do not depend on its tokens, and
        the next dispatch grows the tables from them); its tokens reach
        the ``_last_tok`` mirror in :meth:`decode_collect`. Returns the
        step in flight, which also joins the engine's queue of them."""
        hists = self.hists
        act = (self._active.copy() if active is None
               else np.asarray(active, bool))
        if active is not None:
            # the device's copy holds the engine's own mask: this step
            # sends the mirrors with the caller's, the next one again
            self._touch_slot_state("override")
        restart = self._state_dev is None
        if restart:
            if self._flight:
                raise RuntimeError(
                    "decode_dispatch after a host write with a step in "
                    "flight: the mirrors lack its tokens; collect it first")
            metrics.bump("engine.restarts." + self._stale_why)
        with telemetry.phase("decode.prepare", hists):
            # grow block tables whose write position crossed a block
            # boundary, then whatever the host changed since the last
            # step goes to the device
            with telemetry.phase("decode.prepare.grow", hists):
                for slot in np.flatnonzero(act):
                    self._grow_slot_to(slot, int(self._positions[slot]))
            with telemetry.phase("decode.prepare.upload", hists):
                args = self._step_args(act)
            # stale until this step is dispatched: a call that raises
            # produced no next state (and may have consumed the donated
            # pools), so the step after it starts from the mirrors. No
            # writer: the reason found stays
            self._state_dev = None
        with telemetry.phase("decode.dispatch", hists):
            # if the device stood empty, this step ends that: a restart
            # where the pump would have run ahead but for a host write, a
            # turn that is synchronous by design otherwise
            nxt, new_pools, new_rec, state = self._call(
                self._get_step(), *args, name="serving.step",
                cause="restart" if restart and self._ahead else "sync")
        with telemetry.phase("decode.wait", hists):
            with telemetry.phase("decode.release", hists):
                # the step's argument arrays and the donated pools must
                # die HERE, while the device runs: each device array's
                # destructor hands the GIL over and queues for it again,
                # tens of ms a step under load. Kept alive to the
                # function's end, that time came after the device's step
                # instead of under it (PERF.md, PR 24: a third fewer
                # tokens a second)
                del args
                self.arena.set_pools(new_pools)
                self.arena.set_slot_state(new_rec)
        if active is None:
            # the mirrors as they read once this step's tokens are in:
            # the next step may be dispatched from it before they are
            self._state_dev = state
        self._positions[act] += 1
        step = _StepInFlight(nxt, act, self._tenancy.copy(),
                             self._dispatched)
        self._flight.append(step)
        return step

    def decode_collect(self) -> np.ndarray:
        """The second half, of the OLDEST step in flight: block until the
        device is done with it, the token vector is back and this thread
        has the GIL again; mirror the tokens of the lanes that still hold
        the request the step ran them for. A lane retired since the
        dispatch (its request ended at the step before, or was preempted)
        was computed for nobody: its token is dropped here, whoever holds
        the slot by now. Returns the step's ``[num_slots]`` tokens; the
        lanes that count are ``lanes_read``."""
        step = self._flight.popleft()
        with telemetry.phase("decode.wait", self.hists):
            # a device that died under the step says so here
            resilience.maybe_fault("serving_step")
            out = np.asarray(step.tokens)
        if not self._flight:
            self._device_drained(step.seq)
        for name, value in zip(self._step_counters, out[self.num_slots:]):
            metrics.bump(name, int(value))
        out = out[:self.num_slots]
        live = step.lanes & (step.tenancy == self._tenancy)
        self._last_tok[live] = out[live]
        self.lanes_read = live
        n_live, n_ran = int(live.sum()), int(step.lanes.sum())
        metrics.bump("engine.steps")
        metrics.bump("engine.lane_steps", n_ran)
        metrics.bump("engine.lane_steps_discarded", n_ran - n_live)
        metrics.bump("tokens.generated", n_live)
        self._meter.tick(n_live)
        metrics.set_gauge("tokens_per_sec", round(self._meter.rate(), 1))
        return out

    def decode_drop(self) -> None:
        """Forget every step in flight, unread (nothing waits for the
        device: its stream still orders them before any later prefill).
        For a pump with nothing left running, a failed engine and
        :meth:`rebuild`: the lanes those steps ran are all retired, so
        no mirror needs their tokens."""
        metrics.bump("engine.lane_steps_discarded",
                     sum(int(step.lanes.sum()) for step in self._flight))
        self._flight.clear()

    @property
    def steps_in_flight(self) -> int:
        return len(self._flight)

    def decode_step(self, active=None) -> np.ndarray:
        """One iteration: every active slot's last token is forwarded at
        its own position, its k/v lands in its current block, and one new
        token per slot comes back ([num_slots] int32; inactive lanes carry
        garbage — callers must mask by activity). ``active`` overrides the
        lane mask (runtime data — same program): the speculative decoder
        drives the sampled/constrained/adapter lanes it must not cover
        through here, see :meth:`spec_ineligible`.

        Synchronous for a direct caller: dispatch, then collect. Inside
        :meth:`decode_turn` with ``ahead`` (the scheduler's pump) the call
        reads the oldest step in flight and, while the state the device
        carries is still what the next step needs (no host write since the
        last dispatch), dispatches that next step BEFORE the read: the
        host's share of the turn then runs under the device's step."""
        with telemetry.phase("decode_step", self.hists):
            if active is not None and self._flight:
                raise RuntimeError(
                    "decode_step(active=...) with a step in flight: a "
                    "lane-mask override is a synchronous turn")
            if not self._flight:
                self.decode_dispatch(active)
            ahead = (self._ahead and active is None
                     and self._state_dev is not None)
            if ahead:
                self.decode_dispatch()
            # counted every turn, so a window of synchronous turns reads 0
            metrics.bump("engine.steps_run_ahead", int(ahead))
            return self.decode_collect()

    def decode_turn(self, ahead: bool) -> Tuple[np.ndarray, np.ndarray]:
        """The decode call of a caller that comes back every turn
        (``Scheduler.step``): :meth:`decode_step`, reached through the
        attribute so that whatever wraps it wraps the pump's steps too,
        with one step left in flight behind the one it reads if ``ahead``
        (the next step's inputs do not wait for this one's tokens on the
        host). Returns the tokens of the step read and ``lanes_read``."""
        self._ahead = bool(ahead)
        try:
            toks = self.decode_step()
        finally:
            self._ahead = False
        return toks, self.lanes_read

    # -------------------------------------------------------------- stats

    def kernel_route(self) -> str:
        """The effective attention route x mesh topology this engine was
        BUILT with — ``"kernel@data1.model4"``, ``"gather@single"``, ...
        (the ``kernel.mesh`` gauge). "kernel" means the decode step and
        the spec sub-steps read K/V through the Pallas paged decode
        kernel (per model-shard on a multi-device mesh), whether that was
        asked for or is the default on this device; "gather" is the XLA
        path. Construction-time structure, so the route a run took is
        in its record, not inferred from step times."""
        route = "kernel" if self.decode_kernel else "gather"
        topo = ("single" if self.mesh is None else
                ".".join(f"{a}{int(self.mesh.shape[a])}"
                         for a in self.mesh.axis_names))
        return f"{route}@{topo}"

    def _publish_arena_bytes(self) -> None:
        """Byte/dtype gauges per arena namespace (scale pools broken out)
        — the memory win of the int8 arena is observable, not asserted:
        ``tools/serving_stats.py --run`` and ``EnginePredictor.close()``
        both read these."""
        metrics.set_gauge("arena.kv_bytes", self.arena.bytes_total())
        # layers that own a paged pool, and layers that read one (a
        # "shared" layer reads the pool of the layer it names)
        for gauge in sorted({g for row in cache_views.KINDS.values()
                             for g in row.counted_in}):
            metrics.set_gauge(gauge, sum(gauge in row.counted_in
                                         for row in self._layer_kinds))
        if self.recurrent:
            metrics.set_gauge("state.bytes_total",
                              self.arena.state_bytes_total())
            by_gauge = {row.bytes_gauge: 0
                        for row in cache_views.KINDS.values()
                        if row.bytes_gauge}
            for st, entry in zip(self._slot_layers, self.arena.slot_state):
                by_gauge[cache_views.KINDS[st.kind].bytes_gauge] += sum(
                    int(a.size) * a.dtype.itemsize for a in entry)
            for gauge, nbytes in by_gauge.items():
                metrics.set_gauge(gauge, nbytes)
        by_ns = self.arena.bytes_by_namespace()
        metrics.set_gauge("arena.scale_bytes",
                          sum(d["scale_bytes"] for d in by_ns.values()))
        for name, d in by_ns.items():
            metrics.set_gauge(f"arena.bytes.{name}", d["bytes"])
            metrics.set_gauge(f"arena.dtype.{name}", d["dtype"])

    def _refresh_gauges(self) -> None:
        metrics.set_gauge("slots.active", self.active_slots())
        a = self.arena.stats()
        metrics.set_gauge("arena.blocks_free", a["blocks_free"])
        metrics.set_gauge("arena.blocks_total", a["blocks_total"])
        metrics.set_gauge("arena.blocks_cached", a["blocks_cached"])
        metrics.set_gauge("arena.high_water", a["high_water"])
        # internal fragmentation: filled-block capacity minus live context
        frag = 0
        for slot in np.flatnonzero(self._active):
            frag += int(self._slot_filled[slot]) * self.block_size \
                - int(self._positions[slot])
        metrics.set_gauge("arena.frag_tokens", frag)
        metrics.set_gauge("sampling.active_slots",
                          int((self._sampled & self._active).sum()))
        metrics.set_gauge("constrain.active_slots",
                          int((self._constrained & self._active).sum()))
        if self.lora is not None:
            metrics.set_gauge("lora.active_slots",
                              int(((self._adapter != 0)
                                   & self._active).sum()))
        if self.prefix_cache is not None:
            metrics.set_gauge("prefix.resident_blocks",
                              self.prefix_cache.resident_blocks())

    def stats(self) -> dict:
        out = {"slots.total": self.num_slots,
               "slots.active": self.active_slots(),
               "decode_traces": self.decode_traces,
               "prefill_traces": dict(self.prefill_traces),
               "prefix_prefill_traces": dict(self.prefix_prefill_traces),
               "cow_traces": self.cow_traces,
               "restore_traces": self.restore_traces,
               "chunk_size": self.chunk_size,
               "tier.enabled": int(self.tier is not None),
               "mesh.key": self.mesh_key,
               "mesh.model_axis": self._mesh_model,
               "mesh.data_axis": self._mesh_data,
               "kernel.paged": int(self.decode_kernel),
               "kernel.mesh": self.kernel_route(),
               "quant.weights": int(self.quant_weights),
               "quant.kv": int(self.quant_kv),
               # effective, not the raw flag: quant_draft without a draft
               # model quantizes nothing (matches the quant.draft gauge)
               "quant.draft": int(self.quant_draft
                                  and self.spec is not None
                                  and self.spec.draft_mode),
               "state.bytes_total": self.arena.state_bytes_total()}
        out.update({
            "sampling.admits": self.sampled_admits,
            "constrain.admits": self.constrained_admits,
            "lora.admits": self.adapter_admits,
        })
        out.update({f"arena.{k}": v for k, v in self.arena.stats().items()})
        if self.prefix_cache is not None:
            out.update({f"prefix.{k}": v
                        for k, v in self.prefix_cache.stats().items()})
        if self.tier is not None:
            out.update(self.tier.stats())
        if self.spec is not None:
            out.update(self.spec.stats())
        if self.lora is not None:
            out.update(self.lora.stats())
        return out
