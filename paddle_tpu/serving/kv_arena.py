"""Block-granular KV cache allocation over a fixed arena (vLLM-style pages).

The serving engine's KV cache is NOT per-request buffers (one allocation per
admit would fragment HBM and retrace XLA) but one fixed **arena** per layer:

    k_pool, v_pool : [num_blocks, block_size, num_heads, head_dim]

With ``quantized=True`` (``FLAGS_serving_quant_kv``) each per-layer entry is
a 4-tuple instead: ``(k, v, k_scale, v_scale)`` — int8 payload plus float32
``[num_blocks, block_size]`` per-block-row scale pools that travel as one
unit through every pools consumer (iterate entries, never unpack ``k, v``;
``check_invariants`` rejects adopted pools missing their scales).

A request's cache is a *block table* — an ordered list of physical block ids
covering its context. Blocks are taken from a LIFO free list as the context
grows and returned at retire, so churn reuses the hottest blocks instead of
growing the footprint. **Physical block 0 is reserved as the scratch sink**:
masked writes from inactive/padded lanes land there, which is what lets one
compiled decode step serve any admit/retire pattern without recompiling.

Admission control is two-phase: :meth:`KVArena.reserve` claims a request's
worst-case block budget up front (so mid-decode growth can never fail — no
preemption/swap machinery needed), and :meth:`Reservation.take` converts one
reserved block at a time into a physical block as the context actually
crosses a block boundary.

**Refcounted sharing** (the radix prefix cache,
:mod:`paddle_tpu.serving.prefix_cache`): a physical block may be referenced
by several slots' block tables at once — shared prompt prefixes attach the
same block by reference instead of re-prefilling it. Every block therefore
carries a refcount: ``take()`` starts it at 1, :meth:`ref` adds a sharer,
:meth:`deref` drops one, and a block returns to the free list only at
refcount zero — unless the prefix cache holds it resident
(:meth:`mark_cached`), in which case it stays out of the free list at
refcount zero as a best-effort cached prefix, reclaimed by LRU eviction
only when :meth:`reserve` would otherwise fail. Shared blocks are
read-only by contract; a slot that must write into one copies it first
(copy-on-write, in the engine).

**Two kinds of state, one manager** (the engine<->model seam,
``models/serving_seam.py``): the block pools above hold the ``"kv"``
layers' state, which grows a row a token (a ``"latent"`` layer's likewise:
ONE row a token for all heads, its entry the 1-tuple ``(rows,)`` of
``latent_width`` values a token, same blocks, same tables, same
accounting; a ``"sparse"`` layer's is K and V rows AND one index key of
``index_width`` values a token: the 3-tuple ``(k, v, index_rows)``, the
index rows packed as a latent pool's are, :meth:`_fresh_index`). A
``"recurrent"`` layer's state
has a fixed size whatever the context, so it lives in a second,
**slot-indexed** store: per such layer a tuple of ``[num_slots, *shape]``
arrays (:attr:`KVArena.slot_state`), the lane a request decodes in being its
index. Admission still counts blocks only: a lane IS its state's
allocation. A lane's state is started from zeros and written by the prefill
that admits a request to it, advanced in place by the decode step, left as
it is while the lane is inactive, and never read by another lane.
:meth:`bytes_total` stays the paged pools alone; :meth:`state_bytes_total`
is the store's.

Counters (``arena.*`` in ``serving.metrics``): allocs, frees, reuse (a taken
block that had been used before — the free list working), alloc failures,
high-water blocks in use.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from ..core import flags
from . import metrics


class ArenaExhaustedError(RuntimeError):
    """No free (unreserved) blocks left for the requested budget — arena
    *pressure*: more load than capacity right now. The scheduler reacts with
    admission gating and (under starvation) preemption."""


class ReservationExhaustedError(ArenaExhaustedError):
    """A request tried to ``take()`` past its own admission-time budget —
    this request *under-reserved*, which is a bug in the caller's block
    accounting, not arena pressure. Kept distinct from
    :class:`ArenaExhaustedError` so supervisor/preemption logic never
    confuses "this request is broken" with "the arena is full" (preempting
    victims cannot heal an under-reservation)."""


@dataclass
class Reservation:
    """A request's admission-time block budget. ``take()`` converts one
    reserved block into a physical block id; ``release()`` returns every
    taken block to the free list and drops the unused remainder."""

    arena: "KVArena"
    total: int
    taken: List[int] = field(default_factory=list)
    released: bool = False

    def remaining(self) -> int:
        return self.total - len(self.taken)

    def take(self) -> int:
        if self.released:
            raise RuntimeError("reservation already released")
        if self.remaining() <= 0:
            raise ReservationExhaustedError(
                f"reservation exhausted: all {self.total} budgeted blocks "
                f"already taken ({len(self.taken)} taken) — the request "
                "under-reserved at admission")
        blk = self.arena._pop_block()
        self.taken.append(blk)
        return blk

    def release(self) -> None:
        if self.released:
            return
        self.released = True
        self.arena._release(self)


class KVArena:
    """The fixed paged KV storage + its free-list allocator.

    ``num_blocks`` INCLUDES the reserved scratch block 0; allocatable
    capacity is ``num_blocks - 1`` blocks of ``block_size`` tokens each.
    Pools are jax arrays and are *replaced* after every compiled step (the
    engine donates them into the step under ``FLAGS_decode_donate``, so the
    previous arrays are dead the moment the step runs).
    """

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_blocks: int, block_size: Optional[int] = None,
                 dtype: str = "float32", quantized: bool = False,
                 mesh=None, num_slots: int = 0, slot_state=(),
                 latent_width: int = 0, index_width: int = 0):
        """``slot_state``: per recurrent layer, its
        ``((name, per-lane shape, dtype), ...)``; ``num_slots`` lanes each.
        ``latent_width`` > 0: the ``num_layers`` pools are latent ones, ONE
        row of that many values a token (``num_heads``, ``head_dim``
        unused): each entry a 1-tuple ``(rows,)``, see
        :meth:`_fresh_latent`. ``index_width`` > 0: ONE row of that many
        values a token BESIDE its K and V rows (a ``"sparse"`` layer's
        index keys): each entry the 3-tuple ``(k, v, index_rows)``, see
        :meth:`_fresh_index`. Not both."""
        import jax.numpy as jnp

        # mesh-sharded pools (ISSUE 14): every pool entry — primary and
        # namespace alike — is committed via sharding_util.shard_kv_entry
        # (K/V payload heads-sharded over "model", scale pools
        # replicated). The engine passes its captured mesh through
        # _arena_args, so a supervisor rebuild reconstructs the SAME
        # placement (same shardings => zero recompiles). All allocator /
        # refcount / COW bookkeeping below is host-side numpy and never
        # sees the layout. None = single-chip, byte-identical to PR 13.
        self.mesh = mesh
        self.block_size = int(block_size or flags.flag("kv_block_size"))
        if self.block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the scratch sink)")
        self.num_blocks = int(num_blocks)
        self.num_layers = int(num_layers)
        # `dtype` stays the LOGICAL (compute) dtype; with `quantized` the
        # physical k/v payload is int8 and each per-layer pool entry grows
        # per-block scale pools: (k, v) -> (k, v, k_scale, v_scale), with
        # scales shaped [num_blocks, block_size] float32 (one symmetric
        # scale per token row of each block). The 4-tuple travels as one
        # unit through pools()/set_pools()/namespaces/donation/COW — a
        # consumer that copies or adopts K/V without its scales cannot
        # exist structurally (check_invariants audits the entry shape).
        self.dtype = dtype
        self.quantized = bool(quantized)
        self.latent_width = int(latent_width)
        #: values of the index key a token keeps beside its K and V rows
        self.index_width = int(index_width)
        if self.latent_width and self.index_width:
            raise ValueError("a pool set holds latent rows alone or index "
                             "rows beside K and V, not both")
        if (self.latent_width or self.index_width) and (
                self.quantized or mesh is not None):
            raise ValueError("a latent pool and an index pool have no int8 "
                             "form (quant_kv) and no heads to shard over a "
                             "mesh")
        self._pools: List[Tuple] = [
            self._fresh_latent(jnp) if self.latent_width
            else self._fresh_entry(jnp, num_heads, head_dim)
            + self._fresh_index(jnp)
            for _ in range(num_layers)]
        if self.index_width:
            metrics.set_gauge("arena.index_bytes", sum(
                int(e[2].size) * e[2].dtype.itemsize for e in self._pools))
        # LIFO: churny workloads keep re-taking the most recently freed
        # blocks (cache-friendly, and makes reuse observable)
        self._free: List[int] = list(range(1, self.num_blocks))
        self._reserved = 0
        self._ever_used: set = set()
        self._high_water = 0
        # refcounted sharing (prefix cache): per-block reference counts,
        # the set of blocks resident in the radix cache at refcount zero,
        # and the cache itself (bound by PrefixCache.__init__) as the
        # eviction authority reserve() turns to under pressure
        self._refs: List[int] = [0] * self.num_blocks
        self._cached: set = set()
        self._cache = None
        # named pool namespaces (speculative decoding's draft cache): a
        # second per-layer pool set addressed by the SAME block ids and the
        # same free-list/refcount accounting — a block taken for a slot's
        # draft table is one allocation like any other, it just indexes a
        # different physical pool. Namespace shapes may differ from the
        # primary's (a draft model has its own layers/heads/head_dim).
        self._ns_pools: dict = {}
        self._ns_shapes: dict = {}
        # the slot-indexed store of the recurrent layers' state
        self._slot_state: List[Tuple] = [
            tuple(jnp.zeros((int(num_slots),) + tuple(shape), dtype)
                  for _, shape, dtype in arrays)
            for arrays in slot_state]

    # ------------------------------------------------------------- pools

    def _fresh_entry(self, jnp, num_heads: int, head_dim: int,
                     quantized: Optional[bool] = None,
                     dtype: Optional[str] = None) -> Tuple:
        """One layer's zeroed pool entry: ``(k, v)`` full-precision, or
        ``(k, v, k_scale, v_scale)`` int8 + per-block-row scales."""
        quantized = self.quantized if quantized is None else quantized
        dtype = dtype or self.dtype
        shape = (self.num_blocks, self.block_size, int(num_heads),
                 int(head_dim))
        if not quantized:
            entry = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        else:
            sshape = (self.num_blocks, self.block_size)
            entry = (jnp.zeros(shape, "int8"), jnp.zeros(shape, "int8"),
                     jnp.zeros(sshape, "float32"),
                     jnp.zeros(sshape, "float32"))
        if self.mesh is None:
            return entry
        from ..distributed.sharding_util import shard_kv_entry

        return shard_kv_entry(entry, self.mesh)

    def _fresh_latent(self, jnp) -> Tuple:
        """One latent layer's zeroed pool entry ``(rows,)``: ``[num_blocks,
        block_size, W]`` values in the compute dtype, kept as
        ``[num_blocks, block_size / pack, pack * W]`` (the same bytes in
        the same order): ``pack`` consecutive tokens share a pool row so
        that its lanes fill whole tiles on the chip
        (:func:`paddle_tpu.ops.paged_attention.latent_pack`)."""
        return (self._packed_rows(jnp, self.latent_width),)

    def _fresh_index(self, jnp) -> Tuple:
        """A ``"sparse"`` layer's zeroed index pool, behind its ``(k, v)``:
        ``(index_rows,)``, one key of :attr:`index_width` values a token,
        packed as a latent pool is; ``()`` for any other layer."""
        return ((self._packed_rows(jnp, self.index_width),)
                if self.index_width else ())

    def _packed_rows(self, jnp, width: int):
        from ..ops.paged_attention import latent_pack

        pack = latent_pack(width)
        if self.block_size % pack:
            raise ValueError(f"kv_block_size {self.block_size} does not "
                             f"hold whole rows of {pack} tokens of {width}")
        return jnp.zeros((self.num_blocks, self.block_size // pack,
                          pack * width), self.dtype)

    @property
    def pools(self) -> List[Tuple]:
        return self._pools

    def kernel_layout(self) -> dict:
        """The block-table/pool layout contract the Pallas paged kernels
        (:mod:`paddle_tpu.ops.paged_attention`) compile against — stated
        once, next to the arrays it describes:

        * per-layer pool entries are ``(k, v)`` arrays shaped
          ``[num_blocks, block_size, heads, head_dim]`` in the compute
          dtype, or int8 ``(k, v, k_scale, v_scale)`` with ``float32``
          ``[num_blocks, block_size]`` per-token-row scale pools;
        * a block table is int32, indexes pool axis 0, and row 0 is the
          scratch sink (masked/padded writes land there, so a kernel may
          read any table entry without validity checks — garbage rows are
          masked by position, never out of bounds);
        * tables, positions and prefix lengths are runtime data: a kernel
          keyed on this layout is keyed on shapes only, so admit/retire/
          accept/reject churn never re-lowers it.

        Returns the shape facts (``num_blocks``, ``block_size``,
        ``quantized``, ``dtype``, ``scratch_block``) kernels and benches
        size their launches from."""
        return {"num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "quantized": self.quantized,
                "dtype": self.dtype,
                "scratch_block": 0,
                "mesh": self.mesh_key()}

    def set_pools(self, pools) -> None:
        """Adopt the pool arrays returned by a compiled step (the old ones
        were donated into it and are no longer valid)."""
        self._pools = list(pools)

    @property
    def slot_state(self) -> List[Tuple]:
        """Per recurrent layer, its ``[num_slots, ...]`` state arrays."""
        return self._slot_state

    def set_slot_state(self, state) -> None:
        """Adopt the store a compiled call handed back (donation contract
        identical to :meth:`set_pools`)."""
        self._slot_state = [tuple(entry) for entry in state]

    def state_bytes_total(self) -> int:
        """Bytes of the slot-indexed store (not part of
        :meth:`bytes_total`, which is the paged pools')."""
        return sum(int(a.size) * a.dtype.itemsize
                   for entry in self._slot_state for a in entry)

    def add_namespace(self, name: str, num_layers: int, num_heads: int,
                      head_dim: int, dtype: Optional[str] = None,
                      quantized: Optional[bool] = None) -> None:
        """Create a named secondary pool set over the same block ids (the
        speculative decoder's draft KV cache). Shares the allocator: a
        block id taken from the free list is simultaneously valid in every
        namespace — the engine decides which namespace a given slot table
        actually writes. ``quantized`` defaults to the arena's own mode
        (an int8 arena quantizes its draft namespace too, scale pools
        included). Idempotent per name only via :meth:`rebuild`-style
        reconstruction (adding an existing name raises)."""
        import jax.numpy as jnp

        if name in self._ns_pools:
            raise ValueError(f"namespace {name!r} already exists")
        dtype = dtype or self.dtype
        quantized = self.quantized if quantized is None else bool(quantized)
        self._ns_pools[name] = [
            self._fresh_entry(jnp, num_heads, head_dim,
                              quantized=quantized, dtype=dtype)
            for _ in range(int(num_layers))]
        self._ns_shapes[name] = (int(num_layers), int(num_heads),
                                 int(head_dim), dtype, quantized)

    def ns_pools(self, name: str) -> List[Tuple]:
        return self._ns_pools[name]

    def set_ns_pools(self, name: str, pools) -> None:
        """Adopt a namespace's pool arrays after a compiled step (donation
        contract identical to :meth:`set_pools`)."""
        if name not in self._ns_pools:
            raise KeyError(f"unknown namespace {name!r}")
        self._ns_pools[name] = list(pools)

    def namespaces(self) -> List[str]:
        return list(self._ns_pools)

    # -------------------------------------------------------- allocation

    def blocks_free(self) -> int:
        return len(self._free)

    def blocks_in_use(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    def blocks_cached(self) -> int:
        """Blocks resident in the prefix cache (in use, but reclaimable)."""
        return len(self._cached)

    def grantable(self) -> int:
        """Blocks a new reservation could claim right now: the free list
        minus the untaken remainder of outstanding reservations, plus
        whatever the prefix cache could evict — cached prefixes are a
        best-effort extension of the free list, never a competitor."""
        n = len(self._free) - self._reserved
        if self._cache is not None:
            n += self._cache.evictable_blocks()
        return n

    def can_reserve(self, n: int) -> bool:
        return self.grantable() >= n

    def reserve(self, n: int) -> Reservation:
        """Claim a worst-case budget of ``n`` blocks (none taken yet).
        When the free list alone cannot cover it, cold cached prefixes are
        evicted (LRU leaves first) to make room — eviction happens only
        here, where it would otherwise be an admission failure."""
        n = int(n)
        short = n - (len(self._free) - self._reserved)
        if (short > 0 and self._cache is not None
                and short <= self._cache.evictable_blocks()):
            # feasibility first: a doomed reservation must not flush the
            # cache on its way to raising anyway
            self._cache.evict(short)
        if len(self._free) - self._reserved < n:
            metrics.bump("arena.alloc_failed")
            raise ArenaExhaustedError(
                f"cannot reserve {n} blocks "
                f"({len(self._free)} free, {self._reserved} already reserved)")
        self._reserved += n
        return Reservation(self, n)

    def _pop_block(self) -> int:
        if not self._free:
            metrics.bump("arena.alloc_failed")
            raise ArenaExhaustedError("free list empty")
        blk = self._free.pop()
        self._reserved -= 1
        self._refs[blk] = 1
        metrics.bump("arena.alloc")
        if blk in self._ever_used:
            metrics.bump("arena.reuse")
        self._ever_used.add(blk)
        self._high_water = max(self._high_water, self.blocks_in_use())
        return blk

    def _release(self, res: Reservation) -> None:
        self._reserved -= res.remaining()
        for blk in res.taken:
            self.deref(blk)
        res.taken = []

    def take_cached_block(self) -> int:
        """Pop one free block for a tier restore (``serving.tiered``):
        the block starts at refcount ZERO with cache residency — after the
        restore scatter it is indistinguishable from any resident prefix
        block (admissions ``ref`` it, retire ``deref``s it back to cached
        residency, eviction can spill it again). Outside the reservation
        system by design, but it must never eat into outstanding
        reservations' guaranteed ``take()`` headroom; under pressure it
        evicts cold cached prefixes exactly like :meth:`reserve`."""
        short = 1 - (len(self._free) - self._reserved)
        if (short > 0 and self._cache is not None
                and short <= self._cache.evictable_blocks()):
            self._cache.evict(short)
        if len(self._free) - self._reserved < 1:
            metrics.bump("arena.alloc_failed")
            raise ArenaExhaustedError(
                "no free block for a tier restore "
                f"({len(self._free)} free, {self._reserved} reserved)")
        blk = self._free.pop()
        self._refs[blk] = 0
        self._cached.add(blk)
        metrics.bump("arena.alloc")
        if blk in self._ever_used:
            metrics.bump("arena.reuse")
        self._ever_used.add(blk)
        self._high_water = max(self._high_water, self.blocks_in_use())
        if self._cache is not None:
            self._cache.invalidate()
        return blk

    def read_block(self, blk: int):
        """Host copy of one physical block's rows across every PRIMARY
        pool layer — the spill payload of ``serving.tiered`` (the prefix
        cache only ever covers the primary namespace; draft blocks are
        private). Every array of each entry is read, so an int8 arena's
        payload and its per-row scales travel as one unit. On a device
        mesh ``np.asarray`` re-assembles the committed shards host-side;
        the restore scatter re-commits them through the pool's own
        sharding, so a rebuild on the same ``mesh_axes_key`` reproduces
        identical placements."""
        import numpy as np

        return [tuple(np.asarray(arr[blk]) for arr in entry)
                for entry in self._pools]

    # --------------------------------------------------- refcount / cache

    def bind_cache(self, cache) -> None:
        """Adopt a :class:`~.prefix_cache.PrefixCache` as this arena's
        eviction authority (called by the cache's constructor)."""
        self._cache = cache

    def refcount(self, blk: int) -> int:
        return self._refs[blk]

    def ref(self, blk: int) -> None:
        """Attach one more reference to a live or cached block (a slot
        sharing a resident prefix block)."""
        if blk <= 0 or (self._refs[blk] == 0 and blk not in self._cached):
            raise RuntimeError(
                f"ref() on block {blk} which is neither live nor cached")
        self._refs[blk] += 1
        # only the 0 -> 1 transition can change evictability
        if self._refs[blk] == 1 and self._cache is not None:
            self._cache.invalidate()

    def deref(self, blk: int) -> None:
        """Drop one reference; at refcount zero the block returns to the
        free list — unless the prefix cache holds it resident, in which
        case it stays allocated (reclaimable by eviction) so its KV
        content survives for future admissions to share."""
        if self._refs[blk] <= 0:
            raise RuntimeError(f"deref() on block {blk} with refcount 0 — "
                               "double free in the caller's accounting")
        self._refs[blk] -= 1
        # only the 1 -> 0 transition can change evictability
        if self._refs[blk] == 0 and self._cache is not None:
            self._cache.invalidate()
        if self._refs[blk] == 0 and blk not in self._cached:
            self._free.append(blk)
            metrics.bump("arena.freed")

    def mark_cached(self, blk: int) -> None:
        """The prefix cache took residency of ``blk``: at refcount zero it
        is retained (not freed) until evicted."""
        self._cached.add(blk)

    def uncache(self, blk: int) -> None:
        """The prefix cache evicted ``blk``: if no slot still references
        it, it returns to the free list now."""
        if blk not in self._cached:
            raise RuntimeError(f"uncache() on block {blk} that is not "
                               "cached — double eviction in the caller's "
                               "accounting")
        self._cached.discard(blk)
        if self._refs[blk] == 0:
            self._free.append(blk)
            metrics.bump("arena.freed")

    def check_invariants(self, tables=None) -> None:
        """Audit the refcount layer (flag-gated; on in tests). Free-list
        blocks must be refcount-zero and uncached; ``tables`` — an
        iterable of per-slot block-id lists for ACTIVE slots — must
        reference each block exactly ``refcount`` times (a block id in two
        slots' tables is legal only when its refcount says so)."""
        # structural audit of the quantized pool entries: adopted pools
        # (set_pools after a compiled step, COW, rebuild) must carry their
        # scale pools — K/V copied without scales is silent corruption
        for name, pools in [("primary", self._pools)] + [
                (n, p) for n, p in self._ns_pools.items()]:
            if name == "primary":
                quantized = self.quantized
            else:
                quantized = self._ns_shapes[name][4]
            want = 4 if quantized else 2
            if name == "primary" and self.latent_width:
                want = 1
            if name == "primary" and self.index_width:
                want = 3  # neither (k, v) nor (k, v, k_scale, v_scale)
            for li, entry in enumerate(pools):
                if len(entry) != want:
                    raise RuntimeError(
                        f"invariant violated: {name} pool entry {li} has "
                        f"{len(entry)} arrays (expected {want}) — a "
                        "quantized pool was adopted without its scales, or "
                        "a sparse layer's without its index keys")
                if quantized and tuple(entry[2].shape) != (
                        self.num_blocks, self.block_size):
                    raise RuntimeError(
                        f"invariant violated: {name} scale pool {li} shape "
                        f"{tuple(entry[2].shape)} != "
                        f"{(self.num_blocks, self.block_size)}")
        if len(self._free) != len(set(self._free)):
            raise RuntimeError(
                "invariant violated: duplicate block id on the free list")
        for blk in self._free:
            if self._refs[blk] != 0:
                raise RuntimeError(
                    f"invariant violated: free block {blk} has refcount "
                    f"{self._refs[blk]}")
            if blk in self._cached:
                raise RuntimeError(
                    f"invariant violated: free block {blk} is marked cached")
        if tables is not None:
            counts: dict = {}
            for table in tables:
                for blk in table:
                    counts[blk] = counts.get(blk, 0) + 1
            for blk, n in counts.items():
                if blk != 0 and self._refs[blk] != n:
                    raise RuntimeError(
                        f"invariant violated: block {blk} appears in {n} "
                        f"slot table entries but has refcount "
                        f"{self._refs[blk]}")

    # ------------------------------------------------------------- stats

    @staticmethod
    def _pool_bytes(pools) -> Tuple[int, int]:
        """(kv payload bytes, scale-pool bytes) of one pool set.
        ``.dtype.itemsize`` is host metadata (works for ml_dtypes bf16 and
        int8 alike): stats()/gauges poll this — it must never allocate on
        the device."""
        kv = scale = 0
        for entry in pools:
            for i, arr in enumerate(entry):
                per = 1
                for d in arr.shape:
                    per *= int(d)
                b = per * arr.dtype.itemsize
                if i < 2 or len(entry) == 3:  # (k, v, index keys): payload
                    kv += b
                else:
                    scale += b
        return kv, scale

    def bytes_total(self) -> int:
        """All pool bytes — K/V payload PLUS scale pools, every namespace.
        The equal-memory comparisons (the >=1.9x-slots acceptance gate,
        the --quantized bench) budget against this number, so the scale
        overhead is never hidden."""
        total = 0
        for pools in [self._pools] + list(self._ns_pools.values()):
            kv, scale = self._pool_bytes(pools)
            total += kv + scale
        return total

    def bytes_by_namespace(self) -> dict:
        """Per-namespace byte/dtype breakdown: ``{name: {kv_bytes,
        scale_bytes, bytes, dtype, quantized}}`` with the primary pools
        under ``"primary"`` — the observable form of the quantized-arena
        memory win (tools/serving_stats.py --run, EnginePredictor.close)."""
        out = {}

        def record(name, pools, dtype, quantized):
            kv, scale = self._pool_bytes(pools)
            out[name] = {"kv_bytes": kv, "scale_bytes": scale,
                         "bytes": kv + scale,
                         "dtype": "int8" if quantized else dtype,
                         "quantized": bool(quantized)}

        record("primary", self._pools, self.dtype, self.quantized)
        for name, pools in self._ns_pools.items():
            _, _, _, dtype, quantized = self._ns_shapes[name]
            record(name, pools, dtype, quantized)
        return out

    def mesh_key(self):
        """The arena's mesh fingerprint (None single-chip) — part of every
        consumer's program-key story, surfaced next to the shape facts."""
        from ..distributed.sharding_util import mesh_axes_key

        return mesh_axes_key(self.mesh) if self.mesh is not None else None

    def stats(self) -> dict:
        return {
            "blocks_total": self.num_blocks - 1,
            "blocks_free": self.blocks_free(),
            "blocks_in_use": self.blocks_in_use(),
            "blocks_reserved": self._reserved,
            "blocks_cached": self.blocks_cached(),
            "high_water": self._high_water,
            "block_size": self.block_size,
            "kv_bytes": self.bytes_total(),
            "quantized": self.quantized,
            "bytes_by_namespace": self.bytes_by_namespace(),
            "namespaces": len(self._ns_pools),
            "mesh": self.mesh_key(),
        }
