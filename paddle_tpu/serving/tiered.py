"""Tiered KV cache: host-RAM / on-disk spill tiers under the paged arena.

At millions of users the working set of shared prompt prefixes dwarfs one
device arena. Before this module, a refcount-zero cached block evicted
under pressure was simply freed — its prefill was paid again in full on
the next hit. The radix prefix cache's content hashes
(``hash(parent_hash, chunk_tokens)`` — :mod:`.prefix_cache`) are
*location-independent*, which makes memory tiering natural: the same key
that names a device-resident block can name its spilled copy in host RAM
or on disk.

The hierarchy (HBM -> host RAM -> disk):

* **Spill** — when :meth:`PrefixCache.evict` must reclaim a cold block,
  its pool rows (EVERY array of the per-layer entry: the int8 payload and
  its per-row scale pools travel as one unit) are copied host-side
  (``KVArena.read_block``) into the :class:`HostKVCache`, and the radix
  node stays in the tree marked *spilled* instead of being removed.
* **Host tier** — an LRU dict under ``FLAGS_serving_host_cache_bytes``.
  Insertions are also *written through* here at radix-insert time, so a
  prefix prefilled on gateway replica A is a host-tier hit on replica B:
  every engine attaches to ONE shared ``HostKVCache``
  (:func:`get_tier_store`, or an explicit ``ServingConfig.tier_store``).
* **Disk tier** — LRU overflow lands in ``FLAGS_serving_disk_cache_dir``
  as atomic tmp+rename files with a crc32 header; a corrupt or truncated
  file is deleted and reads as a miss, so the worst case is always
  *recompute*, never garbage KV. Because the files are content-addressed
  they survive the process: a restarted server re-scans the directory and
  serves warm.
* **Restore** — a radix hit on a spilled node takes a fresh arena block
  (cached, refcount zero — indistinguishable from any resident prefix
  block thereafter) and scatters the host rows into it through ONE
  compiled program (``ServingEngine._get_restore``; the ``_cow_copy``
  gather/scatter is the template: the destination block id is runtime
  data, so every restore of every block reuses the same executable —
  zero new compiles, trace-asserted via ``restore_traces``).

Entries are namespaced by an arena *signature* (layers/heads/head_dim/
block_size/dtype/quantized/mesh fingerprint — :class:`TierView`), so
engines serving different models or meshes can share one store without
ever restoring incompatible bytes. On a device mesh the spilled rows are
the committed shards re-assembled host-side (``np.asarray`` gathers), and
the restore scatter re-commits them through the pool's own sharding — a
rebuild on the same ``mesh_axes_key`` gets identical placements.

Counters/gauges (``tier.*`` in ``serving.metrics``, mirrored namespace in
``core.resilience``): ``spilled_blocks`` / ``spilled_bytes`` /
``restored_blocks`` / ``restored_bytes``, per-tier ``host_hits`` /
``disk_hits`` / ``misses`` (a spilled node whose entry was lost),
``host_evictions`` / ``host_drops`` / ``disk_writes`` / ``disk_corrupt``,
and the occupancy gauges ``host_bytes`` / ``host_entries`` /
``disk_bytes`` / ``disk_entries``.
"""
from __future__ import annotations

import hashlib
import io
import os
import struct
import threading
import zlib
from collections import OrderedDict
from typing import Callable, List, Optional, Tuple

import numpy as np

from ..core import flags, resilience
from . import metrics, telemetry

#: disk entry layout: MAGIC + 4-byte little-endian crc32(body) + body,
#: where body is an ``np.savez`` archive of the entry's arrays
_MAGIC = b"PTKV1\n"

#: a spilled block's payload: one tuple per layer, each tuple holding the
#: block's rows of every pool array — ``(k, v)`` or ``(k, v, ks, vs)``
Payload = List[Tuple[np.ndarray, ...]]


def _payload_bytes(payload: Payload) -> int:
    return sum(arr.nbytes for entry in payload for arr in entry)


def _pack(payload: Payload) -> bytes:
    """Serialize a payload to the on-disk body (structure rides as two
    scalar arrays so loading needs no side-channel metadata)."""
    arrays = {"layers": np.int64(len(payload)),
              "arrs": np.int64(len(payload[0]))}
    for li, entry in enumerate(payload):
        for ai, arr in enumerate(entry):
            arrays[f"l{li}a{ai}"] = arr
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def _unpack(body: bytes) -> Payload:
    with np.load(io.BytesIO(body), allow_pickle=False) as z:
        layers = int(z["layers"])
        arrs = int(z["arrs"])
        return [tuple(z[f"l{li}a{ai}"] for ai in range(arrs))
                for li in range(layers)]


class DiskTier:
    """Content-addressed spill files under one directory.

    Writes are atomic (tmp in the same directory + ``os.replace``) and
    every file carries a crc32 of its body: a load that fails the check —
    torn write, bit rot, truncation — deletes the file and returns None,
    so the caller recomputes instead of serving corrupt KV. The directory
    is re-scanned at construction (oldest-first by mtime), which is what
    makes the tier survive both arena rebuilds and full process restarts
    (warm-cache replay). Bounded by ``max_bytes``
    (``FLAGS_serving_disk_cache_bytes``): past the budget the
    oldest-written entries are deleted, so a churning working set can
    never fill the disk. A write that fails anyway (ENOSPC, dead disk)
    degrades that entry to a miss and is COUNTED
    (``tier.disk_write_failed``) — the tier never fails an admission,
    but it never degrades invisibly either.

    The lock guards only the ``_sizes`` index; file reads, writes, and
    (de)serialization run outside it — the files are content-addressed
    and replaced atomically, so concurrent writers of one key produce
    identical bytes and a slow disk never stalls another replica's
    restore path."""

    def __init__(self, root: str, max_bytes: Optional[int] = None):
        if max_bytes is None:
            max_bytes = int(flags.flag("serving_disk_cache_bytes"))
        self.root = root
        self.max_bytes = int(max_bytes)
        os.makedirs(root, exist_ok=True)
        self._lock = threading.Lock()
        self._sizes: "OrderedDict[str, int]" = OrderedDict()
        found = []
        for name in os.listdir(root):
            if name.endswith(".kv"):
                try:
                    st = os.stat(os.path.join(root, name))
                    found.append((st.st_mtime, name, st.st_size))
                except OSError:
                    pass
        for _, name, size in sorted(found):
            self._sizes[name] = size
        self._publish()

    def _name(self, key: bytes) -> str:
        return key.hex() + ".kv"

    def _publish(self) -> None:
        # caller holds self._lock
        metrics.set_gauge("tier.disk_entries", len(self._sizes))
        metrics.set_gauge("tier.disk_bytes", sum(self._sizes.values()))

    def has(self, key: bytes) -> bool:
        name = self._name(key)
        with self._lock:
            if name in self._sizes:
                return True
        # cross-process adoption (disagg handoff): another worker may
        # have published this content hash into the shared directory
        # after our construction scan — a miss in the in-memory index is
        # only authoritative for what THIS process wrote, so fall back
        # to a stat and adopt the file (content-addressed + atomically
        # replaced, so an existing path is always a complete entry)
        try:
            size = os.stat(os.path.join(self.root, name)).st_size
        except OSError:
            return False
        with self._lock:
            if name not in self._sizes:
                self._sizes[name] = size
                self._publish()
        return True

    def put(self, key: bytes, payload: Payload) -> None:
        body = _pack(payload)
        blob = _MAGIC + struct.pack("<I", zlib.crc32(body)) + body
        name = self._name(key)
        path = os.path.join(self.root, name)
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)  # atomic: readers never see a torn file
        except OSError:
            # a full/broken disk degrades the tier to a miss, never an
            # admission failure — but counted, so the decaying hit rate
            # is explicable from the dashboards
            metrics.bump("tier.disk_write_failed")
            resilience.bump("tier.disk_write_failed")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return
        evict = []
        with self._lock:
            self._sizes.pop(name, None)
            self._sizes[name] = len(blob)  # newest last
            metrics.bump("tier.disk_writes")
            total = sum(self._sizes.values())
            while total > self.max_bytes and len(self._sizes) > 1:
                victim, vsize = self._sizes.popitem(last=False)
                total -= vsize
                evict.append(victim)
            self._publish()
        for victim in evict:
            metrics.bump("tier.disk_evictions")
            try:
                os.unlink(os.path.join(self.root, victim))
            except OSError:
                pass

    def get(self, key: bytes) -> Optional[Payload]:
        name = self._name(key)
        path = os.path.join(self.root, name)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            with self._lock:
                self._sizes.pop(name, None)
                self._publish()
            return None
        ok = (blob[:len(_MAGIC)] == _MAGIC and len(blob) >= len(_MAGIC) + 4
              and struct.unpack(
                  "<I", blob[len(_MAGIC):len(_MAGIC) + 4])[0]
              == zlib.crc32(blob[len(_MAGIC) + 4:]))
        if ok:
            try:
                payload = _unpack(blob[len(_MAGIC) + 4:])
            except (OSError, ValueError, KeyError):
                ok = False
        if not ok:
            # crc/format mismatch: delete the entry and miss — the
            # caller falls back to recompute instead of serving
            # whatever bytes landed on disk
            metrics.bump("tier.disk_corrupt")
            resilience.bump("tier.disk_corrupt")
            try:
                os.unlink(path)
            except OSError:
                pass
            with self._lock:
                self._sizes.pop(name, None)
                self._publish()
            return None
        return payload

    def drop(self, key: bytes) -> None:
        name = self._name(key)
        try:
            os.unlink(os.path.join(self.root, name))
        except OSError:
            pass
        with self._lock:
            self._sizes.pop(name, None)
            self._publish()

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._sizes),
                    "bytes": sum(self._sizes.values()),
                    "budget_bytes": self.max_bytes}


class HostKVCache:
    """The shared host-RAM tier: an LRU byte-budgeted dict of spilled
    block payloads, overflowing to an optional :class:`DiskTier`.

    ONE instance is shared by every engine that participates in tiering
    (gateway replicas attach to the same store — that is what turns a
    prefill on replica A into a host-tier hit on replica B). Thread-safe:
    replicas pump on their own threads. Keys arrive already namespaced by
    the owning :class:`TierView`'s arena signature, so incompatible
    engines can coexist in one store without aliasing."""

    def __init__(self, max_bytes: Optional[int] = None,
                 disk_dir: Optional[str] = None):
        if max_bytes is None:
            max_bytes = int(flags.flag("serving_host_cache_bytes"))
        if disk_dir is None:
            disk_dir = str(flags.flag("serving_disk_cache_dir"))
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._host: "OrderedDict[bytes, Payload]" = OrderedDict()
        self._bytes = 0
        self.disk = DiskTier(disk_dir) if disk_dir else None

    # ------------------------------------------------------------- lookup

    def has(self, key: bytes) -> bool:
        """Residency probe (no LRU touch, no load): host or disk."""
        with self._lock:
            if key in self._host:
                return True
        return self.disk.has(key) if self.disk is not None else False

    def tier_of(self, key: bytes) -> Optional[str]:
        """Which tier holds ``key`` right now: 'host', 'disk', or None."""
        with self._lock:
            if key in self._host:
                return "host"
        if self.disk is not None and self.disk.has(key):
            return "disk"
        return None

    def get(self, key: bytes):
        """Load a payload for restore: ``(payload, tier)`` or
        ``(None, None)`` on a miss (entry dropped, or disk corruption —
        counted, and the caller recomputes). A disk hit is promoted back
        into the host tier (it is about to be hot again)."""
        with self._lock:
            payload = self._host.get(key)
            if payload is not None:
                self._host.move_to_end(key)
                return payload, "host"
        if self.disk is not None:
            payload = self.disk.get(key)
            if payload is not None:
                self._insert(key, payload)
                return payload, "disk"
        return None, None

    # ------------------------------------------------------------- insert

    def put(self, key: bytes, payload: Payload) -> None:
        self._insert(key, payload)

    def ensure(self, key: bytes, reader: Callable[[], Payload],
               publish: bool = False) -> int:
        """Make sure ``key`` is resident in SOME tier; ``reader`` is only
        called (one device->host copy) when it is not — the write-through
        at insert time usually means a later spill finds the bytes
        already here. Returns the bytes actually written (0 = present).

        ``publish=True`` additionally guarantees the bytes reach the
        DISK tier now (not just on host-LRU overflow): disaggregated
        prefill workers publish each finished block so decode workers in
        OTHER processes — which share only the disk directory, never this
        host dict — can restore the chain. No-op without a disk tier."""
        payload = None
        with self._lock:
            payload = self._host.get(key)
            if payload is not None:
                self._host.move_to_end(key)
        on_disk = self.disk is not None and self.disk.has(key)
        if payload is None and not on_disk:
            payload = reader()
            self._insert(key, payload)
            written = _payload_bytes(payload)
        else:
            written = 0
        if (publish and self.disk is not None and not on_disk
                and payload is not None):
            self.disk.put(key, payload)
            metrics.bump("tier.published_blocks")
        return written

    def _insert(self, key: bytes, payload: Payload) -> None:
        with self._lock:
            old = self._host.pop(key, None)
            if old is not None:
                self._bytes -= _payload_bytes(old)
            self._host[key] = payload
            self._bytes += _payload_bytes(payload)
            # choose LRU victims WITHOUT removing them yet: they must
            # stay host-readable until their bytes are safely on disk,
            # or a concurrent lookup in the handoff window would miss
            # BOTH tiers and the engine would prune a perfectly
            # restorable chain (the host stays transiently over budget
            # by the in-flight victims instead — bounded and harmless)
            victims = []
            excess = self._bytes - self.max_bytes
            for k, v in self._host.items():
                if excess <= 0 or len(self._host) - len(victims) <= 1:
                    break
                if k == key:
                    continue
                victims.append((k, v))
                excess -= _payload_bytes(v)
        # disk writes happen outside the host lock: a slow disk must not
        # stall every replica's spill/restore path behind one flush
        if self.disk is not None:
            for k, v in victims:
                self.disk.put(k, v)
        with self._lock:
            for k, v in victims:
                if self._host.get(k) is v:  # a concurrent _insert may
                    del self._host[k]       # have evicted or replaced it
                    self._bytes -= _payload_bytes(v)
                    metrics.bump("tier.host_evictions")
                    if self.disk is None:
                        metrics.bump("tier.host_drops")
            metrics.set_gauge("tier.host_entries", len(self._host))
            metrics.set_gauge("tier.host_bytes", self._bytes)

    def drop(self, key: bytes) -> None:
        with self._lock:
            payload = self._host.pop(key, None)
            if payload is not None:
                self._bytes -= _payload_bytes(payload)
                metrics.set_gauge("tier.host_entries", len(self._host))
                metrics.set_gauge("tier.host_bytes", self._bytes)
        if self.disk is not None:
            self.disk.drop(key)

    def stats(self) -> dict:
        with self._lock:
            out = {"host_entries": len(self._host),
                   "host_bytes": self._bytes,
                   "host_budget_bytes": self.max_bytes}
        if self.disk is not None:
            d = self.disk.stats()
            out["disk_entries"] = d["entries"]
            out["disk_bytes"] = d["bytes"]
            out["disk_dir"] = self.disk.root
        return out


class TierView:
    """One engine's handle on a shared :class:`HostKVCache`.

    Namespaces every chunk key by the arena *signature* — layers, heads,
    head_dim, block_size, dtype, quantized mode, and the mesh fingerprint
    — so only byte-compatible engines can exchange entries, and carries
    the per-engine lifetime counters that ``EnginePredictor.close()`` and
    ``engine.stats()`` report (the module-global ``tier.*`` metrics
    aggregate across instances). The view survives ``engine.rebuild()``
    unchanged: the tiers are off-device by construction, which is what
    buys crash recovery its warm-cache replay."""

    def __init__(self, store: HostKVCache, signature: tuple):
        self.store = store
        self.signature = signature
        self._ns = hashlib.blake2b(repr(signature).encode(),
                                   digest_size=8).digest()
        # per-engine lifetime counters (process metrics are global)
        self.spilled_blocks = 0
        self.spilled_bytes = 0
        self.restored_blocks = 0
        self.restored_bytes = 0
        self.host_hits = 0
        self.disk_hits = 0
        self.misses = 0

    def _k(self, key: bytes) -> bytes:
        return self._ns + key

    def has(self, key: bytes) -> bool:
        return self.store.has(self._k(key))

    def tier_of(self, key: bytes) -> Optional[str]:
        return self.store.tier_of(self._k(key))

    def spill(self, key: bytes, reader: Callable[[], Payload]) -> None:
        """A device block is being evicted: make its bytes tier-resident
        (``reader`` runs only when the write-through copy is gone)."""
        with telemetry.phase("spill"):
            written = self.store.ensure(self._k(key), reader)
        self.spilled_blocks += 1
        self.spilled_bytes += written
        metrics.bump("tier.spilled_blocks")
        if written:
            metrics.bump("tier.spilled_bytes", written)

    def write_through(self, key: bytes, reader: Callable[[], Payload]) -> None:
        """Radix-insert publication: freshly prefilled full blocks land in
        the shared host tier so OTHER replicas (and a post-crash rebuild)
        can hit them while this replica still serves them from device.
        With ``FLAGS_serving_tier_publish`` the bytes also land on disk
        immediately — the cross-process handoff contract of the
        disaggregated prefill role (docs/serving.md)."""
        self.store.ensure(self._k(key), reader,
                          publish=bool(flags.flag("serving_tier_publish")))

    def lookup(self, key: bytes) -> Optional[Payload]:
        """Load for restore; None = the entry was lost (host LRU dropped
        it with no disk tier, or the disk copy failed its crc). Counts
        the per-tier hit/miss only — ``restored_*`` is counted by
        :meth:`note_restored` AFTER the scatter lands, so a restore
        truncated by arena pressure (payload loaded, no block taken)
        never inflates the restore counters."""
        payload, tier = self.store.get(self._k(key))
        if payload is None:
            self.misses += 1
            metrics.bump("tier.misses")
            return None
        if tier == "host":
            self.host_hits += 1
            metrics.bump("tier.host_hits")
        else:
            self.disk_hits += 1
            metrics.bump("tier.disk_hits")
        return payload

    def note_restored(self, payloads: List[Payload]) -> None:
        """The engine's restore scatter committed these payloads into
        fresh arena blocks — the ground truth the restore counters
        report."""
        if not payloads:
            return
        n = sum(_payload_bytes(p) for p in payloads)
        self.restored_blocks += len(payloads)
        self.restored_bytes += n
        metrics.bump("tier.restored_blocks", len(payloads))
        metrics.bump("tier.restored_bytes", n)

    def stats(self) -> dict:
        out = {"tier.spilled_blocks": self.spilled_blocks,
               "tier.spilled_bytes": self.spilled_bytes,
               "tier.restored_blocks": self.restored_blocks,
               "tier.restored_bytes": self.restored_bytes,
               "tier.host_hits": self.host_hits,
               "tier.disk_hits": self.disk_hits,
               "tier.misses": self.misses}
        out.update({f"tier.{k}": v for k, v in self.store.stats().items()
                    if isinstance(v, (int, float))})
        return out


_default_store: Optional[HostKVCache] = None
_default_lock = threading.Lock()


def get_tier_store() -> HostKVCache:
    """The process-global shared store (built once from
    ``FLAGS_serving_host_cache_bytes`` / ``FLAGS_serving_disk_cache_dir``).
    Every engine with ``FLAGS_serving_kv_tiering`` and no explicit
    ``ServingConfig.tier_store`` attaches here — which is exactly what
    gateway replicas need to share prefixes."""
    global _default_store
    with _default_lock:
        if _default_store is None:
            _default_store = HostKVCache()
        return _default_store


def reset_tier_store() -> None:
    """Drop the process-global store (tests; a fresh store re-reads the
    budget/dir flags)."""
    global _default_store
    with _default_lock:
        _default_store = None
