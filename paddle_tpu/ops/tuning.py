"""Shared kernel-tuning store: per-(kernel, chip, shape-bucket) records.

Generalizes the flash kernel's per-chip tile adoption
(:func:`paddle_tpu.ops.pallas_ops._tuned_blocks`) into one store every
Pallas kernel shares. A *record* is the best-measured launch parameters
(tile sizes, head grouping, ...) for one kernel at one shape bucket on one
chip generation:

    {"records": {"<device_kind>": {"<kernel>": {"<bucket>": {
        "params": {...}, "measured_us": ..., "baseline_us": ...}}}}}

* **kernel** — a stable name ("flash_fwd", "paged_decode",
  "paged_prefill"); each kernel documents which params it understands.
* **device_kind** — ``jax.devices()[0].device_kind`` (platform name
  off-TPU). Records are only served to the chip they were measured on:
  tiles verified on one TPU generation must not be adopted on another
  (VMEM limits differ; Mosaic may reject them). CPU-interpreter tunes are
  stored under the cpu kind and therefore never leak onto a chip.
* **bucket** — :func:`bucket_key` over the kernel's shape dims, each dim
  rounded through the compile cache's power-of-two-ish
  :func:`~paddle_tpu.core.compile_cache.bucket_dim` ladder, so the tuning
  key buckets exactly like the compiled-program key does (a shape that
  reuses a compiled program reuses its tuned params too).
* **mesh topology** (ISSUE 16) — the SPMD paged kernels run per
  model-shard with LOCAL head counts and per-device VMEM budgets, so a
  launch tuned on one topology must not be served on another.
  :func:`lookup`/:func:`adopt` take the
  :func:`~paddle_tpu.distributed.sharding_util.mesh_axes_key`
  fingerprint and append a canonical ``mesh=<axis><size>...`` suffix to
  the bucket. Legacy migration: records adopted before mesh-keying carry
  no suffix — they were measured without a mesh, so a lookup on any
  1-device topology (every axis size 1) falls back to the unsuffixed
  record; a multi-device topology never does.

Adoption is *persisted*: :func:`adopt` merges the record into
``TUNED_KERNELS.json`` in the checkout's compile-cache directory
(``core.compile_cache.default_cache_dir()``; atomic tmp+replace write),
so a tune run on a chip benefits every later process on that chip.
Lookups are memoized per process: the
params a compiled program traced against never change under it
(zero-recompile discipline — a mid-run adopt only affects *new*
processes).

Absent or malformed stores never block a kernel: :func:`lookup` returns
``None`` and callers fall back to their safe defaults.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Dict, Optional

__all__ = ["bucket_key", "mesh_suffix", "lookup", "adopt", "entries",
           "device_kind", "set_store_path", "reset"]

_lock = threading.Lock()
_STORE: Optional[dict] = None      # lazy-loaded file contents
_STORE_PATH: Optional[str] = None  # test/bench override
_LOOKUPS: Dict[tuple, Optional[dict]] = {}  # per-process memo (stability)


def _default_path() -> str:
    from ..core.compile_cache import default_cache_dir

    return os.path.join(default_cache_dir(), "TUNED_KERNELS.json")


def store_path() -> str:
    return _STORE_PATH or _default_path()


def set_store_path(path: Optional[str]) -> None:
    """Point the store at ``path`` (tests/benches) and drop every memo —
    lookups after this read the new file."""
    global _STORE_PATH
    with _lock:
        _STORE_PATH = path
        _reset_locked()


def reset() -> None:
    """Forget the loaded store and lookup memos (re-read on next use)."""
    with _lock:
        _reset_locked()


def _reset_locked() -> None:
    global _STORE
    _STORE = None
    _LOOKUPS.clear()


def device_kind() -> str:
    """The chip generation tuning records are keyed by —
    ``device_kind`` of device 0, or the backend platform name off-TPU
    (cpu-interpreter tunes stay under "cpu", never adopted on a chip)."""
    try:
        import jax

        d = jax.devices()[0]
        return str(getattr(d, "device_kind", "") or d.platform)
    # analysis: allow(broad-except) — backend probe: any failure to
    # resolve a device (no backend, broken plugin) just keys records
    # under "unknown"; tuning must never take a kernel down
    except Exception:  # pragma: no cover - no backend at all
        return "unknown"


def bucket_key(**dims) -> str:
    """Canonical bucket key over a kernel's shape dims: each dim rounded
    through the compile cache's bucket ladder, rendered sorted —
    ``bucket_key(h=12, d=64)`` -> ``"d=64,h=16"``. Shapes that share a
    compiled program share a tuning record."""
    from ..core.compile_cache import bucket_dim

    return ",".join(f"{k}={bucket_dim(v, 1)}"
                    for k, v in sorted(dims.items()))


def mesh_suffix(mesh) -> Optional[str]:
    """Canonical mesh-topology key component from a
    :func:`~paddle_tpu.distributed.sharding_util.mesh_axes_key`
    fingerprint (``((axis, size), ...)``): ``"mesh=data1.model4"``.
    ``None`` off-mesh — the legacy (unsuffixed) key space."""
    if not mesh:
        return None
    return "mesh=" + ".".join(f"{a}{int(n)}" for a, n in mesh)


def _effective_key(key: str, mesh) -> str:
    sfx = mesh_suffix(mesh)
    return f"{key},{sfx}" if sfx else key


def _load() -> dict:
    global _STORE
    if _STORE is None:
        try:
            with open(store_path()) as f:
                data = json.load(f)
            recs = data.get("records")
            _STORE = recs if isinstance(recs, dict) else {}
        # analysis: allow(broad-except) — absent OR malformed store
        # (fresh checkout, truncated write, bad hand edit) must never
        # block a kernel: fall back to the safe default launch params
        except Exception:
            _STORE = {}
    return _STORE


def _params_of(rec) -> Optional[dict]:
    return dict(rec["params"]) if (
        isinstance(rec, dict) and isinstance(rec.get("params"), dict)
    ) else None


def lookup(kernel: str, key: str, mesh=None) -> Optional[dict]:
    """Best-measured params for ``kernel`` at bucket ``key`` on THIS chip
    and mesh topology (``mesh``: a ``mesh_axes_key`` fingerprint or
    ``None``), or ``None`` (fresh checkout, different chip/topology, no
    tune yet). A 1-device topology falls back to the legacy unsuffixed
    record — pre-ISSUE-16 stores keep resolving there; a multi-device
    topology never borrows a single-device tune. Memoized per process:
    the compiled programs traced against a result must keep seeing it."""
    memo_key = (kernel, key, mesh_suffix(mesh))
    with _lock:
        if memo_key in _LOOKUPS:
            return _LOOKUPS[memo_key]
        table = _load().get(device_kind(), {}).get(kernel, {})
        params = _params_of(table.get(_effective_key(key, mesh)))
        if params is None and mesh and all(int(n) == 1 for _, n in mesh):
            # legacy-record migration: a 1-device mesh runs the same
            # launch geometry as no mesh
            params = _params_of(table.get(key))
        _LOOKUPS[memo_key] = params
    return params


def adopt(kernel: str, key: str, params: dict, measured_us: float,
          baseline_us: Optional[float] = None, mesh=None) -> bool:
    """Persist a measured-best record (tune benches call this after the
    numerics check passed). Merges into a FRESH read of the store file —
    never the per-process snapshot, which may predate another process's
    adoption (flash_tune racing the serving bench on one host): a
    stale-snapshot rewrite would silently erase its records. Atomic
    write; the in-process lookup memo is NOT invalidated — live compiled
    programs keep the params they traced against, new processes get the
    adoption. ``mesh`` (a ``mesh_axes_key`` fingerprint) keys the record
    to the topology it was measured on. Returns whether the record
    actually reached disk (callers must not report a failed persist as
    published)."""
    global _STORE
    with _lock:
        _STORE = None  # drop the snapshot: merge into what's on disk NOW
        store = _load()
        rec = {"params": dict(params), "measured_us": round(
            float(measured_us), 3)}
        if baseline_us is not None:
            rec["baseline_us"] = round(float(baseline_us), 3)
        store.setdefault(device_kind(), {}).setdefault(
            kernel, {})[_effective_key(key, mesh)] = rec
        path = store_path()
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"records": store}, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except OSError:
            # adoption is best-effort (read-only checkout, full disk):
            # the in-memory store still serves this process, but the
            # caller must know nothing persisted
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        return True


def entries(kernel: Optional[str] = None) -> int:
    """Record count for THIS chip (optionally one kernel's) — the
    ``kernel.tuned_entries`` gauge."""
    with _lock:
        mine = _load().get(device_kind(), {})
        if kernel is not None:
            return len(mine.get(kernel, {}))
        return sum(len(v) for v in mine.values())
