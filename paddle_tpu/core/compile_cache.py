"""Framework-wide compilation cache: persistent XLA cache, counters, bucketing.

The reference framework caches compiled kernels process-wide in its
KernelFactory (ref:paddle/phi/core/kernel_factory.h) and reuses executor
programs across steps. On TPU the "kernel" is an XLA executable and the
expensive step is *compilation* (a whole-model step program takes from
seconds to minutes). This module makes compilation a framework-level
resource instead of a per-bench hack:

* **Persistent on-disk cache** — ``initialize()`` points JAX's compilation
  cache at ONE directory (:func:`resolve_cache_dir`: where
  ``JAX_COMPILATION_CACHE_DIR`` says when it is set, else the fixed
  ``<checkout>/.jax_cache``) and runs once at ``import paddle_tpu``, so
  ``chip_smoke.py``, ``benchmark/run.py``, ``@to_static``, ``TrainStep``,
  eager dispatch, and ``jit.save``'s export path all warm-start from the same
  cache. The directory is part of the cache key, so it never moves with
  the user, the process or the clock. Entries are keyed on HLO + compile
  options + backend, so CPU and TPU programs never collide.
* **Observability** — hit/miss/compile-time counters for every compiled
  entry point (persistent disk cache via jax.monitoring events, the eager
  ``_JIT_CACHE`` in ``core.dispatch``, ``@to_static`` signatures, TrainStep
  and static-Executor builds), surfaced through :func:`stats`, registered as
  ``core.memory_stats`` providers, and snapshotted per-run by the profiler.
* **Shape bucketing** — :func:`bucket_dim` / :func:`pad_to_bucket` pad
  variable batch sizes up to power-of-two-ish buckets (max ~33% padding) so
  shape-polymorphic callers stop minting one executable per unique batch
  size. ``@to_static(bucket_batch=True)`` applies it automatically on the
  inference path; see docs/compile_cache.md for the semantic contract.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

from . import flags

_lock = threading.Lock()
_initialized = False
_listeners_installed = False
_providers_registered = False
_cache_dir: Optional[str] = None

# plain dicts mutated under the GIL: the eager-dispatch hot path bumps these
# per op call, so no lock on update (reads snapshot under the lock)
_counts: Dict[str, int] = {}
_times: Dict[str, float] = {}


def bump(key: str, n: int = 1) -> None:
    """Increment a counter (hot path: GIL-atomic dict update, no lock)."""
    _counts[key] = _counts.get(key, 0) + n


def bump_secs(key: str, secs: float) -> None:
    """Accumulate seconds into a timing counter (hot path: GIL-atomic
    dict update, no lock — same contract as :func:`bump`)."""
    _times[key] = _times.get(key, 0.0) + float(secs)


# ------------------------------------------------------------- observability

# jax.monitoring event -> stats key (events fire from inside jax's compile
# path; the persistent-cache ones only fire once initialize() enabled it)
_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "persistent.hits",
    "/jax/compilation_cache/cache_misses": "persistent.misses",
    "/jax/compilation_cache/compile_requests_use_cache": "persistent.requests",
}
_DURATION_KEYS = {
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "persistent.retrieval_secs",
    "/jax/compilation_cache/compile_time_saved_sec":
        "persistent.saved_secs",
    "/jax/core/compile/backend_compile_duration": "compile.backend_secs",
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_secs",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_secs",
}


def _on_event(event: str, **kw) -> None:
    key = _EVENT_KEYS.get(event)
    if key is not None:
        bump(key)


def _on_duration(event: str, duration: float, **kw) -> None:
    key = _DURATION_KEYS.get(event)
    if key is not None:
        bump_secs(key, duration)
        if key == "compile.backend_secs":
            bump("compile.backend")  # count of actual backend compiles


def _install_listeners() -> None:
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listeners_installed = True


def _register_providers() -> None:
    """Expose the headline counters through core.memory_stats so
    ``memory_stats()``/``memory_summary()`` show compile-cache behavior next
    to the allocator picture (one observability surface, not two)."""
    global _providers_registered
    with _lock:
        if _providers_registered:
            return
        from . import memory_stats

        for name, key in (("compile_cache.persistent_hits", "persistent.hits"),
                          ("compile_cache.persistent_misses",
                           "persistent.misses"),
                          ("compile_cache.eager_jit_hits", "eager_jit.hits"),
                          ("compile_cache.eager_jit_misses",
                           "eager_jit.misses"),
                          # serving-engine compile counters: the invariant
                          # the engine sells is "admit/retire never
                          # recompiles", so its trace counts live on the
                          # same surface as every other compile number
                          ("compile_cache.serving_decode_compiles",
                           "serving.decode_compiles"),
                          ("compile_cache.serving_prefill_compiles",
                           "serving.prefill_compiles")):
            memory_stats.register_stat_provider(
                name, lambda k=key: _counts.get(k, 0))
        _providers_registered = True


def stats() -> dict:
    """One merged snapshot: counts, accumulated seconds, live cache sizes."""
    with _lock:
        out: dict = dict(_counts)
        out.update({k: round(v, 6) for k, v in _times.items()})
    from . import dispatch

    out["eager_jit.entries"] = len(dispatch._JIT_CACHE)
    out["persistent.dir"] = _cache_dir
    out["persistent.enabled"] = _initialized
    if _cache_dir and os.path.isdir(_cache_dir):
        try:
            out["persistent.files"] = sum(
                1 for n in os.listdir(_cache_dir) if n.endswith("-cache"))
        except OSError:
            pass
    return out


def reset_stats() -> None:
    with _lock:
        _counts.clear()
        _times.clear()


def stats_delta(before: dict, after: dict, *, drop_zero: bool = False) -> dict:
    """Numeric difference of two :func:`stats` snapshots (counts and
    seconds); non-numeric keys (dir/enabled) pass through from ``after``.
    One definition shared by the profiler and tools/cache_stats.py so the
    two reports cannot drift."""
    out = {}
    for k, v in after.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            d = round(v - before.get(k, 0), 6)
            if drop_zero and d == 0:
                continue
            out[k] = d
        else:
            out[k] = v
    return out


# ------------------------------------------------------------ persistent dir


def default_cache_dir() -> str:
    """The one fixed cache location when the environment names none:
    ``.jax_cache`` beside the ``paddle_tpu`` package, i.e. inside the
    checkout (``.gitignore`` lists it)."""
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """THE precedence rule, shared by :func:`initialize` and
    ``tools/cache_stats.py``: ``JAX_COMPILATION_CACHE_DIR`` when set —
    nothing outranks it, so whoever launches the process places the cache
    — else the explicit ``cache_dir`` (the tests' tmp-dir fixture), else
    :func:`default_cache_dir`."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
            or default_cache_dir())


def cache_dir() -> Optional[str]:
    """The active persistent cache directory (None until initialize ran)."""
    return _cache_dir


def is_initialized() -> bool:
    return _initialized


def initialize(cache_dir: Optional[str] = None, *, force: bool = False,
               min_compile_secs: Optional[float] = None) -> Optional[str]:
    """Enable the persistent XLA compilation cache (idempotent).

    Runs automatically at ``import paddle_tpu`` unless
    ``FLAGS_xla_compile_cache=0``. The directory is
    :func:`resolve_cache_dir`'s: ``cache_dir`` is for the tests' tmp-dir
    fixture and is ignored when ``JAX_COMPILATION_CACHE_DIR`` is set.
    ``min_compile_secs`` (default
    ``FLAGS_xla_compile_cache_min_compile_secs``) keeps sub-threshold
    compiles out of the cache. ``force=True`` re-applies config after a
    first call (how that fixture re-points the cache).

    Returns the directory in use, or None when disabled/unavailable.
    Monitoring listeners and memory_stats providers are installed either
    way, so in-process counters work even with the disk cache off.
    """
    global _initialized, _cache_dir
    # counters are optional: a jax without the monitoring API (or a failed
    # provider hookup) must never make `import paddle_tpu` crash
    try:
        _install_listeners()
    except Exception:  # analysis: allow(broad-except) — optional observability;
        pass           # import must never crash on a jax without it
    try:
        _register_providers()
    except Exception:  # analysis: allow(broad-except) — optional observability;
        pass           # import must never crash on a jax without it
    if not flags.flag("xla_compile_cache"):
        return None
    if _initialized and not force:
        return _cache_dir
    d = resolve_cache_dir(cache_dir)
    if min_compile_secs is None:
        min_compile_secs = flags.flag("xla_compile_cache_min_compile_secs")
    try:
        import jax

        from . import resilience

        # a cache dir on network storage can fail transiently (EIO/ESTALE):
        # creation rides the shared IO retry policy
        resilience.call_with_retry(os.makedirs, d, exist_ok=True,
                                   name="compile_cache.mkdir")
        if force and _initialized and d != _cache_dir:
            # jax builds its cache object once per process; a re-point to a
            # different directory needs the (private, best-effort) reset or
            # entries keep landing in the old dir
            try:
                from jax._src import compilation_cache as _jcc

                _jcc.reset_cache()
            except Exception:  # analysis: allow(broad-except) — private jax API,
                pass           # best-effort cache re-point only
        jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_secs))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except Exception:  # analysis: allow(broad-except) — optimization only,
        return None    # never a blocker at import
    with _lock:
        _initialized = True
        _cache_dir = d
    return d


def clear(path: Optional[str] = None) -> int:
    """Delete persistent cache entries; returns the number of files removed.
    Only cache/atime files are touched (never the directory itself)."""
    d = path or _cache_dir or resolve_cache_dir()
    removed = 0
    if not os.path.isdir(d):
        return 0
    for name in os.listdir(d):
        if name.endswith(("-cache", "-atime")):
            try:
                os.remove(os.path.join(d, name))
                removed += 1
            except OSError:
                pass
    return removed


# ------------------------------------------------------------ shape bucketing


def bucket_dim(n: int, min_bucket: Optional[int] = None) -> int:
    """Round ``n`` up to the next power-of-two-ish bucket (powers of two plus
    the 3·2^k midpoints: 8, 12, 16, 24, 32, 48, 64, ...), bounding padding
    waste at ~33%. Values at or below the floor share one bucket."""
    n = int(n)
    m = int(min_bucket if min_bucket is not None
            else flags.flag("shape_bucket_min"))
    if n <= m:
        return m
    p = 1 << (n - 1).bit_length()  # next power of two >= n
    mid = 3 * (p // 4)  # the 3*2^k point between p/2 and p
    return mid if mid >= n else p


def bucket_shape(shape, axes=(0,), min_bucket: Optional[int] = None):
    """Bucketed copy of ``shape``: listed axes rounded up via bucket_dim."""
    shape = tuple(int(s) for s in shape)
    axes = {a % len(shape) for a in axes} if shape else set()
    return tuple(bucket_dim(s, min_bucket) if i in axes else s
                 for i, s in enumerate(shape))


def prefill_bucket(n: int, max_len: Optional[int] = None,
                   min_bucket: Optional[int] = None) -> int:
    """Prompt-length bucket for the serving engine's prefill compiles.

    Same power-of-two-ish ladder as :func:`bucket_dim` but floored at
    ``FLAGS_serving_prefill_bucket_min`` (sequence buckets want a coarser
    floor than batch buckets) and clamped to ``max_len`` (the model's
    position budget — padding past it would index past ``wpe``). Mixed
    prompt lengths therefore land in at most
    ``log2(max_len / min_bucket) * 2`` distinct compiled prefill programs.
    """
    m = int(min_bucket if min_bucket is not None
            else flags.flag("serving_prefill_bucket_min"))
    b = bucket_dim(n, m)
    if max_len is not None:
        b = min(b, int(max_len))
    return max(b, int(n))


def pad_to_bucket(x, axis: int = 0, min_bucket: Optional[int] = None):
    """Zero-pad ``x`` (jax/numpy array or Tensor) along ``axis`` up to its
    bucket. Returns ``(padded, original_size)``; the caller slices results
    back with ``out[:original_size]``. No-op (same object) when already at a
    bucket boundary."""
    from .tensor import Tensor

    arr = x._data if isinstance(x, Tensor) else x
    n = arr.shape[axis]
    b = bucket_dim(n, min_bucket)
    if b == n:
        return x, n
    import jax.numpy as jnp

    pads = [(0, 0)] * arr.ndim
    pads[axis] = (0, b - n)
    padded = jnp.pad(arr, pads)
    bump("bucket.padded")
    return (Tensor(padded, stop_gradient=x.stop_gradient)
            if isinstance(x, Tensor) else padded), n
