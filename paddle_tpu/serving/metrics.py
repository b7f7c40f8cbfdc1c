"""Serving observability: counters + gauges for the decode engine.

Same contract as ``core.compile_cache`` / ``core.resilience`` counters (plain
dicts mutated under the GIL, snapshot under a lock), plus *gauges* — point-in-
time values the engine refreshes each scheduler iteration (queue depth, slot
occupancy, KV-arena free blocks/bytes). Headline numbers are registered as
``core.memory_stats`` providers so ``memory_summary()`` shows the serving
picture next to the allocator/compile-cache picture, the profiler snapshots
per-run deltas, and ``tools/serving_stats.py`` dumps them standalone.

Counter namespaces:

* ``requests.*``   — submitted / finished / cancelled / expired / failed
* ``tokens.*``     — ``generated`` (decode) and ``prefill`` (prompt) tokens
* ``engine.*``     — steps (decode steps whose tokens were read), admits,
  retires, rebuilds, trace counts, ``step_uploads`` (host-to-device
  transfers made preparing decode steps) and ``step_upload_bytes``
  (their bytes), ``steps_run_ahead`` (steps dispatched while the step
  before was still un-read), ``lane_steps`` (lanes a read step ran),
  ``lane_steps_discarded`` (lane-steps computed for a request that had
  already ended or been preempted: never emitted, never counted in
  ``tokens.generated``) and ``restarts.<why>`` (decode steps prepared
  from the host's mirrors because a host write had made the carried slot
  state stale, by the last writer: ``admit``, ``retire``, ``preempt``,
  ``slot_update``, ``override``, ``error``)
* ``arena.*``      — block allocs / frees / reuse / alloc failures
* ``scheduler.*``  — ``preemptions`` (starvation-triggered victim
  evictions), ``cache_skips`` (cache-affinity admissions past a cold head)
* ``supervisor.*`` — ``rebuilds`` / ``replays`` (transient-failure recovery)
* ``api.*``        — ``drains`` / ``drain_stragglers`` / ``guard_drains`` /
  ``recoveries`` (the mirror counters land in ``core.resilience`` as
  ``serving.*`` for the shared resilience dashboards)
* ``prefix.*``     — the radix prefix cache: ``hits`` / ``misses`` /
  ``hit_tokens`` (prefill tokens avoided, also ``tokens.prefill_avoided``)
  / ``inserted_blocks`` / ``evictions`` / ``cow_copies`` /
  ``suffix_prefills``
* ``spec.*``       — speculative decoding (``serving.spec_decode``):
  ``proposed`` / ``accepted`` / ``rollback_tokens`` (proposed but
  rejected — positions rolled back as runtime data) / ``emitted`` /
  ``iterations``
* ``chunk.*``      — chunked prefill: ``admits`` (admissions that went
  chunked) / ``chunks`` (compiled chunk calls) / ``tokens`` (prompt
  tokens scattered through chunks)
* ``quant.*``      — quantized serving (``FLAGS_serving_quant_*``):
  ``weight_layers`` / ``draft_layers`` (linears int8-quantized at model
  load), plus the mode gauges ``quant.weights`` / ``quant.kv`` /
  ``quant.draft`` (0/1) and ``quant.draft_acceptance`` (the quantized
  draft's acceptance rate — its tuning signal)
* ``gateway.*``    — the multi-tenant front door (``serving.gateway``):
  ``routed`` / ``rerouted`` (journaled fail-over onto a healthy replica) /
  ``affinity_routes`` (warm-cache wins within the bounded slack) /
  ``ejected`` / ``respawned`` (replica health) / ``scale_downs`` /
  ``drains`` / ``guard_drains`` / ``http_submits`` / ``http_streams`` /
  ``client_disconnects`` (mid-stream hangups, cancelled server-side) /
  ``stream_wakeups`` (a background pool's stream consumer came back from
  its wait: about one a token) / ``stream_wait_timeouts`` (it came back
  by the backstop with nothing new)
* ``tenant.*``     — quota admission: ``admitted`` / ``completed`` /
  ``shed_rate`` / ``shed_concurrency`` / ``shed_share``, plus per-tenant
  ``tenant.<name>.admitted`` / ``.shed`` / ``.tokens_out`` (goodput)
* ``worker.*``     — the process-isolated replica fleet
  (``serving.gateway.procpool``, ``FLAGS_gateway_process_replicas``):
  per-worker gauges ``worker.<idx>.pid`` / ``worker.<idx>.heartbeat_age_ms``
  / ``worker.<idx>.restarts`` (the watchdog's live fleet picture —
  ``tools/serving_stats.py --run`` and ``/v1/metrics`` render them); the
  eject-classification counters (spawns/exits/kills/hangs/heartbeat
  misses/protocol errors) live in ``core.resilience`` as ``worker.*``
* ``sampling.*``   — per-slot sampling (``serving.sampling``):
  ``admits`` (non-greedy admissions) / ``spec_fallback_slots`` (lanes
  the speculative decoder routed through the plain step per the compose
  rule — sampled/constrained/adapter slots never take spec's greedy
  verify path)
* ``constrain.*``  — constrained decoding (``serving.constrain``):
  ``admits`` (masked admissions) / ``mask_updates`` (walker advances
  scattered into the slot mask) / ``dead_ends`` (user walkers sanitized
  to unconstrained)
* ``lora.*``       — the multi-LoRA adapter arena (``serving.adapters``):
  ``registered`` / ``unregistered`` / ``register_failed`` (capacity) /
  ``admits`` (slots admitted with a non-zero adapter)
* ``tier.*``       — the tiered KV cache (``serving.tiered``,
  ``FLAGS_serving_kv_tiering``): ``spilled_blocks`` / ``spilled_bytes``
  (device blocks demoted to host/disk; bytes only when the write-through
  copy was gone) / ``restored_blocks`` / ``restored_bytes`` (compiled
  scatter restores on radix hits), per-tier ``host_hits`` / ``disk_hits``
  / ``misses`` (a spilled node whose entry was lost — recompute),
  ``host_evictions`` / ``host_drops`` (LRU past the byte budget, with /
  without a disk tier) / ``disk_writes`` / ``disk_evictions``
  (oldest entries deleted past ``FLAGS_serving_disk_cache_bytes``) /
  ``disk_write_failed`` (ENOSPC/dead disk — the entry degrades to a
  miss, mirrored into ``core.resilience``) / ``disk_corrupt``
  (crc-failed loads, mirrored into ``core.resilience``); gauges
  ``tier.enabled``
  (0/1 mode), ``host_bytes`` / ``host_entries`` / ``disk_bytes`` /
  ``disk_entries`` (occupancy)
* ``kernel.*``     — the Pallas paged-attention serving kernels
  (``ServingConfig.paged_kernel``, ``ops.paged_attention``):
  trace-time counters ``decode_traces`` / ``prefill_traces`` /
  ``verify_traces`` (the kernel twins of the engine's no-recompile
  counters — churn must never re-lower a kernel), plus the gauges
  ``kernel.paged`` (0/1: the route the decode step was built with),
  ``kernel.paged_latent`` (0/1: that route is the latent decode kernel,
  ``ops.paged_attention.paged_latent_decode``), ``kernel.hyper_connection``
  (0/1, set for a model whose ``ServingSpec.kernels`` names it: the
  streams' update, mixers and read-out run as the Pallas kernel
  ``ops.hyper_connection``, not as its body in the interpreter) and
  ``kernel.gdn_chunk`` (0/1, likewise: a prefill's chunkwise gated delta
  rule runs as the Pallas kernel of ``ops.gated_delta``, not as its
  ``jax.numpy`` form)

* ``moe.*``        — the expert layers' load, summed by the decode step
  program over its expert layers and the lanes that hold a request, and
  read back behind the step's tokens (``serving_seam.add_step_counters``;
  no transfer of its own): counters ``assignments`` (token, expert pairs),
  ``max_expert_assignments`` (the busiest expert's, per layer and step),
  ``experts_touched`` (experts that got a token, per layer and step) and
  ``layer_steps`` (expert layers x steps)

* ``state.*``      — the slot-indexed store of recurrent-layer state
  (``kv_arena.KVArena.slot_state``; engines of a model that declares a
  ``"recurrent"`` layer only): counter ``resets`` (an admission: the
  prefill started the lane from zeros and wrote the request's state into
  it); gauge ``state.bytes_total`` (the store's bytes, beside
  ``arena.kv_bytes`` for the paged pools)

* ``time_us.*``    — wall time of the serving loop by phase, in whole
  microseconds (``serving.telemetry.phase``): ``time_us.<phase>`` grows
  by every use's elapsed time, so a window's delta over the window is
  that phase's share of wall time and over the delta of ``engine.steps``
  its mean per decode step. ``pump.unlocked`` and ``sched.step``
  partition the pump thread's time; ``sched.admit`` (parent of
  ``prefill``, itself the parent of ``prefill.setup`` / ``.upload`` /
  ``.dispatch`` / ``.wait`` / ``.draft`` / ``.finish``), ``decode_step``
  (parent of ``decode.prepare`` (``.grow`` + ``.upload``) /
  ``decode.dispatch`` / ``decode.wait``, the last the parent of
  ``decode.release``; run ahead, one turn's ``decode_step`` prepares and
  dispatches step N+1 and waits for step N) and ``sched.emit`` lie
  inside ``sched.step``;
  ``submit.lock_wait`` is handler threads' time;
  ``device.empty.<cause>`` (``restart`` / ``admit`` / ``sync`` /
  ``idle``) is the time the engine knew the device had nothing of its
  left to run, by what ended it (docs/observability.md "Phases of the
  serving loop")

Gauges: ``queue.depth``, ``queue.prefilling`` (chunked prefills in
progress), ``spec.acceptance_rate``, ``slots.active``, ``slots.total``,
``arena.blocks_free``, ``arena.blocks_total``, ``arena.blocks_cached``
(resident prefix blocks — in use but reclaimable), ``arena.high_water``,
``arena.kv_bytes``, ``arena.frag_tokens`` (allocated-block capacity minus
live context tokens — internal fragmentation of the paged cache),
``prefix.resident_blocks``, ``tokens_per_sec`` (the engine's decode rate
over its :class:`Meter`'s sliding window — idle tails decay it to 0
instead of averaging into a lifetime mean),
``gateway.replicas_healthy`` / ``gateway.replicas_total`` /
``gateway.outstanding`` (the router's fleet picture),
``gateway.stream_consumers`` (``ReplicaPool.stream`` generators alive),
``sampling.active_slots`` / ``constrain.active_slots`` /
``lora.active_slots`` (scenario mix of the live batch), and the adapter
arena's ``lora.slots`` / ``lora.live`` / ``lora.arena_bytes``.

Latency *distributions* live next door in ``serving.telemetry``
(``latency.*`` histograms + ``telemetry.*`` span meta-counters — see its
docstring for the key registry); :func:`histograms` re-exports them here
so this module stays the one-stop stats surface, and ``GET /v1/metrics``
renders both planes as Prometheus text via ``telemetry.prometheus_text``.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

_lock = threading.Lock()

# plain dicts mutated under the GIL (compile_cache._counts contract): the
# per-step hot path bumps these without taking the lock
_counts: Dict[str, int] = {}
_gauges: Dict[str, float] = {}
_providers_registered = False

#: every serving counter/gauge key lives in one of these namespaces (the
#: segment before the first ``.``, or the whole key for the bare gauges) —
#: the docstring above documents each. ``tools/analyze.py``'s
#: ``unknown-metric-key`` rule checks every literal ``metrics.bump``/
#: ``metrics.set_gauge`` key against this registry, so a typo'd or
#: undocumented namespace fails the lint instead of silently vanishing
#: from the stats CLIs and dashboards.
DOCUMENTED_NAMESPACES = (
    "requests", "tokens", "engine", "arena", "scheduler", "supervisor",
    "api", "prefix", "spec", "chunk", "quant", "gateway", "tenant",
    "sampling", "constrain", "lora", "kernel",
    # tier.* (ISSUE 15): the tiered KV cache's spill/restore telemetry —
    # serving.tiered / docs/serving.md "Tiered KV cache"
    "tier",
    # mesh.* (ISSUE 14): the engine's captured device-mesh topology —
    # mesh.devices / mesh.model_axis / mesh.data_axis gauges set at
    # construction (docs/distributed.md "Tensor-parallel serving")
    "mesh",
    # telemetry.* (ISSUE 17): the tracing plane's own meta-counters —
    # spans recorded / spans_dropped (ring overflow), mirrored from
    # serving.telemetry (docs/observability.md)
    "telemetry",
    # latency.* (ISSUE 17): duration histograms (ttft, inter_token,
    # queue_wait, prefill, decode_step, spec_step, spec_verify, restore,
    # spill, e2e) — serving.telemetry observe() keys, exported as
    # paddle_latency_*_seconds (docs/observability.md)
    "latency",
    # worker.* (ISSUE 18): per-worker-process gauges of the
    # process-isolated replica fleet — pid / heartbeat_age_ms / restarts
    # per worker index (serving.gateway.procpool, docs/robustness.md
    # "Process isolation")
    "worker",
    # disagg.* (ISSUE 19): disaggregated prefill/decode serving —
    # handoffs, prefill/decode/degraded route counts, restore-ahead
    # prefetches / prefetched_chains / prefetched_blocks
    # (serving.disagg, docs/serving.md "Disaggregated prefill/decode")
    "disagg",
    # wal.* (ISSUE 20): the gateway write-ahead request log — records /
    # accepted / emitted_tokens / terminals / commits / rotations /
    # compactions / carried / torn_tail / replayed{,_live,_results}
    # counters and segments / bytes gauges (serving.gateway.wal,
    # docs/robustness.md "Gateway crash recovery")
    "wal",
    # time_us.* (ISSUE 24): microseconds spent in each phase of the
    # serving loop, summed — one counter per serving.telemetry.phase
    # name, the exact sums beside the latency.* buckets
    # (docs/observability.md "Phases of the serving loop")
    "time_us",
    # state.* (ISSUE 26): the slot-indexed store of recurrent-layer state
    # beside the paged KV arena — the resets counter,
    # bytes_total gauge (docs/serving_model_seam.md)
    "state",
    # prefill.* (ISSUE 31): tokens an admission ran through the layers
    # before the model's prefill tail (body_tokens) and through the tail
    # (tail_tokens: one a prefill where a model declares a tail) —
    # docs/observability.md, docs/serving_model_seam.md "The prefill tail";
    # (ISSUE 37) compiled prefill calls, the positions they computed
    # (positions_computed: the buckets), upload_bytes, lane_us_blocked;
    # (ISSUE 45) block_writes: pool arrays a full prefill wrote in whole
    # blocks — docs/observability.md "An admission, from inside"
    "prefill",
    # moe.* (ISSUE 33), window.* (ISSUE 44), sparse.* (ISSUE 48): what a
    # model's layers count inside the step program (``carry["counters"]``,
    # ``models.serving_seam.add_step_counters``) and the engine reads back
    # behind the step's tokens: expert loads; ring rows live / read; a
    # sparse layer's rows live / read / index rows scored
    # (docs/serving_model_seam.md "The step carry")
    "moe", "window", "sparse",
    "queue", "slots", "tokens_per_sec",
)


def bump(key: str, n: int = 1) -> None:
    """Increment a serving counter (GIL-atomic dict update, no lock)."""
    _counts[key] = _counts.get(key, 0) + n


def set_gauge(key: str, value) -> None:
    """Record a point-in-time value (slot occupancy, queue depth, ...) —
    GIL-atomic single-key dict update, no lock (see :func:`bump`)."""
    _gauges[key] = value


def stats() -> dict:
    """One merged snapshot: counters plus current gauge values."""
    with _lock:
        out: dict = dict(_counts)
        out.update(_gauges)
    return out


def gauges() -> dict:
    """Gauges-only snapshot (point-in-time state — occupancy, residency —
    that a delta report must NOT difference)."""
    with _lock:
        return dict(_gauges)


def reset_stats() -> None:
    with _lock:
        _counts.clear()
        _gauges.clear()


def stats_delta(before: dict, after: dict, *, drop_zero: bool = False) -> dict:
    """Numeric difference of two :func:`stats` snapshots — one shared
    definition with the compile cache so every report agrees. NOTE gauges
    are differenced too (a delta report shows occupancy *change*)."""
    from ..core import compile_cache

    return compile_cache.stats_delta(before, after, drop_zero=drop_zero)


class Meter:
    """Tokens/s meter over a SLIDING window: ``tick(n)`` per step,
    ``rate()`` for the windowed rate. Ticks land in per-second buckets
    and ``rate()`` sums only the last ``window`` seconds, so an idle
    tail decays the gauge toward 0 instead of averaging into a lifetime
    mean (the pre-ISSUE-17 behaviour, which made ``tokens_per_sec``
    useless as a load signal after the first lull). ``tokens()`` still
    reports the lifetime count. ``now`` is injectable for deterministic
    decay tests."""

    def __init__(self, window: float = 10.0, now=time.perf_counter) -> None:
        self._window = float(window)
        self._now = now
        self.reset()

    def reset(self) -> None:
        self._t0 = self._now()
        self._n = 0
        self._buckets: Dict[int, int] = {}

    def tick(self, n: int) -> None:
        n = int(n)
        self._n += n
        sec = int(self._now())
        self._buckets[sec] = self._buckets.get(sec, 0) + n
        # GIL-safe pruning: the dict stays O(window) without a lock
        if len(self._buckets) > self._window * 2 + 2:
            horizon = sec - self._window
            for k in [k for k in self._buckets if k < horizon]:
                self._buckets.pop(k, None)

    def tokens(self) -> int:
        """Lifetime tick total (NOT windowed)."""
        return self._n

    def rate(self) -> float:
        """Tokens/s over the sliding window. Before a full window has
        elapsed since construction/reset, divides by the elapsed time so
        early readings aren't diluted by the empty remainder."""
        now = self._now()
        horizon = now - self._window
        n = sum(c for sec, c in list(self._buckets.items())
                if sec >= horizon - 1.0)
        dt = min(now - self._t0, self._window)
        return n / dt if dt > 0 else 0.0


def histograms() -> dict:
    """The latency histograms (``serving.telemetry``'s process-global
    set), re-exported so callers already importing ``metrics`` get the
    whole stats picture from one module. Lazy import: telemetry imports
    this module for its meta-counters."""
    from . import telemetry

    return telemetry.histograms()


def _register_providers() -> None:
    """Headline serving numbers on the shared observability surface."""
    global _providers_registered
    with _lock:
        if _providers_registered:
            return
        from ..core import memory_stats

        for name, key, table in (
                ("serving.tokens_generated", "tokens.generated", _counts),
                ("serving.requests_finished", "requests.finished", _counts),
                ("serving.requests_shed", "requests.shed", _counts),
                ("serving.tokens_per_sec", "tokens_per_sec", _gauges),
                ("serving.prefix_hit_tokens", "prefix.hit_tokens", _counts),
                ("serving.prefix_resident_blocks",
                 "prefix.resident_blocks", _gauges),
                ("serving.queue_depth", "queue.depth", _gauges),
                ("serving.slots_active", "slots.active", _gauges),
                ("serving.arena_blocks_free", "arena.blocks_free", _gauges),
                ("serving.kv_arena_bytes", "arena.kv_bytes", _gauges)):
            memory_stats.register_stat_provider(
                name, lambda k=key, t=table: t.get(k, 0))
        _providers_registered = True


try:
    _register_providers()
except Exception:  # analysis: allow(broad-except) — observability is
    pass           # optional, never an import blocker
