"""Exported-flags registry.

Equivalent of the reference's ``PHI_DEFINE_EXPORTED_*`` global flag registry
(ref:paddle/phi/core/flags.cc, ref:paddle/phi/core/flags.h:142 ExportedFlagInfoMap)
and the Python ``paddle.set_flags/get_flags`` surface
(ref:python/paddle/fluid/framework.py:7506,7531).

Flags are typed, documented, and overridable via ``FLAGS_<name>`` environment
variables at import time, matching the reference's env-var contract.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Union

_lock = threading.Lock()


@dataclass
class _FlagInfo:
    name: str
    default: Any
    type: type
    doc: str
    value: Any


_REGISTRY: Dict[str, _FlagInfo] = {}


def _parse(type_, raw: str):
    if type_ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return type_(raw)


def define_flag(name: str, default: Any, doc: str = "") -> None:
    """Register an exported flag; FLAGS_<name> env var overrides the default."""
    type_ = type(default)
    value = default
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        value = _parse(type_, env)
    with _lock:
        _REGISTRY[name] = _FlagInfo(name, default, type_, doc, value)


def get_flags(flags: Union[str, List[str]]) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag: {f}")
        out[f] = _REGISTRY[key].value
    return out


def set_flags(flags: Dict[str, Any]) -> None:
    for f, v in flags.items():
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag: {f}")
        info = _REGISTRY[key]
        info.value = _parse(info.type, v) if isinstance(v, str) and info.type is not str else info.type(v)


def flag(name: str) -> Any:
    """Fast read of a single flag value."""
    return _REGISTRY[name].value


def all_flags() -> Dict[str, Any]:
    return {k: v.value for k, v in _REGISTRY.items()}


# ---- Core flags (subset of ref:paddle/phi/core/flags.cc relevant on TPU) ----
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf in eager mode (ref flags.cc:74).")
define_flag("check_nan_inf_level", 0, "0: fail on nan/inf; >0 report-only.")
define_flag("eager_jit_ops", True, "Cache per-op jitted executables for eager mode dispatch.")
define_flag("default_device", "", "Override default device: 'cpu' | 'tpu'.")
define_flag("selected_devices", "",
            "Comma-separated local device ids for this process. Set per "
            "rank by the distributed launcher (distributed.launch) and "
            "read back from the PROCESS ENVIRONMENT by ParallelEnv "
            "(distributed.api_extra) — declared here so the flag-registry "
            "lint can prove every FLAGS_* reference resolves.")
define_flag("sequence_parallel_mode", "auto",
            "Context parallelism for attention: auto|ring|ulysses|none.")
define_flag("flash_block_q", 128,
            "Pallas flash-attention q-block tile. At the 128 defaults of "
            "this and flash_block_k the kernel takes the tiles measured "
            "for the chip's kind, where there are any; any other value "
            "wins.")
define_flag("flash_block_k", 128,
            "Pallas flash-attention k-block tile (multiple of 128).")
define_flag("flash_attention_min_seqlen", -1,
            "Route attention through the Pallas flash kernel at kv "
            "sequence length >= this. -1 (default) = auto: 1024 when "
            "tiles measured for this chip's kind will be adopted "
            "(flash_block_q/_k at their 128 defaults; that kernel "
            "measured faster than XLA at every seqlen >= 1k on v5e), "
            "else 4608 (the 128-tile "
            "kernel loses below ~4.6k). 0 = always flash.")

# ---- Compilation cache / donation / bucketing (core.compile_cache) ----
define_flag("xla_compile_cache", True,
            "Enable the persistent on-disk XLA compilation cache at import "
            "(core.compile_cache.initialize). Warm-starts every compiled "
            "entry point: eager dispatch, to_static, TrainStep.")
define_flag("xla_compile_cache_min_compile_secs", 1.0,
            "Only persist compiles that took at least this many seconds "
            "(keeps thousands of tiny eager-op entries off disk).")
define_flag("trainstep_donate", True,
            "Donate params + optimizer slots into the compiled TrainStep "
            "update (XLA reuses their HBM in place; halves update peak). "
            "0 keeps the copying build for A/B verification.")
define_flag("decode_donate", True,
            "Donate the preallocated KV cache and output token buffer into "
            "the compiled generate() decode loop.")
define_flag("shape_bucketing", False,
            "Pad batch dims of to_static inference inputs to power-of-two-"
            "ish buckets (core.compile_cache.bucket_dim) so variable batch "
            "sizes stop minting one executable each. Opt-in; see "
            "docs/compile_cache.md for the semantic contract.")
define_flag("shape_bucket_min", 8,
            "Smallest shape bucket: batch dims at or below this share one "
            "bucket.")

# ---- Serving: continuous-batching decode engine (paddle_tpu.serving) ----
define_flag("serving_slots", 8,
            "Default decode-slot count of a ServingEngine: the fixed batch "
            "dimension of the compiled slot-based decode step (admitting/"
            "retiring a request reuses a slot, never recompiles).")
define_flag("kv_block_size", 16,
            "Tokens per KV-arena page block. A request's cache is a list of "
            "blocks, allocated as its context grows and returned to the "
            "free list at retire.")
define_flag("serving_max_queue", 0,
            "Queue-overload shedding: submit() raises QueueOverloadError "
            "when this many requests are already waiting (0 = unlimited).")
define_flag("serving_prefill_bucket_min", 16,
            "Smallest prompt-length bucket for serving prefill compiles; "
            "prompts at or below this share one compiled prefill program.")
define_flag("serving_starvation_steps", 8,
            "Priority admission: scheduler steps the best waiting request "
            "may be blocked on capacity before the scheduler preempts the "
            "lowest-priority (most recently admitted) running request to "
            "make room. 0 disables preemption.")
define_flag("serving_max_rebuilds", 3,
            "Serving supervisor crash-loop breaker: after this many engine "
            "rebuilds within FLAGS_serving_rebuild_window scheduler steps, "
            "transient failures stop being recovered and fail fast "
            "(CrashLoopError).")
define_flag("serving_rebuild_window", 200,
            "Scheduler-step window over which the serving supervisor counts "
            "rebuilds toward the crash-loop breaker.")
define_flag("serving_drain_grace", 30.0,
            "Default grace budget (seconds) for ServingAPI.drain(): "
            "admissions stop immediately, in-flight requests pump to "
            "completion within the budget, stragglers fail with the "
            "retriable RequestDrainedError.")
define_flag("serving_prefix_cache", False,
            "Radix prefix cache over the paged KV arena: full prompt "
            "blocks are content-hashed into a tree and shared by "
            "reference across slots (refcounted, copy-on-write), so an "
            "admission whose prefix is resident prefills only its "
            "unmatched suffix. 0 (default) keeps the PR 5 behavior: "
            "every admit prefills its full prompt into private blocks.")
define_flag("serving_cache_affinity", 0,
            "Cache-aware admission: how many times the strict "
            "(priority, arrival) head-of-line waiter may be skipped in "
            "favor of a same-priority waiter whose prefix is resident in "
            "the radix cache. Bounded so a cache-cold head request is "
            "never starved past this window. 0 disables the preference "
            "(strict PR 5 admission order).")
define_flag("serving_kv_tiering", False,
            "Tiered KV cache (serving.tiered): instead of discarding an "
            "evicted refcount-zero cached prefix block, spill its pool "
            "rows (int8 payload + scales as one unit) to a host-RAM tier "
            "keyed by the radix cache's content hashes, overflowing to an "
            "on-disk tier; a radix hit on a spilled block restores it via "
            "ONE compiled scatter (zero new compiles per restore). "
            "Requires FLAGS_serving_prefix_cache. 0 (default) keeps the "
            "PR 14 behavior bit-for-bit: eviction frees the block and its "
            "prefill is recomputed on the next hit.")
define_flag("serving_host_cache_bytes", 256 * 1024 * 1024,
            "Byte budget of the host-RAM KV spill tier "
            "(serving.tiered.HostKVCache, shared across gateway "
            "replicas). LRU entries past the budget overflow to "
            "FLAGS_serving_disk_cache_dir when set, else drop (the next "
            "hit recomputes). Only read when FLAGS_serving_kv_tiering.")
define_flag("serving_disk_cache_dir", "",
            "Directory of the on-disk KV spill tier (third tier under "
            "HBM -> host RAM). Files are written atomically "
            "(tmp + rename) and crc-checked on load — a corrupt or "
            "truncated entry falls back to recompute, never serves "
            "garbage. Empty (default) disables the disk tier.")
define_flag("serving_disk_cache_bytes", 8 * 1024 * 1024 * 1024,
            "Byte budget of the on-disk KV spill tier: past it the "
            "oldest-written entries are deleted (a churning working set "
            "must never fill the disk). Only read when "
            "FLAGS_serving_disk_cache_dir is set.")
define_flag("serving_arena_invariants", False,
            "Audit the refcount layer after every release path (retire, "
            "cancel, preemption, drain stragglers): free-list blocks must "
            "have refcount zero, and a block id may appear in multiple "
            "slots' tables only when its refcount says so. Costs a host "
            "walk per retire; tests turn it on, production leaves it off.")
define_flag("serving_spec_k", 0,
            "Speculative decoding: tokens proposed per decode iteration "
            "(0 = off, one token per compiled call — the PR 8/9 "
            "behavior). With a draft model configured "
            "(ServingConfig.draft_model) the draft proposes k tokens into "
            "its own KV namespace and the target verifies all k in ONE "
            "batched compiled call, accepting the longest matching prefix "
            "(greedy semantics unchanged — bit-identical). Without a "
            "draft the engine self-drafts (lockstep fused multi-token "
            "decode: k target sub-steps per dispatch, acceptance "
            "structurally 1.0). Part of the engine's program key: changing "
            "it builds new executables, never reuses old ones.")
define_flag("serving_quant_weights", False,
            "Weight-only int8 serving: quantize every GPT attention/MLP "
            "matmul per output channel at engine construction "
            "(models.serving_seam.quantize_serving_weights — the single "
            "quantization.quantize_weight path) and dequantize in-kernel "
            "inside the compiled decode/prefill/verify programs, so "
            "weight HBM traffic is 1 byte/param. Greedy output is gated "
            "on parity (or the documented per-token tolerance) vs the "
            "unquantized compute dtype — see docs/quantization.md. Part "
            "of the engine's program key like the donation flags; 0 "
            "(default) keeps the serving path bit-identical to PR 10.")
define_flag("serving_quant_kv", False,
            "Int8 KV arena: the paged K/V pools store int8 with per-block "
            "float32 scale pools (one symmetric scale per token row, "
            "carried through pools()/set_pools()/namespaces/COW), "
            "quantize-on-scatter at every KV write and dequant-on-attend "
            "at every read — halves KV HBM traffic and roughly doubles "
            "the slots an arena of equal bytes seats. Same parity gate "
            "and program-key contract as FLAGS_serving_quant_weights; 0 "
            "(default) keeps full-precision pools.")
define_flag("serving_quant_draft", False,
            "Quantize the speculative-decoding draft model's weights to "
            "int8 (models.serving_seam.quantize_serving_weights on "
            "ServingConfig.draft_model). Never changes emitted tokens — "
            "verification keeps target-greedy semantics; a quantized "
            "draft only moves spec.acceptance_rate (per-mode telemetry: "
            "quant.draft_acceptance). No effect without a draft model.")
define_flag("serving_chunked_prefill", 0,
            "Chunked prefill: slice a long prompt's prefill into chunks of "
            "this many tokens, interleaved one chunk per scheduler "
            "iteration, so admitting a long prompt bounds the decode "
            "stall of running streams to one chunk instead of the whole "
            "prompt. 0 = off (admission prefills the full prompt in one "
            "bucketed call — the PR 8/9 behavior). Chunks reuse the "
            "suffix-prefill programs (one per chunk-length bucket); chunk "
            "size joins the engine's program key like donation flags do.")
define_flag("serving_lora_rank", 0,
            "Multi-LoRA serving: the adapter arena's low-rank dimension "
            "(serving.adapters.AdapterArena). 0 = off (no arena, the "
            "compiled programs carry no adapter parameters — the PR 11 "
            "behavior). Rank is static per engine (program key, like "
            "donation/quant flags); which adapters are live and which "
            "slot wears which are runtime data — registration and "
            "per-slot adapter churn never recompile. Adapter id 0 is "
            "the identity (base weights, token-identical).")
define_flag("serving_lora_adapters", 4,
            "Capacity of the serving LoRA adapter arena: how many "
            "adapters can be registered (live) at once. Row 0 is the "
            "reserved identity adapter on top of this count. Static per "
            "engine; AdapterExhaustedError past it (unregister or "
            "resize). Only read when FLAGS_serving_lora_rank > 0.")

# ---- Serving gateway: replica router + tenant quotas (serving.gateway) ----
define_flag("serving_replicas", 2,
            "Default replica count of a gateway ReplicaPool: independent "
            "ServingAPI engine replicas (threads sharing one process) the "
            "router load-balances across by least outstanding work.")
define_flag("gateway_port", 8100,
            "Default TCP port of the HTTP/SSE serving gateway (0 = bind an "
            "ephemeral port; Gateway.port reports the bound one).")
define_flag("gateway_affinity_slack", 2,
            "Bounded prefix-cache affinity in the replica router: a replica "
            "whose radix cache holds the request's prefix may win routing "
            "over the least-loaded replica only while its outstanding work "
            "exceeds the minimum by at most this many requests. Bounded so "
            "warm traffic can never pile onto (and starve) one replica. "
            "0 = pure least-outstanding-work routing. No effect unless "
            "FLAGS_serving_prefix_cache is on.")
define_flag("gateway_max_reroutes", 3,
            "How many times one gateway request may be re-routed onto "
            "another replica (crash-loop ejection, scale-down) before it "
            "fails; each re-route resumes from the request's token journal.")
define_flag("gateway_respawn_backoff", 0.5,
            "Seconds before the router respawns an ejected replica "
            "(doubles per consecutive ejection, capped at 30s; a healthy "
            "respawn resets it).")
define_flag("gateway_tenant_rate", 0.0,
            "Default per-tenant token-bucket refill rate (generated tokens "
            "per second) for tenants without an explicit TenantConfig. "
            "0 = unlimited.")
define_flag("gateway_tenant_burst", 0.0,
            "Default per-tenant token-bucket capacity (tokens). 0 = one "
            "second of the tenant's rate (or unlimited when the rate is 0).")
define_flag("gateway_tenant_concurrency", 0,
            "Default per-tenant cap on concurrently in-flight gateway "
            "requests. 0 = unlimited.")
define_flag("serving_telemetry", False,
            "Request-lifecycle span collection (serving.telemetry): "
            "SUBMITTED/QUEUED/ADMITTED/FIRST_TOKEN/... events keyed by "
            "each request's trace_id land in a bounded ring buffer, "
            "exported via GET /v1/trace/<id> and tools/trace_dump.py "
            "(Chrome trace-event JSON). Latency histograms are always on "
            "regardless — this flag gates only the per-event span path. "
            "Host-side only: never read inside a compiled region, so the "
            "zero-recompile invariant is unaffected either way.")
define_flag("serving_trace_events", 4096,
            "Capacity of the serving telemetry span ring buffer "
            "(serving.telemetry.TraceLog): the newest N span events are "
            "kept, older ones are dropped oldest-first (counted as "
            "telemetry.spans_dropped). Sized so one scrape interval of "
            "traces fits; raising it only costs host RAM.")
define_flag("gateway_fair_share", True,
            "Weighted fair-share admission under overload: once the pool's "
            "outstanding work reaches TWICE its slot capacity (slots plus "
            "one capacity's worth of queued buffering), a tenant holding "
            "more than its weight-proportional share of that budget is "
            "shed with the retriable QuotaExceededError (retry-after hint) "
            "so a noisy tenant cannot starve compliant ones.")
define_flag("gateway_process_replicas", False,
            "Run gateway replicas as supervised OS worker processes "
            "(serving.gateway.procpool.ProcessReplicaPool) instead of "
            "in-process threads: each replica is one spawned worker "
            "owning its own engine, reached over a local length-prefixed "
            "JSON-RPC socket, so a segfault/OOM/wedged XLA call in one "
            "replica cannot take down the fleet. Off (default) keeps the "
            "thread-replica ReplicaPool bit-for-bit; the gateway/tenancy/"
            "HTTP layers see the same ReplicaPool interface either way.")
define_flag("gateway_heartbeat_interval", 0.2,
            "Seconds between worker-process heartbeats (process-replica "
            "mode). Each worker pushes a heartbeat frame carrying its "
            "outstanding count, crash-loop breaker state, and new "
            "telemetry spans; the pool's watchdog reads the age of the "
            "last one.")
define_flag("gateway_heartbeat_misses", 3,
            "Consecutive missed heartbeat intervals before the watchdog "
            "classifies a worker as hung/dead and ejects it (its "
            "journaled in-flight streams re-route to survivors, the "
            "process respawns after the doubling gateway_respawn_backoff).")
define_flag("gateway_worker_timeout", 10.0,
            "Per-RPC deadline (seconds) on gateway->worker calls "
            "(submit/poll/cancel/stats/...). A call that outlives it "
            "classifies the worker as dead and ejects it. drain() adds "
            "its grace budget on top; worker SPAWN uses its own fixed "
            "boot budget since a cold worker imports jax and builds an "
            "engine first.")
define_flag("gateway_prefill_replicas", 0,
            "Disaggregated serving: worker processes in the PREFILL role "
            "(serving.disagg.DisaggReplicaPool). A prefill worker "
            "runs chunked prefill only, write-through-publishes each "
            "finished full block into the shared tier store under its "
            "radix content hash, emits the first token, and hands the "
            "request off to the decode pool. 0 together with "
            "FLAGS_gateway_decode_replicas = 0 keeps the unified "
            "ProcessReplicaPool behavior. Requires "
            "FLAGS_gateway_process_replicas.")
define_flag("gateway_decode_replicas", 0,
            "Disaggregated serving: worker processes in the DECODE role. "
            "A decode worker admits a handed-off request by restoring its "
            "published content-hash chain through the existing one-scatter "
            "compiled restore path and decodes it to completion — "
            "token-for-token identical to a unified run, zero new "
            "compiled programs per handoff. 0 together with "
            "FLAGS_gateway_prefill_replicas = 0 keeps the unified pool.")
define_flag("gateway_prefetch", 0,
            "Restore-ahead prefetch depth: how many QUEUED decode-phase "
            "requests the gateway-side planner may pre-restore per pump "
            "sweep, pulling their published/spilled KV chains into the "
            "target decode worker's arena before admission (bounded by "
            "free refcount-zero headroom, so prefetch can never starve "
            "admission). 0 = off (restore happens at admission).")
define_flag("serving_tier_publish", False,
            "Write-through publish: every tier write-through (radix "
            "insert of a full prompt block) also lands the payload in "
            "the on-disk tier immediately instead of only on host-RAM "
            "LRU overflow, making the chain restorable by OTHER worker "
            "processes sharing FLAGS_serving_disk_cache_dir — the "
            "disaggregated prefill->decode handoff contract. No effect "
            "without a disk tier.")
define_flag("serving_publish_chunks", False,
            "Publish each finished full prompt block into the radix "
            "cache (and, with FLAGS_serving_tier_publish, the shared "
            "disk tier) at every chunked-prefill chunk boundary instead "
            "of only at admission finish — so a prefill worker's partial "
            "chain is already restorable when the request hands off (or "
            "when the worker dies mid-prompt: the successor re-prefills "
            "only the unpublished suffix). Requires "
            "FLAGS_serving_prefix_cache; no effect without chunked "
            "prefill.")
define_flag("gateway_wal", False,
            "Gateway write-ahead request log (serving.gateway.wal, "
            "ISSUE 20): journal every accepted stream's lifecycle "
            "(ACCEPTED / EMITTED deltas / REROUTE-HANDOFF moves / "
            "TERMINAL) to FLAGS_gateway_wal_dir so a SIGKILLed gateway "
            "restarted on the same directory replays it — live streams "
            "resubmit journal-seeded (token-identical, zero new compiled "
            "programs), terminal ids serve from a bounded result cache. "
            "Off (default) keeps the gateway bit-for-bit WAL-free.")
define_flag("gateway_wal_dir", "",
            "Directory of the gateway WAL's segment files "
            "(wal-<seq>.log). Required when FLAGS_gateway_wal is on; a "
            "restarted gateway pointed at the same directory recovers "
            "the previous incarnation's accepted streams.")
define_flag("gateway_wal_segment_bytes", 1 << 20,
            "Rotate the gateway WAL's active segment once it exceeds "
            "this many bytes; sealed segments are deleted (compacted) "
            "once every request recorded in them is terminal.")
define_flag("gateway_wal_results", 256,
            "How many terminal results the gateway WAL keeps replayable "
            "(the bounded cache /v1/result serves from across a "
            "restart); older results are forgotten by compaction.")

# ---- Resilience: retry / sentinel / fault injection (core.resilience) ----
define_flag("io_retries", 3,
            "Max attempts (first try included) for retried IO: checkpoint "
            "save/restore, paddle.save, compile-cache dir setup, "
            "TCPStore/collective init.")
define_flag("io_retry_backoff", 0.05,
            "Base delay (seconds) of the jittered exponential backoff "
            "between retried IO attempts; doubles per attempt, capped at "
            "the policy max_delay.")
define_flag("io_retry_deadline", 120.0,
            "Wall-clock budget (seconds) across all attempts of one retried "
            "operation; retries stop when it is exhausted.")
define_flag("trainstep_sentinel", True,
            "Compile a finiteness reduction over loss+grads into TrainStep; "
            "nonfinite steps skip the optimizer update (lax.cond, no "
            "recompile) and bump the sentinel.skipped counter. With the "
            "fault off, results are bit-identical to a sentinel-disabled "
            "build (read at build time).")
define_flag("max_bad_steps", 0,
            "After this many CONSECUTIVE nonfinite TrainStep steps, trigger "
            "rollback to the last checkpoint (resilience.trigger_rollback). "
            "0 = keep skipping bad steps, never roll back.")
define_flag("ckpt_manifest", True,
            "Write a per-step manifest (tree paths + per-leaf crc32) on "
            "TrainCheckpointer.save and verify it on restore, so truncated/"
            "corrupt steps are skipped in favor of the previous valid one.")
define_flag("ckpt_manifest_crc_max_bytes", 256 * 1024 * 1024,
            "PER-SAVE byte budget for manifest checksums (smallest leaves "
            "first); leaves beyond the budget are recorded structurally "
            "(shape/dtype) without a crc32, bounding the device->host "
            "stall a manifest costs the step loop. Raise for full "
            "coverage, lower for huge models.")
define_flag("fault_injection", False,
            "Master gate for the deterministic fault-injection registry "
            "(resilience.inject_fault). Off = every probe is a no-op; "
            "production cannot arm faults by accident.")
define_flag("inject_faults", "",
            "Arm faults from the environment: 'kind:times[:after],...' "
            "(e.g. 'ckpt_io:2,preempt:1:5'). Honored only with "
            "FLAGS_fault_injection=1; used by the chaos harness to drive "
            "subprocesses.")
