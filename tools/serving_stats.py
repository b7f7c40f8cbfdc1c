#!/usr/bin/env python
"""Serving stats CLI: dump the continuous-batching engine's counters/gauges
(tokens, queue depth, slot occupancy, KV-arena blocks) for a run, or show
the flag-configured engine sizing (mirrors tools/cache_stats.py /
tools/resilience_stats.py for paddle_tpu.serving).

Usage:
    python tools/serving_stats.py                # engine sizing from flags
                                                 # (no jax backend init)
    python tools/serving_stats.py --run CMD ...  # run CMD..., report the
                                                 # run's serving counters
    python tools/serving_stats.py --json         # machine-readable output

Without --run this only reports the FLAGS_serving_* / FLAGS_kv_block_size
configuration and the KV-arena bytes they imply for a given model shape —
it never initializes a jax backend, so it never claims a chip another
process holds. With --run, CMD executes in-process via runpy with the
framework imported first, and the delta of ``serving.metrics.stats()``
across the run is reported — a healthy serving run shows
``tokens.generated`` climbing with ``engine.decode_compiles`` frozen after
warmup. The resilience layer's counters ride the same delta:
``scheduler.preemptions`` (starvation-triggered victim evictions),
``supervisor.rebuilds`` / ``supervisor.replays`` (transient-failure
recovery), ``api.drains`` / ``api.drain_stragglers`` / ``api.recoveries``.
So do the radix prefix cache's (``FLAGS_serving_prefix_cache``):
``prefix.hits`` / ``prefix.hit_tokens`` (prefill tokens avoided) /
``prefix.inserted_blocks`` / ``prefix.evictions`` / ``prefix.cow_copies``.
The tiered KV cache (``FLAGS_serving_kv_tiering``, ``serving.tiered``)
adds ``tier.spilled_blocks`` / ``tier.restored_blocks`` (evictions
demoted to host/disk and their compiled-scatter restores),
``tier.host_hits`` / ``tier.disk_hits`` / ``tier.misses``,
``tier.disk_corrupt`` (crc-failed loads — recomputed, never served), and
the end-of-run occupancy gauges ``tier.host_bytes`` / ``tier.host_entries``
/ ``tier.disk_bytes`` / ``tier.disk_entries``;
``FLAGS_serving_host_cache_bytes`` / ``FLAGS_serving_disk_cache_dir``
size the tiers in config mode.
Speculative decoding (``FLAGS_serving_spec_k``) adds ``spec.proposed`` /
``spec.accepted`` / ``spec.rollback_tokens`` / ``spec.emitted`` /
``spec.iterations`` (+ the ``spec.acceptance_rate`` end-of-run gauge),
and chunked prefill (``FLAGS_serving_chunked_prefill``) adds
``chunk.admits`` / ``chunk.chunks`` / ``chunk.tokens``. Quantized
serving (``FLAGS_serving_quant_weights`` / ``_kv`` / ``_draft``) adds
``quant.weight_layers`` / ``quant.draft_layers`` plus the end-of-run
mode gauges (``quant.weights`` / ``quant.kv`` / ``quant.draft`` /
``quant.draft_acceptance``) and the per-namespace arena byte gauges
(``arena.kv_bytes`` / ``arena.scale_bytes`` / ``arena.bytes.<ns>`` /
``arena.dtype.<ns>``) — the int8 memory win, observable per run.
Scenario diversity (ISSUE 12) adds per-slot sampling
(``sampling.admits`` / ``sampling.spec_fallback_slots``), constrained
decoding (``constrain.admits`` / ``constrain.mask_updates`` /
``constrain.dead_ends``), and the multi-LoRA arena (``lora.registered`` /
``lora.admits``, plus the end-of-run ``lora.slots`` / ``lora.live`` /
``lora.arena_bytes`` and per-scenario ``*.active_slots`` gauges);
``FLAGS_serving_lora_rank`` / ``FLAGS_serving_lora_adapters`` size the
arena in config mode.
The Pallas paged-attention kernels (``ops.paged_attention``; the decode
step's default route where they compile natively, every route under
``ServingConfig.paged_kernel=True``) add the trace-time ``kernel.decode_traces`` /
``kernel.prefill_traces`` / ``kernel.verify_traces`` counters (frozen
after warmup in a healthy run — churn never re-lowers a kernel) and the
end-of-run ``kernel.paged`` gauge (the route the decode step was built
with).
The mesh-sharded execution core (ISSUE 14, docs/distributed.md) adds the
``mesh.devices`` / ``mesh.model_axis`` / ``mesh.data_axis`` topology
gauges — a tensor-parallel run shows ``mesh.model_axis`` > 1 with the
same frozen compile counters as a single chip. ``kernel.mesh`` /
``kernel.mesh.<namespace>`` (ISSUE 16) state the EFFECTIVE attention
route x topology per arena namespace — ``kernel@data1.model4``,
``gather@single``, ... — so a silent fallback to the gather path (Pallas
unavailable, flag off) is observable per run instead of inferred from
step times; on a multi-device mesh ``kernel@...`` means the sharded
(per-model-shard) Pallas route served every decode/prefill/spec
sub-step.
The multi-tenant gateway's counters ride it too (``serving.gateway``):
``gateway.routed`` / ``gateway.rerouted`` (journaled fail-over) /
``gateway.ejected`` / ``gateway.respawned`` (replica health) /
``gateway.affinity_routes`` / ``gateway.drains``, plus tenant admission:
``tenant.admitted`` / ``tenant.shed_rate`` / ``tenant.shed_concurrency`` /
``tenant.shed_share`` and the per-tenant ``tenant.<name>.tokens_out``
goodput counters.
The process-isolated replica fleet (``FLAGS_gateway_process_replicas``,
``serving.gateway.procpool``) adds the ``worker.*`` namespace:
``worker.spawns`` / ``worker.exits`` / ``worker.kills`` (processes that
died to a signal — a kill -9'd worker shows up here, not as a hang) /
``worker.hangs`` (missed-heartbeat or RPC-deadline ejections) /
``worker.heartbeats`` / ``worker.heartbeat_misses`` /
``worker.protocol_errors`` (malformed RPC frames — classified eject,
never a hung handle), plus the per-worker end-of-run gauges
``worker.<i>.pid`` / ``worker.<i>.heartbeat_age_ms`` /
``worker.<i>.restarts`` — a healthy fleet shows every heartbeat age far
under ``FLAGS_gateway_heartbeat_interval * FLAGS_gateway_heartbeat_misses``
and restart counts flat after warmup.
Disaggregated prefill/decode serving (``FLAGS_gateway_prefill_replicas``
/ ``FLAGS_gateway_decode_replicas``, ``serving.disagg``) adds the
``disagg.*`` namespace: ``disagg.handoffs`` (prefill → decode moves) /
``disagg.prefill_routes`` / ``disagg.decode_routes`` /
``disagg.degraded_routes`` (a role pool was empty and the request ran
unified), the restore-ahead planner's ``disagg.prefetches`` /
``disagg.prefetched_chains`` / ``disagg.prefetched_blocks``, and the
publish side's ``tier.published_blocks`` (full KV blocks write-through-
published to the shared disk tier during chunked prefill).
The crash-safe gateway (``FLAGS_gateway_wal``, ``serving.gateway.wal``)
adds the ``wal.*`` namespace: ``wal.records`` / ``wal.accepted`` /
``wal.emitted_tokens`` / ``wal.terminals`` (journal writes),
``wal.commits`` (batched fsyncs — one per pump sweep, not per token),
``wal.rotations`` / ``wal.compactions`` / ``wal.carried``
(segment lifecycle: sealed segments whose every stream is terminal are
deleted, live/result records carried forward), ``wal.replayed`` /
``wal.replayed_live`` / ``wal.replayed_results`` (restart recovery) and
``wal.torn_tail`` (crc/length-truncated tail records discarded on
replay — also bumped on the resilience surface), plus the end-of-run
``wal.segments`` / ``wal.bytes`` occupancy gauges.
The observability plane (ISSUE 17, docs/observability.md) adds the
``latency.*`` histograms (ttft, inter_token, queue_wait, prefill,
decode_step, restore, e2e, ... — recorded host-side around compiled
calls) rendered as a per-run p50/p95/p99 percentile table, plus the
``telemetry.spans`` / ``telemetry.spans_dropped`` trace-ring counters
(the headline ``serving.ttft_p50_ms`` / ``serving.inter_token_p99_ms``
percentiles live on the shared ``memory_stats`` surface).
A run report also gives what an ADMISSION cost (``admission`` in the
JSON, a block of lines in the text), from the engine's own phase counters
(docs/observability.md, "An admission, from inside"): the compiled
prefill calls, the ``prefill`` phase's mean a call and its children
(``setup`` + ``upload`` + ``dispatch`` + ``wait`` (+ ``draft``) +
``finish``), the padded share of the positions those calls computed,
their upload bytes, the lane-time they took from decoding requests, then
the restarts from the host's mirrors by who made the slot state stale
(``engine.restarts.<why>``) and the time the device stood empty by what
ended it (``time_us.device.empty.<cause>``).
A run report also prints the end-of-run arena/prefix/gateway gauges
(occupancy, cached/resident blocks, high-water, fragmentation, replica
health) next to the delta — point-in-time state, not differenced.
After the script returns, every ServingAPI it left open is drained
(``serving.drain_all``) so the reported run always exercises the graceful
shutdown path and no engine exits holding live slots or arena blocks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _flag_env(name: str, default):
    raw = os.environ.get("FLAGS_" + name)
    if raw is None:
        return default
    try:
        return type(default)(raw)
    except ValueError:
        return default


def _config_report() -> dict:
    # mirror core.flags defaults without importing the framework
    slots = _flag_env("serving_slots", 8)
    block = _flag_env("kv_block_size", 16)
    return {
        "serving_slots": slots,
        "kv_block_size": block,
        "serving_max_queue": _flag_env("serving_max_queue", 0),
        "serving_prefill_bucket_min": _flag_env("serving_prefill_bucket_min",
                                                16),
        "decode_donate": _flag_env("decode_donate", 1),
        # resilience layer (priority preemption / supervisor / drain)
        "serving_starvation_steps": _flag_env("serving_starvation_steps", 8),
        "serving_max_rebuilds": _flag_env("serving_max_rebuilds", 3),
        "serving_rebuild_window": _flag_env("serving_rebuild_window", 200),
        "serving_drain_grace": _flag_env("serving_drain_grace", 30.0),
        # radix prefix cache (content-addressed KV block sharing)
        "serving_prefix_cache": _flag_env("serving_prefix_cache", 0),
        "serving_cache_affinity": _flag_env("serving_cache_affinity", 0),
        # tiered KV cache (serving.tiered: host-RAM/disk spill + restore)
        "serving_kv_tiering": _flag_env("serving_kv_tiering", 0),
        "serving_host_cache_bytes": _flag_env("serving_host_cache_bytes",
                                              256 * 1024 * 1024),
        "serving_disk_cache_dir": _flag_env("serving_disk_cache_dir", ""),
        "serving_disk_cache_bytes": _flag_env(
            "serving_disk_cache_bytes", 8 * 1024 * 1024 * 1024),
        "serving_arena_invariants": _flag_env("serving_arena_invariants", 0),
        # speculative decoding + chunked prefill (serving.spec_decode)
        "serving_spec_k": _flag_env("serving_spec_k", 0),
        "serving_chunked_prefill": _flag_env("serving_chunked_prefill", 0),
        # quantized serving (int8 weights / int8 KV arena / int8 draft)
        "serving_quant_weights": _flag_env("serving_quant_weights", 0),
        "serving_quant_kv": _flag_env("serving_quant_kv", 0),
        "serving_quant_draft": _flag_env("serving_quant_draft", 0),
        # multi-LoRA adapter arena (serving.adapters; 0 rank = off)
        "serving_lora_rank": _flag_env("serving_lora_rank", 0),
        "serving_lora_adapters": _flag_env("serving_lora_adapters", 4),
        # multi-tenant gateway (serving.gateway: router/tenancy/front door)
        "serving_replicas": _flag_env("serving_replicas", 2),
        "gateway_port": _flag_env("gateway_port", 8100),
        "gateway_affinity_slack": _flag_env("gateway_affinity_slack", 2),
        "gateway_max_reroutes": _flag_env("gateway_max_reroutes", 3),
        "gateway_respawn_backoff": _flag_env("gateway_respawn_backoff", 0.5),
        "gateway_tenant_rate": _flag_env("gateway_tenant_rate", 0.0),
        "gateway_tenant_burst": _flag_env("gateway_tenant_burst", 0.0),
        "gateway_tenant_concurrency": _flag_env("gateway_tenant_concurrency",
                                                0),
        "gateway_fair_share": _flag_env("gateway_fair_share", 1),
        # process-isolated replica fleet (serving.gateway.procpool;
        # 0 = in-process thread replicas, bit-for-bit the same routing)
        "gateway_process_replicas": _flag_env("gateway_process_replicas", 0),
        "gateway_heartbeat_interval": _flag_env("gateway_heartbeat_interval",
                                                0.2),
        "gateway_heartbeat_misses": _flag_env("gateway_heartbeat_misses", 3),
        "gateway_worker_timeout": _flag_env("gateway_worker_timeout", 10.0),
        # disaggregated prefill/decode serving (serving.disagg; both role
        # counts > 0 turns the process fleet into a DisaggReplicaPool)
        "gateway_prefill_replicas": _flag_env("gateway_prefill_replicas", 0),
        "gateway_decode_replicas": _flag_env("gateway_decode_replicas", 0),
        "gateway_prefetch": _flag_env("gateway_prefetch", 0),
        "serving_tier_publish": _flag_env("serving_tier_publish", 0),
        "serving_publish_chunks": _flag_env("serving_publish_chunks", 0),
        # crash-safe gateway WAL (serving.gateway.wal; 0 = no journal,
        # bit-for-bit the non-durable gateway)
        "gateway_wal": _flag_env("gateway_wal", 0),
        "gateway_wal_dir": _flag_env("gateway_wal_dir", ""),
        "gateway_wal_segment_bytes": _flag_env("gateway_wal_segment_bytes",
                                               1 << 20),
        "gateway_wal_results": _flag_env("gateway_wal_results", 256),
    }


#: the children of the ``prefill`` phase, in the order an admission runs them
PREFILL_CHILDREN = ("setup", "upload", "dispatch", "wait", "draft", "finish")


def admission_report(delta: dict) -> dict:
    """What an admission cost in a run, from the delta of the serving
    counters: the ``prefill`` phase's tree in ms a compiled call, the
    padded positions, then the restarts and the empty device by cause.
    Empty when the run made no compiled prefill call."""
    calls = delta.get("prefill.calls", 0)
    if not calls:
        return {}

    def ms(us, n=calls):
        return round(us / n / 1e3, 3) if n else None

    def by_suffix(prefix):
        return {k[len(prefix):]: v for k, v in sorted(delta.items())
                if k.startswith(prefix)}

    computed = delta.get("prefill.positions_computed", 0)
    admits = delta.get("engine.admits", 0)
    empty = by_suffix("time_us.device.empty.")
    return {
        "prefill_calls": calls,
        "admits": admits,
        "prefill_ms_per_call": ms(delta.get("time_us.prefill", 0)),
        "children_ms_per_call": {
            c: ms(delta["time_us.prefill." + c]) for c in PREFILL_CHILDREN
            if "time_us.prefill." + c in delta},
        "padding_pct": round(100.0 * (1.0 - delta.get("tokens.prefill", 0)
                                      / computed), 2) if computed else None,
        "upload_kb_per_call": round(delta.get("prefill.upload_bytes", 0)
                                    / calls / 1e3, 2),
        "lane_s_blocked": round(delta.get("prefill.lane_us_blocked", 0)
                                / 1e6, 3),
        "restarts": by_suffix("engine.restarts."),
        "device_empty_ms": {c: round(us / 1e3, 3) for c, us in empty.items()},
        "restart_ms_per_admission": ms(empty.get("restart", 0), admits),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true", help="JSON output")
    ap.add_argument("--drain-grace", type=float, default=0.0,
                    help="grace budget (seconds) for the post-run drain of "
                         "any ServingAPI the script left open (default 0: "
                         "stragglers fail with the retriable "
                         "RequestDrainedError)")
    ap.add_argument("--run", nargs=argparse.REMAINDER,
                    help="script [args...] to execute in-process; serving "
                         "counters are reported for that run, and every "
                         "ServingAPI left open is drained afterwards")
    args = ap.parse_args(argv)

    if args.run:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import runpy

        from paddle_tpu.serving import metrics, telemetry

        before = metrics.stats()
        hists_before = telemetry.histograms()
        t0 = time.perf_counter()
        sys.argv = list(args.run)
        try:
            runpy.run_path(args.run[0], run_name="__main__")
        finally:
            # shutdown epilogue: drain every ServingAPI the script left
            # open so the run always exits through the graceful path (no
            # engine holding live slots/blocks) and the drain counters are
            # part of the reported delta
            from paddle_tpu import serving

            serving.drain_all(grace=args.drain_grace)
        wall = time.perf_counter() - t0
        delta = {k: v for k, v in metrics.stats_delta(
                     before, metrics.stats(), drop_zero=True).items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
        toks = delta.get("tokens.generated", 0)
        # end-of-run arena/prefix gauges: point-in-time occupancy picture
        # (cached blocks, high-water, fragmentation), NOT differenced
        gauges = {k: v for k, v in metrics.gauges().items()
                  if k.split(".")[0] in ("arena", "prefix", "slots",
                                         "spec", "queue", "quant",
                                         "gateway", "tenant", "sampling",
                                         "constrain", "lora", "kernel",
                                         "mesh", "tier", "telemetry",
                                         "serving", "worker", "disagg",
                                         "wal")}
        # latency histograms recorded during the run (ISSUE 17): the same
        # per-run delta discipline as the counters, rendered as percentiles
        hists = telemetry.histograms_delta(hists_before)
        latency = {name: {"count": h.n,
                          "p50_ms": round(h.percentile(50) * 1e3, 3),
                          "p95_ms": round(h.percentile(95) * 1e3, 3),
                          "p99_ms": round(h.percentile(99) * 1e3, 3),
                          "mean_ms": round(h.mean() * 1e3, 3)}
                   for name, h in sorted(hists.items())}
        admission = admission_report(delta)
        rec = {"wall_secs": round(wall, 3), "stats": delta,
               "gauges": gauges, "latency": latency, "admission": admission,
               "tokens_per_sec": round(toks / wall, 2) if wall > 0 else None}
        if args.json:
            print(json.dumps(rec))
        else:
            print("\n".join([f"wall_secs: {rec['wall_secs']}",
                             f"tokens_per_sec: {rec['tokens_per_sec']}"]
                            + [f"{k}: {v}" for k, v in sorted(delta.items())]
                            + [f"gauge {k}: {v}"
                               for k, v in sorted(gauges.items())]
                            + [f"admission {k}: {v}"
                               for k, v in admission.items()]))
            table = telemetry.percentile_table(hists)
            if table:
                print(table)
        return 0

    rep = _config_report()
    if args.json:
        print(json.dumps(rep))
    else:
        for k, v in rep.items():
            print(f"{k}: {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
