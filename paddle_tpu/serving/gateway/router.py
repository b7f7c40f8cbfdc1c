"""Replica router: N engine replicas behind one submit/stream surface.

One :class:`~paddle_tpu.serving.api.ServingAPI` is one engine: one compiled
slot arena, one scheduler, one supervisor. The :class:`ReplicaPool` owns N
of them (threads sharing this process today; mesh shards when the GSPMD
refactor lands) and adds the three behaviors a fleet needs that a single
engine cannot express:

* **Routing** — each accepted request goes to the replica with the least
  outstanding work (waiting + running), with *bounded prefix-cache
  affinity*: when the radix cache is on, a replica that already holds the
  request's prompt prefix ON DEVICE may win instead, but only while its
  load is within ``FLAGS_gateway_affinity_slack`` requests of the minimum
  — warm traffic can never pile onto one replica and starve a cold tenant
  of capacity. Residency comes from the shared
  :class:`GlobalRadixIndex` (ISSUE 15): every replica's
  :class:`~..prefix_cache.PrefixCache` publishes its insert/evict/spill
  deltas of chunk-key chains, so routing consults TRUE per-replica
  residency instead of the PR 8 approximation of probing each private
  tree from the router thread. With tiering on
  (``FLAGS_serving_kv_tiering``), replicas also attach to ONE shared
  :class:`~..tiered.HostKVCache`, so a prefix prefilled on replica A is a
  host-tier hit on replica B whatever the routing decision — affinity
  then only decides who serves from HBM versus who pays one compiled
  restore.
* **Health** — replica health is driven by the supervisor's crash-loop
  state: a replica whose breaker opens (or whose pump surfaces a
  :class:`~paddle_tpu.serving.supervisor.CrashLoopError` / transient device
  error) is **ejected**. Its journaled in-flight requests re-queue onto
  healthy replicas — the same ``prompt + tokens`` journal replay the PR 5
  supervisor uses in-engine, so a re-routed stream finishes token-for-token
  identical to an uninterrupted one. The dead replica respawns after a
  doubling backoff (``FLAGS_gateway_respawn_backoff``, capped at 30s).
* **Tenancy** — every submission is charged to a tenant through
  :class:`~paddle_tpu.serving.gateway.tenancy.TenantManager` *before* any
  replica is touched, and the tenant's configured priority class rides the
  scheduler's PR 5 priority admission.

Scale-down routes through ``drain(grace)``: :meth:`ReplicaPool.scale_to`
drains the retiring replica (in-flight requests get the grace budget to
finish), then re-routes stragglers onto the survivors — autoscaling never
drops an accepted stream. ``bind_preemption_guard`` gives the whole pool
the SIGTERM-drain semantics each API already had alone.

Counters (``serving.metrics``): ``gateway.routed`` / ``gateway.rerouted``
/ ``gateway.affinity_routes`` / ``gateway.ejected`` / ``gateway.respawned``
/ ``gateway.scale_downs`` / ``gateway.drains`` / ``gateway.guard_drains``
/ ``gateway.stream_wakeups`` / ``gateway.stream_wait_timeouts``;
gauges ``gateway.replicas_healthy`` / ``gateway.replicas_total`` /
``gateway.outstanding`` / ``gateway.stream_consumers``. Ejections and
respawns mirror into ``core.resilience`` as
``serving.replica_ejections`` / ``serving.replica_respawns`` for the
shared resilience dashboards.
"""
from __future__ import annotations

import itertools
import logging
import os
import signal
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ...core import flags, resilience
from .. import metrics, telemetry
from ..api import STREAM_WAIT_S, ServingAPI
from ..scheduler import Request, RequestState, StreamSignal
from ..supervisor import CrashLoopError, is_transient_serving_error
from .tenancy import TenantManager

_logger = logging.getLogger("paddle_tpu.serving.gateway")

_RESPAWN_BACKOFF_CAP = 30.0
_REAP_EVERY = 16  # submits between abandoned-handle sweeps
#: period of a background pool's one housekeeping thread (respawn, breaker
#: sweep, observe pass, WAL heartbeat)
_HOUSEKEEPING_S = 0.005
_gw_counter = itertools.count()


class GlobalRadixIndex:
    """Cross-replica residency index over radix chunk-key chains.

    Replicas PUBLISH their device-residency deltas (radix insert /
    restore -> ``publish_insert``; evict / spill -> ``publish_evict``;
    rebuild / respawn -> ``publish_reset``) through
    :meth:`~..prefix_cache.PrefixCache.bind_index`; the router CONSULTS
    the index per candidate replica. Content-hash chunk keys are
    location-independent, so one key chain (hashed once per request)
    probes every replica. Host/disk residency is not tracked here — it
    lives in the shared tier store and is replica-independent by
    construction (:meth:`residency` folds it in for observability).

    Thread-safe: publishes arrive from every replica's pump thread,
    lookups from the router. Lookups walk the chain front-to-back and
    stop at the first non-resident key — matching the radix walk's
    longest-resident-prefix semantics exactly."""

    def __init__(self):
        self._lock = threading.Lock()
        self._replicas_of: Dict[bytes, set] = {}
        self._keys_of: Dict[int, set] = {}

    def publish_insert(self, replica: int, keys) -> None:
        with self._lock:
            mine = self._keys_of.setdefault(replica, set())
            for k in keys:
                self._replicas_of.setdefault(k, set()).add(replica)
                mine.add(k)

    def publish_evict(self, replica: int, key: bytes) -> None:
        with self._lock:
            reps = self._replicas_of.get(key)
            if reps is not None:
                reps.discard(replica)
                if not reps:
                    del self._replicas_of[key]
            mine = self._keys_of.get(replica)
            if mine is not None:
                mine.discard(key)

    def publish_reset(self, replica: int) -> None:
        with self._lock:
            for k in self._keys_of.pop(replica, ()):
                reps = self._replicas_of.get(k)
                if reps is not None:
                    reps.discard(replica)
                    if not reps:
                        del self._replicas_of[k]

    def resident_blocks(self, keys, replica: int) -> int:
        """Longest prefix of ``keys`` device-resident on ``replica``."""
        n = 0
        with self._lock:
            for k in keys:
                reps = self._replicas_of.get(k)
                if reps is None or replica not in reps:
                    break
                n += 1
        return n

    def residency(self, keys, tier=None) -> dict:
        """The full tier picture of one key chain: device blocks per
        replica, plus (with a ``tiered.TierView``) the host/disk-resident
        chain length — the ``/v1/stats`` observability payload."""
        with self._lock:
            replicas = set()
            for reps in (self._replicas_of.get(k) for k in keys):
                if reps:
                    replicas |= reps
        out = {"device": {r: self.resident_blocks(keys, r)
                          for r in sorted(replicas)}}
        if tier is not None:
            host = disk = 0
            for k in keys:
                where = tier.tier_of(k)
                if where is None:
                    break
                if where == "host":
                    host += 1
                else:
                    disk += 1
            out["host"] = host
            out["disk"] = disk
        return out

    def stats(self) -> dict:
        with self._lock:
            return {"keys": len(self._replicas_of),
                    "replicas": {r: len(ks)
                                 for r, ks in self._keys_of.items() if ks}}


class NoHealthyReplicaError(RuntimeError):
    """Every replica is ejected, draining, or removed. Retriable — the
    router's respawn loop brings ejected replicas back after their backoff;
    the gateway maps this to HTTP 503 with a Retry-After hint."""


#: backend failures the router answers with a re-route instead of failing
#: the gateway request: the replica died (crash loop / transient device
#: error that escaped the supervisor) or was intentionally drained away
#: under the request (scale-down, ejection sweep)
def _is_reroutable(exc: BaseException) -> bool:
    return (isinstance(exc, (CrashLoopError,
                             resilience.RequestDrainedError))
            or is_transient_serving_error(exc))


class _Replica:
    """One engine replica plus its health record. ``generation`` bumps on
    every respawn so stale routed requests can't mis-attribute a fresh
    api's failures to the incarnation that died."""

    def __init__(self, idx: int, api: ServingAPI):
        self.idx = idx
        self.api = api
        self.healthy = True
        self.draining = False   # scale-down in progress: no new routes
        self.removed = False    # scaled away for good
        self.generation = 0
        self.ejections = 0      # lifetime; drives the respawn backoff
        self.ejected_at = 0.0
        self.backoff = 0.0
        self.respawning = False  # claimed by one respawner at a time

    def outstanding(self) -> int:
        return self.api.outstanding()

    def routable(self) -> bool:
        return self.healthy and not self.draining and not self.removed


class RoutedRequest:
    """The gateway-side handle for one stream: survives replica ejection
    and scale-down by carrying its own token journal across backends.

    ``tokens()`` is the single source of truth the streaming surface reads:
    tokens from dead backends (``_base``) plus the live backend's tokens
    past the journal it was seeded with. Re-routing swaps the backend under
    the lock; because the journal snapshot is taken at swap time from the
    backend's append-only token list, a consumer's view is monotone — no
    token is ever re-delivered or skipped across a re-route."""

    def __init__(self, pool: "ReplicaPool", prompt: np.ndarray,
                 max_new_tokens: int, stop_token_id: Optional[int],
                 tenant: str, priority: int,
                 deadline: resilience.Deadline, request_id: str,
                 sampling=None, constraint=None, adapter: int = 0):
        self.pool = pool
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.stop_token_id = stop_token_id
        self.tenant = tenant
        self.priority = int(priority)
        # decode-scenario state rides the handle so a re-route re-submits
        # the SAME scenario: positional sampling keys + a journal-rebuilt
        # constraint walker make the resumed stream token-identical
        self.sampling = sampling
        self.constraint = constraint
        self.adapter = int(adapter)
        self.deadline = deadline
        self.request_id = request_id or f"gw-{next(_gw_counter)}"
        # ONE lifecycle trace for the whole handle: every backend Request
        # this handle ever routes to (re-routes included) carries this id,
        # so eject -> re-route -> replay reads as one span timeline
        self.trace_id = telemetry.mint_trace_id()
        self.reroutes = 0
        self.state = RequestState.QUEUED
        self.error: Optional[BaseException] = None
        self.done_event = threading.Event()
        # what a background pool's stream consumer blocks on: handed to
        # every backend this handle rides (its tokens and its terminal
        # state fire it) and fired by the router whenever it changes the
        # handle under the consumer (attach, finalize, cancel)
        self.signal = StreamSignal()
        self._lock = threading.Lock()
        self._base: List[int] = []      # tokens from previous backends
        self._backend: Optional[Request] = None
        self._backend_journal = 0       # len of journal the backend carries
        self._replica_idx = -1
        self._replica_gen = -1
        self._released = False          # tenant release happened exactly once
        self._cancelled = False         # survives re-routes (backend _cancel
        self._rerouting = False         # does not); one re-route at a time
        # WAL bookkeeping (ISSUE 20): how many of this stream's tokens the
        # gateway WAL has journaled, under its own lock — the sweep (pump
        # thread) and the finalize tail write (any consumer thread) must
        # never journal the same delta twice
        self._wal_lock = threading.Lock()
        self._wal_logged = 0
        self._wal_accepted = False      # ACCEPTED record durably appended
        self._wal_terminal = False      # TERMINAL record written exactly once

    # ------------------------------------------------------------- reading

    def tokens(self) -> List[int]:
        """All generated tokens so far (journal + live backend, deduped)."""
        return self.tokens_from(0)

    def tokens_from(self, offset: int) -> List[int]:
        """Tokens past ``offset`` — what an incremental consumer reads per
        poll (a full-list copy per iteration would make a long stream
        O(n^2) while holding the lock)."""
        with self._lock:
            n_base = len(self._base)
            out = list(self._base[offset:]) if offset < n_base else []
            if self._backend is not None:
                start = self._backend_journal + max(0, offset - n_base)
                out.extend(self._backend.tokens[start:])
            return out

    def output_ids(self) -> np.ndarray:
        """prompt + generated tokens (``generate()``'s contract without the
        post-stop fill) — token-for-token identical across re-routes."""
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens(), np.int32)])

    @property
    def finished(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.FAILED)

    def cancel(self) -> None:
        """Flag the stream for cancellation. The flag lives on the GATEWAY
        handle, not just the backend request — a cancel that races a
        re-route (ejection, scale-down) must stick to the replacement
        backend too, not silently resurrect the stream."""
        with self._lock:
            self._cancelled = True
            backend = self._backend
        if backend is not None:
            backend.cancel()
        self.signal.fire()

    # ------------------------------------------------------------ plumbing

    def _attach(self, backend: Request, replica: "_Replica",
                journal_len: int) -> None:
        with self._lock:
            self._backend = backend
            self._backend_journal = int(journal_len)
            self._replica_idx = replica.idx
            self._replica_gen = replica.generation
            # under the lock: a check-then-set outside it races _finalize —
            # a cancel/failure finalizing between the check and the set
            # would be overwritten back to RUNNING, resurrecting a stream
            # every consumer already saw reach a terminal state
            if self.state == RequestState.QUEUED:
                self.state = RequestState.RUNNING
        # from here the backend's tokens and its terminal state wake this
        # handle's consumers; the fire covers whatever it emitted before
        backend.signal = self.signal
        self.signal.fire()

    def _detach_journal(self) -> List[int]:
        """Fold the (dead) backend's tokens into the journal and detach;
        returns the full journal the replacement backend resumes from."""
        with self._lock:
            if self._backend is not None:
                self._base.extend(
                    self._backend.tokens[self._backend_journal:])
                self._backend = None
            return list(self._base)

    def _finalize(self, state: str,
                  error: Optional[BaseException] = None) -> None:
        with self._lock:
            if self.finished:
                return
            if self._backend is not None:
                self._base.extend(
                    self._backend.tokens[self._backend_journal:])
                self._backend = None
            self.state = state
            self.error = error
        self.done_event.set()
        self.signal.fire()


class ReplicaPool:
    """N ServingAPI replicas behind one tenant-aware routed front door.

    ``model`` is either a model instance (shared read-only by every
    replica's engine — the single-host case) or a zero-arg factory called
    per replica/respawn (the hook for per-replica mesh shards).
    ``background=True`` gives every replica its own pump thread (what the
    HTTP gateway runs on); ``background=False`` keeps pumping in the
    consumer's thread — deterministic, what the tests and bench drive."""

    #: a background pool runs ONE housekeeping thread (respawn, breaker
    #: sweep, observe pass, WAL heartbeat) so that its stream consumers do
    #: none of it; subclasses with their own supervision loop (the process
    #: pools' watchdog) turn this off and do the same work from there
    _own_housekeeping = True

    def __init__(self, model, replicas: Optional[int] = None,
                 config=None, tenants: Optional[TenantManager] = None,
                 background: bool = False,
                 affinity_slack: Optional[int] = None,
                 respawn_backoff: Optional[float] = None,
                 max_reroutes: Optional[int] = None,
                 max_queue: Optional[int] = None, wal=None, **engine_kw):
        n = int(flags.flag("serving_replicas")
                if replicas is None else replicas)
        if n < 1:
            raise ValueError("a ReplicaPool needs at least one replica")
        # a zero-arg factory builds one model per replica (mesh shards
        # later); a model INSTANCE (itself callable — nn.Layer.__call__ is
        # forward) is shared read-only by every replica's engine
        self._factory: Callable[[], object] = (
            model if callable(model) and not hasattr(model,
                                                     "functional_state")
            else (lambda: model))
        self._api_kw = dict(config=config, background=background,
                            max_queue=max_queue, **engine_kw)
        self.tenants = tenants if tenants is not None else TenantManager()
        self._affinity_slack = (int(flags.flag("gateway_affinity_slack"))
                                if affinity_slack is None
                                else int(affinity_slack))
        self._respawn_backoff = (
            float(flags.flag("gateway_respawn_backoff"))
            if respawn_backoff is None else float(respawn_backoff))
        self._max_reroutes = (int(flags.flag("gateway_max_reroutes"))
                              if max_reroutes is None else int(max_reroutes))
        self._background = bool(background)
        self._lock = threading.RLock()
        # gateway write-ahead request log (ISSUE 20): set BEFORE replicas
        # spawn so every later path may read self.wal; recovery itself is
        # kicked off at the END of construction, once routing exists
        self.wal = wal
        self._recovering = wal is not None
        self._recovered: List[RoutedRequest] = []
        self._recovered_results: Dict[str, dict] = {}
        self._wal_sweep_lock = threading.Lock()
        self._wal_last_sweep = 0.0
        # the shared cross-replica residency index (ISSUE 15): every
        # replica's prefix cache publishes insert/evict/spill deltas here;
        # routing reads it instead of probing private trees. Engines with
        # FLAGS_serving_kv_tiering also share ONE HostKVCache — either the
        # explicit tier_store engine kwarg or the process-global default —
        # so cross-replica host hits need no extra plumbing.
        self.index = GlobalRadixIndex()
        # pool-level LoRA registrations, in order: respawned replicas
        # replay them so every replica serves identical adapter ids
        self._adapters: List[tuple] = []
        self._replicas: List[_Replica] = [
            _Replica(i, self._spawn_api(i)) for i in range(n)]
        #: live (unfinished) routed requests per replica index
        self._live: Dict[int, List[RoutedRequest]] = {
            r.idx: [] for r in self._replicas}
        self._draining = False
        self._closed = False
        self._guard = None
        self._guard_grace: Optional[float] = None
        self.drain_count = 0
        self._reap_tick = 0
        self._consumers = 0  # stream() generators alive, under its own lock
        self._consumers_lock = threading.Lock()
        self._housekeeping_stop = threading.Event()
        self._housekeeper: Optional[threading.Thread] = None
        self._refresh_gauges()
        if wal is not None:
            # replay the previous incarnation's accepted streams: live
            # requests resubmit journal-seeded, terminal ids fill the
            # recovered-result cache. Background pools (the HTTP gateway)
            # recover off-thread so construction returns fast — /healthz
            # reports 503-not-ready until _recovering clears (the
            # liveness/readiness split); foreground pools recover inline
            # (tests/benches see a fully replayed pool on return).
            if self._background:
                threading.Thread(target=self._wal_recover,
                                 name="gateway-wal-recover",
                                 daemon=True).start()
            else:
                self._wal_recover()
        if self._background and self._own_housekeeping:
            # each replica's engine pumps itself and a stream consumer
            # only waits for its tokens, so everything else a fleet needs
            # done (and the WAL's commit heartbeat: durability must not
            # depend on a client blocking in stream()) runs here, once
            self._housekeeper = threading.Thread(
                target=self._housekeeping_loop, name="gateway-housekeeping",
                daemon=True)
            self._housekeeper.start()

    def _spawn_api(self, idx: int) -> ServingAPI:
        api = ServingAPI(self._factory(), **self._api_kw)
        # ordered replay of pool-level adapter registrations: the arena
        # hands out rows in registration order, so a respawned replica
        # reconstructs the exact id assignment its peers serve
        for adapter, name in self._adapters:
            api.engine.lora.register(adapter, name=name)
        # bind the residency index (resets this replica's published
        # state: a fresh/respawned engine starts device-cold; supervisor
        # rebuilds re-bind through the old cache's carried binding)
        cache = api.engine.prefix_cache
        if cache is not None:
            cache.bind_index(self.index, idx)
        return api

    def register_adapter(self, adapter, name: Optional[str] = None) -> int:
        """Install one :class:`~..adapters.LoraAdapter` on EVERY replica
        (and on every future respawn); returns the pool-wide adapter id.
        Requires the replicas' engines to carry an adapter arena
        (``FLAGS_serving_lora_rank`` > 0). Registration is value-only —
        zero recompiles on any replica."""
        with self._lock:
            if self._closed:
                raise RuntimeError("ReplicaPool is closed")
            ids = [rep.api.register_adapter(adapter, name=name)
                   for rep in self._replicas if not rep.removed]
            if not ids:
                raise NoHealthyReplicaError("no replica to register on")
            if len(set(ids)) != 1:  # ordered replay makes this impossible
                raise RuntimeError(f"replicas disagree on adapter id: {ids}")
            self._adapters.append((adapter, name))
            metrics.bump("lora.pool_registered")
            return ids[0]

    def vocab_size(self) -> int:
        """The served model's vocab size (what gateway-built constraint
        walkers size their masks to)."""
        with self._lock:
            return int(self._replicas[0].api.engine.vocab)

    # ----------------------------------------------------------- capacity

    def replicas(self) -> List[_Replica]:
        with self._lock:
            return [r for r in self._replicas if not r.removed]

    def healthy_replicas(self) -> List[_Replica]:
        with self._lock:
            return [r for r in self._replicas if r.routable()]

    def capacity(self) -> int:
        """Total decode slots across routable replicas — the fair-share
        gate's notion of what "overloaded" means."""
        return sum(r.api.engine.num_slots for r in self.healthy_replicas())

    def outstanding(self) -> int:
        return sum(r.outstanding() for r in self.healthy_replicas())

    # ------------------------------------------------------------- submit

    def submit(self, prompt, max_new_tokens: int = 32,
               stop_token_id: Optional[int] = None,
               tenant: str = "default",
               timeout: Optional[float] = None,
               request_id: str = "",
               priority: Optional[int] = None,
               sampling=None, constraint=None,
               adapter: Optional[int] = None,
               constraint_spec: Optional[dict] = None) -> RoutedRequest:
        """Admit one stream through the tenant gates and route it to a
        replica. ``priority=None`` takes the tenant's configured class —
        as do ``sampling`` (the tenant's default SamplingParams) and
        ``adapter`` (the tenant's configured LoRA row: every tenant gets
        its own fine-tune on the shared base weights). ``constraint`` is
        always per-request (a ``serving.constrain`` walker);
        ``constraint_spec`` is its serializable client spec (the gateway
        body's ``choices``/``grammar``), journaled by the WAL so a
        recovered stream can rebuild an identical walker.
        Raises :class:`core.resilience.QuotaExceededError` (tenant gates,
        retriable with ``retry_after``),
        :class:`core.resilience.QueueOverloadError` (every routable replica
        queue full), :class:`NoHealthyReplicaError` (no routable replica),
        or the retriable ``RequestDrainedError`` during a pool drain."""
        self._check_guard()
        with self._lock:
            if self._closed:
                raise RuntimeError("ReplicaPool is closed")
            if self._draining:
                raise resilience.RequestDrainedError(
                    "gateway is draining: admissions are stopped; "
                    "resubmit to another instance")
        self._maybe_respawn()
        self._sweep_health()
        self._reap()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cfg = self.tenants.admit(tenant, int(max_new_tokens),
                                 outstanding=self.outstanding(),
                                 capacity=self.capacity())
        ad = cfg.adapter if adapter is None else int(adapter)
        if not cfg.adapter_allowed(ad):
            # a per-request adapter override must be authorized for the
            # tenant: fine-tunes are tenant property, and check_live alone
            # would let any client decode through another tenant's row.
            # Never enqueued — make the tenant whole like any routing shed
            self.tenants.release(tenant, failed=True)
            self.tenants.refund(tenant, int(max_new_tokens))
            metrics.bump("lora.denied")
            raise ValueError(
                f"adapter {ad} is not authorized for tenant {tenant!r} "
                "(TenantConfig.allowed_adapters)")
        samp = cfg.sampling if sampling is None else sampling
        if samp is not None:
            # pin an unset seed at the GATEWAY handle: re-routes re-submit
            # the materialized params, so a fail-over continues the exact
            # stream instead of re-drawing entropy mid-journal
            samp = samp.materialized()
        rr = RoutedRequest(self, prompt, max_new_tokens, stop_token_id,
                           tenant, cfg.priority if priority is None
                           else int(priority),
                           resilience.Deadline.after(timeout), request_id,
                           sampling=samp,
                           constraint=constraint,
                           adapter=ad)
        # the gateway is this trace's minting site (api.submit sees a
        # non-empty trace_id and stays quiet — exactly one SUBMITTED
        # per trace)
        telemetry.span(rr.trace_id, telemetry.SUBMITTED,
                       request_id=rr.request_id, tenant=tenant,
                       prompt_tokens=int(prompt.shape[0]),
                       max_new_tokens=int(max_new_tokens))
        try:
            self._route(rr, journal=None)
        except Exception:
            # analysis: allow(broad-except) — cleanup-and-reraise: whatever
            # the routing failure, the tenant must be made whole.
            # the request was never enqueued: free the concurrency slot AND
            # refund the bucket charge — a retriable routing shed must not
            # drain a compliant tenant's rate budget (the shed contract)
            self.tenants.release(tenant, failed=True)
            self.tenants.refund(tenant, int(max_new_tokens))
            raise
        if self.wal is not None:
            # durably ACCEPTED only after routing succeeded: a shed
            # request must not be resurrected by replay. The append only
            # buffers — in-process callers hold an unacknowledged handle
            # until the next batched commit, and the HTTP front door
            # syncs it via the ack barrier BEFORE the 200 leaves
            # (gateway._submit), so an acknowledged client always finds
            # its stream after a crash. The sweep skips un-accepted
            # handles, so no EMITTED record can ever precede its
            # ACCEPTED in the log. Appending and flagging under
            # rr._wal_lock keeps a concurrent finalize's TERMINAL
            # strictly behind the ACCEPTED record.
            with rr._wal_lock:
                self.wal.accepted(rr, constraint_spec)
                rr._wal_accepted = True
            if rr.finished:
                # the stream finished (and was swept) before its
                # ACCEPTED record existed — that sweep's _wal_finalize
                # saw _wal_accepted False and skipped the TERMINAL;
                # write it now or replay resurrects a finished stream
                self._wal_finalize(rr)
        metrics.bump("gateway.routed")
        return rr

    def _route(self, rr: RoutedRequest,
               journal: Optional[Sequence[int]]) -> None:
        """Place ``rr`` on the best replica (least outstanding work, warm
        radix cache within the bounded slack); falls through the candidate
        order when the preferred replica's queue sheds. Re-routes
        (``journal`` not None) bypass per-replica queue shedding — the
        request was already accepted once."""
        candidates = self._candidates(rr)
        last_exc: Optional[BaseException] = None
        budget = self._backend_budget(rr, journal)
        for rep in candidates:
            try:
                backend = rep.api.submit(
                    rr.prompt, max_new_tokens=budget,
                    stop_token_id=rr.stop_token_id,
                    timeout=(None if rr.deadline.expires_at is None
                             else max(0.001, rr.deadline.remaining())),
                    request_id=f"{rr.request_id}.{rr.reroutes}",
                    priority=rr.priority, journal=journal,
                    shed=journal is None, sampling=rr.sampling,
                    constraint=rr.constraint, adapter=rr.adapter,
                    trace_id=rr.trace_id)
            except (resilience.QueueOverloadError,
                    resilience.RequestDrainedError) as e:
                last_exc = e  # replica-local condition: try the next one
                continue
            rr._attach(backend, rep, len(journal) if journal else 0)
            if rr._cancelled:
                backend.cancel()  # cancel raced the attach: make it stick
            with self._lock:
                bucket = self._live.setdefault(rep.idx, [])
                if rr not in bucket:  # membership, not multiplicity: a
                    bucket.append(rr)  # double-routed handle must not need
            self._refresh_gauges()     # two finalizes to leave the pool
            return
        raise last_exc if last_exc is not None else NoHealthyReplicaError(
            "no healthy serving replica (all ejected, draining, or "
            "removed); retry after the respawn backoff")

    def _backend_budget(self, rr: RoutedRequest,
                        journal: Optional[Sequence[int]]) -> int:
        """The ``max_new_tokens`` the BACKEND submit is given. The base
        pool always hands over the request's full budget; a role-typed
        pool (disagg) caps a prefill-phase placement at first-token so
        the prefill worker finishes its backend request at the handoff
        point. Never mutates ``rr.max_new_tokens`` — the reroute/handoff
        completion checks compare the journal against the REQUEST's
        budget, not any one placement's."""
        return rr.max_new_tokens

    def _candidates(self, rr: RoutedRequest) -> List[_Replica]:
        """Routable replicas, best first: least outstanding work, with the
        bounded warm-cache preference applied to the front of the order.
        Warmth is TRUE device residency from the shared
        :class:`GlobalRadixIndex` (replicas publish their radix deltas),
        not a cross-thread probe of each replica's private tree — and it
        is deliberately DEVICE-only: host/disk tier residency is shared by
        every replica, so it cannot differentiate candidates (a cold-HBM
        route still hits the host tier and pays one compiled restore
        instead of a prefill)."""
        reps = self.healthy_replicas()
        if not reps:
            raise NoHealthyReplicaError(
                "no healthy serving replica (all ejected, draining, or "
                "removed); retry after the respawn backoff")
        load = {r.idx: r.outstanding() for r in reps}
        reps.sort(key=lambda r: (load[r.idx], r.idx))
        slack = self._affinity_slack
        if slack > 0 and len(reps) > 1:
            keys = self._prefix_keys(rr, reps[0])
            if keys:
                floor = load[reps[0].idx]
                best, best_blocks = None, 0
                for r in reps:
                    if load[r.idx] > floor + slack:
                        continue  # bounded: never pile onto a busy replica
                    blocks = self.index.resident_blocks(keys, r.idx)
                    if blocks > best_blocks:
                        best, best_blocks = r, blocks
                if best is not None and best is not reps[0]:
                    reps.remove(best)
                    reps.insert(0, best)
                    metrics.bump("gateway.affinity_routes")
        return reps

    def _prefix_keys(self, rr: RoutedRequest, rep: _Replica):
        """Memoized chunk-key chain for the request's prompt (PR 6's
        residency probe): content hashes depend only on tokens and block
        size, so one chain probes every replica's tree."""
        cache = rep.api.engine.prefix_cache
        if cache is None:
            return None
        keys = getattr(rr, "_prefix_keys", None)
        if keys is None:
            keys = cache.chunk_keys(rr.prompt)
            rr._prefix_keys = keys
        return keys

    # ---------------------------------------------------- health / reroute

    def _sweep_health(self) -> None:
        """Eject any replica whose supervisor breaker is open — the router
        reads the crash-loop state directly instead of waiting for the next
        request to fail through it."""
        for rep in self.healthy_replicas():
            if rep.api.supervisor.breaker_open:
                self._eject(rep, CrashLoopError(
                    f"replica {rep.idx} crash-loop breaker open"))

    def _eject(self, rep: _Replica, cause: BaseException) -> None:
        """Take a crash-looping replica out of rotation: mark it ejected
        (respawn after backoff), re-queue its journaled in-flight requests
        onto healthy replicas, then close the dead API (zero-grace drain —
        already-detached backends fail harmlessly)."""
        with self._lock:
            if not rep.healthy or rep.removed:
                return
            rep.healthy = False
            rep.ejections += 1
            rep.ejected_at = time.monotonic()
            rep.backoff = min(_RESPAWN_BACKOFF_CAP,
                              self._respawn_backoff
                              * (2 ** (rep.ejections - 1)))
            live = [r for r in self._live.get(rep.idx, ())
                    if not r.finished]
            self._live[rep.idx] = []
        _logger.warning(
            "ejecting serving replica %d (%d in flight re-queued, respawn "
            "in %.2fs): %r", rep.idx, len(live), rep.backoff, cause)
        metrics.bump("gateway.ejected")
        resilience.bump("serving.replica_ejections")
        for rr in live:
            self._reroute(rr)
        try:
            rep.api.close()
        except Exception:  # analysis: allow(broad-except) — the replica is
            # already out of rotation; a dead engine failing its own close
            # must not abort the ejection that is removing it
            _logger.exception("closing ejected replica %d failed", rep.idx)
        self._refresh_gauges()

    def _reroute(self, rr: RoutedRequest) -> None:
        """Move one in-flight request to a healthy replica, resuming from
        its token journal (token-for-token parity — the cross-replica twin
        of the supervisor's in-engine replay). Serialized per request: an
        ejection sweep and a consumer's `_observe` may both decide to move
        the same stream — only one wins, and a request whose backend was
        already replaced (alive again on a healthy replica) is never
        detached a second time (that would orphan the live backend and
        double-decode the stream)."""
        with self._lock:
            if rr.finished or rr._rerouting:
                return
            rr._rerouting = True
        try:
            with rr._lock:
                backend = rr._backend
            if backend is not None and not backend.finished:
                # a concurrent re-route already moved it — OR the backend
                # was enqueued on the ejecting replica after its pump died
                # (submit racing eject) and is about to be drain-failed by
                # close(). Either way the handle must stay registered in
                # its replica's live bucket, or no reap/observe would ever
                # finalize it (leaking its tenant concurrency slot)
                with self._lock:
                    bucket = self._live.setdefault(rr._replica_idx, [])
                    if rr not in bucket:
                        bucket.append(rr)
                return
            self._reroute_locked(rr)
        finally:
            rr._rerouting = False

    def _reroute_locked(self, rr: RoutedRequest) -> None:
        if rr._cancelled:
            # a cancel acknowledged before/through the failure must stick:
            # resurrecting the stream on a fresh replica would decode
            # output nobody wants and charge the tenant for it
            self._finalize(rr, RequestState.CANCELLED)
            return
        journal = rr._detach_journal()
        stop = rr.stop_token_id
        if (len(journal) >= rr.max_new_tokens
                or (stop is not None and journal and journal[-1] == stop)):
            # the journal already completes the stream: the replica died on
            # the very step that finished it — nothing left to decode
            self._finalize(rr, RequestState.FINISHED)
            return
        if rr.reroutes >= self._max_reroutes:
            self._finalize(rr, RequestState.FAILED, NoHealthyReplicaError(
                f"{rr.request_id} re-routed {rr.reroutes} times "
                f"(FLAGS_gateway_max_reroutes); giving up"))
            return
        rr.reroutes += 1
        # the span marks the DECISION, before the re-submit, so the
        # timeline reads REROUTED -> QUEUED -> ADMITTED on the survivor
        # (docs/observability.md); a failed re-route shows REROUTED
        # followed by FAILED — the attempt is part of the story
        telemetry.span(rr.trace_id, telemetry.REROUTED,
                       request_id=rr.request_id, reroute=rr.reroutes,
                       from_replica=rr._replica_idx,
                       journal_tokens=len(journal))
        self._wal_moved(rr, "REROUTE")
        try:
            self._route(rr, journal=journal)
        except Exception as e:  # analysis: allow(broad-except) — any
            # re-route failure must finalize the handle (tenant slot
            # freed, done_event fired), never strand it in no bucket
            self._finalize(rr, RequestState.FAILED, e)
            return
        metrics.bump("gateway.rerouted")

    def _maybe_respawn(self) -> None:
        """Bring ejected replicas back once their backoff elapsed (a fresh
        ServingAPI: compiled programs reload from the persistent compile
        cache, the KV arena starts empty)."""
        now = time.monotonic()
        with self._lock:
            if self._draining or self._closed:
                return  # a draining pool must not spawn fresh admitters
            due = [r for r in self._replicas
                   if not r.healthy and not r.removed and not r.draining
                   and not r.respawning
                   and now >= r.ejected_at + r.backoff]
            for r in due:
                # claimed under the lock: two concurrent pumps seeing the
                # same expired backoff must not BOTH spawn an API (the
                # loser's engine + pump thread would leak unreferenced)
                r.respawning = True
        for rep in due:
            try:
                api = self._spawn_api(rep.idx)
            except Exception:  # analysis: allow(broad-except) — engine
                # construction can die arbitrarily on a sick device; a
                # failed respawn re-enters backoff instead of crashing
                # the pump that happened to trigger it
                _logger.exception("respawn of replica %d failed; backing "
                                  "off again", rep.idx)
                with self._lock:
                    rep.ejected_at = time.monotonic()
                    rep.backoff = min(_RESPAWN_BACKOFF_CAP, rep.backoff * 2)
                    rep.respawning = False
                continue
            with self._lock:
                if rep.removed or rep.draining or self._draining \
                        or self._closed:
                    # scale_to / drain retired this replica while the fresh
                    # API was being built: installing it would resurrect a
                    # removed replica and leak a live engine past close()
                    rep.respawning = False
                    stillborn = api
                else:
                    rep.api = api
                    rep.generation += 1
                    rep.healthy = True
                    rep.respawning = False
                    stillborn = None
            if stillborn is not None:
                try:
                    stillborn.close()
                except Exception:  # analysis: allow(broad-except) — best-
                    pass           # effort teardown of a never-installed API
                continue
            _logger.info("respawned serving replica %d (generation %d)",
                         rep.idx, rep.generation)
            metrics.bump("gateway.respawned")
            resilience.bump("serving.replica_respawns")
        if due:
            self._refresh_gauges()

    # ------------------------------------------------------------ progress

    def _observe(self, rr: RoutedRequest) -> None:
        """Reconcile one routed request with its backend: propagate finish,
        convert a re-routable backend failure (crash loop, drain-under-me,
        transient device error) into an ejection + re-route."""
        if rr.finished:
            return
        with rr._lock:
            backend = rr._backend
            rep_idx, rep_gen = rr._replica_idx, rr._replica_gen
        if backend is None or not backend.finished:
            return
        if backend.state == RequestState.FINISHED:
            self._finalize(rr, RequestState.FINISHED)
        elif backend.state == RequestState.CANCELLED:
            self._finalize(rr, RequestState.CANCELLED)
        else:
            err = backend.error
            if self._draining or err is None or not _is_reroutable(err):
                self._finalize(rr, RequestState.FAILED, err)
                return
            rep = self._replica_at(rep_idx)
            if (rep is not None and rep.generation == rep_gen
                    and rep.healthy and not rep.draining
                    and not isinstance(err, resilience.RequestDrainedError)):
                # the replica this died on is still in rotation: the crash
                # surfaced through the request before any sweep — eject it
                # (which re-routes every live request it holds, this one
                # included)
                self._eject(rep, err)
            else:
                # replica already ejected/draining/respawned under us (or
                # intentionally drained for scale-down): just move this one
                self._reroute(rr)

    def _reap(self) -> None:
        """Finalize abandoned handles whose backends already reached a
        terminal state (an SSE client that hung up, a submit that was never
        streamed): without a consumer calling ``_observe``, their tenant
        concurrency slot and ``_live`` entry would leak forever. Throttled
        to every ``_REAP_EVERY`` submits — a full sweep per submit would
        make admission latency O(live handles); the sweep is a backstop
        (the disconnect path finalizes its own handle eagerly)."""
        self._reap_tick += 1
        if self._reap_tick % _REAP_EVERY:
            return
        self._observe_live()

    def _replica_at(self, idx: int) -> Optional[_Replica]:
        with self._lock:
            for r in self._replicas:
                if r.idx == idx:
                    return r
        return None

    def _finalize(self, rr: RoutedRequest, state: str,
                  error: Optional[BaseException] = None) -> None:
        rr._finalize(state, error)
        self._wal_finalize(rr)
        with self._lock:
            bucket = self._live.get(rr._replica_idx)
            if bucket is not None and rr in bucket:
                bucket.remove(rr)
            release = not rr._released
            rr._released = True
        if release:
            self.tenants.release(
                rr.tenant,
                tokens_out=len(rr.tokens()),
                failed=state != RequestState.FINISHED)
        self._refresh_gauges()

    # ----------------------------------------------------------------- wal

    def _wal_moved(self, rr: RoutedRequest, kind: str) -> None:
        if self.wal is not None and rr._wal_accepted:
            self.wal.moved(rr.request_id, kind)

    def _wal_emit(self, rr: RoutedRequest) -> None:
        """Journal one stream's new tokens since the last sweep (one
        EMITTED delta per stream per pump iteration, not per token)."""
        wal = self.wal
        if wal is None or not rr._wal_accepted:
            return
        with rr._wal_lock:
            if rr._wal_terminal:
                return
            new = rr.tokens_from(rr._wal_logged)
            if new:
                wal.emitted(rr.request_id, new)
                rr._wal_logged += len(new)

    def _wal_finalize(self, rr: RoutedRequest) -> None:
        """Journal the TERMINAL record exactly once: the token tail past
        the last EMITTED delta plus the full stream for the bounded
        result cache."""
        wal = self.wal
        if wal is None:
            return
        with rr._wal_lock:
            # _wal_accepted is read under the lock: submit sets it in
            # the same critical section as the ACCEPTED append, so a
            # TERMINAL can never land ahead of (or instead of) it
            if not rr._wal_accepted or rr._wal_terminal:
                return
            rr._wal_terminal = True
            tail = rr.tokens_from(rr._wal_logged)
            rr._wal_logged += len(tail)
            wal.terminal(rr.request_id, rr.state, tail, rr.tokens())

    def _wal_sweep(self, final: bool = False) -> None:
        """One WAL pump iteration: journal every live stream's token
        delta, then ONE batched flush+fsync (``commit``, which also
        rotates/compacts segments). Throttled and contended-skip — many
        consumer threads drive ``_pump`` concurrently on a background
        pool, and per-token fsyncs would put disk latency on the submit
        path. ``final=True`` (drain/close) always runs to completion.
        Doubles as the ``gateway_kill`` chaos site: the probe SIGKILLs
        THIS process at the sweep boundary — exactly the torn-tail
        crash point the replay discipline is built for."""
        wal = self.wal
        if wal is None:
            return
        if resilience.maybe_fault("gateway_kill"):
            os.kill(os.getpid(), signal.SIGKILL)
        if not self._wal_sweep_lock.acquire(blocking=final):
            return  # another thread is mid-sweep: its commit covers us
        try:
            now = time.monotonic()
            if not final and now - self._wal_last_sweep < 0.01:
                return
            self._wal_last_sweep = now
            with self._lock:
                live = [rr for bucket in self._live.values()
                        for rr in bucket]
            for rr in live:
                self._wal_emit(rr)
            wal.commit()
        finally:
            self._wal_sweep_lock.release()

    def _observe_live(self) -> None:
        with self._lock:
            live = [rr for bucket in self._live.values() for rr in bucket]
        for rr in live:
            self._observe(rr)

    def _housekeeping_loop(self) -> None:
        """A background pool's one housekeeping thread: bring ejected
        replicas back, eject those whose breaker opened, reconcile every
        live stream with its backend (a finished stream gets finalized,
        and its TERMINAL record, with no consumer attached) and run one
        batched WAL sweep+commit — what every consumer's wait loop used
        to do a thousand times a second. Runs through a graceful drain
        (in-flight streams keep their commit cadence) and ends when the
        drain does; ``drain()`` runs the final sweep itself."""
        while not self._housekeeping_stop.wait(_HOUSEKEEPING_S):
            try:
                self._maybe_respawn()
                self._sweep_health()
                self._observe_live()
                self._wal_sweep()
            # analysis: allow(broad-except) — nothing else would respawn a
            # replica or commit the WAL: a failed sweep must leave the
            # thread alive for the next one
            except Exception:
                _logger.exception("gateway housekeeping sweep failed")

    def _wal_recover(self) -> None:
        """Replay the WAL's recovered state into this pool: live streams
        resubmit journal-seeded (the existing ``_route(journal=...,
        shed=False)`` contract — token-identical, zero new compiled
        programs), terminal ids fill the recovered-result cache the
        gateway serves ``/v1/result`` from. Always clears
        ``_recovering`` — readiness must flip even if replay fails."""
        try:
            state = self.wal.recover()
            self._recovered_results = state["results"]
            for rec in state["live"]:
                try:
                    self._resubmit_recovered(rec)
                # analysis: allow(broad-except) — one unrecoverable
                # stream (e.g. its adapter no longer registered) must
                # not abort the replay of every other accepted stream
                except Exception:
                    _logger.exception("WAL recovery of %r failed",
                                      rec.get("rid"))
            if state["live"] or state["results"]:
                _logger.info(
                    "gateway WAL recovery: %d live stream(s) resubmitted "
                    "journal-seeded, %d terminal result(s) cached",
                    len(state["live"]), len(state["results"]))
        finally:
            self._recovering = False
            self._refresh_gauges()

    def _resubmit_recovered(self, rec: dict) -> None:
        """Rebuild one WAL-live stream and re-route it with its journal.
        The recorded request keeps its id, trace, pinned sampling seed,
        rebuilt constraint walker, and disagg phase; tenant accounting is
        re-charged (rebuilding the buckets the crash destroyed) — a
        recovery-time quota shed keeps the stream alive uncharged rather
        than dropping an already-accepted request."""
        rid = rec["rid"]
        sampling = None
        if rec.get("samp"):
            from ..sampling import SamplingParams

            sampling = SamplingParams(**rec["samp"])
        constraint = None
        if rec.get("cspec"):
            from .wal import build_constraint

            constraint = build_constraint(rec["cspec"], self.vocab_size())
        charged = True
        try:
            self.tenants.admit(rec["tenant"], int(rec["mnt"]),
                               outstanding=self.outstanding(),
                               capacity=self.capacity())
        except resilience.QuotaExceededError:
            charged = False
        rr = RoutedRequest(self, np.asarray(rec["prompt"], np.int32),
                           int(rec["mnt"]), rec.get("stop"),
                           rec["tenant"], int(rec.get("prio", 0)),
                           resilience.Deadline.after(None), rid,
                           sampling=sampling, constraint=constraint,
                           adapter=int(rec.get("adapter", 0)))
        if rec.get("tid"):
            rr.trace_id = rec["tid"]  # one timeline across the crash
        toks = [int(t) for t in rec.get("toks", ())]
        rr._base = list(toks)
        rr._wal_logged = len(toks)  # the WAL already holds these tokens
        rr._wal_accepted = True     # ...and the ACCEPTED record
        if not charged:
            rr._released = True     # never charged -> never released
        if rec.get("phase") == "decode":
            rr._disagg_phase = "decode"  # restore, don't re-prefill
        telemetry.span(rr.trace_id, telemetry.RECOVERED,
                       request_id=rid, tenant=rr.tenant,
                       journal_tokens=len(toks))
        metrics.bump("gateway.recovered")
        with self._lock:
            self._recovered.append(rr)
        stop = rr.stop_token_id
        if (len(toks) >= rr.max_new_tokens
                or (stop is not None and toks and toks[-1] == stop)):
            # the journal already completes the stream: the crash landed
            # between the final token and its TERMINAL record
            self._finalize(rr, RequestState.FINISHED)
            return
        try:
            # an explicit (possibly empty) journal list: shed=False — a
            # recovered stream was already accepted once and must not
            # re-enter admission shedding
            self._route(rr, journal=list(toks))
        except Exception as e:  # analysis: allow(broad-except) — any
            # placement failure must finalize the handle (done_event
            # fired, WAL terminal written), never strand it bucketless
            self._finalize(rr, RequestState.FAILED, e)

    def recovered_live(self) -> List[RoutedRequest]:
        """Streams the WAL replay resubmitted (live and since-finished) —
        the gateway folds these into its id registry so duplicate-id
        rejection and /v1/stream attach work across the restart."""
        with self._lock:
            return list(self._recovered)

    def recovered_results(self) -> Dict[str, dict]:
        """Terminal results replayed from the WAL: ``{request_id:
        {"state", "tokens"}}`` — the exactly-once ``/v1/result`` cache."""
        return dict(self._recovered_results)

    @property
    def recovering(self) -> bool:
        """True while WAL replay / recovered-stream resubmission is in
        flight — the gateway's readiness gate (503 + Retry-After)."""
        return self._recovering

    # ------------------------------------------------------------ pumping

    def pump_once(self) -> None:
        """Foreground event loop: one guarded scheduler step on every
        routable replica with work. A step that surfaces a crash-loop /
        transient error ejects that replica (re-routing its requests); the
        pool keeps serving on the survivors."""
        if self._check_guard():
            return
        self._maybe_respawn()
        for rep in self.healthy_replicas():
            self._pump_replica(rep)
        self._wal_sweep()

    def _pump_replica(self, rep: _Replica) -> None:
        """One guarded foreground step on a single replica (the chaos
        bench drives this directly to confine injected faults to one
        replica's supervisor)."""
        if rep.api._thread is not None:
            return  # background replica pumps itself
        if not rep.api.scheduler.has_work():
            return
        try:
            rep.api._pump_once()
        # analysis: allow(broad-except) — classification inside:
        # reroutable failures eject the replica, the rest re-raise
        except Exception as e:
            if _is_reroutable(e):
                self._eject(rep, e)
            else:
                raise

    def _wait_for(self, rr: RoutedRequest, seen: int) -> bool:
        """One turn of a consumer with nothing to read. On a foreground
        pool the consumer IS the pump: step every replica. On a
        background pool the replicas pump themselves and housekeeping has
        its own thread, so block until ``rr``'s signal fires past
        ``seen`` (a token, a terminal backend, a re-route, a finalize, a
        cancel); the timeout is the liveness backstop. False if the turn
        ended by the backstop with nothing new."""
        if not self._background:
            self.pump_once()
            return True
        fired = rr.signal.wait(seen, STREAM_WAIT_S)
        metrics.bump("gateway.stream_wakeups")
        if not fired:
            metrics.bump("gateway.stream_wait_timeouts")
        return fired

    def _count_consumer(self, delta: int) -> None:
        with self._consumers_lock:
            self._consumers += delta
            metrics.set_gauge("gateway.stream_consumers", self._consumers)

    def stream(self, rr: RoutedRequest, idle_turns: bool = False):
        """Yield ``rr``'s tokens as they are generated — across replica
        ejections and re-routes. Raises the request's error at the end of
        a failed stream (mirrors ``ServingAPI.stream``). With
        ``idle_turns`` a wait that ends by the backstop yields ``None``:
        the gateway's stream handler writes a comment line then, the only
        way it learns that the client of a QUEUED request has left."""
        sent = 0
        self._count_consumer(+1)
        try:
            while True:
                seen = rr.signal.seq  # before the read: a fire after it
                for tok in rr.tokens_from(sent):  # cuts the wait short
                    yield int(tok)
                    sent += 1
                if rr.finished:
                    break
                self._observe(rr)
                if rr.finished:
                    continue  # flush tokens folded in by the finalize
                if not self._wait_for(rr, seen) and idle_turns:
                    yield None
            # drain any tokens recorded between the last read and the
            # finalize
            for tok in rr.tokens_from(sent):
                yield int(tok)
                sent += 1
        finally:
            self._count_consumer(-1)
        if rr.state == RequestState.FAILED and rr.error is not None:
            raise rr.error

    def result(self, rr: RoutedRequest,
               timeout: Optional[float] = None) -> np.ndarray:
        """Block until ``rr`` finishes; returns prompt+generated ids."""
        deadline = resilience.Deadline.after(timeout)
        while not rr.finished:
            deadline.check(f"result({rr.request_id})")
            self._observe(rr)
            if rr.finished:
                break
            if self._background:
                rr.done_event.wait(0.01)
            else:
                self.pump_once()
        if rr.state == RequestState.FAILED:
            raise rr.error
        if rr.state == RequestState.CANCELLED:
            raise RuntimeError(f"{rr.request_id} was cancelled")
        return rr.output_ids()

    def run_until_idle(self) -> None:
        """Pump every replica until no routed request is live (foreground
        helper for tests/benches)."""
        while True:
            self._observe_live()
            with self._lock:
                live = [rr for bucket in self._live.values()
                        for rr in bucket]
            if not live:
                return
            if self._background:
                live[0].done_event.wait(0.01)
            else:
                self.pump_once()

    # ------------------------------------------------------- drain / scale

    def drain(self, grace: Optional[float] = None,
              reason: str = "gateway drain") -> None:
        """Gateway-wide graceful shutdown: stop admissions, drain every
        replica within the shared ``grace`` budget (default
        ``FLAGS_serving_drain_grace``), then fail stragglers with the
        retriable ``RequestDrainedError``. Idempotent."""
        if grace is None:
            grace = float(flags.flag("serving_drain_grace"))
        grace = max(0.0, float(grace))
        with self._lock:
            if self._draining:
                return
            self._draining = True
        self.drain_count += 1
        metrics.bump("gateway.drains")
        deadline = resilience.Deadline.after(grace)
        for rep in self.replicas():
            if rep.healthy:
                rep.api.drain(max(0.0, min(grace, deadline.remaining())),
                              reason=reason)
        # every backend is now terminal: reconcile the routed handles (the
        # _draining flag makes _observe propagate RequestDrainedError
        # instead of re-routing)
        with self._lock:
            live = [rr for bucket in self._live.values() for rr in bucket]
        for rr in live:
            self._observe(rr)
            if not rr.finished:
                self._finalize(rr, RequestState.FAILED,
                               resilience.RequestDrainedError(
                                   f"{reason}: request drained before "
                                   f"completion (grace={grace:g}s); safe "
                                   f"to resubmit"))
        # the terminal sweep: every TERMINAL record written above reaches
        # disk NOW — before close() tears anything else down (satellite 2:
        # a clean shutdown never leaves live-looking records)
        self._wal_sweep(final=True)
        self._housekeeping_stop.set()
        self._refresh_gauges()

    def close(self) -> None:
        """Drain with zero grace and close every replica. Idempotent.
        The drain's final WAL sweep (terminal records + fsync) runs
        BEFORE any replica teardown; the WAL file handle itself closes
        last, after every path that could still append is gone."""
        if self._closed:
            return
        self.drain(grace=0.0, reason="ReplicaPool is closed")
        for rep in self.replicas():
            try:
                rep.api.close()
            except Exception:  # analysis: allow(broad-except) — pool close
                # must close every OTHER replica even if one dies closing
                _logger.exception("closing replica %d failed", rep.idx)
        with self._lock:
            self._closed = True
        hk = self._housekeeper
        if hk is not None and hk is not threading.current_thread():
            hk.join(timeout=2.0)  # its last sweep must not meet a closed WAL
        if self.wal is not None:
            self.wal.close()

    def scale_to(self, n: int, grace: Optional[float] = None) -> None:
        """Scale the pool down to ``n`` replicas through ``drain(grace)``:
        each retiring replica stops taking new routes, pumps its in-flight
        requests to completion within the grace budget, and any stragglers
        re-route onto the survivors — no accepted stream is dropped.
        (Scale-UP is just respawn capacity: ejected replicas come back on
        their own; adding brand-new replicas is not supported yet.)"""
        n = int(n)
        if n < 1:
            raise ValueError("cannot scale below one replica")
        while True:
            with self._lock:
                active = [r for r in self._replicas if not r.removed]
                if len(active) <= n:
                    return
                # retire ejected (unhealthy) replicas first — scaling down
                # must never remove the last healthy replica while a dead
                # one idles toward respawn; among healthy ones, retire the
                # highest index (keeps replica 0, the most-warmed, longest)
                victim = None
                for rep in reversed(active):
                    if not rep.draining and not rep.healthy:
                        victim = rep
                        break
                if victim is None:
                    for rep in reversed(active):
                        if not rep.draining:
                            victim = rep
                            break
                if victim is None:
                    return
                victim.draining = True
            self._remove_replica(victim, grace)

    def _remove_replica(self, rep: _Replica,
                        grace: Optional[float]) -> None:
        if rep.healthy:
            rep.api.drain(grace, reason=f"replica {rep.idx} scale-down")
        with self._lock:
            live = [r for r in self._live.get(rep.idx, ())
                    if not r.finished]
            self._live[rep.idx] = []
            rep.removed = True
            rep.healthy = False
        for rr in live:
            # completed-during-drain backends just finalize; stragglers
            # failed with RequestDrainedError re-route to the survivors
            # (_observe's draining-replica branch does the re-route itself;
            # the explicit call only covers a backend that somehow never
            # reached a terminal state)
            self._observe(rr)
            if not rr.finished and rr._replica_idx == rep.idx:
                self._reroute(rr)
        try:
            rep.api.close()
        except Exception:  # analysis: allow(broad-except) — the stragglers
            # were already re-routed; a close failure must not undo the
            # scale-down bookkeeping
            _logger.exception("closing scaled-down replica %d failed",
                              rep.idx)
        metrics.bump("gateway.scale_downs")
        self._refresh_gauges()

    # ----------------------------------------------------- guard / gauges

    def bind_preemption_guard(self, guard,
                              grace: Optional[float] = None
                              ) -> "ReplicaPool":
        """SIGTERM/SIGINT drains the WHOLE pool instead of killing it
        mid-decode: every replica's in-flight work gets the grace budget,
        stragglers fail retriably — the fleet mirror of
        ``ServingAPI.bind_preemption_guard``."""
        self._guard = guard
        self._guard_grace = grace
        return self

    def _check_guard(self) -> bool:
        g = self._guard
        if g is None or self._draining or not g.requested():
            return False
        metrics.bump("gateway.guard_drains")
        self.drain(self._guard_grace,
                   reason=f"preemption requested ({g.reason or 'signal'})")
        return True

    def _refresh_gauges(self) -> None:
        with self._lock:
            total = sum(1 for r in self._replicas if not r.removed)
            healthy = sum(1 for r in self._replicas if r.routable())
        metrics.set_gauge("gateway.replicas_total", total)
        metrics.set_gauge("gateway.replicas_healthy", healthy)
        metrics.set_gauge("gateway.outstanding", self.outstanding())

    def stats(self) -> dict:
        """Pool + tenant snapshot (the ``/v1/stats`` payload next to the
        process-global ``serving.metrics`` counters). With speculative
        decoding / chunked prefill on, each replica row carries its
        engine's acceptance picture — per-replica, since acceptance skew
        across replicas is a routing signal worth watching.

        The whole replica picture — rows AND the healthy/capacity/
        outstanding totals — comes from ONE lock acquisition. The totals
        used to be recomputed after release via :meth:`healthy_replicas`
        etc., so a scrape racing an eject/respawn could report e.g. a row
        marked unhealthy next to a capacity that still counted it (a
        half-updated fleet picture on exactly the dashboards meant to
        debug ejections)."""
        with self._lock:
            reps = []
            healthy = capacity = outstanding = 0
            for r in self._replicas:
                routable = r.routable()
                if routable:
                    healthy += 1
                    capacity += r.api.engine.num_slots
                    outstanding += r.outstanding()
                row = {"idx": r.idx, "healthy": r.healthy,
                       "draining": r.draining, "removed": r.removed,
                       "generation": r.generation, "ejections": r.ejections,
                       "outstanding": (r.outstanding()
                                       if not r.removed else 0)}
                spec = (getattr(r.api.engine, "spec", None)
                        if not r.removed else None)
                if spec is not None:
                    row["spec_acceptance_rate"] = round(
                        spec.acceptance_rate(), 4)
                    row["spec_emitted"] = spec.emitted
                if not r.removed and getattr(r.api.engine, "chunk_size", 0):
                    row["prefilling"] = len(r.api.scheduler.prefilling)
                reps.append(row)
            tier_store = None
            for r in self._replicas:
                if r.routable():
                    tier = getattr(r.api.engine, "tier", None)
                    if tier is not None:
                        tier_store = tier.store
                        break
        out = {"replicas": reps,
               "replicas_total": sum(1 for r in reps if not r["removed"]),
               "replicas_healthy": healthy,
               "capacity_slots": capacity,
               "outstanding": outstanding,
               "draining": self._draining,
               "recovering": self._recovering,
               "radix_index": self.index.stats(),
               "tenants": self.tenants.stats()}
        if self.wal is not None:
            out["wal"] = self.wal.stats()
            out["wal"]["recovered"] = len(self._recovered)
        # the shared spill-tier picture (ISSUE 15): replicas attach to one
        # HostKVCache, so reporting any live replica's store covers all
        if tier_store is not None:
            out["tier"] = tier_store.stats()
        return out
