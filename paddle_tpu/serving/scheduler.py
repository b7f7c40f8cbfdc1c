"""Iteration-level (continuous) batching scheduler.

Classic batch serving admits a batch, decodes it to completion, then admits
the next batch — every request waits for the stragglers. Orca's insight
(and vLLM's): schedule at *iteration* granularity. Between any two decode
steps the engine can retire finished requests and admit waiting ones into
the freed slots, because the compiled step is occupancy-agnostic
(:mod:`paddle_tpu.serving.engine`).

The scheduler owns the policy half of that loop:

* **Priority admission with capacity gating** — the best waiting request
  (lowest ``priority`` value, then earliest arrival; default 0 = normal,
  FCFS within a class) is admitted when a slot is free AND the KV arena
  can reserve its worst-case block budget (so a running request can never
  be starved of cache mid-decode). Admission is strict head-of-line: a
  smaller, lower-priority waiter never jumps a blocked higher-priority one.
* **Preemption under starvation** — when the best waiter has been blocked
  on capacity for ``FLAGS_serving_starvation_steps`` scheduler steps and a
  strictly lower-priority request is running, the lowest-priority
  most-recently-admitted victim is preempted: its slot and block
  reservation are released and it re-queues WITH its token journal, so
  re-admission re-prefills prompt+generated-so-far into fresh blocks and
  resumes token-for-token (prefill buckets and the slot step treat all of
  this as runtime data — no recompile).
* **Cache-aware admission** (``FLAGS_serving_cache_affinity``) — with the
  radix prefix cache on, a same-priority waiter whose prompt prefix is
  resident may be admitted ahead of a cache-cold head (its matched
  prefill is free), but only within a bounded skip window so strict
  FCFS/priority order is never starved: after W skips the head is served
  regardless. Admission capacity itself is cache-aware too — a request
  whose prefix is resident reserves only its suffix's blocks
  (``ServingEngine.admit_blocks_needed``).
* **Finish detection** at every step boundary: stop-token hit, token
  budget, cancellation, and per-request wall-clock deadlines
  (``core.resilience.Deadline``).
* **Queue hygiene**: cancelled/expired requests are culled before they
  ever cost a prefill; submission overload is shed by the caller via
  ``core.resilience.check_overload`` (see ``serving.api``).

Decoding is greedy (temperature-0) — the deterministic serving mode whose
outputs are asserted token-for-token against ``GPT.generate()``.
"""
from __future__ import annotations

import itertools
import queue as _queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from ..core import flags, resilience
from . import metrics, telemetry

_req_counter = itertools.count()
_seq_counter = itertools.count()  # arrival / admission ordering ticks


class StreamSignal:
    """What a stream consumer blocks on while it has nothing to read.

    A producer calls :meth:`fire` after every change a consumer may be
    waiting for (a token landed, the request reached a terminal state, the
    gateway swapped the backend under a routed handle); a consumer notes
    :attr:`seq` BEFORE it reads, and blocks in :meth:`wait` on that value
    only if the read found nothing. A fire between the read and the wait
    has moved ``seq`` on, so the wait returns at once: no wake-up is lost,
    whatever the number of consumers (an ``Event`` one consumer clears
    would lose the other's)."""

    __slots__ = ("_cond", "_seq")

    def __init__(self):
        self._cond = threading.Condition(threading.Lock())
        self._seq = 0

    @property
    def seq(self) -> int:
        return self._seq

    def fire(self) -> None:
        with self._cond:
            self._seq += 1
            self._cond.notify_all()

    def wait(self, seen: int, timeout: float) -> bool:
        """Block until fired past ``seen`` or ``timeout`` seconds; True if
        it was fired."""
        with self._cond:
            if self._seq == seen:
                self._cond.wait(timeout)
            return self._seq != seen


class RequestState:
    QUEUED = "QUEUED"
    RUNNING = "RUNNING"
    FINISHED = "FINISHED"
    CANCELLED = "CANCELLED"
    FAILED = "FAILED"


@dataclass(eq=False)  # identity equality: list membership must never
class Request:        # compare numpy prompt payloads
    """One generation request moving through the engine.

    ``tokens`` accumulates generated ids (the stop token, when hit, is the
    last entry — mirroring ``generate()``'s fill semantics trimmed at the
    first stop); it doubles as the request's *journal*: preemption and
    supervisor replay re-prefill ``prompt + tokens`` to resume exactly
    where decode left off. ``priority`` follows the vLLM convention —
    LOWER values are served first, default 0 is normal traffic.
    ``stream_queue``/``done_event`` are the streaming surface
    ``api.stream()`` consumes; ``signal`` fires beside them for a consumer
    that reads ``tokens`` instead (the gateway's ``RoutedRequest`` hands
    its own in at attach, so one wait covers every backend it rides)."""

    prompt: np.ndarray
    max_new_tokens: int = 32
    stop_token_id: Optional[int] = None
    request_id: str = ""
    priority: int = 0
    # per-request scenario state (ISSUE 12): sampling params
    # (serving.sampling.SamplingParams; None = greedy), an incremental
    # decoding constraint (serving.constrain.Constraint; its walker state
    # `_cstate` is pure data derived from `tokens`, so journal replay /
    # preemption / gateway re-routes reconstruct it for free), and the
    # LoRA adapter arena row this request decodes with (0 = base weights)
    sampling: Optional[object] = None
    constraint: Optional[object] = None
    adapter_id: int = 0
    deadline: resilience.Deadline = field(
        default_factory=resilience.Deadline)
    state: str = RequestState.QUEUED
    tokens: List[int] = field(default_factory=list)
    error: Optional[BaseException] = None
    slot: Optional[int] = None
    stream_queue: "_queue.SimpleQueue" = field(
        default_factory=_queue.SimpleQueue)
    done_event: threading.Event = field(default_factory=threading.Event)
    signal: StreamSignal = field(default_factory=StreamSignal)
    _cancel: bool = False
    _arrival: int = 0     # submit-order tick (priority tie-break)
    _admit_seq: int = 0   # last admission tick ("most recent victim")
    _starved: int = 0     # consecutive steps blocked at the queue head
    _cache_skips: int = 0  # times cache-affinity admitted someone past us
    _prefix_keys: Optional[list] = None  # memoized radix chunk-key chain
    preemptions: int = 0  # times this request was preempted mid-decode
    # observability (ISSUE 17): ONE trace id names this request's whole
    # lifecycle — minted here unless the caller (gateway RoutedRequest,
    # supervisor replay via journal-seeded resubmit) already carries one,
    # so preemption re-queue / replay / re-route all land their spans on
    # the same timeline (docs/observability.md)
    trace_id: str = ""
    _submit_ts: float = 0.0     # perf_counter at submit's entry (ttft/e2e)
    _queued_ts: float = 0.0     # perf_counter at enqueue (queue_wait)
    _last_emit_ts: float = 0.0  # perf_counter of the last emitted token

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        self.priority = int(self.priority)
        self.adapter_id = int(self.adapter_id)
        if self.sampling is not None:
            # pin an unset seed NOW (fresh entropy per request): the
            # request then replays/preempts/re-routes token-identically
            self.sampling = self.sampling.materialized()
        self._arrival = next(_seq_counter)
        if not self._submit_ts:
            # built directly; ServingAPI.submit passes the time of its
            # own entry, so ttft/e2e hold its wait for the API lock
            self._submit_ts = time.perf_counter()
        if not self.request_id:
            self.request_id = f"req-{next(_req_counter)}"
        if not self.trace_id:
            self.trace_id = telemetry.mint_trace_id()
        self._cstate = (None if self.constraint is None
                        else self.constraint.initial())

    @property
    def finished(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.FAILED)

    def cancel(self) -> None:
        self._cancel = True

    # ------------------------------------------------ constraint walker

    def reset_constraint(self) -> None:
        """Rebuild the walker state from the token journal (a journal-
        seeded submit — gateway re-route — arrives with tokens the walker
        never saw)."""
        if self.constraint is None:
            return
        st = self.constraint.initial()
        for t in self.tokens:
            st = self.constraint.advance(st, int(t))
        self._cstate = st
        self._dead_ended = False

    def advance_constraint(self, token: int) -> None:
        if self.constraint is not None:
            self._cstate = self.constraint.advance(self._cstate, int(token))

    def allowed_mask(self) -> Optional[np.ndarray]:
        """The walker's current allowed-vocab mask (None = unconstrained).
        An empty mask — a dead-ended user DFA — is sanitized to
        unconstrained, counted ONCE per dead-ending (the mask is polled
        every emitted token — a per-call bump would make the dashboard
        count tokens, not incidents)."""
        if self.constraint is None:
            return None
        mask = self.constraint.allowed(self._cstate)
        if mask is not None and not mask.any():
            if not getattr(self, "_dead_ended", False):
                self._dead_ended = True
                metrics.bump("constrain.dead_ends")
            return None
        return mask

    def output_ids(self) -> np.ndarray:
        """prompt + generated tokens (the serving analog of generate()'s
        return, without the post-stop fill)."""
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens, np.int32)])


def admit_kwargs(req: Request) -> dict:
    """The engine-admission keyword set derived from one request's
    scenario state (sampling params, adapter id, the constraint walker's
    CURRENT mask) — shared by the scheduler's admission paths and the
    supervisor's journal replay so the two can never drift. Replay-safe
    by construction: the walker state is a pure function of the journal,
    and sampling PRNG keys are positional (``serving.sampling``).
    ``spec_exclude`` tells the engine a CONSTRAINT exists even when its
    current mask is None (unconstrained start): such a lane must never
    take the speculative path, so its draft prefill/blocks are skipped
    up front."""
    return {"sampling": req.sampling, "adapter": req.adapter_id,
            "mask": req.allowed_mask(),
            "spec_exclude": req.constraint is not None,
            # the engine holds this as its trace context for the admit
            # call so restore-path spans (RESTORED) land on this timeline
            "trace_id": req.trace_id}


class Scheduler:
    """Drives one :class:`ServingEngine` at iteration granularity. Not
    thread-safe by itself — ``serving.api`` serializes access."""

    def __init__(self, engine):
        self.engine = engine
        self.waiting: List[Request] = []
        self.running: List[Request] = []
        # chunked prefill (FLAGS_serving_chunked_prefill): requests whose
        # admission claimed a slot but whose prompt is still scattering,
        # one chunk per step — they hold capacity but don't decode yet
        self.prefilling: List[Request] = []
        self.preempt_count = 0  # this scheduler's lifetime preemptions

    # ---------------------------------------------------------- admission

    def submit(self, request: Request) -> Request:
        """Enqueue (capacity errors surface immediately; overload shedding
        happens in ``api.submit`` where the queue-depth policy lives)."""
        self.engine.validate(int(request.prompt.shape[0]),
                             int(request.max_new_tokens),
                             adapter=request.adapter_id)
        request.state = RequestState.QUEUED
        request._queued_ts = time.perf_counter()
        self.waiting.append(request)
        metrics.bump("requests.submitted")
        telemetry.span(request.trace_id, telemetry.QUEUED,
                       request_id=request.request_id,
                       priority=request.priority,
                       journal_tokens=len(request.tokens))
        self._gauges()
        return request

    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self.prefilling)

    # ------------------------------------------------------------ finish

    def _finish(self, req: Request, state: str,
                error: Optional[BaseException] = None) -> None:
        if req.finished:
            # idempotent: close()-after-a-failed-pump (or any double sweep)
            # must not deliver a second error/sentinel/done_event
            return
        if req.slot is not None:
            self.engine.retire(req.slot)
            if req in self.running:
                self.running.remove(req)
            if req in self.prefilling:
                self.prefilling.remove(req)
            req.slot = None
        req.state = state
        req.error = error
        key = {RequestState.FINISHED: "requests.finished",
               RequestState.CANCELLED: "requests.cancelled",
               RequestState.FAILED: "requests.failed"}[state]
        metrics.bump(key)
        if error is not None and isinstance(
                error, resilience.DeadlineExceededError):
            metrics.bump("requests.expired")
            # the shared resilience counter dashboards watch (the same key
            # Deadline.check() bumps)
            resilience.bump("deadline.exceeded")
        if state == RequestState.FINISHED:
            # e2e = submit -> complete output (only for requests
            # that delivered one — failures/cancels would skew the tail)
            telemetry.observe("latency.e2e",
                              time.perf_counter() - req._submit_ts,
                              getattr(self.engine, "hists", None))
        telemetry.span(req.trace_id,
                       telemetry.FINISHED if state == RequestState.FINISHED
                       else telemetry.FAILED,
                       request_id=req.request_id, state=state,
                       tokens=len(req.tokens),
                       error=type(error).__name__ if error else None)
        req.stream_queue.put(None)  # stream sentinel
        req.done_event.set()
        req.signal.fire()

    def _emit(self, req: Request, token: int) -> None:
        if req.finished:
            return  # a walker failure mid-iteration already closed it
        now = time.perf_counter()
        if not req.tokens and req._last_emit_ts == 0.0:
            # TRUE first token only: a journal-seeded resubmit (gateway
            # re-route) arrives with tokens, a replayed/preempted request
            # keeps its _last_emit_ts — neither re-records TTFT
            telemetry.observe("latency.ttft", now - req._submit_ts,
                              getattr(self.engine, "hists", None))
            telemetry.span(req.trace_id, telemetry.FIRST_TOKEN,
                           request_id=req.request_id, token=int(token))
        elif req._last_emit_ts > 0.0:
            telemetry.observe("latency.inter_token",
                              now - req._last_emit_ts,
                              getattr(self.engine, "hists", None))
        req._last_emit_ts = now
        req.tokens.append(int(token))
        req.stream_queue.put(int(token))
        req.signal.fire()
        if req.constraint is not None:
            # advance the host-side walker one token and scatter the new
            # allowed-vocab row into the slot's mask (runtime data — the
            # next decode step constrains under it, zero recompiles)
            try:
                req.advance_constraint(token)
                if req.slot is not None:
                    self.engine.set_slot_mask(req.slot, req.allowed_mask())
            # analysis: allow(broad-except) — user-supplied walker code
            # (Constraint is a public protocol): its failure — wrong-width
            # mask, a raising advance() — fails THIS request, never the
            # pump (an escaped exception would read as engine sickness
            # and rebuild-loop the supervisor toward CrashLoopError)
            except Exception as e:
                self._finish(req, RequestState.FAILED, e)

    def _check_boundary(self, req: Request) -> bool:
        """Policy checks at a step boundary; True if the request ended."""
        if req._cancel:
            self._finish(req, RequestState.CANCELLED)
            return True
        # completion outranks the deadline: output that is already whole
        # (stop token emitted / budget reached) is returned even if the
        # clock ran out on the same step — paid-for work is never discarded
        if req.tokens:
            stop = req.stop_token_id
            if stop is not None and req.tokens[-1] == stop:
                self._finish(req, RequestState.FINISHED)
                return True
            if len(req.tokens) >= req.max_new_tokens:
                self._finish(req, RequestState.FINISHED)
                return True
        if req.deadline.expired():
            self._finish(req, RequestState.FAILED,
                         resilience.DeadlineExceededError(
                             f"{req.request_id} exceeded its deadline"))
            return True
        return False

    # ----------------------------------------------------- admission order

    def _next_waiter(self) -> Optional[Request]:
        """Best waiting request: lowest priority value, earliest arrival.
        Admission is strict head-of-line — nothing bypasses a blocked
        better-priority waiter (a stream of small fillers must not starve
        one big request forever)."""
        if not self.waiting:
            return None
        return min(self.waiting, key=lambda r: (r.priority, r._arrival))

    def _keys_for(self, r: Request):
        """Memoized radix chunk-key chain for a request's prompt: a pure
        function of the tokens, hashed once at first probe and reused by
        every later residency/feasibility poll (they run per pump step)."""
        if r._prefix_keys is None:
            r._prefix_keys = self.engine.prefix_cache.chunk_keys(r.prompt)
        return r._prefix_keys

    def _cache_preferred(self, head: Request) -> Request:
        """Cache-aware admission (``FLAGS_serving_cache_affinity`` = W > 0):
        prefer a SAME-priority waiter whose prompt prefix is resident in
        the engine's radix cache over a cache-cold head — a warm admission
        skips its matched prefill entirely, so serving it first is nearly
        free capacity. Strictly bounded: the head may be skipped at most W
        times (each skip is counted on the head), priorities are never
        crossed, and a head that is itself warm — or not even admissible —
        is never skipped. With the window spent, admission is the exact
        (priority, arrival) order of PR 5."""
        window = int(flags.flag("serving_cache_affinity"))
        if window <= 0 or head._cache_skips >= window:
            return head
        engine = self.engine
        if getattr(engine, "prefix_cache", None) is None:
            return head
        if engine.free_slots() == 0:
            return head  # nothing can be admitted: skip the radix walks
        cache = engine.prefix_cache
        if cache.resident_tokens_for(self._keys_for(head)) > 0:
            return head  # the head is warm: no reason to skip it
        if not engine.can_admit(int(head.prompt.shape[0]),
                                int(head.max_new_tokens),
                                keys=self._keys_for(head),
                                journal_len=len(head.tokens)):
            # a capacity-blocked head belongs to the starvation/preemption
            # machinery — skipping it would burn its bounded window on
            # passes where it could not have been admitted anyway
            return head
        best, best_tokens = head, 0
        for r in self.waiting:
            if r is head or r.priority != head.priority:
                continue
            tokens = cache.resident_tokens_for(self._keys_for(r))
            if tokens > best_tokens and engine.can_admit(
                    int(r.prompt.shape[0]), int(r.max_new_tokens),
                    keys=self._keys_for(r), journal_len=len(r.tokens)):
                best, best_tokens = r, tokens
        if best is not head:
            head._cache_skips += 1
            metrics.bump("scheduler.cache_skips")
        return best

    def _preempt_for(self, waiter: Request) -> bool:
        """Preempt the lowest-priority, most-recently-admitted running
        request that is STRICTLY lower-priority than ``waiter``; the victim
        releases its slot + block reservation and re-queues with its token
        journal (re-admission re-prefills prompt+generated-so-far — no
        recompile, token-for-token resume). Declines (returns False) when
        evicting every eligible victim still could not seat the waiter —
        higher-priority runners hold the arena, and wasting the victims'
        prefilled work would free nothing useful. Returns True if a victim
        was preempted."""
        candidates = [r for r in self.running if r.priority > waiter.priority]
        if not candidates:
            return False
        # feasibility must use the same cache-aware sizing as admission:
        # a waiter with a resident prefix reserves only its suffix, so the
        # worst-case blocks_needed() would decline preemptions that the
        # very next can_admit() would in fact grant
        cache_on = getattr(self.engine, "prefix_cache", None) is not None
        # journal_len: a preempted/re-routed waiter re-prefills
        # prompt+journal, so its COW trigger compares against the full
        # prefilled context (admit_sizing) — the handed-off/replayed case
        need, pinned = self.engine.admit_sizing(
            int(waiter.prompt.shape[0]), int(waiter.max_new_tokens),
            keys=self._keys_for(waiter) if cache_on else None,
            journal_len=len(waiter.tokens))
        reclaimable = (self.engine.arena.grantable() - pinned
                       + sum(self.engine.reserved_blocks(r.slot)
                             for r in candidates))
        if reclaimable < need:
            return False
        victim = max(candidates, key=lambda r: (r.priority, r._admit_seq))
        self.engine.retire(victim.slot, why="preempt")
        telemetry.span(victim.trace_id, telemetry.PREEMPTED,
                       request_id=victim.request_id, slot=victim.slot,
                       by=waiter.request_id, tokens=len(victim.tokens))
        self.running.remove(victim)
        victim.slot = None
        victim.state = RequestState.QUEUED
        victim._queued_ts = time.perf_counter()  # re-queued: new wait
        telemetry.span(victim.trace_id, telemetry.QUEUED,
                       request_id=victim.request_id,
                       journal_tokens=len(victim.tokens))
        victim._starved = 0
        victim.preemptions += 1
        self.waiting.append(victim)
        self.preempt_count += 1
        metrics.bump("scheduler.preemptions")
        resilience.bump("serving.preemptions")
        return True

    # -------------------------------------------------------------- step

    def _advance_prefill(self) -> bool:
        """Chunked prefill: cull dead in-progress admissions (EVERY one,
        not just the head — a cancelled request behind the head must not
        hold its slot and block reservations for the head's remaining
        chunks), then advance the oldest survivor by exactly one chunk —
        one compiled suffix-prefill call — so the decode stall this
        iteration imposes on running streams is bounded by one chunk, not
        one prompt. The final chunk emits the first token and promotes
        the request to running."""
        progress = False
        for req in list(self.prefilling):
            if req._cancel or req.deadline.expired():
                # _finish retires the slot (engine releases chunk state)
                self._finish(req,
                             RequestState.CANCELLED if req._cancel
                             else RequestState.FAILED,
                             None if req._cancel
                             else resilience.DeadlineExceededError(
                                 f"{req.request_id} expired mid-prefill"))
                progress = True
        if not self.prefilling:
            return progress
        req = self.prefilling[0]
        try:
            first = self.engine.admit_chunk(req.slot)
            telemetry.span(req.trace_id, telemetry.PREFILL_CHUNK,
                           request_id=req.request_id, slot=req.slot,
                           done=first is not None)
        # analysis: allow(broad-except) — classification inside:
        # transient engine sickness re-queues + re-raises for the
        # supervisor; anything else fails THIS request, not the pump
        except Exception as e:
            from .supervisor import is_transient_serving_error

            self.prefilling.remove(req)
            req.slot = None  # the engine already unwound the admission
            if is_transient_serving_error(e):
                req.state = RequestState.QUEUED
                self.waiting.append(req)
                raise
            self._finish(req, RequestState.FAILED, e)
            return True
        if first is not None:
            self.prefilling.remove(req)
            req._admit_seq = next(_seq_counter)
            self.running.append(req)
            self._emit(req, first)
            self._check_boundary(req)  # may retire at once (stop/budget)
        return True

    def _admit_pass(self) -> bool:
        """The pass before the decode call: cull dead queue entries,
        advance one chunked prefill, admit while capacity allows
        (preempting under starvation). True if any request made
        progress."""
        progress = False
        # cull queued requests that died before costing a prefill
        for req in list(self.waiting):
            if req._cancel or req.deadline.expired():
                self.waiting.remove(req)
                self._finish(req,
                             RequestState.CANCELLED if req._cancel
                             else RequestState.FAILED,
                             None if req._cancel
                             else resilience.DeadlineExceededError(
                                 f"{req.request_id} expired in queue"))
                progress = True
        # one chunk of at most one in-progress chunked prefill per step
        if self.prefilling:
            progress |= self._advance_prefill()
        # priority admission into free slots
        starve_after = int(flags.flag("serving_starvation_steps"))
        starved_this_step = False
        while True:
            req = self._next_waiter()
            if req is None:
                break
            req = self._cache_preferred(req)
            cache_on = getattr(self.engine, "prefix_cache", None) is not None
            if not self.engine.can_admit(
                    int(req.prompt.shape[0]), int(req.max_new_tokens),
                    keys=self._keys_for(req) if cache_on else None,
                    journal_len=len(req.tokens)):
                # the head waiter is capacity-blocked: count starvation
                # once per step, then preempt one victim per pass until it
                # fits or no strictly-lower-priority victim remains
                if not starved_this_step:
                    req._starved += 1
                    starved_this_step = True
                if (starve_after > 0 and req._starved > starve_after
                        and self._preempt_for(req)):
                    progress = True
                    continue  # retry admission with the freed capacity
                break
            self.waiting.remove(req)
            req._starved = 0
            chunked = getattr(self.engine, "chunk_size", 0) > 0
            try:
                if chunked:
                    # chunked admission: the engine decides whether the
                    # context fits one chunk (plain admit) or stays in
                    # progress (first is None — one chunk per step)
                    slot, first = self.engine.admit_begin(
                        req.prompt, req.max_new_tokens, tokens=req.tokens,
                        **admit_kwargs(req))
                else:
                    slot, first = self.engine.admit(req.prompt,
                                                    req.max_new_tokens,
                                                    tokens=req.tokens,
                                                    **admit_kwargs(req))
            # analysis: allow(broad-except) — classification inside:
            # transient engine sickness re-queues + re-raises for the
            # supervisor; anything else fails THIS request, not the pump
            except Exception as e:
                from .supervisor import is_transient_serving_error

                if is_transient_serving_error(e):
                    # transient prefill failure: the ENGINE is sick, not
                    # this request — requeue it untouched and let the
                    # api-level supervisor rebuild and resume everything
                    req.state = RequestState.QUEUED
                    self.waiting.append(req)
                    raise
                # a failed prefill fails THIS request (done_event set,
                # stream sentinel delivered) — never the whole pump
                self._finish(req, RequestState.FAILED, e)
                progress = True
                continue
            req.slot = slot
            req.state = RequestState.RUNNING
            telemetry.observe("latency.queue_wait",
                              time.perf_counter() - req._queued_ts,
                              getattr(self.engine, "hists", None))
            telemetry.span(req.trace_id, telemetry.ADMITTED,
                           request_id=req.request_id, slot=slot,
                           chunked=first is None,
                           journal_tokens=len(req.tokens))
            progress = True
            if first is None:
                # chunked prefill in progress: holds its slot/blocks but
                # decodes nothing until the final chunk emits its token
                self.prefilling.append(req)
                continue
            req._admit_seq = next(_seq_counter)
            self.running.append(req)
            self._emit(req, first)
            self._check_boundary(req)  # may retire immediately (stop/budget)
        return progress

    def step(self) -> bool:
        """One scheduler iteration: the admission pass
        (:meth:`_admit_pass`), one engine decode call, the emit of ONE
        step's tokens, retire finished. On the plain path one decode step
        stays in flight from turn to turn (``engine.decode_turn``): the
        step whose tokens this turn emits was dispatched a turn ago, and
        the next is dispatched before they are read, so the emit, the
        pump's unlocked stretch and the next admission pass run under the
        device's step. A host write to the slot state (an admission, a
        retirement, a preemption, a cancel) makes the turn read without
        dispatching, and the next turn starts again from the mirrors; a
        constrained request or the speculative path keeps every turn
        synchronous. Returns True if any request made progress. Three
        phases (``telemetry.phase``): ``sched.admit`` around the pass
        (parent of the engine's ``prefill``), the engine's own
        ``decode_step``, and ``sched.emit`` around the emit loop as a
        whole."""
        hists = getattr(self.engine, "hists", None)
        with telemetry.phase("sched.admit", hists):
            progress = self._admit_pass()
        # one decode iteration over every occupied slot
        if self.running:
            if getattr(self.engine, "spec", None) is not None:
                # speculative: up to k accepted tokens per slot from one
                # compiled call; emission stays per-token so stop-token /
                # budget / deadline boundaries keep generate() semantics
                # (tokens past a stop are dropped, exactly like the
                # sequential path that would never have generated them)
                accepted = self.engine.spec_decode_step()
                with telemetry.phase("sched.emit", hists):
                    for req in list(self.running):
                        for tok in accepted.get(req.slot, ()):
                            self._emit(req, int(tok))
                            if self._check_boundary(req):
                                break
            else:
                # the tokens of the oldest step in flight, with the next
                # one dispatched before they are read wherever it can be
                # (engine.decode_step). A request admitted since that step
                # was dispatched has no lane in it: its turn comes with
                # the next step. One that ended since is not in `running`
                toks, lanes = self.engine.decode_turn(self._may_run_ahead())
                with telemetry.phase("sched.emit", hists):
                    for req in list(self.running):
                        if lanes[req.slot]:
                            self._emit(req, int(toks[req.slot]))
                        self._check_boundary(req)
            progress = True
        if not self.running:
            self._drop_in_flight()
            if not self.has_work():
                # whatever the device stands empty for from here on is
                # nobody's delay (time_us.device.empty.idle)
                note_idle = getattr(self.engine, "note_idle", None)
                if note_idle is not None:
                    note_idle()
        self._gauges()
        return progress

    def _may_run_ahead(self) -> bool:
        """Whether the step after the one in flight may be dispatched
        before this one's tokens are read: not while a running request
        has a ``constraint`` (its next mask row needs the token, through
        the walker on the host). With ``engine.spec`` set the turn never
        comes here."""
        return all(r.constraint is None for r in self.running)

    def _drop_in_flight(self) -> None:
        """Nothing is running: a step dispatched ahead was computed for
        requests that have all ended. No handle outlives the work."""
        drop = getattr(self.engine, "decode_drop", None)
        if drop is not None:
            drop()

    def fail_all(self, error: BaseException) -> None:
        """Fail every queued and running request (engine fatality or
        shutdown): each gets its error, stream sentinel, and done_event —
        no caller is ever left blocking on an abandoned request."""
        for req in list(self.waiting):
            self.waiting.remove(req)
            self._finish(req, RequestState.FAILED, error)
        for req in list(self.prefilling):
            self._finish(req, RequestState.FAILED, error)
        for req in list(self.running):
            self._finish(req, RequestState.FAILED, error)
        self._drop_in_flight()
        self._gauges()

    def run_until_idle(self, max_steps: Optional[int] = None) -> None:
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"scheduler still busy after {max_steps} steps")

    def _gauges(self) -> None:
        metrics.set_gauge("queue.depth", len(self.waiting))
        metrics.set_gauge("queue.prefilling", len(self.prefilling))
