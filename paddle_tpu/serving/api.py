"""Serving front door: ``submit()`` / ``stream()`` / ``cancel()`` / ``drain()``.

Thin, thread-safe policy shell over the engine+scheduler+supervisor stack:

* **submit** applies queue-overload shedding
  (``core.resilience.check_overload`` / ``FLAGS_serving_max_queue``),
  attaches the per-request wall-clock deadline, and stamps the request's
  priority class (lower value = served first; see
  ``scheduler.Scheduler``'s admission/preemption policy).
* **stream** yields tokens as the engine produces them. In foreground mode
  (default) the consumer's iteration *is* the event loop — each ``next()``
  pumps scheduler steps; with ``background=True`` a pump thread drives the
  engine and streams are plain queue consumers.
* **cancel** flags the request; the scheduler retires its slot at the next
  step boundary (queued requests never cost a prefill).
* **supervision** — every pump step routes through
  :class:`serving.supervisor.EngineSupervisor`: a transient device/arena
  failure rebuilds the engine and replays in-flight requests from their
  journals (token-for-token identical output, zero recompiles) instead of
  failing them; non-transient errors keep the fail-fast path, and the
  crash-loop breaker degrades to fail-fast with
  :class:`serving.supervisor.CrashLoopError`.
* **drain** — ``drain(grace)`` stops admissions, pumps in-flight requests
  to completion within the grace budget, then fails stragglers with the
  *retriable* ``core.resilience.RequestDrainedError``. ``close()`` routes
  through ``drain(grace=0)`` so the two shutdown paths cannot diverge, and
  ``bind_preemption_guard`` turns SIGTERM/SIGINT into a drain instead of a
  mid-decode kill — the serving mirror of the training loop's
  step-boundary finalize (docs/robustness.md, "Serving under failure").

The :class:`EnginePredictor` bridge at the bottom gives the classic
``paddle.inference`` predictor surface (``get_input_handle`` /
``run`` / ``get_output_handle``) a continuous-batching backend: a batch of
prompts becomes one request per row, so short rows free their slots for
other traffic instead of idling until the longest row finishes. It is
routed through ``inference.Config.enable_serving_engine()`` +
``inference.create_predictor``.
"""
from __future__ import annotations

import atexit
import logging
import queue as _queue
import threading
import time
import weakref
from typing import Iterator, List, Optional, Sequence

import numpy as np

from ..core import flags, resilience
from . import metrics, telemetry
from .engine import ServingConfig, ServingEngine
from .scheduler import Request, RequestState, Scheduler
from .supervisor import EngineSupervisor

_logger = logging.getLogger("paddle_tpu.serving")

#: the longest a stream consumer blocks before it looks again. Liveness
#: backstop only: every token, terminal state and backend swap wakes its
#: consumer (``scheduler.StreamSignal``, the stream queue's sentinel), so
#: this bounds what a wake-up lost to a bug would cost, and nothing waits
#: for it in a healthy run. Seconds, not milliseconds, because a consumer
#: whose request waits in the scheduler's queue (half of a loaded
#: gateway's streams) pays one empty wake-up per period.
STREAM_WAIT_S = 5.0

#: every live ServingAPI, so process-level shutdown epilogues
#: (``tools/serving_stats.py --run``, operator scripts) can drain them all
_live_apis: "weakref.WeakSet" = weakref.WeakSet()


def drain_all(grace: float = 0.0) -> int:
    """Drain every live :class:`ServingAPI` (shutdown epilogue — e.g.
    ``tools/serving_stats.py --run`` calls this after the driven script so
    no engine exits holding live slots). Returns how many were drained."""
    n = 0
    for api in list(_live_apis):
        if not api._closed and not api._draining:
            api.drain(grace)
            n += 1
    return n


@atexit.register
def _drain_at_exit() -> None:  # pragma: no cover - interpreter shutdown
    """Interpreter shutdown must never strand a background pump thread
    mid-decode: zero-grace-drain every API still live (admissions stop, in
    flight requests fail with the retriable ``RequestDrainedError``, every
    done_event fires). Idempotent with an explicit ``close()``/``drain()``
    — already-closed or already-draining APIs are skipped by
    :func:`drain_all`, so operator scripts that shut down properly see no
    second sweep."""
    try:
        drain_all(grace=0.0)
    except Exception:
        # analysis: allow(broad-except) — shutdown epilogue: must never
        # turn a clean exit into a traceback (the GC may already have
        # torn down parts of the runtime)
        pass


class ServingAPI:
    """One served model: engine + scheduler + supervisor + (optional)
    pump thread."""

    def __init__(self, model, config: Optional[ServingConfig] = None,
                 background: bool = False,
                 max_queue: Optional[int] = None, **engine_kw):
        self.engine = ServingEngine(model, config, **engine_kw)
        self.scheduler = Scheduler(self.engine)
        self.supervisor = EngineSupervisor(self.engine, self.scheduler)
        self._lock = threading.RLock()
        self._max_queue = max_queue
        self._closed = False
        self._draining = False
        self.drain_count = 0  # this API's lifetime drains
        self._guard = None
        self._guard_grace: Optional[float] = None
        self._turn = 0  # locked loop turns so far (the sched.step phase's arg)
        self._thread = None
        _live_apis.add(self)
        if background:
            self._thread = threading.Thread(target=self._pump_loop,
                                            name="serving-pump", daemon=True)
            self._thread.start()

    # ------------------------------------------------------------ public

    def submit(self, prompt, max_new_tokens: int = 32,
               stop_token_id: Optional[int] = None,
               timeout: Optional[float] = None,
               request_id: str = "", priority: int = 0,
               journal: Optional[Sequence[int]] = None,
               shed: bool = True, sampling=None, constraint=None,
               adapter: int = 0, trace_id: str = "") -> Request:
        """Enqueue one generation request; returns its handle immediately.

        ``timeout`` is the request's end-to-end wall-clock deadline
        (queue wait included). ``priority`` follows the vLLM convention —
        lower values are served first; default 0 is normal traffic (FCFS
        within a class). Raises
        :class:`core.resilience.QueueOverloadError` when the waiting queue
        is at the shedding limit — callers retry later or route elsewhere;
        unbounded queues just convert overload into timeouts. During a
        drain, new submissions raise the retriable
        :class:`core.resilience.RequestDrainedError`.

        ``journal`` seeds the request's token journal: admission prefills
        ``prompt + journal`` and decode resumes at the journal's next token
        (``journal`` counts toward ``max_new_tokens``, and only tokens
        PAST it are streamed). This is the gateway router's re-queue path —
        a request whose replica crash-looped resumes token-for-token on a
        healthy replica. ``shed=False`` bypasses the queue-depth shed for
        such re-routed requests: they were already accepted once, and
        dropping accepted work at an overloaded fail-over target would turn
        one replica's crash into request loss.

        ``sampling`` (a :class:`~.sampling.SamplingParams`; None = greedy,
        bit-identical to the classic engine), ``constraint`` (a
        :class:`~.constrain.Constraint` walker masking the vocab per
        step), and ``adapter`` (a registered LoRA arena row id — see
        :meth:`register_adapter`; 0 = base weights) select the request's
        decode scenario. All three are per-slot runtime data in the ONE
        compiled decode step — mixing them across a batch never
        recompiles.

        ``trace_id`` carries an existing lifecycle trace onto this
        request (the gateway passes its ``RoutedRequest``'s id so a
        re-route continues ONE timeline); empty mints a fresh one and
        emits its SUBMITTED span here — exactly one site ever emits
        SUBMITTED per trace (docs/observability.md)."""
        t_submit = time.perf_counter()  # ttft/e2e start here, lock wait in
        with telemetry.phase("submit.lock_wait",
                             self.engine.hists) as wait, self._lock:
            wait.stop()  # the lock is held: the wait is over
            # checked under the lock: a submit racing drain()/close() must
            # never enqueue after the straggler sweep (its request would
            # sit unpumped forever)
            if self._closed:
                raise RuntimeError("ServingAPI is closed")
            if self._draining:
                raise resilience.RequestDrainedError(
                    "ServingAPI is draining: admissions are stopped; "
                    "resubmit to another instance")
            if shed:
                try:
                    resilience.check_overload(len(self.scheduler.waiting),
                                              self._max_queue, name="serving")
                except resilience.QueueOverloadError:
                    metrics.bump("requests.shed")
                    raise
            minted = not trace_id
            req = Request(prompt, max_new_tokens=max_new_tokens,
                          stop_token_id=stop_token_id,
                          request_id=request_id, priority=priority,
                          sampling=sampling, constraint=constraint,
                          adapter_id=int(adapter), trace_id=trace_id,
                          deadline=resilience.Deadline.after(timeout),
                          _submit_ts=t_submit)
            if minted:
                telemetry.span(req.trace_id, telemetry.SUBMITTED,
                               request_id=req.request_id,
                               prompt_tokens=int(req.prompt.shape[0]),
                               max_new_tokens=int(max_new_tokens))
            if journal:
                if len(journal) >= int(max_new_tokens):
                    raise ValueError(
                        f"journal of {len(journal)} tokens already exhausts "
                        f"max_new_tokens={max_new_tokens}; nothing to resume")
                req.tokens = [int(t) for t in journal]
                # the walker never saw the journal's tokens: rebuild its
                # state so the re-routed stream stays in-grammar
                req.reset_constraint()
            return self.scheduler.submit(req)

    def register_adapter(self, adapter, name: Optional[str] = None) -> int:
        """Install a :class:`~.adapters.LoraAdapter` into this engine's
        adapter arena; returns the id requests pass as ``adapter=``.
        Value-only (shape-preserving) — zero recompiles. Requires the
        engine to have been built with ``FLAGS_serving_lora_rank`` > 0 /
        ``ServingConfig.lora_rank``."""
        if self.engine.lora is None:
            raise RuntimeError(
                "this engine has no adapter arena "
                "(FLAGS_serving_lora_rank is 0)")
        with self._lock:
            return self.engine.lora.register(adapter, name=name)

    def unregister_adapter(self, adapter) -> None:
        """Free one adapter row (by id or name). Refused while ANY
        request — running, prefilling, or still queued — names the row:
        the arena's own guard only sees occupied slots, but a queued
        request that passed ``check_live`` at submit would otherwise be
        admitted onto a freed (and possibly recycled-to-another-tenant)
        row."""
        lora = self.engine.lora
        if lora is None:
            raise RuntimeError(
                "this engine has no adapter arena "
                "(FLAGS_serving_lora_rank is 0)")
        with self._lock:
            idx = (lora.adapter_id(adapter) if isinstance(adapter, str)
                   else int(adapter))
            sched = self.scheduler
            worn = [r.request_id
                    for r in (sched.waiting + sched.prefilling
                              + sched.running)
                    if r.adapter_id == idx]
            if worn:
                raise RuntimeError(
                    f"adapter {adapter!r} (id {idx}) is named by "
                    f"in-flight/queued request(s) {worn[:4]}; let them "
                    "finish (or cancel them) before unregistering")
            lora.unregister(idx)

    def outstanding(self) -> int:
        """Waiting + prefilling + running request count — the router's
        least-outstanding-work routing signal (a chunked prefill in
        progress is committed work, so the gateway must weigh it)."""
        return (len(self.scheduler.waiting)
                + len(self.scheduler.prefilling)
                + len(self.scheduler.running))

    def prefetch(self, prompt, trace_id: str = "") -> int:
        """Restore-ahead (disagg): pre-restore ``prompt``'s published/
        spilled radix chain into this engine's arena before its request
        is admitted — see :meth:`ServingEngine.prefetch` for the
        never-starves-admission bound. Serialized with the pump under
        the api lock; a closed/draining instance declines (returns 0)."""
        with self._lock:
            if self._closed or self._draining:
                return 0
            return self.engine.prefetch(prompt, trace_id=trace_id)

    def stream(self, req: Request) -> Iterator[int]:
        """Yield ``req``'s tokens as they are generated; raises the
        request's error (deadline, shed, engine failure) at the end of a
        failed stream. With a pump thread the consumer blocks on the
        stream queue until the pump puts a token or the finish sentinel;
        without one the consumer is the pump, and an empty queue means
        "step the scheduler"."""
        while True:
            pumped = self._thread is not None
            try:
                tok = req.stream_queue.get(
                    pumped, STREAM_WAIT_S if pumped else None)
            except _queue.Empty:
                if req.done_event.is_set():
                    break
                if not pumped:
                    self._pump_once()
                continue
            if tok is None:  # finish sentinel (always the queue's last item)
                break
            yield tok
        if req.state == RequestState.FAILED and req.error is not None:
            raise req.error

    def cancel(self, req: Request) -> None:
        req.cancel()
        if self._thread is None:
            self._pump_once()  # make cancellation take effect promptly

    def result(self, req: Request, timeout: Optional[float] = None
               ) -> np.ndarray:
        """Block until ``req`` finishes; returns prompt+generated ids.
        Raises the request's error for FAILED, RuntimeError for CANCELLED."""
        if self._thread is None:
            deadline = resilience.Deadline.after(timeout)
            while not req.finished:
                deadline.check(f"result({req.request_id})")
                self._pump_once()
        elif not req.done_event.wait(timeout):
            raise resilience.DeadlineExceededError(
                f"result({req.request_id}) timed out")
        if req.state == RequestState.FAILED:
            raise req.error
        if req.state == RequestState.CANCELLED:
            raise RuntimeError(f"{req.request_id} was cancelled")
        return req.output_ids()

    def run_until_idle(self) -> None:
        while True:
            if self._check_guard():
                return
            with self._lock:
                if not self.scheduler.has_work():
                    return
                # analysis: allow(blocking-call-in-lock) — the API lock IS
                # the engine serialization point: exactly one thread may
                # step the scheduler, and waiters queue on this lock
                self._step_guarded()

    # -------------------------------------------------------- drain / close

    def drain(self, grace: Optional[float] = None,
              reason: str = "serving drain") -> None:
        """Graceful shutdown of in-flight work: stop admissions immediately
        (``submit`` raises the retriable ``RequestDrainedError``), pump
        everything already accepted to completion within ``grace`` seconds
        (default ``FLAGS_serving_drain_grace``), then fail stragglers with
        the same retriable error — their callers resubmit to another
        instance instead of blocking on an engine that is going away.
        Idempotent. ``close()`` routes through ``drain(grace=0)`` so close
        and drain share one code path.

        Counters: ``serving.drains`` / ``serving.drain_stragglers``
        (``core.resilience``, memory_stats providers, profiler Resilience
        delta) and ``api.drains`` / ``api.drain_stragglers``
        (``serving.metrics``, profiler Serving delta)."""
        if grace is None:
            grace = float(flags.flag("serving_drain_grace"))
        grace = max(0.0, float(grace))
        with self._lock:
            if self._draining:
                return
            self._draining = True
        self.drain_count += 1
        resilience.bump("serving.drains")
        metrics.bump("api.drains")
        deadline = resilience.Deadline.after(grace)
        # with a background pump the thread keeps stepping and drain just
        # waits; foreground (or drain initiated FROM the pump thread, via
        # a bound PreemptionGuard) pumps right here
        own_pump = (self._thread is None
                    or threading.current_thread() is self._thread)
        while grace > 0 and not deadline.expired():
            with self._lock:
                if not self.scheduler.has_work():
                    break
                if own_pump:
                    try:
                        # analysis: allow(blocking-call-in-lock) — the API
                        # lock is the engine serialization point (drain
                        # pumps under it by design)
                        self._step_guarded()
                    except Exception:
                        # analysis: allow(broad-except) — any step failure
                        # already failed every in-flight request with its
                        # real error (fail_all); nothing left for the
                        # grace loop to pump
                        break
            if not own_pump:
                time.sleep(0.001)
        self._fail_stragglers(grace, reason)

    def _fail_stragglers(self, grace: float, reason: str) -> None:
        with self._lock:
            stragglers = (len(self.scheduler.waiting)
                          + len(self.scheduler.prefilling)
                          + len(self.scheduler.running))
            if stragglers:
                for req in (self.scheduler.waiting
                            + self.scheduler.prefilling
                            + self.scheduler.running):
                    # DRAINED precedes the FAILED span fail_all emits:
                    # the timeline shows retriable-drain, then terminal
                    telemetry.span(req.trace_id, telemetry.DRAINED,
                                   request_id=req.request_id,
                                   reason=reason)
                self.scheduler.fail_all(resilience.RequestDrainedError(
                    f"{reason}: request drained before completion "
                    f"(grace={grace:g}s); safe to resubmit"))
                resilience.bump("serving.drain_stragglers", stragglers)
                metrics.bump("api.drain_stragglers", stragglers)

    def close(self) -> None:
        """Shut down through :meth:`drain` with a zero grace budget (close
        and drain share one code path). Idempotent — and safe after a
        failed pump: ``Scheduler._finish`` is idempotent, so requests the
        pump already failed are never double-failed (no second error,
        sentinel, or done_event)."""
        if self._closed:
            return
        self.drain(grace=0.0, reason="ServingAPI is closed")
        # if another drain (e.g. a guard drain with a long grace) was
        # already in flight, the idempotent drain() above returned without
        # sweeping — close() must still uphold its zero-grace contract, so
        # fail whatever is left right now instead of letting it outlive the
        # API (the in-flight drain's own sweep then finds nothing)
        self._fail_stragglers(0.0, "ServingAPI is closed")
        with self._lock:
            self._closed = True
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def bind_preemption_guard(self, guard,
                              grace: Optional[float] = None) -> "ServingAPI":
        """SIGTERM/SIGINT (or an injected ``preempt`` fault) drains this
        API instead of killing it mid-decode — the serving mirror of the
        training loop's ``PreemptionGuard.maybe_finalize`` step-boundary
        semantics. The pump polls ``guard.requested()`` at step
        boundaries; once requested, admissions stop and in-flight requests
        get ``grace`` (default ``FLAGS_serving_drain_grace``) to finish,
        then stragglers fail with the retriable ``RequestDrainedError``.
        Returns ``self`` for chaining."""
        self._guard = guard
        self._guard_grace = grace
        return self

    # ----------------------------------------------------------- pumping

    def _check_guard(self) -> bool:
        """Poll the bound PreemptionGuard at a pump boundary: a pending
        preemption request turns into a drain, never a mid-step kill."""
        g = self._guard
        if g is None or self._draining or not g.requested():
            return False
        metrics.bump("api.guard_drains")
        self.drain(self._guard_grace,
                   reason=f"preemption requested ({g.reason or 'signal'})")
        return True

    def _pump_once(self) -> None:
        if self._check_guard():
            return
        with self._lock:
            if self.scheduler.has_work():
                # analysis: allow(blocking-call-in-lock) — the API lock is
                # the engine serialization point (foreground pump)
                self._step_guarded()

    def _step_guarded(self) -> None:
        # caller holds the lock. One SUPERVISED scheduler step: a transient
        # device/arena failure is recovered by rebuild+replay and the pump
        # just continues; anything else fails every in-flight request
        # (error + stream sentinel + done_event) before propagating, so a
        # pumping caller can never strand RUNNING requests holding slots
        # and arena blocks. The phase is the whole locked turn, a
        # recovery included (its seconds show beside supervisor.rebuilds).
        self._turn += 1
        with telemetry.phase("sched.step", self.engine.hists,
                             turn=self._turn):
            try:
                self.scheduler.step()
                self.supervisor.note_step()
            # analysis: allow(broad-except) — THE classification point:
            # the supervisor decides transient-vs-fatal for every step error
            except Exception as e:
                try:
                    recovered = self.supervisor.handle(e)
                # analysis: allow(broad-except) — recovery failure of any
                # kind must fail staged requests, never strand them RUNNING
                except Exception as e2:
                    # recovery itself died (e.g. the rebuilt arena's
                    # allocation failed on a still-dead device): the
                    # supervisor already failed the requests it had staged
                    # for replay; fail_all sweeps whatever is left
                    # registered, so nothing is ever stranded RUNNING with
                    # its done_event unset
                    self.scheduler.fail_all(e2)
                    raise e2 from e
                if recovered:
                    metrics.bump("api.recoveries")
                    return
                err = self.supervisor.wrap(e)
                self.scheduler.fail_all(err)
                if err is e:
                    raise
                raise err

    def _pump_loop(self) -> None:
        # the pump thread's wall time is two phases end to end:
        # pump.unlocked (the guard poll, the wait to take the lock back
        # from submitting handler threads, has_work, the idle sleep) and
        # sched.step (_step_guarded: the locked turn)
        while not self._closed:
            with telemetry.phase("pump.unlocked",
                                 self.engine.hists) as unlocked:
                self._check_guard()
                with self._lock:
                    busy = self.scheduler.has_work()
                    if busy:
                        unlocked.stop()
                        try:
                            # analysis: allow(blocking-call-in-lock) — the
                            # API lock is the engine serialization point
                            # (background pump thread)
                            self._step_guarded()
                        except Exception:
                            # analysis: allow(broad-except) — the pump
                            # thread must never die silently with
                            # requests in flight: _step_guarded already
                            # failed them all (done_event + sentinel) —
                            # keep serving; new submissions surface errors
                            # through their own results
                            pass
                if not busy:
                    time.sleep(0.001)


class EnginePredictor:
    """``paddle.inference`` predictor surface over the serving engine.

    Input ``input_ids`` is an int32 ``[batch, prompt_len]`` array; ``run``
    submits one request per row and continuous-batches them through the
    slot engine. Output ``output_0`` is ``[batch, prompt_len +
    max_new_tokens]`` with post-stop positions filled with the stop token
    (exactly ``GPT.generate(stop_token_id=...)``'s contract, so swapping a
    predictor backend never changes downstream parsing). ``priority``
    (constructor default, overridable per ``run``) rides the scheduler's
    priority admission — an offline-batch predictor can mark itself
    preemptible under a latency-sensitive one sharing the engine."""

    def __init__(self, model, max_new_tokens: int = 32,
                 stop_token_id: Optional[int] = None, priority: int = 0,
                 sampling=None, adapter: int = 0,
                 config: Optional[ServingConfig] = None, **engine_kw):
        self._api = ServingAPI(model, config, **engine_kw)
        self._max_new = int(max_new_tokens)
        self._stop = stop_token_id
        self._priority = int(priority)
        self._sampling = sampling   # SamplingParams for every row (None =
        self._adapter = int(adapter)  # greedy); LoRA row id (0 = base)
        self._inputs = {}
        self._outputs = {}
        self._finished = 0  # this predictor's own rows, for close()'s
        self._failed = 0    # summary (metrics.stats() is process-global)

    def get_input_names(self) -> List[str]:
        return ["input_ids"]

    def get_output_names(self) -> List[str]:
        return sorted(self._outputs) or ["output_0"]

    def get_input_handle(self, name: str):
        from ..inference import PredictorTensor

        return PredictorTensor(self, name)

    def get_output_handle(self, name: str):
        from ..inference import PredictorTensor

        return PredictorTensor(self, name)

    def run(self, inputs: Optional[List[np.ndarray]] = None,
            priority: Optional[int] = None):
        """One predictor run. ``priority`` overrides the constructor's
        class for this batch only (lower = served first; None = keep)."""
        if inputs is not None:
            ids = np.asarray(inputs[0])
        else:
            ids = np.asarray(self._inputs["input_ids"])
        ids = np.atleast_2d(ids).astype(np.int32)
        b, plen = ids.shape
        pr = self._priority if priority is None else int(priority)
        reqs = []
        try:
            for row in ids:
                reqs.append(self._api.submit(row,
                                             max_new_tokens=self._max_new,
                                             stop_token_id=self._stop,
                                             priority=pr,
                                             sampling=self._sampling,
                                             adapter=self._adapter))
        except Exception:
            # analysis: allow(broad-except) — cleanup-and-reraise: a
            # mid-batch submit failure (overload shed, validation) must
            # not strand the rows already queued: their handles would be
            # unreachable, and admission would still spend capacity on them
            # ahead of the next run(). Flag every cancel BEFORE pumping so
            # the cull runs once and no doomed row gets admitted (and
            # charged a prefill) while its siblings are being cancelled.
            for req in reqs:
                req.cancel()
            if reqs:
                self._api._pump_once()
            raise
        self._api.run_until_idle()
        self._finished += sum(r.state == RequestState.FINISHED for r in reqs)
        self._failed += sum(r.state == RequestState.FAILED for r in reqs)
        fill = self._stop if self._stop is not None else 0
        out = np.full((b, plen + self._max_new), fill, np.int32)
        out[:, :plen] = ids
        for i, req in enumerate(reqs):
            if req.state == RequestState.FAILED:
                raise req.error
            toks = np.asarray(req.tokens, np.int32)
            out[i, plen:plen + len(toks)] = toks
        self._outputs = {"output_0": out}
        if inputs is not None:
            return [out]

    def close(self) -> None:
        """Close the underlying API (drain with grace=0) and log this
        predictor's lifetime summary — including the resilience picture:
        supervisor replays/rebuilds, scheduler preemptions, drains. All
        counts come from this predictor's OWN engine stack (the
        ``serving.metrics`` counters are process-global and would
        misattribute a concurrent instance's activity)."""
        api = self._api
        api.close()
        cache = api.engine.prefix_cache
        if cache is not None and (cache.hits or cache.misses):
            prefix = (", prefix hit-rate %.0f%% (%d/%d admits, "
                      "%d prefill tokens avoided)") % (
                          100.0 * cache.hits / (cache.hits + cache.misses),
                          cache.hits, cache.hits + cache.misses,
                          cache.hit_tokens)
        else:
            prefix = ""
        tier_view = getattr(api.engine, "tier", None)
        if tier_view is not None and (tier_view.host_hits
                                      or tier_view.disk_hits
                                      or tier_view.misses
                                      or tier_view.spilled_blocks):
            # the tiered-KV picture next to the prefix hit-rate: how many
            # spilled-block lookups each tier answered (a miss = the
            # entry was lost and the prefix recomputed)
            lookups = (tier_view.host_hits + tier_view.disk_hits
                       + tier_view.misses)
            rate = (100.0 * (tier_view.host_hits + tier_view.disk_hits)
                    / lookups) if lookups else 0.0
            tier = (", tier hit-rate %.0f%% (%d host / %d disk hits, "
                    "%d blocks spilled, %d restored)") % (
                        rate, tier_view.host_hits, tier_view.disk_hits,
                        tier_view.spilled_blocks, tier_view.restored_blocks)
        else:
            tier = ""
        spec = api.engine.spec
        if spec is not None and spec.proposed:
            speculation = (", speculation %d proposed / %d accepted "
                           "(%.0f%% acceptance, %d emitted, %s k=%d)") % (
                               spec.proposed, spec.accepted,
                               100.0 * spec.acceptance_rate(),
                               spec.emitted, spec.mode(), spec.k)
        else:
            speculation = ""
        engine = api.engine
        if engine.quant_weights or engine.quant_kv or engine.quant_draft:
            # the quantized-serving memory picture, per arena namespace —
            # the int8 win is reported, not just asserted in tests
            by_ns = engine.arena.bytes_by_namespace()
            arena_desc = " + ".join(
                "%s %s %.2f MiB%s" % (
                    name, d["dtype"], d["bytes"] / 2 ** 20,
                    (" (%.2f MiB scales)" % (d["scale_bytes"] / 2 ** 20)
                     if d["scale_bytes"] else ""))
                for name, d in by_ns.items())
            quant = ", quantized serving [weights=%d kv=%d draft=%d]: %s" % (
                int(engine.quant_weights), int(engine.quant_kv),
                int(engine.quant_draft), arena_desc)
        else:
            quant = ""
        if (engine.sampled_admits or engine.constrained_admits
                or engine.adapter_admits or engine.lora is not None):
            # the scenario-diversity picture: per-slot sampling /
            # constrained decoding / multi-LoRA admissions of THIS engine
            lora_desc = ""
            if engine.lora is not None:
                st = engine.lora.stats()
                lora_desc = ", lora arena rank %d: %d/%d live (%.2f MiB)" % (
                    st["lora.rank"], st["lora.live"], st["lora.slots"],
                    st["lora.arena_bytes"] / 2 ** 20)
            scenario = (", scenarios: %d sampled / %d constrained / "
                        "%d adapter admits%s") % (
                            engine.sampled_admits,
                            engine.constrained_admits,
                            engine.adapter_admits, lora_desc)
        else:
            scenario = ""
        # headline latency percentiles from THIS engine's histograms
        # (satellite: the benches read the same surface instead of
        # re-deriving percentiles from ad-hoc sample lists)
        ttft_h = engine.hists.peek("latency.ttft")
        gap_h = engine.hists.peek("latency.inter_token")
        latency = ""
        if ttft_h is not None and ttft_h.n:
            latency = (", ttft p50/p95/p99 %.1f/%.1f/%.1f ms" % (
                ttft_h.percentile(50) * 1e3, ttft_h.percentile(95) * 1e3,
                ttft_h.percentile(99) * 1e3))
            if gap_h is not None and gap_h.n:
                latency += (", inter-token p50/p95/p99 "
                            "%.2f/%.2f/%.2f ms" % (
                                gap_h.percentile(50) * 1e3,
                                gap_h.percentile(95) * 1e3,
                                gap_h.percentile(99) * 1e3))
        _logger.info(
            "EnginePredictor closed: %d finished, %d failed, "
            "%d supervisor replays (%d rebuilds), %d preemptions, "
            "%d drains%s%s%s%s%s%s",
            self._finished, self._failed,
            api.supervisor.replay_count, api.supervisor.rebuild_count,
            api.scheduler.preempt_count, api.drain_count, prefix, tier,
            speculation, quant, scenario, latency)
