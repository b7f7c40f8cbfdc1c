"""Engine supervisor: rebuild-and-replay recovery for the serving runtime.

The PR 4 engine treated every step exception as fatal: ``Scheduler.fail_all``
failed each in-flight request and the caller resubmitted from scratch. On
preemptible TPUs that is the wrong default — a lost device or an evicted
backend is *transient*, and each request already
journals everything needed to resume (``Request.prompt`` + the emitted
``Request.tokens``). The supervisor turns those failures into a bounded
recovery loop:

1. **Classify** — :func:`is_transient_serving_error`. Recoverable:
   :class:`core.resilience.ServingDeviceError`,
   :class:`core.resilience.ArenaCorruptError` (the fault-injection kinds
   ``serving_device`` / ``arena_corrupt``), and real ``jaxlib`` runtime
   errors (``XlaRuntimeError`` — the class a dying PJRT client actually
   raises). Everything else (bugs, validation, deadlines) keeps the
   fail-fast path.
2. **Rebuild** — ``ServingEngine.rebuild()`` drops the (possibly corrupt or
   donation-consumed) KV arena and resets all slot state — including the
   radix prefix tree, which indexed the dead arena's blocks. Compiled
   programs depend only on shapes — and, on a device mesh, on committed
   shardings: the engine's ``_arena_args`` carry its captured mesh, so a
   rebuilt arena re-commits the SAME model-axis pool placement and the
   rebuilt engine serves with ZERO recompiles, tensor-parallel or not
   (tests/test_mesh_serving.py asserts the mesh case).
3. **Replay** — every live request is re-prefilled from its journal
   (``engine.admit(prompt, max_new, tokens=...)``): the prefill runs over
   ``prompt + tokens`` and emits the journal's next token, leaving the slot
   exactly where an uninterrupted decode would be. Output is
   token-for-token identical (prefill and decode share one numerics
   contract — ``models.serving_seam.masked_attention`` / ``serving_head``). With
   the prefix cache on, each replayed admission re-inserts its prompt's
   full blocks, so replays that share a prefix re-attach the SAME fresh
   blocks by reference — the tree re-populates as a side effect of
   recovery, with the same refcount discipline as live traffic.
4. **Break the crash loop** — ``FLAGS_serving_max_rebuilds`` rebuilds within
   ``FLAGS_serving_rebuild_window`` scheduler steps open the breaker:
   further transient failures degrade to fail-fast with a
   :class:`CrashLoopError` naming the loop, instead of rebuilding forever
   against a genuinely dead device.

Counters: ``serving.rebuilds`` / ``serving.replays`` via
``core.resilience.bump`` (memory_stats providers, profiler Resilience
delta, ``tools/resilience_stats.py``) and mirrored as ``supervisor.*`` in
``serving.metrics`` (profiler "Serving" per-run delta,
``tools/serving_stats.py``).
"""
from __future__ import annotations

from typing import List, Optional

from ..core import flags, resilience
from . import metrics, telemetry
from .scheduler import RequestState, _seq_counter, admit_kwargs


class CrashLoopError(RuntimeError):
    """The supervisor's crash-loop breaker is open: too many engine
    rebuilds in too few steps. The underlying transient error is chained as
    ``__cause__``; in-flight requests fail fast with this error instead of
    replaying into a device that keeps dying."""


#: error classes the supervisor recovers by rebuild+replay
TRANSIENT_ERRORS = (resilience.ServingDeviceError,
                    resilience.ArenaCorruptError)


def is_transient_serving_error(exc: BaseException) -> bool:
    """True when a serving-step/prefill failure is worth a rebuild+replay:
    the registry's ``serving_device``/``arena_corrupt`` fault classes, or a
    real ``jaxlib`` runtime error (lost PJRT device, evicted backend).
    IO-class errors are NOT claimed here — they belong to the engine's
    (donation-off) retry policy; and plain bugs/validation errors must keep
    failing fast."""
    if isinstance(exc, TRANSIENT_ERRORS):
        return True
    if not isinstance(exc, Exception):
        return False  # KeyboardInterrupt/SystemExit are never "transient"
    for klass in type(exc).__mro__:
        mod = getattr(klass, "__module__", "") or ""
        if klass.__name__ == "XlaRuntimeError" or mod.startswith("jaxlib"):
            return True
    return False


class EngineSupervisor:
    """Owns recovery policy for one engine+scheduler pair. The API layer
    routes every step/prefill exception through :meth:`handle`; a True
    return means the engine was rebuilt and every live request replayed —
    the pump just continues."""

    def __init__(self, engine, scheduler,
                 max_rebuilds: Optional[int] = None,
                 window: Optional[int] = None):
        self.engine = engine
        self.scheduler = scheduler
        self.max_rebuilds = int(flags.flag("serving_max_rebuilds")
                                if max_rebuilds is None else max_rebuilds)
        self.window = int(flags.flag("serving_rebuild_window")
                          if window is None else window)
        self._steps = 0  # successful scheduler steps (breaker clock)
        self._rebuild_steps: List[int] = []
        self.breaker_open = False
        # lifetime totals for THIS engine stack (the module-global
        # serving.metrics counters aggregate across instances)
        self.rebuild_count = 0
        self.replay_count = 0

    # ------------------------------------------------------------ plumbing

    def note_step(self) -> None:
        """Called by the pump after each successful scheduler step — the
        breaker window is measured in steps of actual progress."""
        self._steps += 1

    def wrap(self, error: BaseException) -> BaseException:
        """The error to fail requests with when recovery was declined:
        transient errors hitting an open breaker become a
        :class:`CrashLoopError` (clear operator signal), everything else
        passes through unchanged."""
        if self.breaker_open and is_transient_serving_error(error):
            wrapped = CrashLoopError(
                f"serving supervisor crash-loop breaker open: "
                f"{len(self._rebuild_steps)} engine rebuilds within "
                f"{self.window} steps (FLAGS_serving_max_rebuilds="
                f"{self.max_rebuilds}); failing fast on: {error!r}")
            wrapped.__cause__ = error
            return wrapped
        return error

    # ------------------------------------------------------------ recovery

    def handle(self, error: BaseException) -> bool:
        """Recover from ``error`` if it is transient and the breaker
        allows: rebuild the engine, replay every live request from its
        journal. Returns True on recovery; False means the caller must
        fail-fast (use :meth:`wrap` for the error to surface) — including
        when the breaker exhausted mid-recovery (replayed state was failed
        fast), so a total failure is never reported as a recovery."""
        if not is_transient_serving_error(error):
            return False
        if not self._allow_rebuild():
            return False
        return self._recover()

    def _allow_rebuild(self) -> bool:
        """Breaker bookkeeping for ONE rebuild attempt: prune rebuilds that
        aged out of the window, open the breaker when the budget is spent,
        else record this attempt and allow it."""
        if self.breaker_open:
            return False
        self._rebuild_steps = [s for s in self._rebuild_steps
                               if self._steps - s < self.window]
        if len(self._rebuild_steps) >= self.max_rebuilds:
            self.breaker_open = True
            return False
        self._rebuild_steps.append(self._steps)
        return True

    def _recover(self) -> bool:
        """Rebuild the arena/slot state and re-prefill every live request
        from prompt+journal. A replay admission that fails TRANSIENTLY
        means the engine died again mid-recovery: it burns another breaker
        token and the rebuild starts over with every not-yet-finished
        request (breaker exhaustion fails them fast with :meth:`wrap`'s
        CrashLoopError and returns False — not a recovery). A
        non-transient replay failure fails that request alone; the rest
        resume. If recovery itself dies unexpectedly (the fresh arena
        allocation failing on a still-dead device), every request still
        staged for replay is failed before the error propagates — nothing
        is ever left slot-less with its done_event unset."""
        sched = self.scheduler
        # mid-chunked-prefill requests died with the arena too: their
        # journal is just prompt (+ any pre-crash tokens), so replay
        # re-admits them through the normal one-shot prefill — recovery
        # favors simplicity over chunk interleaving (the outage already
        # stalled every stream; with speculation on, admit() also
        # reconstructs each slot's draft cache)
        pending = list(sched.running) + list(
            getattr(sched, "prefilling", ()))
        sched.running.clear()
        if hasattr(sched, "prefilling"):
            sched.prefilling.clear()
        for req in pending:
            req.slot = None  # the old slot numbers die with the old arena
        try:
            return self._rebuild_and_replay(pending)
        # analysis: allow(broad-except) — any replay failure must fail
        # the staged requests (done_event + sentinel), never strand them
        except Exception as e:
            for req in list(pending):
                sched._finish(req, RequestState.FAILED, e)
            raise
        finally:
            sched._gauges()

    def _rebuild_and_replay(self, pending) -> bool:
        # mutates ``pending`` in place so _recover can fail exactly the
        # requests still staged if this raises
        sched = self.scheduler
        while True:
            self.engine.rebuild()
            self.rebuild_count += 1
            metrics.bump("supervisor.rebuilds")
            resilience.bump("serving.rebuilds")
            died_again: Optional[BaseException] = None
            for req in list(pending):
                try:
                    # admit_kwargs re-threads the request's sampling
                    # params, adapter id and the constraint walker's
                    # current mask: positional PRNG keys + journal-derived
                    # walker state make the replayed stream bit-identical
                    # to the uninterrupted one
                    slot, nxt = self.engine.admit(req.prompt,
                                                  req.max_new_tokens,
                                                  tokens=req.tokens,
                                                  **admit_kwargs(req))
                # analysis: allow(broad-except) — classification inside:
                # transient errors restage the replay, the rest fail one
                # request each
                except Exception as e:
                    if is_transient_serving_error(e):
                        died_again = e
                        break
                    # replay must never strand a request: a non-transient
                    # admission failure fails it alone, the rest resume
                    pending.remove(req)
                    sched._finish(req, RequestState.FAILED, e)
                    continue
                pending.remove(req)
                req.slot = slot
                req._admit_seq = next(_seq_counter)
                sched.running.append(req)
                # REPLAYED before the replayed token's emit: the timeline
                # reads rebuild -> resume -> tokens, on the SAME trace_id
                # the request carried since submit
                telemetry.span(req.trace_id, telemetry.REPLAYED,
                               request_id=req.request_id, slot=slot,
                               journal_tokens=len(req.tokens),
                               rebuilds=self.rebuild_count)
                sched._emit(req, nxt)
                self.replay_count += 1
                metrics.bump("supervisor.replays")
                resilience.bump("serving.replays")
                sched._check_boundary(req)  # the replayed token may finish it
            if died_again is None:
                return True
            # every slot re-admitted so far sits in the arena that just
            # died: retire it (host-side bookkeeping — frees the slot and
            # its block reservation, so breaker exhaustion leaks nothing)
            # and restage the request with the remainder, then let the
            # breaker decide whether one more rebuild is allowed
            for req in list(sched.running):
                self.engine.retire(req.slot)
                req.slot = None
                pending.append(req)
            sched.running.clear()
            if not self._allow_rebuild():
                err = self.wrap(died_again)
                for req in list(pending):
                    pending.remove(req)
                    sched._finish(req, RequestState.FAILED, err)
                return False
