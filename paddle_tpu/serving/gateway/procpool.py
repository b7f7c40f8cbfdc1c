"""Process-isolated replica fleet: ``ReplicaPool`` over OS workers (ISSUE 18).

:class:`ProcessReplicaPool` keeps the router's entire contract — journal
crash recovery, eject / respawn-backoff / crash-loop breaker, ``scale_to``
and drain semantics, tenant accounting, span timelines — and swaps the
replica substrate: each replica is a supervised **worker process**
(``worker.worker_main`` spawned via ``multiprocessing.get_context("spawn")``)
instead of an in-process background thread. A segfault, OOM kill, or wedged
runtime call now takes down one process's fault domain; the gateway
classifies the death from the outside and re-routes the victim's journaled
streams token-for-token onto survivors, exactly like a thread-replica
ejection.

The pieces:

* :class:`WorkerHandle` — the RPC client half of ``worker.py``'s framing.
  It impersonates a ``ServingAPI`` closely enough for the base router
  (``submit`` returns a :class:`RemoteRequest` that mirrors
  ``scheduler.Request``'s observable surface; ``engine`` / ``supervisor`` /
  ``scheduler`` are thin proxies carrying the handful of attributes the
  router and ``/v1/metrics`` read). A reader thread demultiplexes response
  frames from spontaneous heartbeats; the thread doubles as the
  ``api._thread`` sentinel, so the base pump loop correctly treats every
  worker as self-pumping.
* the **heartbeat watchdog** — workers push liveness every
  ``FLAGS_gateway_heartbeat_interval`` seconds; the sweep classifies
  silence (``FLAGS_gateway_heartbeat_misses`` missed intervals →
  ``worker.hangs``), a negative exit code (``worker.kills`` — the kill -9
  case), and a plain exit (``worker.exits``) into the SAME eject taxonomy
  the thread pool uses, so backoff doubling and the crash-loop breaker
  carry over per process unchanged.
* crash recovery — the gateway's :class:`~.router.RoutedRequest` already
  keeps each stream's prompt + emitted-token journal client-side; a killed
  worker's in-flight streams re-enter ``_route(journal=..., shed=False)``
  on survivors. Workers ship their telemetry spans over the wire
  (heartbeat + poll frames → :func:`~..telemetry.ingest`), so one trace_id
  still reads as one contiguous SUBMITTED → ... → REROUTED → ... timeline.

``FLAGS_gateway_process_replicas=0`` (default) never touches this module —
``serve()`` keeps building the thread-replica ``ReplicaPool`` bit-for-bit.

Known, accepted race: a submit can land on a worker that died microseconds
ago and surface :class:`WorkerDiedError` to the caller (a retriable 503 at
the gateway) — the next sweep ejects the corpse; admissions after that
route around it.
"""
from __future__ import annotations

import atexit
import dataclasses
import logging
import multiprocessing
import os
import signal
import socket
import threading
import time
import weakref
from typing import Dict, List, Optional

import numpy as np

from ...core import flags, resilience
from .. import metrics, telemetry
from ..scheduler import RequestState, StreamSignal
from . import worker
from .router import _RESPAWN_BACKOFF_CAP, ReplicaPool, _is_reroutable

_logger = logging.getLogger("paddle_tpu.serving.gateway")

#: worker boot budget: a spawn interpreter + jax import + engine build +
#: compile-cache reload; generous because blowing it ejects a HEALTHY boot
_BOOT_TIMEOUT = 180.0


class WorkerDiedError(resilience.ServingDeviceError):
    """The worker process behind a handle is gone (killed, exited,
    connection lost, or silent past the heartbeat budget). Subclasses
    ``ServingDeviceError`` on purpose: the router's ``_is_reroutable``
    already treats that as "eject the replica, re-route the journaled
    streams" — process death rides the existing taxonomy."""


class WorkerProtocolError(resilience.ServingDeviceError):
    """The worker's byte stream broke framing (truncated / oversized /
    garbage frame). The connection is unrecoverable, so the worker is
    ejected like a death — but counted separately
    (``worker.protocol_errors``): corruption is a bug signal, not an
    infra fault."""


class WorkerBusyError(RuntimeError):
    """A poll RPC blew its deadline while the worker process was alive
    AND heartbeating — load (cold compiles, an oversubscribed host), not
    a hang. Deliberately NOT a ``ServingDeviceError``: it must never ride
    the reroute taxonomy; ``poll()`` absorbs it and retries next cycle,
    ejecting only after ``hb_misses`` consecutive busy timeouts (a main
    loop that is wedged while its heartbeat thread lives)."""


# --------------------------------------------------------------- proxies


class _EngineProxy:
    """The engine attributes the router + metrics plane read, with every
    in-process-only feature pinned off: no prefix cache (affinity routing
    has nothing to probe across a process boundary — load-based candidate
    order still applies), no spec/tier/chunked-prefill introspection, no
    latency hists (the worker's live in ITS process; ``remote_stats``
    scrapes the counters)."""

    prefix_cache = None
    spec = None
    tier = None
    hists = None
    chunk_size = 0
    lora = None

    def __init__(self, num_slots: int, vocab: int):
        self.num_slots = int(num_slots)
        self.vocab = int(vocab)


class _SupervisorProxy:
    """Mirrors the worker-reported crash-loop breaker state (shipped on
    every heartbeat and poll response) — ``_sweep_health`` reads it
    exactly like a local ``EngineSupervisor``'s."""

    def __init__(self):
        self.breaker_open = False


class _SchedulerProxy:
    """The worker's scheduler is remote; the base pump loop never steps a
    replica whose ``api._thread`` is set, so this only has to exist."""

    prefilling = ()

    def has_work(self) -> bool:
        return False


# --------------------------------------------------------- remote request


class _TERMINAL:
    STATES = (RequestState.FINISHED, RequestState.CANCELLED,
              RequestState.FAILED)


class RemoteRequest:
    """Client-side mirror of one worker-resident ``scheduler.Request`` —
    the ``backend`` object a :class:`~.router.RoutedRequest` attaches to.
    ``tokens`` is seeded with the journal exactly like the worker seeds its
    request, so both sides agree on offsets and the router's
    journal-folding arithmetic carries over unchanged.

    Mutated only by its owning handle's (serialized) poll / death paths;
    readers tolerate torn progress the same way they do for a live
    ``scheduler.Request`` (``state`` goes terminal only AFTER the final
    tokens landed)."""

    def __init__(self, handle: "WorkerHandle", rid: str, request_id: str,
                 trace_id: str, journal):
        self.handle = handle
        self.rid = rid
        self.request_id = request_id
        self.trace_id = trace_id
        self.tokens: List[int] = [int(t) for t in (journal or ())]
        self.state = RequestState.QUEUED
        self.error: Optional[BaseException] = None
        self.done_event = threading.Event()
        # fired when a poll brought tokens or a terminal state: the
        # router swaps its handle's own in at attach (as on a live
        # ``scheduler.Request``), so a stream consumer of a background
        # pool blocks until the watchdog's poll has something for it
        self.signal = StreamSignal()

    @property
    def finished(self) -> bool:
        return self.state in _TERMINAL.STATES

    def cancel(self) -> None:
        if self.finished:
            return
        try:
            self.handle.cancel_request(self.rid)
        except (WorkerDiedError, WorkerProtocolError):
            pass  # a dead worker's requests are failed by mark_dead; the
            # router's cancelled flag makes the cancel stick on re-route

    def _apply(self, entry: dict) -> None:
        """Fold one poll entry in: tokens first, terminal state last, so
        ``finished`` implies the token tail is complete."""
        tail = entry.get("tokens") or ()
        if tail:
            self.tokens.extend(int(t) for t in tail)
        err = entry.get("error")
        if err is not None:
            self.error = worker.decode_error(err)
        state = entry.get("state")
        if state:
            self.state = state
        if self.finished:
            self.done_event.set()
        if tail or self.finished:
            self.signal.fire()

    def _fail(self, cause: BaseException) -> None:
        if self.finished:
            return
        self.error = cause
        self.state = RequestState.FAILED
        self.done_event.set()
        self.signal.fire()


# ---------------------------------------------------------- worker handle


class WorkerHandle:
    """RPC client for one worker process; quacks like the slice of
    ``ServingAPI`` the router touches. One socket carries everything: a
    reader thread routes response frames to their pending calls and folds
    heartbeat frames into liveness/breaker state + span ingestion. Every
    call takes a ``resilience.Deadline`` (``FLAGS_gateway_worker_timeout``
    unless the op brings its own budget) — a worker that blows it is
    classified dead, never waited on forever."""

    def __init__(self, idx: int, conn: socket.socket, proc,
                 pid: int, num_slots: int, vocab: int,
                 call_timeout: float, hb_interval: float,
                 hb_misses: int = 3):
        self.idx = int(idx)
        self.proc = proc
        self.pid = int(pid)
        self._conn = conn
        self._wlock = threading.Lock()   # frame writes
        self._lock = threading.Lock()    # _pending / _reqs / _dead / seqs
        self._poll_lock = threading.Lock()  # serialize whole poll cycles
        self._pending: Dict[int, list] = {}   # call id -> [event, resp]
        self._reqs: Dict[str, RemoteRequest] = {}
        # finalized rids the worker hasn't confirmed dropping yet — the
        # worker retains a finished request until this ack reaches it
        # (poll responses are lossy under busy timeouts; see _op_poll)
        self._done_unacked: set = set()
        self._dead: Optional[BaseException] = None
        self._closing = False
        self._exit_classified = False
        self._rid_seq = 0
        self._call_seq = 0
        self._call_timeout = float(call_timeout)
        self.hb_interval = float(hb_interval)
        self.hb_misses = max(1, int(hb_misses))
        self._busy_polls = 0  # consecutive, poll-cycle thread only
        # plain float slam from the reader thread, read anywhere — a torn
        # read is impossible for a single attribute rebind under the GIL
        self._last_hb = time.monotonic()
        self.engine = _EngineProxy(num_slots, vocab)
        self.supervisor = _SupervisorProxy()
        self.scheduler = _SchedulerProxy()
        # doubles as the base router's "self-pumping replica" sentinel
        # (`rep.api._thread is not None` skips the foreground pump)
        self._thread = threading.Thread(
            target=self._reader_loop, name=f"worker-{idx}-reader",
            daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- spawn

    @classmethod
    def spawn(cls, idx: int, payload: dict,
              boot_timeout: float = _BOOT_TIMEOUT,
              call_timeout: float = 10.0,
              hb_interval: float = 0.2,
              hb_misses: int = 3) -> "WorkerHandle":
        """Bind an ephemeral loopback listener, spawn ``worker_main``
        (fresh interpreter — no forked jax state), take its dial-back and
        hello (or its typed boot error), return the live handle."""
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        proc = None
        try:
            listener.bind(("127.0.0.1", 0))
            listener.listen(1)
            host, port = listener.getsockname()
            ctx = multiprocessing.get_context("spawn")
            proc = ctx.Process(target=worker.worker_main,
                               args=(host, port, idx, payload),
                               name=f"serving-worker-{idx}", daemon=True)
            proc.start()
            listener.settimeout(boot_timeout)
            conn, _ = listener.accept()
        except OSError as e:
            if proc is not None and proc.is_alive():
                proc.kill()
            raise WorkerDiedError(
                f"worker {idx} never dialed back within {boot_timeout}s "
                f"({e})") from e
        finally:
            listener.close()
        try:
            conn.settimeout(boot_timeout)
            hello = worker.recv_frame(conn)
        except (worker.FrameError, OSError) as e:
            conn.close()
            if proc.is_alive():
                proc.kill()
            raise WorkerProtocolError(
                f"worker {idx} boot handshake broke framing: {e}") from e
        if hello is None or not hello.get("hello"):
            conn.close()
            if proc.is_alive():
                proc.kill()
            proc.join(5.0)
            cause = (worker.decode_error(hello.get("error"))
                     if isinstance(hello, dict) else None)
            raise WorkerDiedError(
                f"worker {idx} failed to boot: "
                f"{cause if cause is not None else 'no hello frame'}")
        conn.settimeout(None)
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        return cls(idx, conn, proc, hello.get("pid", proc.pid or 0),
                   hello.get("num_slots", 1), hello.get("vocab", 1),
                   call_timeout, hb_interval, hb_misses)

    # ------------------------------------------------------ reader thread

    def _reader_loop(self) -> None:
        conn = self._conn
        while True:
            try:
                msg = worker.recv_frame(conn)
            except worker.FrameError as e:
                resilience.bump("worker.protocol_errors")
                self.mark_dead(WorkerProtocolError(
                    f"worker {self.idx} (pid {self.pid}): {e}"))
                return
            except OSError as e:
                self.mark_dead(WorkerDiedError(
                    f"worker {self.idx} (pid {self.pid}): "
                    f"connection lost ({e})"))
                return
            if msg is None:
                self.mark_dead(WorkerDiedError(
                    f"worker {self.idx} (pid {self.pid}): "
                    "connection closed"))
                return
            if msg.get("hb"):
                self._on_heartbeat(msg)
                continue
            with self._lock:
                slot = self._pending.pop(msg.get("id"), None)
            if slot is not None:
                slot[1] = msg
                slot[0].set()

    def _on_heartbeat(self, msg: dict) -> None:
        self._last_hb = time.monotonic()
        self.supervisor.breaker_open = bool(msg.get("breaker_open"))
        resilience.bump("worker.heartbeats")
        spans = msg.get("spans")
        if spans:
            telemetry.ingest(spans)

    # --------------------------------------------------------------- RPC

    def _dead_copy(self) -> BaseException:
        # a fresh instance per raiser: the recorded cause is shared state,
        # and re-raising one exception object from many threads splices
        # tracebacks
        cause = self._dead
        return type(cause)(str(cause))

    def _call(self, op: str, body: Optional[dict] = None,
              timeout: Optional[float] = None,
              busy_ok: bool = False) -> dict:
        event = threading.Event()
        slot: list = [event, None]
        with self._lock:
            if self._dead is not None:
                raise self._dead_copy()
            self._call_seq += 1
            cid = self._call_seq
            self._pending[cid] = slot
        msg = dict(body or {})
        msg["id"] = cid
        msg["op"] = op
        try:
            worker.send_frame(self._conn, msg, self._wlock)
        except (worker.FrameError, OSError) as e:
            with self._lock:
                self._pending.pop(cid, None)
            cause = WorkerDiedError(
                f"worker {self.idx} (pid {self.pid}): send of {op!r} "
                f"failed ({e})")
            self.mark_dead(cause)
            raise cause from e
        deadline = resilience.Deadline.after(
            self._call_timeout if timeout is None else timeout)
        if not event.wait(deadline.remaining()):
            with self._lock:
                self._pending.pop(cid, None)
            alive = self.proc is not None and self.proc.is_alive()
            if (busy_ok and alive
                    and self.heartbeat_age()
                    < self.hb_interval * self.hb_misses):
                # alive AND heartbeating: a slow answer under load (cold
                # compiles, oversubscribed host), not a hang — the caller
                # retries the cycle; a late response frame for the
                # abandoned id is dropped by the reader
                resilience.bump("worker.busy_polls")
                raise WorkerBusyError(
                    f"worker {self.idx} (pid {self.pid}): RPC {op!r} "
                    f"busy past its deadline, heartbeats fresh")
            if alive:
                # the process lives but neither answers nor heartbeats:
                # that's a hang — same classification the heartbeat
                # sweep would reach
                resilience.bump("worker.hangs")
            cause = WorkerDiedError(
                f"worker {self.idx} (pid {self.pid}): RPC {op!r} timed "
                f"out after "
                f"{self._call_timeout if timeout is None else timeout}s")
            self.mark_dead(cause)
            raise cause
        resp = slot[1]
        if resp is None:
            raise self._dead_copy() if self._dead is not None else \
                WorkerDiedError(f"worker {self.idx}: RPC {op!r} aborted")
        if not resp.get("ok"):
            raise worker.decode_error(resp.get("error"))
        return resp

    # ------------------------------------------------- ServingAPI surface

    def submit(self, prompt, max_new_tokens: int = 32,
               stop_token_id: Optional[int] = None,
               timeout: Optional[float] = None,
               request_id: str = "", priority: int = 0,
               journal=None, shed: bool = True,
               sampling=None, constraint=None, adapter: int = 0,
               trace_id: str = "") -> RemoteRequest:
        with self._lock:
            if self._dead is not None:
                raise self._dead_copy()
            self._rid_seq += 1
            rid = f"{self.idx}.{self._rid_seq}"
        body = {
            "rid": rid,
            "prompt": [int(t) for t in np.asarray(prompt).reshape(-1)],
            "max_new_tokens": int(max_new_tokens),
            "stop_token_id": (None if stop_token_id is None
                              else int(stop_token_id)),
            "timeout": None if timeout is None else float(timeout),
            "request_id": str(request_id),
            "priority": int(priority),
            "journal": (None if journal is None
                        else [int(t) for t in journal]),
            "shed": bool(shed),
            "adapter": int(adapter),
            "trace_id": str(trace_id),
        }
        if sampling is not None:
            body["sampling"] = dataclasses.asdict(sampling)
        if constraint is not None:
            body["constraint"] = worker.b64_dumps(constraint)
        self._call("submit", body)
        req = RemoteRequest(self, rid, request_id, trace_id, journal)
        with self._lock:
            cause = self._dead
            if cause is None:
                self._reqs[rid] = req
        if cause is not None:  # died between the ack and the registration
            req._fail(cause)
            raise type(cause)(str(cause))
        return req

    def poll(self) -> None:
        """One progress cycle: ship per-request offsets, fold the token
        tails / terminal states / spans back in. Serialized end-to-end —
        two interleaved cycles would both read the same offsets and
        double-apply the same tail. The deadline is heartbeat-scaled, not
        the full RPC budget: a hung worker swallows the poll, and waiting
        ``FLAGS_gateway_worker_timeout`` for it would stall the watchdog
        past the very heartbeat window that's supposed to catch the hang
        (poll is a trivial loopback op for a live worker — its main loop
        answers even while the pump thread decodes)."""
        breaker = None
        budget = min(self._call_timeout, max(1.0, 10 * self.hb_interval))
        with self._poll_lock:
            with self._lock:
                if self._dead is not None or (not self._reqs
                                              and not self._done_unacked):
                    return
                offsets = {rid: len(r.tokens)
                           for rid, r in self._reqs.items()}
                done = list(self._done_unacked)
            body: dict = {"reqs": offsets}
            if done:
                body["done"] = done
            try:
                resp = self._call("poll", body,
                                  timeout=budget, busy_ok=True)
            except WorkerBusyError:
                # tolerated while heartbeats stay fresh — but a main loop
                # that never answers while its heartbeat thread lives is
                # wedged all the same: eject after hb_misses consecutive
                # busy cycles
                self._busy_polls += 1
                if self._busy_polls < max(3, self.hb_misses):
                    return
                resilience.bump("worker.hangs")
                cause = WorkerDiedError(
                    f"worker {self.idx} (pid {self.pid}): "
                    f"{self._busy_polls} consecutive poll timeouts with "
                    f"live heartbeats — main loop wedged")
                self.mark_dead(cause)
                raise cause from None
            self._busy_polls = 0
            spans = resp.get("spans")
            if spans:
                telemetry.ingest(spans)
            breaker = bool(resp.get("breaker_open"))
            entries = resp.get("reqs") or {}
            with self._lock:
                # the worker saw the ack list of a SUCCESSFUL call; newly
                # finalized rids below re-join the set for the next cycle
                self._done_unacked.difference_update(done)
                pairs = [(self._reqs[rid], entry)
                         for rid, entry in entries.items()
                         if rid in self._reqs]
                for rid, entry in entries.items():
                    if (entry.get("state") in _TERMINAL.STATES
                            and rid in self._reqs):
                        del self._reqs[rid]
                        self._done_unacked.add(rid)
            for req, entry in pairs:
                req._apply(entry)
        if breaker is not None:
            self.supervisor.breaker_open = breaker

    def cancel_request(self, rid: str) -> None:
        self._call("cancel", {"rid": rid})

    def outstanding(self) -> int:
        with self._lock:
            return len(self._reqs)

    def heartbeat_age(self) -> float:
        return time.monotonic() - self._last_hb

    def register_adapter(self, adapter, name: Optional[str] = None) -> int:
        resp = self._call("register_adapter",
                          {"adapter": worker.b64_dumps(adapter),
                           "name": name})
        return int(resp["adapter_id"])

    def remote_stats(self, timeout: Optional[float] = None) -> dict:
        """The worker PROCESS's serving counters (engine compile counters
        included — the bench's per-survivor zero-recompile gate) plus
        pid/outstanding/breaker."""
        return self._call("stats", {}, timeout=timeout)

    def prefetch(self, prompt, trace_id: str = "") -> int:
        """Restore-ahead (disagg): ask the worker to pre-restore this
        prompt's published chain into its arena (bounded worker-side —
        see ``ServingEngine.prefetch``). Returns blocks restored."""
        resp = self._call("prefetch", {
            "prompt": [int(t) for t in np.asarray(prompt).reshape(-1)],
            "trace_id": str(trace_id)})
        return int(resp.get("blocks", 0))

    def hang(self) -> None:
        """Chaos: tell the worker to stop heartbeating and swallow all
        further frames while HOLDING the socket (``worker_hang``)."""
        self._call("hang", {})

    def drain(self, grace: float = 0.0,
              reason: str = "worker drain") -> None:
        grace = 0.0 if grace is None else max(0.0, float(grace))
        try:
            self._call("drain", {"grace": grace, "reason": str(reason)},
                       timeout=self._call_timeout + grace)
        # analysis: allow(broad-except) — drain is best-effort by
        # contract: a worker that dies or wedges mid-drain already failed
        # its requests through mark_dead / will be reaped by close
        except Exception:
            return
        self.poll()  # reconcile the drain-failed terminal states

    def classify_exit(self, wait: float = 0.5) -> None:
        """Bump ``worker.kills`` / ``worker.exits`` exactly once from the
        process's exit code, whichever path noticed the death first (the
        reader's ECONNRESET usually beats the watchdog's ``is_alive``
        check for a SIGKILL). A worker still alive after ``wait`` was
        ejected while running (hang / breaker) — its SIGKILL is counted
        by the reap instead."""
        with self._lock:
            if self._exit_classified:
                return
            self._exit_classified = True
        proc = self.proc
        if proc is None:
            return
        proc.join(wait)
        if proc.is_alive():
            return
        code = proc.exitcode
        if code is not None and code < 0:
            resilience.bump("worker.kills")
        else:
            resilience.bump("worker.exits")

    def mark_dead(self, cause: BaseException) -> None:
        """Classify the worker as lost: fail every pending call and every
        live request with ``cause`` (re-routable — the router's journal
        recovery takes it from there) and drop the socket. Idempotent;
        the first cause wins."""
        with self._lock:
            if self._dead is not None:
                return
            self._dead = cause
            pending = list(self._pending.values())
            self._pending.clear()
            reqs = list(self._reqs.values())
            self._reqs.clear()
            self._done_unacked.clear()  # nobody left to ack to
        for slot in pending:
            slot[0].set()
        for req in reqs:
            req._fail(cause)
        try:
            self._conn.close()
        except OSError:
            pass

    def close(self) -> None:
        """Polite shutdown, then the guarantee: ask the worker to exit,
        classify the handle dead, and reap the process (join, then SIGKILL
        a straggler) — no orphan worker outlives its pool holding the
        compile-cache dir lock."""
        with self._lock:
            already = self._closing
            self._closing = True
            dead = self._dead is not None
        if not already and not dead:
            try:
                self._call("shutdown", {},
                           timeout=min(5.0, self._call_timeout))
            # analysis: allow(broad-except) — a failed goodbye changes
            # nothing: the reap below ends the process either way
            except Exception:
                pass
        self.mark_dead(WorkerDiedError(
            f"worker {self.idx} (pid {self.pid}) closed"))
        self.reap()

    def reap(self, timeout: float = 5.0) -> None:
        proc = self.proc
        if proc is None:
            return
        proc.join(timeout)
        if proc.is_alive():
            proc.kill()
            proc.join(1.0)
            resilience.bump("worker.kills")


# ------------------------------------------------------------------ pool

#: pools with possibly-live worker processes; the atexit sweep reaps them
#: even when nobody called close() (satellite 2: no orphans holding the
#: compile-cache dir lock past interpreter exit)
_live_pools: "weakref.WeakSet[ProcessReplicaPool]" = weakref.WeakSet()


@atexit.register
def _reap_at_exit() -> None:
    for pool in list(_live_pools):
        try:
            pool.close()
        # analysis: allow(broad-except) — interpreter teardown: every
        # remaining pool must get its kill attempt regardless of how the
        # previous one died
        except Exception:
            _logger.exception("atexit reap of a ProcessReplicaPool failed")


class ProcessReplicaPool(ReplicaPool):
    """The router with worker processes for replicas. Everything the base
    class does — candidate ordering, journal re-routes, backoff doubling,
    tenant accounting, drain/scale semantics — runs unchanged against
    :class:`WorkerHandle`; this subclass adds the process lifecycle: spawn
    payload, heartbeat watchdog classification, async respawn (an engine
    boot takes seconds — it must not stall the survivors' token pumps),
    and guaranteed reaping."""

    #: the watchdog loop is this pool's housekeeping thread: it polls the
    #: workers' tokens besides what the base pool's thread does
    _own_housekeeping = False

    def __init__(self, model, replicas: Optional[int] = None,
                 config=None, tenants=None, background: bool = False,
                 affinity_slack: Optional[int] = None,
                 respawn_backoff: Optional[float] = None,
                 max_reroutes: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 heartbeat_interval: Optional[float] = None,
                 heartbeat_misses: Optional[int] = None,
                 worker_timeout: Optional[float] = None,
                 boot_timeout: float = _BOOT_TIMEOUT, wal=None,
                 **engine_kw):
        self._hb_interval = float(
            flags.flag("gateway_heartbeat_interval")
            if heartbeat_interval is None else heartbeat_interval)
        self._hb_misses = int(flags.flag("gateway_heartbeat_misses")
                              if heartbeat_misses is None
                              else heartbeat_misses)
        self._call_timeout = float(flags.flag("gateway_worker_timeout")
                                   if worker_timeout is None
                                   else worker_timeout)
        self._boot_timeout = float(boot_timeout)
        try:
            self._payload = worker.encode_payload(
                model, dict(config=config, max_queue=max_queue,
                            **engine_kw), self._hb_interval)
        except worker.ChipHeldError:
            raise
        except Exception as e:
            # analysis: allow(broad-except) — pickle failures surface as
            # anything (PicklingError, TypeError, recursion); all of them
            # mean the same actionable thing to the caller
            raise ValueError(
                "ProcessReplicaPool ships the model and engine kwargs to "
                "spawned workers by pickle: pass a picklable model or a "
                "zero-arg factory importable by module path, and only "
                "picklable engine kwargs (in-process handles like a shared "
                f"tier_store cannot cross; got: {e!r})") from e
        self._watchdog_stop = threading.Event()
        self._watchdog: Optional[threading.Thread] = None
        # wal is an explicit pool-level kwarg on purpose: anything left in
        # **engine_kw is pickled into the worker spawn payload, and a WAL
        # (open file handle + locks) must never cross — it is gateway
        # state, one per parent process
        super().__init__(model, replicas=replicas, config=config,
                         tenants=tenants, background=background,
                         affinity_slack=affinity_slack,
                         respawn_backoff=respawn_backoff,
                         max_reroutes=max_reroutes,
                         max_queue=max_queue, wal=wal, **engine_kw)
        _live_pools.add(self)
        if background:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="procpool-watchdog",
                daemon=True)
            self._watchdog.start()

    # ----------------------------------------------------- spawn / respawn

    def _payload_for(self, idx: int) -> dict:
        """The spawn payload for replica ``idx``. Seam for role-typed
        pools (disagg): per-role payloads carry flag overrides, and this
        is called from BOTH the constructor and the respawn threads — a
        role override must be a pure function of ``idx``, never mutable
        pool state."""
        return self._payload

    def _spawn_api(self, idx: int) -> WorkerHandle:
        handle = WorkerHandle.spawn(
            idx, self._payload_for(idx), boot_timeout=self._boot_timeout,
            call_timeout=self._call_timeout,
            hb_interval=self._hb_interval,
            hb_misses=self._hb_misses)
        # ordered replay, same contract as the thread pool: a respawned
        # worker reconstructs the exact adapter-id assignment its peers
        # serve (over RPC instead of a direct arena call)
        for adapter, name in self._adapters:
            handle.register_adapter(adapter, name=name)
        resilience.bump("worker.spawns")
        metrics.set_gauge(f"worker.{idx}.pid", handle.pid)
        return handle

    def _maybe_respawn(self) -> None:
        """Async override: claiming works like the base (under the lock,
        ``respawning`` wins races), but the spawn itself — seconds of
        interpreter boot + engine build — runs on its own thread so the
        watchdog / pump keeps polling the SURVIVORS' tokens meanwhile
        (recovery-to-first-token must not pay a stranger's boot time)."""
        now = time.monotonic()
        with self._lock:
            if self._draining or self._closed:
                return
            due = [r for r in self._replicas
                   if not r.healthy and not r.removed and not r.draining
                   and not r.respawning
                   and now >= r.ejected_at + r.backoff]
            for r in due:
                r.respawning = True
        for rep in due:
            threading.Thread(target=self._respawn_one, args=(rep,),
                             name=f"worker-{rep.idx}-respawn",
                             daemon=True).start()

    def _respawn_one(self, rep) -> None:
        try:
            api = self._spawn_api(rep.idx)
        # analysis: allow(broad-except) — same contract as the base
        # respawn path: a boot that dies arbitrarily re-enters backoff
        # instead of killing the thread that triggered it
        except Exception:
            _logger.exception("respawn of worker %d failed; backing off "
                              "again", rep.idx)
            with self._lock:
                rep.ejected_at = time.monotonic()
                rep.backoff = min(_RESPAWN_BACKOFF_CAP, rep.backoff * 2)
                rep.respawning = False
            return
        with self._lock:
            if (rep.removed or rep.draining or self._draining
                    or self._closed):
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
            # analysis: allow(broad-except) — best-effort teardown of a
            # never-installed handle (close() ends the process regardless)
            except Exception:
                pass
            return
        _logger.info("respawned serving worker %d (generation %d, pid "
                     "%d)", rep.idx, rep.generation, rep.api.pid)
        metrics.bump("gateway.respawned")
        resilience.bump("serving.replica_respawns")
        metrics.set_gauge(f"worker.{rep.idx}.restarts", rep.generation)
        self._refresh_gauges()

    # ------------------------------------------------------------ watchdog

    def _sweep_health(self) -> None:
        self._watchdog_sweep()
        super()._sweep_health()  # worker-reported breaker-open ejects

    def _watchdog_sweep(self) -> None:
        """Classify worker-process deaths into the eject taxonomy: a
        negative exit code is a kill (``worker.kills`` — SIGKILL/OOM), a
        plain exit an exit (``worker.exits``), heartbeat silence past
        ``interval * misses`` a hang (``worker.hangs``). Every
        classification funnels into ``_eject`` — backoff doubling, journal
        re-routes, crash-loop breaker all behave exactly as for a
        thread-replica ejection."""
        with self._lock:
            if self._draining or self._closed:
                return  # shutdown path: workers exiting on command are
                # not deaths to classify (they'd eject + double-count)
        self._chaos_probes()
        threshold = self._hb_interval * self._hb_misses
        for rep in self.healthy_replicas():
            handle = rep.api
            if not isinstance(handle, WorkerHandle):
                continue
            dead = handle._dead
            if dead is not None:
                # the handle classified the death first (wedged main loop,
                # send failure): eject with THAT cause — by now the worker
                # has usually seen the closed socket and exited cleanly,
                # and the proc check below would mislabel the hang as
                # "exited with code 0"
                self._eject(rep, dead)
                continue
            proc = handle.proc
            if proc is not None and not proc.is_alive():
                code = proc.exitcode
                if code is not None and code < 0:
                    cause = WorkerDiedError(
                        f"worker {rep.idx} (pid {handle.pid}) killed by "
                        f"signal {-code}")
                else:
                    cause = WorkerDiedError(
                        f"worker {rep.idx} (pid {handle.pid}) exited "
                        f"with code {code}")
                self._eject(rep, cause)  # kills/exits counted in _eject
                continue
            age = handle.heartbeat_age()
            if age > threshold:
                resilience.bump("worker.heartbeat_misses",
                                self._hb_misses)
                resilience.bump("worker.hangs")
                self._eject(rep, WorkerDiedError(
                    f"worker {rep.idx} (pid {handle.pid}) silent for "
                    f"{age:.2f}s (> {self._hb_misses} x "
                    f"{self._hb_interval}s heartbeats)"))
                continue
            metrics.set_gauge(f"worker.{rep.idx}.heartbeat_age_ms",
                              round(age * 1000.0, 1))

    def _chaos_probes(self) -> None:
        """The two process-fleet fault kinds (flag-armed via
        ``inject_fault`` / ``FLAGS_inject_faults``): ``worker_kill``
        SIGKILLs a live worker — the real kill -9 — and ``worker_hang``
        wedges one (heartbeats stop, socket held)."""
        if resilience.maybe_fault("worker_kill"):
            for rep in self.healthy_replicas():
                proc = getattr(rep.api, "proc", None)
                if proc is not None and proc.pid:
                    os.kill(proc.pid, signal.SIGKILL)
                    break
        if resilience.maybe_fault("worker_hang"):
            for rep in self.healthy_replicas():
                try:
                    rep.api.hang()
                # analysis: allow(broad-except) — a chaos probe hitting
                # an already-dying worker is a no-op, not a failure
                except Exception:
                    pass
                break

    def _watchdog_loop(self) -> None:
        interval = max(0.01, min(self._hb_interval / 2.0, 0.05))
        while not self._watchdog_stop.wait(interval):
            with self._lock:
                if self._closed:
                    return
            try:
                if self._check_guard():
                    continue
                self._maybe_respawn()
                self._sweep_health()
                self._poll_workers()
                self._observe_live()
                self._wal_sweep()
            # analysis: allow(broad-except) — the watchdog IS the
            # supervisor of last resort; any sweep failure must leave it
            # alive to classify the next death
            except Exception:
                _logger.exception("procpool watchdog sweep failed")

    # ------------------------------------------------------------ progress

    def pump_once(self) -> None:
        """Foreground loop for process mode: workers pump themselves, so
        one turn here is supervision (respawn + watchdog + breaker
        sweeps), a poll cycle per worker, and an observe pass over live
        routed requests."""
        if self._check_guard():
            return
        self._maybe_respawn()
        self._sweep_health()
        self._poll_workers()
        self._observe_live()
        self._wal_sweep()

    def _poll_workers(self) -> None:
        for rep in self.healthy_replicas():
            try:
                rep.api.poll()
            # analysis: allow(broad-except) — classification inside:
            # reroutable failures eject the worker, the rest re-raise
            # (mirrors the base _pump_replica contract)
            except Exception as e:
                if _is_reroutable(e):
                    self._eject(rep, e)
                else:
                    raise

    def _eject(self, rep, cause: BaseException) -> None:
        # fail the handle's live RemoteRequests BEFORE the base ejection:
        # _reroute's "backend still running" early-return must see them
        # finished, or every stream on the dead worker would be parked
        # instead of re-routed
        api = rep.api
        if isinstance(api, WorkerHandle):
            api.mark_dead(cause if isinstance(cause, BaseException)
                          else WorkerDiedError(str(cause)))
            api.classify_exit()
        super()._eject(rep, cause)

    # ------------------------------------------------------ stats / close

    def worker_stats(self) -> Dict[int, dict]:
        """Per-worker remote scrapes (their own process's ``metrics``
        counters — the bench reads engine compile counters per survivor
        from here)."""
        out: Dict[int, dict] = {}
        for rep in self.healthy_replicas():
            handle = rep.api
            if not isinstance(handle, WorkerHandle):
                continue
            try:
                out[rep.idx] = handle.remote_stats()
            # analysis: allow(broad-except) — a worker dying mid-scrape
            # must not fail the report for the rest of the fleet
            except Exception:
                continue
        return out

    def stats(self) -> dict:
        out = super().stats()
        with self._lock:
            handles = {r.idx: r.api for r in self._replicas}
            gens = {r.idx: r.generation for r in self._replicas}
        for row in out["replicas"]:
            handle = handles.get(row["idx"])
            if isinstance(handle, WorkerHandle):
                row["pid"] = handle.pid
                row["heartbeat_age_ms"] = round(
                    handle.heartbeat_age() * 1000.0, 1)
                row["restarts"] = gens.get(row["idx"], 0)
        out["process_replicas"] = True
        return out

    def close(self) -> None:
        # ordering contract (satellite 2, atexit included — _reap_at_exit
        # funnels here): super().close() runs drain(0) FIRST, whose final
        # _wal_sweep(final=True) writes + fsyncs every TERMINAL record
        # BEFORE any worker handle is closed or reaped — a clean shutdown
        # never leaves live-looking records for the next incarnation to
        # resurrect. Only then are workers shut down and reaped.
        if self._closed:
            return
        super().close()  # drain(0) + WAL terminal sweep, then handle closes
        self._watchdog_stop.set()
        w = self._watchdog
        if w is not None and w is not threading.current_thread():
            w.join(timeout=2.0)
        self._reap_workers()
        _live_pools.discard(self)

    def _reap_workers(self) -> None:
        """Belt and braces behind ``close()``: whatever path a handle
        took, every worker process this pool ever holds a reference to
        gets joined, then SIGKILLed if still alive."""
        with self._lock:
            handles = [r.api for r in self._replicas
                       if isinstance(r.api, WorkerHandle)]
        for handle in handles:
            try:
                handle.reap(timeout=1.0)
            # analysis: allow(broad-except) — keep reaping the rest of
            # the fleet no matter how one corpse misbehaves
            except Exception:
                _logger.exception("reaping worker %d failed", handle.idx)
