"""Worker-process main for the process-isolated replica fleet (ISSUE 18).

One worker process owns ONE complete serving stack — model, engine,
scheduler, supervisor, :class:`~..api.ServingAPI` — and serves it to the
parent gateway over a local length-prefixed JSON-RPC socket. This is the
fleet's first real fault domain boundary: a segfault, OOM, or wedged XLA
call in one replica kills one OS process, not the gateway and every other
tenant with it (the mirror of the reference's ``distributed/fleet``
trainer/worker split, folded into serving).

Boot sequence (driven by ``procpool.WorkerHandle.spawn``):

1. the parent binds an ephemeral loopback listener and spawns this module's
   :func:`worker_main` via ``multiprocessing.get_context("spawn")`` — a
   FRESH interpreter, no forked jax state;
2. the worker applies the parent's runtime config from the spawn payload
   (``jax_platforms`` + matmul precision re-pinned BEFORE any backend
   initializes, then the full flag snapshot via ``flags.set_flags``);
3. it connects back, builds the engine (compiled programs come from the
   shared persistent compile cache, so a respawn re-loads instead of
   re-compiling), and sends a ``hello`` frame carrying pid/num_slots/vocab
   — or a typed boot error;
4. the main thread then serves the RPC loop (submit / poll / cancel /
   drain / stats / register_adapter / hang / shutdown) while a heartbeat
   thread pushes liveness frames every ``FLAGS_gateway_heartbeat_interval``
   seconds, each carrying the outstanding count, the supervisor's
   crash-loop breaker state, and the telemetry spans recorded since the
   last ship (:func:`~..telemetry.events_since` — the gateway ingests them
   so one trace_id reads as one contiguous timeline across processes).

A :class:`~paddle_tpu.core.resilience.PreemptionGuard` is installed so
SIGTERM drains the worker's in-flight requests cleanly (journaled
stragglers fail retriably and re-route on the parent side); SIGKILL is the
chaos case the parent's heartbeat watchdog exists for. Parent death is an
EOF on the socket — the worker tears its engine down and exits instead of
orphaning a process that holds the compile-cache dir lock.

Wire format: 4-byte big-endian length + UTF-8 JSON, frames capped at
``_MAX_FRAME`` (an oversized or unparseable frame is a
:class:`FrameError` — the parent classifies it as a
``WorkerProtocolError`` eject, never a hung handle). Request frames carry
``id``; responses echo it with ``ok`` + payload or a typed ``error``
(:func:`encode_error` / :func:`decode_error` round-trip the serving error
taxonomy, so ``QueueOverloadError`` still means "try the next candidate"
across the process boundary). Sampling params travel as plain dicts;
constraint walkers and LoRA adapters as base64 pickle — the channel is a
loopback socket between a parent and the worker it spawned, both running
this exact tree.
"""
from __future__ import annotations

import base64
import json
import os
import pickle
import socket
import struct
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from ...core import flags, resilience
from .. import metrics, telemetry
from ..supervisor import CrashLoopError

#: hard cap on one frame: a submit carries a prompt + journal (ints), a
#: poll response a few token tails + spans — 8 MiB is orders of magnitude
#: of headroom, while a garbage length prefix (fuzzed/corrupt stream)
#: fails fast instead of waiting for gigabytes that never arrive
_MAX_FRAME = 8 << 20

_SHUTDOWN = object()  # sentinel: handler asks the serve loop to exit


class FrameError(ValueError):
    """The byte stream is not a well-formed frame: truncated mid-frame,
    oversized/garbage length prefix, or an unparseable payload. The
    connection is unrecoverable past one of these — resynchronizing a
    length-prefixed stream is guesswork — so both sides hang up."""


# ------------------------------------------------------------------ framing


def send_frame(sock: socket.socket, obj: dict,
               lock: Optional[threading.Lock] = None) -> None:
    """Serialize ``obj`` and write one length-prefixed frame. ``lock``
    serializes writers (RPC responses and heartbeats interleave on the
    worker side; calls and nothing else on the parent side) so frames
    never shear mid-write."""
    data = json.dumps(obj, separators=(",", ":")).encode("utf-8")
    if len(data) > _MAX_FRAME:
        raise FrameError(f"frame of {len(data)} bytes exceeds the "
                         f"{_MAX_FRAME}-byte cap")
    frame = struct.pack(">I", len(data)) + data
    if lock is None:
        sock.sendall(frame)
    else:
        with lock:
            sock.sendall(frame)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    """``n`` bytes or None on clean EOF at a frame boundary; EOF
    mid-read raises FrameError (a truncated frame is corruption, not a
    shutdown)."""
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            if buf:
                raise FrameError(
                    f"truncated frame: EOF after {len(buf)}/{n} bytes")
            return None
        buf += chunk
    return buf


def recv_frame(sock: socket.socket,
               max_frame: int = _MAX_FRAME) -> Optional[dict]:
    """One frame as a dict, or None on clean EOF. Raises
    :class:`FrameError` on truncation, an oversized/zero length prefix,
    or a payload that is not a JSON object."""
    head = _recv_exact(sock, 4)
    if head is None:
        return None
    (length,) = struct.unpack(">I", head)
    if length == 0 or length > max_frame:
        raise FrameError(f"bad frame length {length} "
                         f"(cap {max_frame} bytes)")
    body = _recv_exact(sock, length)
    if body is None:
        raise FrameError("truncated frame: EOF before payload")
    try:
        msg = json.loads(body.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise FrameError(f"unparseable frame payload: {e}") from e
    if not isinstance(msg, dict):
        raise FrameError(f"frame payload is {type(msg).__name__}, "
                         "expected an object")
    return msg


# ------------------------------------------------------- error round-trip

#: the serving error taxonomy that must survive the process boundary with
#: its semantics intact: shed classes stay retriable fall-through in
#: ``ReplicaPool._route``, transient classes stay re-routable in
#: ``_is_reroutable``, validation stays a client error. Anything outside
#: the registry decodes as RuntimeError — NOT re-routable, so an unknown
#: worker failure fails the request loudly instead of bouncing forever.
_ERROR_TYPES: Dict[str, type] = {
    "QueueOverloadError": resilience.QueueOverloadError,
    "RequestDrainedError": resilience.RequestDrainedError,
    "DeadlineExceededError": resilience.DeadlineExceededError,
    "ServingDeviceError": resilience.ServingDeviceError,
    "ArenaCorruptError": resilience.ArenaCorruptError,
    "CrashLoopError": CrashLoopError,
    "ValueError": ValueError,
    "KeyError": KeyError,
    "RuntimeError": RuntimeError,
    "TimeoutError": TimeoutError,
    "OSError": OSError,
}


def encode_error(exc: BaseException) -> dict:
    return {"type": type(exc).__name__, "message": str(exc)}


def decode_error(obj: Any) -> BaseException:
    if not isinstance(obj, dict):
        return RuntimeError(f"worker error (malformed): {obj!r}")
    name = str(obj.get("type", "RuntimeError"))
    message = str(obj.get("message", ""))
    klass = _ERROR_TYPES.get(name, RuntimeError)
    try:
        return klass(f"{message} [worker {name}]"
                     if klass is RuntimeError and name != "RuntimeError"
                     else message)
    except TypeError:
        return RuntimeError(f"{name}: {message}")


def b64_dumps(obj: Any) -> str:
    return base64.b64encode(
        pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)).decode("ascii")


def b64_loads(data: str) -> Any:
    # trusted channel: the payload travels a loopback socket / spawn args
    # between this process and the worker it spawned from this same tree
    return pickle.loads(base64.b64decode(data))


# ------------------------------------------------------------ spawn payload


class ChipHeldError(RuntimeError):
    """A process pool was handed a LIVE model by a parent whose jax backend
    is a TPU. A chip belongs to one process at a time: the parent that
    built the model holds it, so every spawned worker would fail or hang
    claiming it. Pass a zero-arg factory from a parent that has not
    touched jax instead (docs/serving.md, "Process isolation")."""


def encode_payload(model, api_kw: dict,
                   hb_interval: Optional[float] = None,
                   flag_overrides: Optional[dict] = None) -> dict:
    """The picklable spawn-args payload ``worker_main`` boots from: the
    model (or zero-arg factory) and engine kwargs as base64 pickle, the
    full flag snapshot, and the parent's effective jax platform/precision
    config so the worker's numerics match the parent's token-for-token
    (greedy decode parity across re-routes depends on it).
    ``flag_overrides`` merge over the snapshot — how a disaggregated pool
    gives each ROLE its own flag profile (publish-on-prefill, shared disk
    dir) without mutating the parent's flags."""
    import jax

    is_factory = bool(callable(model)
                      and not hasattr(model, "functional_state"))
    # a live model's arrays exist, so this parent's backend is already up
    # (asking which one initializes nothing new); a factory parent is never
    # asked — the probe itself would claim the chip
    if not is_factory and jax.default_backend() == "tpu":
        raise ChipHeldError(
            "this process built the model on the TPU and holds the chip; "
            "spawned workers cannot claim it. Give the pool a zero-arg "
            "model factory from a parent that has not initialized jax, or "
            "use the in-process ReplicaPool")
    kw = dict(api_kw)
    kw.pop("background", None)  # the worker always pumps itself
    platforms = jax.config.jax_platforms
    precision = getattr(jax.config, "jax_default_matmul_precision", None)
    snapshot = flags.all_flags()
    if flag_overrides:
        snapshot = dict(snapshot, **flag_overrides)
    return {
        "model": b64_dumps(model),
        "model_is_factory": is_factory,
        "api_kw": b64_dumps(kw),
        "flags": snapshot,
        "jax_platforms": platforms,
        "matmul_precision": precision,
        "hb_interval": hb_interval,
    }


def _apply_runtime_config(payload: dict) -> None:
    """Pin the worker's runtime to the parent's BEFORE any jax backend
    initializes: platform selection (a CPU-pinned parent — the tests —
    must get CPU workers), matmul precision (token parity), then the full
    flag snapshot."""
    platforms = payload.get("jax_platforms")
    if platforms:
        os.environ["JAX_PLATFORMS"] = str(platforms)
    import jax

    if platforms:
        jax.config.update("jax_platforms", str(platforms))
    precision = payload.get("matmul_precision")
    if precision:
        jax.config.update("jax_default_matmul_precision", str(precision))
    for name, value in (payload.get("flags") or {}).items():
        try:
            flags.set_flags({name: value})
        except (KeyError, TypeError, ValueError):
            continue  # a flag this build doesn't know: skip, don't die


def _build_api(payload: dict):
    from ..api import ServingAPI  # deferred: jax config is applied first

    obj = b64_loads(payload["model"])
    model = obj() if payload.get("model_is_factory") else obj
    api_kw = b64_loads(payload["api_kw"])
    api_kw.pop("background", None)
    return ServingAPI(model, background=True, **api_kw)


# -------------------------------------------------------------- the server


class _WorkerServer:
    """One worker's RPC loop + heartbeat pusher over one socket.

    Single-threaded request handling (the main loop) — ``reqs`` needs no
    lock; the write lock only serializes response frames against the
    heartbeat thread's pushes. ``hung`` models the ``worker_hang`` chaos
    fault: heartbeats stop and further frames are swallowed unanswered,
    while the socket stays open — the parent must classify this via
    heartbeat age, not ECONNRESET."""

    def __init__(self, idx: int, sock: socket.socket,
                 wlock: threading.Lock, api, hb_interval: float):
        self.idx = int(idx)
        self.sock = sock
        self.wlock = wlock
        self.api = api
        self.hb_interval = float(hb_interval)
        self.reqs: Dict[str, Any] = {}  # rid -> scheduler.Request
        self.stop = threading.Event()
        self.hung = False
        self._span_lock = threading.Lock()
        self._span_seq = -1

    def send(self, obj: dict) -> None:
        send_frame(self.sock, obj, self.wlock)

    def take_spans(self):
        """Telemetry spans recorded since the last ship (heartbeat and
        poll responses both carry them — whichever fires first wins, each
        span ships exactly once)."""
        with self._span_lock:
            events = telemetry.events_since(self._span_seq)
            if events:
                self._span_seq = max(e[0] for e in events)
        return events

    # ------------------------------------------------------------- threads

    def heartbeat_loop(self) -> None:
        while not self.stop.wait(self.hb_interval):
            if self.hung:
                continue
            try:
                self.send({"hb": True, "ts": time.time(),
                           "pid": os.getpid(),
                           "outstanding": self.api.outstanding(),
                           "breaker_open":
                               bool(self.api.supervisor.breaker_open),
                           "spans": self.take_spans()})
            except OSError:
                return  # parent went away; the main loop sees EOF too

    def serve(self) -> None:
        hb = threading.Thread(target=self.heartbeat_loop,
                              name=f"worker-{self.idx}-hb", daemon=True)
        hb.start()
        try:
            while True:
                try:
                    msg = recv_frame(self.sock)
                except (FrameError, OSError):
                    break  # corrupt stream / dead parent: tear down
                if msg is None:
                    break  # clean EOF: parent closed (or died)
                if self.hung:
                    continue  # wedged worker: read and never answer
                cid = msg.get("id")
                try:
                    result = self.handle(msg)
                # analysis: allow(broad-except) — the RPC contract: any
                # handler failure rides back as a typed error frame; an
                # unanswered call would hang the parent's pending slot
                # until its per-call deadline instead
                except Exception as e:
                    if cid is not None:
                        self.send({"id": cid, "ok": False,
                                   "error": encode_error(e)})
                    continue
                if result is _SHUTDOWN:
                    if cid is not None:
                        self.send({"id": cid, "ok": True})
                    break
                if cid is not None:
                    self.send({"id": cid, "ok": True, **result})
        finally:
            self.stop.set()
            try:
                self.api.close()
            # analysis: allow(broad-except) — exit path: a dying engine
            # must not keep the process (and the compile-cache dir lock)
            # alive
            except Exception:
                pass
            try:
                self.sock.close()
            except OSError:
                pass

    # ------------------------------------------------------------ handlers

    def handle(self, msg: dict) -> dict:
        op = msg.get("op")
        handler = getattr(self, f"_op_{op}", None)
        if handler is None:
            raise ValueError(f"unknown worker op: {op!r}")
        return handler(msg)

    def _op_submit(self, msg: dict) -> dict:
        from ..sampling import SamplingParams

        rid = str(msg["rid"])
        sampling = msg.get("sampling")
        if sampling is not None:
            sampling = SamplingParams(**sampling)
        constraint = msg.get("constraint")
        if constraint is not None:
            constraint = b64_loads(constraint)
        journal = msg.get("journal")
        req = self.api.submit(
            np.asarray(msg["prompt"], np.int32),
            max_new_tokens=int(msg.get("max_new_tokens", 32)),
            stop_token_id=msg.get("stop_token_id"),
            timeout=msg.get("timeout"),
            request_id=str(msg.get("request_id", "")),
            priority=int(msg.get("priority", 0)),
            journal=journal,
            shed=bool(msg.get("shed", True)),
            sampling=sampling, constraint=constraint,
            adapter=int(msg.get("adapter", 0)),
            trace_id=str(msg.get("trace_id", "")))
        self.reqs[rid] = req
        return {"rid": rid}

    def _op_poll(self, msg: dict) -> dict:
        # acknowledge-based reap: a finished request is dropped only when
        # the parent's NEXT poll lists it in ``done`` (it applied the
        # terminal state). Reaping on send would lose the terminal entry
        # whenever the response frame outlives the parent's poll deadline
        # (busy-classified under compile load) — the parent would re-poll
        # an rid this side no longer knows and the request would sit
        # QUEUED forever. Acks are idempotent; a lost ack just re-ships.
        for rid in (msg.get("done") or ()):
            self.reqs.pop(str(rid), None)
        out = {}
        for rid, offset in (msg.get("reqs") or {}).items():
            req = self.reqs.get(rid)
            if req is None:
                continue  # unknown rid: acked earlier or never submitted
            entry = {"state": req.state,
                     "tokens": [int(t) for t in req.tokens[int(offset):]]}
            if req.finished and req.error is not None:
                entry["error"] = encode_error(req.error)
            out[rid] = entry
        return {"reqs": out, "spans": self.take_spans(),
                "breaker_open": bool(self.api.supervisor.breaker_open),
                "outstanding": self.api.outstanding()}

    def _op_cancel(self, msg: dict) -> dict:
        req = self.reqs.get(str(msg.get("rid")))
        if req is not None:
            req.cancel()
        return {}

    def _op_drain(self, msg: dict) -> dict:
        # blocking up to grace — heartbeats keep flowing from their own
        # thread, so the watchdog never mistakes a draining worker for a
        # hung one; the parent reconciles final request states with one
        # poll after this returns
        self.api.drain(float(msg.get("grace", 0.0)),
                       reason=str(msg.get("reason", "worker drain")))
        return {}

    def _op_stats(self, msg: dict) -> dict:
        # this PROCESS's serving counters (engine compile counters
        # included — the bench's zero-recompile gate reads them per
        # worker), JSON-safe scalars only
        from ...core import compile_cache

        snap = {k: v for k, v in metrics.stats().items()
                if isinstance(v, (int, float))
                and not isinstance(v, bool)}
        snap.update({k: v for k, v in compile_cache.stats().items()
                     if isinstance(v, (int, float))
                     and not isinstance(v, bool)})
        return {"pid": os.getpid(),
                "outstanding": self.api.outstanding(),
                "breaker_open": bool(self.api.supervisor.breaker_open),
                "drain_count": int(self.api.drain_count),
                "metrics": snap}

    def _op_prefetch(self, msg: dict) -> dict:
        # restore-ahead (disagg): pre-restore a queued request's
        # published chain into this worker's arena; bounded worker-side
        # (never starves admission), so the parent fires and forgets
        return {"blocks": int(self.api.prefetch(
            np.asarray(msg["prompt"], np.int32),
            trace_id=str(msg.get("trace_id", ""))))}

    def _op_register_adapter(self, msg: dict) -> dict:
        adapter = b64_loads(msg["adapter"])
        name = msg.get("name")
        return {"adapter_id":
                int(self.api.register_adapter(adapter, name=name))}

    def _op_hang(self, msg: dict) -> dict:
        # chaos fault "worker_hang": stop heartbeating, swallow every
        # further frame, HOLD the socket — the watchdog must classify
        # this via heartbeat age, not connection reset
        self.hung = True
        return {}

    def _op_shutdown(self, msg: dict) -> dict:
        return _SHUTDOWN


# ------------------------------------------------------------------- main


def worker_main(host: str, port: int, idx: int, payload: dict) -> None:
    """Spawn-process entry: pin runtime config, dial the parent, build
    the serving stack, say hello (or ship the typed boot failure), then
    serve RPC until shutdown / EOF / frame corruption."""
    _apply_runtime_config(payload)
    sock = socket.create_connection((str(host), int(port)), timeout=30.0)
    try:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError:
        pass
    wlock = threading.Lock()
    try:
        api = _build_api(payload)
    # analysis: allow(broad-except) — boot can die arbitrarily (bad
    # pickle, sick device, engine sizing): the parent needs the typed
    # error in the hello slot, not a silent exit code
    except Exception as e:
        try:
            send_frame(sock, {"hello": False, "pid": os.getpid(),
                              "error": encode_error(e)}, wlock)
        finally:
            sock.close()
        return
    guard = resilience.PreemptionGuard(install=True)
    api.bind_preemption_guard(guard)
    hb_interval = payload.get("hb_interval")
    if hb_interval is None:
        hb_interval = flags.flag("gateway_heartbeat_interval")
    send_frame(sock, {"hello": True, "pid": os.getpid(),
                      "num_slots": int(api.engine.num_slots),
                      "vocab": int(api.engine.vocab)}, wlock)
    _WorkerServer(idx, sock, wlock, api, float(hb_interval)).serve()
