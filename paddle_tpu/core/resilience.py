"""Framework-level resilience: retry, fault injection, preemption, rollback.

The north-star runs on *preemptible* TPUs: IO can fail transiently, pods
get SIGTERMed mid-step, and one nonfinite step can silently poison a run. The reference
framework scatters its answers — etcd-leased elastic restarts
(ref:python/paddle/distributed/fleet/elastic/manager.py), AutoCheckpointChecker
epoch checkpoints (ref:python/paddle/fluid/incubate/checkpoint/
auto_checkpoint.py:72), per-op CUDA NaN scans. This module is the one place
the TPU framework keeps its failure-handling policy:

* **Retry** — :func:`call_with_retry` / :func:`retry` run an operation under a
  :class:`RetryPolicy` (jittered exponential backoff + wall-clock deadline).
  Checkpoint save/restore IO, ``paddle_tpu.save``, compile-cache directory
  setup, and TCPStore/collective init all route through it.
* **Fault injection** — a deterministic, env/FLAGS-gated registry
  (:func:`inject_fault` / :func:`maybe_fault`). Production code keeps
  ``maybe_fault("ckpt_io")``-style probes at its failure points; with
  ``FLAGS_fault_injection=0`` (the default) they are a dict-emptiness check.
  The ``chaos`` pytest marker drives these probes.
* **Preemption** — :class:`PreemptionGuard` converts SIGTERM/SIGINT (and the
  elastic module's dead-peer signal) into a step-boundary request for one
  final synchronous checkpoint + resume marker + clean exit.
* **Rollback** — ``jit.TrainStep``'s nonfinite sentinel skips bad optimizer
  updates; after ``FLAGS_max_bad_steps`` consecutive bad steps it calls
  :func:`trigger_rollback`, which invokes the registered handler (typically
  restoring the last valid ``TrainCheckpointer`` step) or raises
  :class:`NonfiniteStepError`.

Counters mirror ``core.compile_cache``: :func:`bump`/:func:`stats`, surfaced
as ``core.memory_stats`` providers, snapshotted per-run by the profiler, and
dumped by ``tools/resilience_stats.py``.
"""
from __future__ import annotations

import os
import random
import signal
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import flags

_lock = threading.Lock()

# plain dict mutated under the GIL (same contract as compile_cache._counts):
# the TrainStep hot path bumps these per step, so no lock on update
_counts: Dict[str, int] = {}

#: resilience counter namespaces (key segment before the first ``.``).
#: ``retry.*`` retried-IO attempts/exhaustions, ``ckpt.*`` checkpoint
#: saves/integrity, ``sentinel.*`` nonfinite-step skips/rollbacks,
#: ``preempt.*`` PreemptionGuard activity, ``overload.*``/``deadline.*``/
#: ``quota.*`` shed taxonomy, ``serving.*`` the serving mirrors (drains,
#: rebuilds, replays, preemptions, replica ejections/respawns),
#: ``faults`` armed-fault gauge, ``fault.<kind>`` per-kind fired-fault
#: counters (dynamic keys from ``maybe_fault`` — invisible to the
#: literal-key lint, so listed here for the runtime-coverage test),
#: ``quant.*`` quantized-serving mirrors (docs/quantization.md — the
#: serving-side counters live in ``serving.metrics``; this registry entry
#: reserves the namespace so resilience dashboards can adopt them).
#: Checked by ``tools/analyze.py``'s ``unknown-metric-key`` rule against
#: every literal ``resilience.bump`` call — register new namespaces here
#: WITH a docs mention, or the lint fails.
DOCUMENTED_NAMESPACES = (
    "retry", "ckpt", "sentinel", "preempt", "overload", "deadline",
    "quota", "serving", "faults", "fault", "quant",
    # scenario-diversity serving (ISSUE 12): per-slot sampling's
    # spec-decode fallbacks, constraint-walker anomalies, LoRA adapter
    # lifecycle — mirrored here so the resilience dashboards see them
    "sampling", "constrain", "lora",
    # Pallas paged-attention serving kernels (ISSUE 13,
    # ops.paged_attention / docs/performance.md): trace/tuning telemetry
    # lives in serving.metrics; this entry reserves the namespace so the
    # resilience dashboards can mirror kernel fallbacks and tune state
    "kernel",
    # tiered KV cache (ISSUE 15, serving.tiered): tier.disk_corrupt —
    # a spill file failing its crc on load (deleted + recomputed, never
    # served) is a resilience event the shared dashboards must see
    "tier",
    # observability plane (ISSUE 17, serving.telemetry /
    # docs/observability.md): telemetry.* span meta-counters (spans
    # recorded / dropped by the bounded ring) and latency.* duration
    # histograms — the primary copies live in serving.metrics/telemetry;
    # these entries reserve the namespaces so resilience dashboards can
    # mirror span-loss and latency-regression alerts
    "telemetry", "latency",
    # process-isolated worker fleet (ISSUE 18, serving.gateway.procpool /
    # docs/robustness.md "Process isolation"): worker.spawns / exits /
    # kills / hangs / heartbeats / heartbeat_misses / protocol_errors —
    # the heartbeat watchdog's classification of worker-process deaths
    "worker",
    # disaggregated prefill/decode serving (ISSUE 19, serving.disagg /
    # docs/serving.md "Disaggregated prefill/decode"):
    # disagg.prefill_ejections / disagg.decode_ejections — per-role
    # worker deaths, the resilience-plane view of the role-typed fleet
    # (routing/handoff/prefetch counters live in serving.metrics)
    "disagg",
    # gateway write-ahead request log (ISSUE 20, serving.gateway.wal /
    # docs/robustness.md "Gateway crash recovery"): wal.torn_tail — a
    # segment whose unfsynced tail tore across the crash (replay
    # truncated at the last good record) is a recovery event the shared
    # dashboards must see; the full wal.* picture lives in
    # serving.metrics
    "wal",
)


def bump(key: str, n: int = 1) -> None:
    """Increment a resilience counter (GIL-atomic dict update, no lock)."""
    _counts[key] = _counts.get(key, 0) + n


def stats() -> dict:
    """Snapshot of all resilience counters plus armed-fault state."""
    with _lock:
        out: dict = dict(_counts)
        out["faults.armed"] = sum(s.times for s in _faults.values())
    return out


def reset_stats() -> None:
    with _lock:
        _counts.clear()


def stats_delta(before: dict, after: dict, *, drop_zero: bool = False) -> dict:
    """Numeric difference of two :func:`stats` snapshots (one shared
    definition with the compile cache so the profiler/tools reports agree)."""
    from . import compile_cache

    return compile_cache.stats_delta(before, after, drop_zero=drop_zero)


def _register_providers() -> None:
    """Headline counters through core.memory_stats, next to the allocator and
    compile-cache picture (one observability surface)."""
    from . import memory_stats

    for name, key in (("resilience.sentinel_skipped", "sentinel.skipped"),
                      ("resilience.rollbacks", "sentinel.rollbacks"),
                      ("resilience.retries", "retry.retries"),
                      ("resilience.preempt_requests", "preempt.requests"),
                      ("resilience.overload_shed", "overload.shed"),
                      ("resilience.deadline_exceeded", "deadline.exceeded"),
                      # serving resilience layer (serving.supervisor /
                      # scheduler preemption / ServingAPI.drain)
                      ("resilience.serving_preemptions", "serving.preemptions"),
                      ("resilience.serving_replays", "serving.replays"),
                      ("resilience.serving_rebuilds", "serving.rebuilds"),
                      ("resilience.serving_drains", "serving.drains"),
                      ("resilience.serving_drain_stragglers",
                       "serving.drain_stragglers"),
                      # multi-tenant gateway (serving.gateway): replica
                      # health + tenant quota shedding
                      ("resilience.replica_ejections",
                       "serving.replica_ejections"),
                      ("resilience.replica_respawns",
                       "serving.replica_respawns"),
                      ("resilience.quota_shed", "quota.shed")):
        memory_stats.register_stat_provider(name, lambda k=key: _counts.get(k, 0))


try:
    _register_providers()
except Exception:  # analysis: allow(broad-except) — observability is
    pass           # optional, never an import blocker


# ------------------------------------------------------------------- errors


class NonfiniteStepError(FloatingPointError):
    """Raised when ``FLAGS_max_bad_steps`` consecutive TrainStep steps were
    nonfinite and no rollback handler is registered."""


class CheckpointIntegrityError(RuntimeError):
    """A checkpoint step failed manifest verification (truncated write,
    corrupted leaf, or structural mismatch)."""


class QueueOverloadError(RuntimeError):
    """Admission was shed because a serving queue exceeded its depth limit
    (load-shedding beats unbounded latency growth under overload)."""


class DeadlineExceededError(TimeoutError):
    """A request's wall-clock deadline expired before it finished."""


class ServingDeviceError(RuntimeError):
    """Transient accelerator/runtime failure inside a compiled serving call
    (lost device, evicted backend). The serving supervisor treats it
    as recoverable: rebuild the KV arena and replay in-flight requests from
    their journals (``serving.supervisor``)."""


class ArenaCorruptError(RuntimeError):
    """The serving KV arena is corrupt or consumed (a donated call died
    holding the pools, a device reset invalidated them). Recoverable by the
    serving supervisor the same way as :class:`ServingDeviceError` — the
    arena is rebuilt from scratch and live requests are re-prefilled."""


class RequestDrainedError(RuntimeError):
    """The request was failed by a serving drain/shutdown before completing.
    Retriable by construction: the request performed no externally visible
    work, so the caller can safely resubmit it to another instance."""


class QuotaExceededError(RuntimeError):
    """A tenant's rate limit, concurrency quota, or fair share was exceeded
    at gateway admission (``serving.gateway.tenancy``). Retriable by
    construction — nothing was enqueued; ``retry_after`` is the seconds the
    caller should wait before resubmitting (the gateway maps it to an HTTP
    429 with a ``Retry-After`` header)."""

    def __init__(self, message: str, retry_after: float = 0.0,
                 tenant: str = ""):
        super().__init__(message)
        self.retry_after = float(retry_after)
        self.tenant = tenant


# ---------------------------------------------------- deadlines / shedding


@dataclass
class Deadline:
    """Absolute wall-clock budget for one unit of work (a serving request,
    a retried operation). ``None`` expiry means "no deadline" — all probes
    report unexpired. Monotonic clock, so NTP steps can't fire it."""

    expires_at: Optional[float] = None

    @classmethod
    def after(cls, timeout: Optional[float]) -> "Deadline":
        """Deadline ``timeout`` seconds from now (None = unbounded)."""
        return cls(None if timeout is None
                   else time.monotonic() + float(timeout))

    def remaining(self) -> float:
        if self.expires_at is None:
            return float("inf")
        return self.expires_at - time.monotonic()

    def expired(self) -> bool:
        return self.remaining() <= 0.0

    def check(self, what: str = "operation") -> None:
        """Raise :class:`DeadlineExceededError` (and count it) if expired."""
        if self.expired():
            bump("deadline.exceeded")
            raise DeadlineExceededError(
                f"{what} exceeded its deadline "
                f"(over by {-self.remaining():.3f}s)")


def check_overload(depth: int, limit: Optional[int] = None,
                   name: str = "serving") -> None:
    """Admission-control probe: raise :class:`QueueOverloadError` when
    ``depth`` waiting requests meet the limit (default
    ``FLAGS_serving_max_queue``; 0/None = unlimited). Every shed bumps
    ``overload.shed`` / ``overload.<name>.shed`` so dashboards see the
    rejected load, not just the served load."""
    if limit is None:
        limit = int(flags.flag("serving_max_queue"))
    if limit and depth >= limit:
        bump("overload.shed")
        if name:
            bump(f"overload.{name}.shed")
        raise QueueOverloadError(
            f"{name} queue is full ({depth} waiting >= limit {limit}); "
            "request shed")


# -------------------------------------------------------------------- retry


@dataclass
class RetryPolicy:
    """Jittered exponential backoff with an attempt cap and a deadline.

    ``max_attempts`` counts the first try; delay before attempt ``k`` (1-based
    retries) is ``min(max_delay, base_delay * 2**(k-1))`` scaled by a uniform
    jitter in ``[1, 1+jitter)``. ``deadline`` bounds total wall-clock across
    attempts; ``giveup(exc)`` short-circuits retries for errors that can
    never heal (e.g. "already initialized").
    """

    max_attempts: int = 3
    base_delay: float = 0.05
    max_delay: float = 2.0
    deadline: float = 120.0
    jitter: float = 0.5
    retry_on: Tuple[type, ...] = (Exception,)
    giveup: Optional[Callable[[BaseException], bool]] = None

    def delay(self, attempt: int) -> float:
        d = min(self.max_delay, self.base_delay * (2 ** max(0, attempt - 1)))
        return d * (1.0 + self.jitter * random.random())


def default_policy(**overrides) -> RetryPolicy:
    """The flag-configured IO policy (FLAGS_io_retries / FLAGS_io_retry_*)."""
    base = dict(max_attempts=int(flags.flag("io_retries")),
                base_delay=float(flags.flag("io_retry_backoff")),
                deadline=float(flags.flag("io_retry_deadline")))
    base.update(overrides)
    return RetryPolicy(**base)


def call_with_retry(fn: Callable, *args, policy: Optional[RetryPolicy] = None,
                    name: str = "", **kwargs):
    """Run ``fn(*args, **kwargs)`` under ``policy`` (default: flag-configured).

    Re-raises the *original* final exception (callers' except clauses keep
    working); every retry bumps ``retry.retries`` and ``retry.<name>``.
    """
    policy = policy or default_policy()
    start = time.monotonic()
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn(*args, **kwargs)
        except policy.retry_on as e:
            if policy.giveup is not None and policy.giveup(e):
                raise
            elapsed = time.monotonic() - start
            if attempt >= policy.max_attempts or elapsed >= policy.deadline:
                bump("retry.exhausted")
                if name:
                    bump(f"retry.{name}.exhausted")
                raise
            delay = min(policy.delay(attempt),
                        max(0.0, policy.deadline - elapsed))
            bump("retry.retries")
            if name:
                bump(f"retry.{name}")
            time.sleep(delay)


#: exception classes worth retrying on filesystem/network paths — structural
#: errors (ValueError on a torn format, TypeError bugs) fail fast instead of
#: sleeping through backoff on a failure that can never heal
IO_RETRY_ON: Tuple[type, ...] = (OSError, ConnectionError, TimeoutError)


def io_policy(**overrides) -> RetryPolicy:
    """The flag-configured policy narrowed to transient IO errors."""
    return default_policy(retry_on=IO_RETRY_ON, **overrides)


def atomic_write(path: str, data, *, name: str = "atomic_write",
                 policy: Optional[RetryPolicy] = None) -> None:
    """Durable file write shared by ``paddle_tpu.save`` and the checkpoint
    manifests: temp file in the target directory, fsync, ``os.replace``,
    then a best-effort directory fsync so the rename itself is durable — a
    kill mid-write never leaves a torn file at ``path``. ``data`` is bytes,
    or a callable taking the open binary file (stream-serialize large
    payloads without materializing them; re-invoked on retry). Retried
    under the IO policy with a ``ckpt_io`` fault probe."""
    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    policy = policy or io_policy()

    def _write():
        maybe_fault("ckpt_io")
        fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                if callable(data):
                    data(f)
                else:
                    f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            try:  # rename durability (no-op where dirs can't be fsynced)
                dfd = os.open(d, os.O_RDONLY)
                try:
                    os.fsync(dfd)
                finally:
                    os.close(dfd)
            except OSError:
                pass
        except BaseException:  # analysis: allow(broad-except) — cleanup-and-
            try:               # reraise: the tmp file must go even on
                os.unlink(tmp)  # KeyboardInterrupt
            except OSError:
                pass
            raise

    call_with_retry(_write, name=name, policy=policy)


def retry(policy: Optional[RetryPolicy] = None, *, name: str = ""):
    """Decorator form of :func:`call_with_retry`."""

    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            return call_with_retry(
                fn, *args, policy=policy,
                name=name or getattr(fn, "__name__", ""), **kwargs)

        return wrapped

    return deco


# ---------------------------------------------------------- fault injection


@dataclass
class _FaultSpec:
    kind: str
    times: int = 1          # how many probes fire before the fault disarms
    after: int = 0          # how many probes to let pass first (deterministic)
    exc: Any = None         # exception instance/class to raise; None = flag
    fired: int = 0
    passed: int = 0


_faults: Dict[str, _FaultSpec] = {}
_env_faults_loaded = False

#: kinds with production probes; inject_fault accepts other kinds too, for
#: tests that place maybe_fault probes in their own code.
#: ``worker_kill``/``worker_hang`` are flag-kind faults probed by the
#: process-replica watchdog (serving.gateway.procpool): kill SIGKILLs a
#: live worker process, hang makes one stop heartbeating while holding
#: its socket — the two failure modes the heartbeat supervision must
#: classify and recover from (docs/robustness.md "Process isolation").
KNOWN_FAULTS = ("ckpt_io", "nonfinite_grads", "preempt", "serving_step",
                "serving_device", "arena_corrupt",
                "worker_kill", "worker_hang",
                # gateway_kill (ISSUE 20): SIGKILL the gateway PARENT at
                # its WAL-sweep boundary — the chaos probe behind the
                # crash-safe-gateway e2e (restart on the same WAL dir,
                # token-identical journal-seeded resumption)
                "gateway_kill")

#: kinds whose probe sites are bare statements (they only react to an
#: exception), so a flag-style fault would silently exercise nothing —
#: inject_fault defaults their exc to the error the real failure would raise
_DEFAULT_FAULT_EXC = {
    "ckpt_io": lambda k: OSError(f"injected {k} fault"),
    "serving_device": lambda k: ServingDeviceError(f"injected {k} fault"),
    "arena_corrupt": lambda k: ArenaCorruptError(f"injected {k} fault"),
}


def inject_fault(kind: str, times: int = 1, after: int = 0,
                 exc: Any = None) -> None:
    """Arm a deterministic fault: the next ``after`` probes of ``kind`` pass,
    then ``times`` probes fire (raising ``exc``, else returning True), then
    the fault disarms. ``ckpt_io``/``serving_device``/``arena_corrupt``
    default ``exc`` to the error class the real failure would raise — their
    probe sites are bare statements that only react to an exception, so a
    flag-style fault would silently exercise nothing. Requires
    ``FLAGS_fault_injection=1`` — production runs cannot arm faults by
    accident."""
    if not flags.flag("fault_injection"):
        raise RuntimeError(
            "fault injection is disabled; set FLAGS_fault_injection=1 "
            "(env or paddle.set_flags) before arming faults")
    if exc is None and kind in _DEFAULT_FAULT_EXC:
        exc = _DEFAULT_FAULT_EXC[kind](kind)
    with _lock:
        _faults[kind] = _FaultSpec(kind, times=int(times), after=int(after),
                                   exc=exc)


def clear_faults() -> None:
    with _lock:
        _faults.clear()


def fault_armed(kind: str) -> bool:
    spec = _faults.get(kind)
    return spec is not None and spec.times > 0


def _load_env_faults() -> None:
    """One-shot parse of FLAGS_inject_faults ("kind:times[:after],..."), so a
    subprocess under the chaos harness can be armed purely via env."""
    global _env_faults_loaded
    _env_faults_loaded = True
    raw = flags.flag("inject_faults")
    if not raw or not flags.flag("fault_injection"):
        return
    for part in raw.split(","):
        fields = part.strip().split(":")
        if not fields[0]:
            continue
        times = int(fields[1]) if len(fields) > 1 else 1
        after = int(fields[2]) if len(fields) > 2 else 0
        mk = _DEFAULT_FAULT_EXC.get(fields[0])
        exc = mk(fields[0]) if mk is not None else None
        with _lock:
            _faults[fields[0]] = _FaultSpec(fields[0], times=times,
                                            after=after, exc=exc)


def maybe_fault(kind: str) -> bool:
    """Probe point: no-op (False) unless a matching fault is armed. Raises the
    armed exception for exception-kind faults, returns True for flag-kind
    faults. Near-zero cost in production: one empty-dict check."""
    if not _faults:
        if not _env_faults_loaded:
            _load_env_faults()
            if not _faults:
                return False
        else:
            return False
    spec = _faults.get(kind)
    if spec is None or not flags.flag("fault_injection"):
        return False
    with _lock:
        if spec.passed < spec.after:
            spec.passed += 1
            return False
        if spec.times <= 0:
            return False
        spec.times -= 1
        spec.fired += 1
    bump(f"fault.{kind}")
    if spec.exc is not None:
        raise spec.exc if isinstance(spec.exc, BaseException) else spec.exc()
    return True


# ----------------------------------------------------------------- rollback

_rollback_handler: Optional[Callable[[str], None]] = None


def set_rollback_handler(fn: Optional[Callable[[str], None]]) -> None:
    """Register what "roll back to the last checkpoint" means for this run —
    typically restoring model+optimizer from a ``TrainCheckpointer`` (which
    bumps the optimizer's state version, so a compiled TrainStep re-seeds its
    cached optimizer state on the next call). ``None`` unregisters."""
    global _rollback_handler
    _rollback_handler = fn


def rollback_handler() -> Optional[Callable[[str], None]]:
    return _rollback_handler


def trigger_rollback(reason: str) -> None:
    """Invoke the registered rollback handler (or raise
    :class:`NonfiniteStepError` when none is registered)."""
    bump("sentinel.rollbacks")
    if _rollback_handler is None:
        raise NonfiniteStepError(
            f"{reason}; no rollback handler registered "
            "(resilience.set_rollback_handler)")
    _rollback_handler(reason)


# --------------------------------------------------------------- preemption


class PreemptionGuard:
    """Convert preemption signals into a clean step-boundary shutdown.

    Installs handlers for SIGTERM/SIGINT (preemptible-TPU eviction notice)
    that *request* shutdown instead of killing the process mid-step. The
    training loop polls :meth:`requested` at step boundaries and calls
    :meth:`maybe_finalize` to write one final synchronous checkpoint, wait
    for it to commit, leave a resume marker, and exit 0 — the restarted pod
    auto-resumes via ``TrainCheckpointer.restore()``. The elastic module's
    dead-peer signal feeds the same guard through
    ``ElasticManager.bind_preemption_guard``.
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT),
                 install: bool = True):
        self._event = threading.Event()
        self.reason: Optional[str] = None
        self._prev: Dict[int, Any] = {}
        if install:
            self.install(signals)

    def install(self, signals=(signal.SIGTERM, signal.SIGINT)) -> None:
        for s in signals:
            if s in self._prev:
                continue  # already ours: re-recording would make "previous"
                # point at our own handler and escalation loop forever
            try:
                self._prev[s] = signal.signal(s, self._on_signal)
            except ValueError:  # not the main thread: poll-only guard
                pass

    def uninstall(self) -> None:
        for s, h in self._prev.items():
            try:
                signal.signal(s, h)
            except ValueError:
                pass
        self._prev.clear()

    def _on_signal(self, signum, frame) -> None:
        if self._event.is_set():
            # SECOND signal: the step-boundary poll is clearly not being
            # reached (hung collective, lost device) and the operator
            # insists — restore the previous handler and re-deliver, so
            # repeated SIGTERM/Ctrl-C escalates instead of being swallowed
            # forever (SIGKILL would skip the final checkpoint anyway)
            prev = self._prev.get(signum)
            if prev is None or prev == self._on_signal:
                prev = signal.SIG_DFL  # never chain back to ourselves
            try:
                signal.signal(signum, prev)
            except (ValueError, TypeError):
                signal.signal(signum, signal.SIG_DFL)
            bump("preempt.escalations")
            os.kill(os.getpid(), signum)
            return
        # first signal: swallow (no chain to the default terminate) — the
        # whole point is to survive until the next step boundary
        self.request(f"signal {signum}")

    def request(self, reason: str = "requested") -> None:
        if not self._event.is_set():
            bump("preempt.requests")
            self.reason = reason
        self._event.set()

    def requested(self) -> bool:
        """Poll at step boundaries. Also consumes an armed ``preempt``
        injected fault (the chaos harness's SIGTERM stand-in)."""
        if not self._event.is_set() and maybe_fault("preempt"):
            self.request("injected preempt fault")
        return self._event.is_set()

    def maybe_finalize(self, step: int, checkpointer, state,
                       exit_process: bool = True) -> bool:
        """At a step boundary: if preemption was requested, save ``state``
        (a state dict, or a zero-arg callable returning one) synchronously at
        ``step``, wait until the write committed, write the resume marker,
        and exit cleanly (``SystemExit(0)``). Returns False when no
        preemption is pending; True when finalized with
        ``exit_process=False``."""
        if not self.requested():
            return False
        sd = state() if callable(state) else state
        # settle any in-flight async save first: if the loop already saved
        # THIS step, committing it is all that's needed (orbax refuses a
        # second save onto an existing step)
        checkpointer.wait_until_finished()
        latest = (checkpointer.latest_step()
                  if hasattr(checkpointer, "latest_step") else None)
        if latest != step:
            checkpointer.save(step, sd, force=True)
            checkpointer.wait_until_finished()
        if hasattr(checkpointer, "write_resume_marker"):
            checkpointer.write_resume_marker(step, reason=self.reason or "")
        bump("preempt.final_saves")
        if exit_process:
            raise SystemExit(0)
        return True
