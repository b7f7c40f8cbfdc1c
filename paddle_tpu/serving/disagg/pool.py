"""Role-typed process-replica pool: the disaggregated serving router.

:class:`DisaggReplicaPool` is a :class:`~..gateway.procpool.ProcessReplicaPool`
whose workers wear roles (``prefill`` / ``decode`` — see
:mod:`.roles`) and whose router places each request by PHASE:

* A fresh request is in its **prefill phase**: it routes to the prefill
  pool with its backend budget capped at first-token
  (``_backend_budget``), so the prefill worker chunk-prefills the
  prompt — publishing every finished full block to the shared disk tier
  as it goes — emits the first token, and finishes its backend request.
* The pool's observe pass intercepts that finish as a **handoff**
  (``_maybe_handoff``): the first token folds into the gateway handle's
  journal, the phase flips to decode, and the request re-routes to the
  decode pool carrying the journal. The decode worker's admission walks
  its radix tree, finds the published chain on the shared disk tier,
  restores it through the ONE compiled scatter, re-prefills only the
  (at most block-sized) unpublished suffix, and decodes to completion.
  Token-for-token identical to a unified run — the handoff is exactly
  the journal-replay invariant every reroute already relies on — and
  zero new compiled programs on either side (restore/prefill/decode all
  reuse existing executables; trace-counter asserted in tests).

Crash recovery rides the same machinery: a dead PREFILL worker's
request re-routes (journal empty) back to the prefill pool, where the
successor's radix walk finds whatever blocks the victim already
published and re-prefills only the unpublished suffix; a dead DECODE
worker's request re-routes with its journal to another decode worker,
which restores the SAME content hashes. When a role's pool has no
routable worker, routing degrades to unified: any healthy worker runs
the full lifecycle (every worker is a complete serving stack), counted
as ``disagg.degraded_routes``.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

from ...core import resilience
from .. import cache_views, metrics, telemetry
from ..gateway.procpool import ProcessReplicaPool
from ..gateway.router import RoutedRequest, _Replica
from ..scheduler import RequestState
from .prefetch import RestorePlanner
from .roles import (DECODE, PREFILL, role_counts, role_flag_overrides,
                    role_of, shared_disk_dir)


class DisaggReplicaPool(ProcessReplicaPool):
    """Prefill/decode-disaggregated worker fleet (see the module
    docstring). ``prefill_replicas`` / ``decode_replicas`` default to
    ``FLAGS_gateway_prefill_replicas`` / ``FLAGS_gateway_decode_replicas``;
    at least one of each is required (a pool without one of the roles is
    just a unified :class:`ProcessReplicaPool` — build that instead)."""

    def __init__(self, model, prefill_replicas: Optional[int] = None,
                 decode_replicas: Optional[int] = None,
                 disk_dir: Optional[str] = None, **pool_kw):
        spec = getattr(model, "serving_spec", None)
        if spec is not None:
            # a request is handed over as its published K/V BLOCK chain,
            # through the prefix cache and the host tier: refused for the
            # kinds of state that are not that (a model factory is refused
            # by its workers' engines: the prefix cache names it)
            cache_views.refuse_options(spec().layers,
                                       {cache_views.HANDOFF: True})
        p, d = role_counts(prefill_replicas, decode_replicas)
        if p < 1 or d < 1:
            raise ValueError(
                f"DisaggReplicaPool needs at least one worker per role "
                f"(got prefill={p}, decode={d}); for a single-role fleet "
                "use ProcessReplicaPool")
        # role state BEFORE super().__init__: the base constructor spawns
        # replicas through _payload_for, which reads it
        self._n_prefill = p
        self._n_decode = d
        self.disk_dir = disk_dir if disk_dir else shared_disk_dir()
        self._role_overrides = {
            role: role_flag_overrides(role, self.disk_dir)
            for role in (PREFILL, DECODE)}
        self._planner = RestorePlanner(self)
        self._handoff_lock = threading.Lock()
        pool_kw.pop("replicas", None)  # the role counts ARE the count
        super().__init__(model, replicas=p + d, **pool_kw)

    # --------------------------------------------------------------- roles

    def role_of(self, idx: int) -> str:
        return role_of(idx, self._n_prefill, self._n_decode)

    def _payload_for(self, idx: int) -> dict:
        overrides = self._role_overrides.get(self.role_of(idx))
        if not overrides:
            return self._payload
        # a shallow re-key of the shared payload: the pickled model/kw
        # blobs are shared, only the flag snapshot differs per role
        return dict(self._payload,
                    flags=dict(self._payload["flags"], **overrides))

    @staticmethod
    def _phase(rr: RoutedRequest) -> str:
        """Which pool ``rr`` routes to next: every request starts in its
        prefill phase; the handoff flips it to decode for good (reroutes
        keep the phase — a dead decode worker's successor restores, it
        never re-prefills from scratch)."""
        return getattr(rr, "_disagg_phase", "prefill")

    def _routable_role(self, role: str) -> bool:
        return any(self.role_of(r.idx) == role
                   for r in self.healthy_replicas())

    # ------------------------------------------------------------- routing

    def _candidates(self, rr: RoutedRequest) -> List[_Replica]:
        reps = super()._candidates(rr)  # load-sorted, raises when empty
        want = PREFILL if self._phase(rr) == "prefill" else DECODE
        pool = [r for r in reps if self.role_of(r.idx) == want]
        if pool:
            metrics.bump(f"disagg.{want}_routes")
            return pool
        # the target pool is empty (ejected / draining / scaled away):
        # degrade to unified — every worker is a full serving stack, so
        # any healthy one can run the request end-to-end
        metrics.bump("disagg.degraded_routes")
        return reps

    def _backend_budget(self, rr: RoutedRequest,
                        journal: Optional[Sequence[int]]) -> int:
        if self._phase(rr) != "prefill":
            return rr.max_new_tokens
        if not self._routable_role(PREFILL):
            # degraded route: the unified stand-in runs it end-to-end
            return rr.max_new_tokens
        # prefill phase: the backend request finishes at first-token
        # (plus the journal a prefill-worker-death reroute carries), which
        # is what turns its completion into the handoff signal. The
        # REQUEST's budget is untouched — completion checks compare the
        # journal against rr.max_new_tokens.
        return len(journal or ()) + 1

    # ------------------------------------------------------------- handoff

    def _observe(self, rr: RoutedRequest) -> None:
        if self._maybe_handoff(rr):
            return
        super()._observe(rr)

    def _maybe_handoff(self, rr: RoutedRequest) -> bool:
        """Intercept a prefill-phase backend FINISH as a handoff: fold
        the first token into the journal, flip the phase, re-route to
        the decode pool. Returns True when this observer owned the event
        (the base observe must not also finalize). Failures are NOT
        intercepted — the base path ejects/reroutes them with the phase
        unchanged, which is per-role crash recovery."""
        if rr.finished or self._phase(rr) != "prefill":
            return False
        with rr._lock:
            backend = rr._backend
        if backend is None or not backend.finished:
            return False
        if backend.state != RequestState.FINISHED:
            return False
        with self._lock:
            if rr.finished or rr._rerouting:
                return True  # another mover owns it already
            rr._rerouting = True
        try:
            if rr._cancelled:
                self._finalize(rr, RequestState.CANCELLED)
                return True
            journal = rr._detach_journal()
            with self._lock:
                bucket = self._live.get(rr._replica_idx)
                if bucket is not None and rr in bucket:
                    bucket.remove(rr)
            stop = rr.stop_token_id
            if (len(journal) >= rr.max_new_tokens
                    or (stop is not None and journal
                        and journal[-1] == stop)):
                # the prefill worker's first token already completed the
                # stream (budget 1, or an immediate stop): nothing to
                # decode — this includes the degraded end-to-end case
                self._finalize(rr, RequestState.FINISHED)
                return True
            rr._disagg_phase = "decode"
            telemetry.span(rr.trace_id, telemetry.HANDOFF,
                           request_id=rr.request_id,
                           from_replica=rr._replica_idx,
                           journal_tokens=len(journal))
            # journal the phase flip: a WAL replay must resubmit this
            # stream into its DECODE phase (restore the published chain),
            # never re-prefill it from scratch
            self._wal_moved(rr, "HANDOFF")
            metrics.bump("disagg.handoffs")
            try:
                self._route(rr, journal=journal)
            # analysis: allow(broad-except) — mirror of _reroute_locked:
            # any placement failure must finalize the handle (tenant slot
            # freed, done_event fired), never strand it bucketless
            except Exception as e:
                self._finalize(rr, RequestState.FAILED, e)
            return True
        finally:
            rr._rerouting = False

    # ------------------------------------------------------------ prefetch

    def _observe_live(self) -> None:
        # both drivers (foreground pump_once and the background watchdog
        # sweep) come through here, so the restore-ahead planner runs
        # exactly once per supervision cycle either way
        super()._observe_live()
        self._planner.sweep()

    # ------------------------------------------------------ health / scale

    def _eject(self, rep, cause: BaseException) -> None:
        role = self.role_of(rep.idx)
        resilience.bump(f"disagg.{role}_ejections")
        super()._eject(rep, cause)

    def scale_to(self, n: Optional[int] = None,
                 grace: Optional[float] = None,
                 prefill: Optional[int] = None,
                 decode: Optional[int] = None) -> None:
        """Per-role scale-down: ``prefill=`` / ``decode=`` retire workers
        of that role (unhealthy first, then highest index) through the
        same drain-and-reroute path as the base ``scale_to``. A role
        scaled to zero leaves the pool in degraded-unified routing for
        that phase. Plain ``scale_to(n)`` keeps the base total-count
        semantics."""
        if prefill is None and decode is None:
            if n is None:
                raise ValueError("scale_to needs a total count or a "
                                 "per-role count")
            return super().scale_to(n, grace)
        if n is not None:
            raise ValueError("pass either a total count or per-role "
                             "counts, not both")
        for role, target in ((PREFILL, prefill), (DECODE, decode)):
            if target is None:
                continue
            target = int(target)
            if target < 0:
                raise ValueError(f"{role} count must be >= 0")
            while True:
                with self._lock:
                    active = [r for r in self._replicas
                              if not r.removed
                              and self.role_of(r.idx) == role]
                    if len(active) <= target:
                        break
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
                        break
                    victim.draining = True
                self._remove_replica(victim, grace)

    # --------------------------------------------------------------- stats

    def stats(self) -> dict:
        out = super().stats()
        with self._lock:
            roles = {r.idx: self.role_of(r.idx) for r in self._replicas
                     if not r.removed}
        healthy = {r.idx for r in self.healthy_replicas()}
        for row in out["replicas"]:
            row["role"] = roles.get(row["idx"], "removed")
        out["disagg"] = {
            "prefill_replicas": self._n_prefill,
            "decode_replicas": self._n_decode,
            "prefill_healthy": sum(1 for i in healthy
                                   if self.role_of(i) == PREFILL),
            "decode_healthy": sum(1 for i in healthy
                                  if self.role_of(i) == DECODE),
            "disk_dir": self.disk_dir,
        }
        return out
