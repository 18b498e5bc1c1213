"""Cluster-wide tenancy enforcement — the executor-side agent.

PR 18 built preemptive tenancy inside one process: the scheduler's
arbiter suspends local victims, HBM budgets bound local reservations.
This module is the cross-process half (ISSUE 20 / ROADMAP item 5): a
``TenancyAgent`` rides the executor's rendezvous heartbeat
(``RendezvousClient.start_heartbeat`` piggyback hooks), reporting
per-tenant state up to the coordinator's ``TenancyArbiter`` and
applying the epoch-tagged suspend/resume/shed directives that come
back on the response — so a tenant breaching its cluster share on
executor A is preempted even when the starved waiter sits on
executor B.

Every protocol edge is a failure domain (chaos-injectable as
``tenancy``):

* **Stale/duplicate directives** — every directive carries the
  coordinator generation as its epoch and a unique id; wrong-epoch
  directives are dropped (``tpuq_tenancy_directives_stale_total``),
  duplicate suspends act as lease renewals, duplicate resumes are
  no-ops.  A directive racing a cancel always loses: the scheduler's
  ``remote_suspend`` refuses cancelled tokens.
* **Executor loss / coordinator restart mid-suspend** — a remote
  suspend is a LEASE (``SUSPEND_TTL_GRACES`` x ``preempt.graceMs``):
  the coordinator renews it every heartbeat
  while warranted; when renewals stop, the token force-resumes itself
  (``tpuq_preempt_force_resumed_total``) and the scheduler's
  accounting follows — a directive can delay work, never wedge it.
* **Heartbeat flaps** — after ``DEGRADED_AFTER_MISSES``
  consecutive misses the agent drops to local-only enforcement
  (``tpuq_tenancy_degraded_total``); the first heartbeat that
  round-trips again re-syncs (``tpuq_tenancy_resyncs_total``):
  applied-directive memory clears, dead leases prune, and the
  arbiter's fresh decisions converge within a few heartbeats.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, Optional

from spark_rapids_tpu.runtime import telemetry as TM

_TM_DEGRADED = TM.REGISTRY.counter(
    "tpuq_tenancy_degraded_total",
    "times an executor dropped to local-only tenancy enforcement "
    "after consecutive heartbeat misses (coordinator down or "
    "unreachable)")
_TM_DIRECTIVES = TM.REGISTRY.labeled_counter(
    "tpuq_tenancy_directives_total",
    "cluster arbiter directives applied by this executor, by kind "
    "(suspend | resume | shed | unshed)", label="kind")
_TM_STALE = TM.REGISTRY.counter(
    "tpuq_tenancy_directives_stale_total",
    "directives dropped as stale (wrong epoch — issued by a previous "
    "coordinator generation) or targeting a finished/cancelled query")
_TM_RESYNC = TM.REGISTRY.counter(
    "tpuq_tenancy_resyncs_total",
    "agent re-syncs with the coordinator after a miss streak or an "
    "epoch (generation) change — coordinator restart recovery")

#: bounded memory of applied directive ids (idempotency window)
_APPLIED_CAP = 512
# a remotely-directed suspension must be renewed within this many
# preempt.graceMs or the token force-resumes itself (the wedge guard)
SUSPEND_TTL_GRACES = 2.0
# consecutive heartbeat failures before local-only enforcement
DEGRADED_AFTER_MISSES = 2


class TenancyAgent:
    """One executor's end of the cluster tenancy protocol.

    Wire it into the heartbeat:
        agent = TenancyAgent(scheduler, conf=conf)
        client.start_heartbeat(period_s, payload_fn=agent.payload,
                               on_response=agent.on_heartbeat,
                               on_miss=agent.on_miss)
    """

    def __init__(self, scheduler, conf=None):
        from spark_rapids_tpu import conf as C
        self.sched = scheduler
        # disabled agents stay wireable (the heartbeat hooks are
        # no-ops): enforcement falls back to process-local only
        self.enabled = (bool(conf.get(C.TENANCY_ENABLED))
                        if conf is not None
                        else bool(C.TENANCY_ENABLED.default))
        self.suspend_ttl_s = max(
            SUSPEND_TTL_GRACES * scheduler.preempt_grace_s, 0.001)
        self.degraded_after = DEGRADED_AFTER_MISSES
        self._lock = threading.Lock()
        self._applied: "OrderedDict[str, str]" = OrderedDict()
        self._holds: Dict[int, str] = {}   # query_id -> directive id
        self._breaches: Dict[str, int] = {}  # pending HBM-breach relays
        self._epoch: Optional[int] = None
        self._misses = 0
        self.degraded = False
        # observability (read by the soak harness / bench)
        self.applied: Dict[str, int] = {"suspend": 0, "resume": 0,
                                        "shed": 0, "unshed": 0}
        self.stale = 0
        self.resyncs = 0
        self.degraded_entries = 0
        self.last_fanout_s: Optional[float] = None
        self.max_fanout_s = 0.0

    # -- heartbeat piggyback -------------------------------------------

    def payload(self) -> dict:
        """The per-tenant report riding this heartbeat: scheduler
        depth/starvation state, live HBM bytes per tenant, and any
        HBM-breach relays since the last beat."""
        if not self.enabled:
            return {}
        rep = self.sched.local_tenancy_report()
        from spark_rapids_tpu.runtime import memory
        mgr = memory.peek_manager()
        if mgr is not None:
            try:
                usage = mgr.tenant_usage()
            except Exception:
                usage = {}
            for name, t in rep.get("tenants", {}).items():
                t["hbm_bytes"] = int(usage.get(name, 0))
        with self._lock:
            self._prune_holds_locked()
            rep["held"] = sorted(self._holds)
            if self._breaches:
                rep["breaches"] = dict(self._breaches)
                self._breaches.clear()
        return rep

    def on_heartbeat(self, resp: dict) -> None:
        """Coordinator replied: leave degraded mode, re-sync on an
        epoch (generation) change or after a miss streak, then apply
        the pending directives."""
        if not self.enabled:
            return
        if not resp.get("ok"):
            self.on_miss()   # declared dead — must re-register to rejoin
            return
        epoch = resp.get("tenancy_epoch")
        with self._lock:
            resync = (self._misses >= 1
                      or (self._epoch is not None and epoch is not None
                          and int(epoch) != self._epoch))
            self._misses = 0
            self.degraded = False
            if epoch is not None:
                self._epoch = int(epoch)
            if resync:
                # a restarted coordinator re-issues what it still
                # wants; everything else must not replay from memory
                self._applied.clear()
                self._prune_holds_locked()
                self.resyncs += 1
        if resync:
            _TM_RESYNC.inc()
        from spark_rapids_tpu.runtime import resilience as R
        try:
            R.INJECTOR.on("tenancy")
        except R.InjectedDeviceError:
            # injected directive-path fault: drop this round's
            # directives — suspends are leases the arbiter renews next
            # beat, so the protocol self-heals
            return
        for d in resp.get("directives") or ():
            self.apply_directive(d)

    def on_miss(self) -> None:
        """Heartbeat could not reach the coordinator."""
        with self._lock:
            self._misses += 1
            trip = (self._misses >= self.degraded_after
                    and not self.degraded)
            if trip:
                self.degraded = True
                self.degraded_entries += 1
        if trip:
            _TM_DEGRADED.inc()
            TM.REGISTRY.record_health({
                "severity": "WARN", "check": "tenancy_degraded",
                "value": self._misses, "threshold": self.degraded_after,
                "detail": "coordinator unreachable — falling back to "
                          "local-only tenancy enforcement"})

    # -- directives -----------------------------------------------------

    def apply_directive(self, d: dict) -> bool:
        """Apply one epoch-tagged directive; idempotent (duplicate
        suspends renew the lease, duplicate resumes/sheds no-op) and
        stale-safe (wrong epoch drops).  Returns True if it took
        effect.  Cancel always wins a directive-vs-cancel race."""
        from spark_rapids_tpu.runtime import cancel as CN
        kind = str(d.get("kind", ""))
        did = str(d.get("id", ""))
        epoch = d.get("epoch")
        qid = d.get("query_id")
        tenant = str(d.get("tenant", "default"))
        with self._lock:
            if (epoch is not None and self._epoch is not None
                    and int(epoch) != self._epoch):
                self.stale += 1
                stale = True
            else:
                stale = False
            dup = did in self._applied
        if stale:
            _TM_STALE.inc()
            return False
        ttl = max(self.suspend_ttl_s, float(d.get("ttl_ms", 0)) / 1000.0)
        if kind == "suspend":
            if dup:
                # lease renewal — push the token's force-resume
                # deadline out another TTL
                tok = CN.get_token(qid) if qid is not None else None
                return bool(tok is not None and tok.refresh_suspend(ttl))
            ok = (qid is not None
                  and self.sched.remote_suspend(
                      qid, d.get("detail") or "cluster arbiter "
                      "directive", ttl_s=ttl))
            self._record(did, kind, ok)
            if ok:
                with self._lock:
                    self._holds[qid] = did
                issued = d.get("issued_wall")
                if issued is not None:
                    lat = max(0.0, time.time() - float(issued))
                    self.last_fanout_s = lat
                    self.max_fanout_s = max(self.max_fanout_s, lat)
            else:
                # target finished or cancelled first — cancel wins
                _TM_STALE.inc()
                with self._lock:
                    self.stale += 1
            return ok
        if kind == "resume":
            if dup:
                return False
            ok = qid is not None and self.sched.remote_resume(qid)
            self._record(did, kind, ok)
            with self._lock:
                self._holds.pop(qid, None)
            return ok
        if kind in ("shed", "unshed"):
            if dup:
                return False
            self.sched.set_cluster_shed(tenant, kind == "shed")
            self._record(did, kind, True)
            return True
        return False

    def _record(self, did: str, kind: str, ok: bool) -> None:
        with self._lock:
            self._applied[did] = kind
            while len(self._applied) > _APPLIED_CAP:
                self._applied.popitem(last=False)
            if ok:
                self.applied[kind] = self.applied.get(kind, 0) + 1
        if ok:
            _TM_DIRECTIVES.inc(kind)

    def _prune_holds_locked(self) -> None:
        # drop leases whose token already resumed (wedge guard fired,
        # query finished, or cancel won) — callers hold self._lock
        from spark_rapids_tpu.runtime import cancel as CN
        for qid in list(self._holds):
            tok = CN.get_token(qid)
            if tok is None or not tok.preempt_pending():
                del self._holds[qid]

    # -- HBM breach relay ----------------------------------------------

    def notify_breach(self, tenant: str) -> None:
        """Memory-arbiter hook: a tenant breached its HBM budget and
        local preemption found no victim — relay it on the next
        heartbeat so the cluster arbiter can suspend the tenant's
        largest-runtime query on another executor."""
        with self._lock:
            self._breaches[tenant] = self._breaches.get(tenant, 0) + 1

    def stats(self) -> dict:
        with self._lock:
            return {"applied": dict(self.applied),
                    "stale": self.stale,
                    "resyncs": self.resyncs,
                    "degraded": self.degraded,
                    "degraded_entries": self.degraded_entries,
                    "live_holds": len(self._holds),
                    "last_fanout_s": self.last_fanout_s,
                    "max_fanout_s": self.max_fanout_s}


# -- process singleton (the memory arbiter's relay target) ----------------

_agent: Optional[TenancyAgent] = None
_agent_lock = threading.Lock()


def set_agent(agent: Optional[TenancyAgent]) -> None:
    global _agent
    with _agent_lock:
        _agent = agent


def peek_agent() -> Optional[TenancyAgent]:
    """The process agent if one is wired up — never creates (an
    executor without the cluster protocol stays purely local)."""
    return _agent


def reset_agent() -> None:
    set_agent(None)
