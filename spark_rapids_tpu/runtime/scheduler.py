"""Multi-tenant admission control and fair query scheduling.

The serving tier's gate in front of the whole engine: many callers
submit queries across named *tenants*; this module decides — before any
plan executes or reserves a byte of HBM — whether each submission is
admitted, queued, or shed, and in what order queued queries get one of
the ``maxConcurrentQueries`` run slots.

Three layers, checked in order:

1. **Load shedding** (service-wide watermarks, conf family
   ``spark.rapids.tpu.scheduler.shed.*``): total depth (queued +
   running), host spill-tier pressure
   (``DeviceMemoryManager.spill_pressure``), and device-admission
   saturation (``(holders + waiting) / permits`` on the
   ``DeviceSemaphore``).  A breach rejects the submission with
   ``QueryRejected(reason='shed_*')``, bumps
   ``tpuq_admission_shed_total{tenant=...}`` and records a health WARN
   — the service defends itself BEFORE the HBM arbiter starts
   thrashing the disk tier.
2. **Per-tenant quotas**: ``maxQueued`` rejects
   (``reason='tenant_queue_full'``); ``maxInFlight`` and the HBM share
   never reject — they bound how many of the tenant's queries may RUN
   at once, so excess submissions queue.  The HBM share is enforced as
   a fraction of the global run slots (each running query may reserve
   up to the full HBM pool, so capping a tenant's concurrent run slots
   caps its share of device-memory pressure).
3. **Fair dispatch**: weighted deficit round-robin across tenants —
   each refill round adds ``weight`` credit to every backlogged
   tenant, one run-slot grant costs one credit — with strict priority
   lanes inside a tenant (higher ``priority`` first, FIFO within a
   lane).  A weight-2 tenant drains twice as fast as a weight-1 tenant
   under contention, and no backlogged tenant starves: its deficit
   grows every round until it wins one.

Cancellation composes: a queued ticket's worker blocks in
``acquire()`` polling its ``CancelToken``, so ``session.cancel`` and
deadline expiry surface ``QueryCancelled`` within ~2x the poll
interval *without* the query ever being admitted, and the vacated
queue entry is dispatched past immediately.

``device_hold`` at the bottom is THE sanctioned path to the
``DeviceSemaphore`` — the ``scheduler-bypass`` tier-1 lint rule fails
any other module that reaches for ``get_semaphore`` directly, so
future execs cannot dodge admission control.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime.semaphore import get_semaphore, peek_semaphore

_TM_SUBMITTED = TM.REGISTRY.labeled_counter(
    "tpuq_scheduler_submitted_total",
    "queries admitted into the scheduler (queued or dispatched)",
    label="tenant")
_TM_COMPLETED = TM.REGISTRY.labeled_counter(
    "tpuq_scheduler_completed_total",
    "queries that finished (released their run slot) per tenant",
    label="tenant")
_TM_REJECTED = TM.REGISTRY.labeled_counter(
    "tpuq_admission_rejected_total",
    "submissions rejected at admission, by structured reason "
    "(shed_* reasons also count in tpuq_admission_shed_total)",
    label="reason")
_TM_SHED = TM.REGISTRY.labeled_counter(
    "tpuq_admission_shed_total",
    "submissions load-shed by watermark breach, per tenant",
    label="tenant")
_TM_CANCELLED_QUEUED = TM.REGISTRY.counter(
    "tpuq_scheduler_cancelled_queued_total",
    "queries cancelled or deadline-expired while still QUEUED "
    "(never admitted to a run slot)")
_TM_QUEUE_WAIT = TM.REGISTRY.histogram(
    "tpuq_scheduler_queue_wait_seconds",
    "queued-to-granted latency per admitted query")
_TM_PREEMPTED = TM.REGISTRY.labeled_counter(
    "tpuq_scheduler_preempted_total",
    "running queries suspended by the preemption arbiter, per victim "
    "tenant", label="tenant")
_TM_SLO_BREACH = TM.REGISTRY.labeled_counter(
    "tpuq_slo_breach_total",
    "sliding-window p99 SLO breach transitions per tenant (entering "
    "the breached state; shedding while breached counts in "
    "tpuq_admission_rejected_total{reason=shed_slo})", label="tenant")
_TM_REMOTE_SUSPENDED = TM.REGISTRY.labeled_counter(
    "tpuq_scheduler_remote_suspended_total",
    "running queries suspended on a cluster arbiter directive (the "
    "cross-executor half of preemption), per victim tenant",
    label="tenant")

# ticket lifecycle (SUSPENDED: granted once, slot reclaimed by the
# preemption arbiter, waiting to resume — resumes before new grants)
QUEUED = "QUEUED"
RUNNING = "RUNNING"
SUSPENDED = "SUSPENDED"
DONE = "DONE"
CANCELLED = "CANCELLED"

#: sanctioned priority band for ``submit`` — out-of-range values are a
#: caller bug surfaced as QueryRejected(reason='bad_priority') at the
#: door, not a KeyError deep in a dispatch lane
PRIORITY_MIN = -100
PRIORITY_MAX = 100

#: a tenant's EFFECTIVE queued cap is its weight share of
#: maxQueuedQueries (never above its own static maxQueued), so one hot
#: tenant's standing queue cannot bury the others' latency behind it
QUEUE_SHAPING = True

#: rejection reasons that mean "the service is overloaded" (counted in
#: the shed counter + health WARN) as opposed to "this tenant hit its
#: own quota"
SHED_REASONS = frozenset({"shed_queue_depth", "shed_spill_pressure",
                          "shed_semaphore_saturation", "shed_slo",
                          "shed_cluster"})

_TENANT_PREFIX = "spark.rapids.tpu.scheduler.tenant."


class QueryRejected(RuntimeError):
    """Structured admission rejection.  ``reason`` is machine-readable
    (``shed_queue_depth`` / ``shed_spill_pressure`` /
    ``shed_semaphore_saturation`` / ``tenant_queue_full`` /
    ``queue_full`` / ``bad_priority``); callers switch on it to retry,
    back off, fix the request, or fail over to another replica."""

    def __init__(self, reason: str, tenant: Optional[str] = None,
                 detail: str = ""):
        self.reason = reason
        self.tenant = tenant
        self.detail = detail
        msg = f"query rejected at admission: {reason}"
        if tenant is not None:
            msg += f" (tenant={tenant})"
        if detail:
            msg += f" — {detail}"
        super().__init__(msg)


def check_priority(priority, tenant: Optional[str] = None) -> int:
    """Validate a submission priority at the door.  Returns the
    normalized int, or raises ``QueryRejected(reason='bad_priority')``
    for non-integers and values outside [PRIORITY_MIN, PRIORITY_MAX] —
    before any token is minted or scheduler state touched."""
    try:
        p = int(priority)
        if p != priority:  # 2.5, "5", ... — only true ints pass
            p = None
    except (TypeError, ValueError):
        p = None
    if p is None or not (PRIORITY_MIN <= p <= PRIORITY_MAX):
        _TM_REJECTED.inc("bad_priority")
        raise QueryRejected(
            "bad_priority", tenant=tenant,
            detail=f"priority={priority!r} outside "
                   f"[{PRIORITY_MIN}, {PRIORITY_MAX}]")
    return p


class Ticket:
    """One submission's place in the service.  Created by ``submit``;
    the owning worker blocks in ``acquire`` until granted, runs the
    query, then ``release``s the slot."""

    __slots__ = ("query_id", "tenant", "priority", "token", "state",
                 "submitted_at", "granted_at", "suspended_at",
                 "remote_hold")

    def __init__(self, query_id: int, tenant: str, priority: int, token):
        self.query_id = query_id
        self.tenant = tenant
        self.priority = priority
        self.token = token
        self.state = QUEUED
        self.submitted_at = time.monotonic()
        self.granted_at: Optional[float] = None
        self.suspended_at: Optional[float] = None
        # suspended on a CLUSTER arbiter directive: local dispatch must
        # not resume it — only remote_resume (or the suspend lease's
        # expiry) lifts the hold
        self.remote_hold = False


class TenantState:
    """Per-tenant queues, quotas, and accounting.  All mutation happens
    under the owning scheduler's condition lock."""

    __slots__ = ("name", "weight", "max_in_flight", "max_queued",
                 "hbm_share", "run_cap", "lanes", "deficit", "running",
                 "queued", "submitted", "completed", "rejected", "shed",
                 "cancelled_queued", "preempted", "suspended",
                 "slo_p99_ms", "slo_window", "slo_breached",
                 "slo_breaches", "cluster_shed")

    def __init__(self, name: str, weight: float, max_in_flight: int,
                 max_queued: int, hbm_share: float, max_concurrent: int,
                 slo_p99_ms: int = 0, slo_window: int = 64):
        self.name = name
        self.weight = max(0.01, float(weight))
        self.max_in_flight = max(1, int(max_in_flight))
        self.max_queued = max(0, int(max_queued))
        self.hbm_share = min(1.0, max(0.0, float(hbm_share)))
        # the HBM share caps concurrent run slots (each slot may
        # reserve up to the whole pool); always at least 1 so a
        # configured tenant can make progress
        self.run_cap = max(1, min(self.max_in_flight,
                                  math.ceil(self.hbm_share
                                            * max_concurrent)))
        # priority -> FIFO of queued tickets; higher priority drains
        # first, strictly
        self.lanes: Dict[int, deque] = {}
        self.deficit = 0.0
        self.running = 0
        self.queued = 0
        self.submitted = 0
        self.completed = 0
        self.rejected = 0
        self.shed = 0
        self.cancelled_queued = 0
        self.preempted = 0   # times one of this tenant's queries was
        self.suspended = 0   # suspended / currently-suspended count
        # SLO guardrail: sliding window of (wall_s, dominant_bucket)
        # completion samples; 0 target disables tracking
        self.slo_p99_ms = max(0, int(slo_p99_ms))
        self.slo_window: deque = deque(maxlen=max(8, int(slo_window)))
        self.slo_breached = False
        self.slo_breaches = 0
        # cluster arbiter ordered this tenant's submissions shed (the
        # tenant is over its cluster share and nothing preemptible is
        # left) — lifted by an 'unshed' directive or agent re-sync
        self.cluster_shed = False

    def backlogged(self) -> bool:
        return self.queued > 0 and self.running < self.run_cap

    def pop_ticket(self) -> Ticket:
        prio = max(p for p, lane in self.lanes.items() if lane)
        lane = self.lanes[prio]
        ticket = lane.popleft()
        if not lane:
            del self.lanes[prio]
        return ticket

    def remove_ticket(self, ticket: Ticket) -> bool:
        lane = self.lanes.get(ticket.priority)
        if lane is None:
            return False
        try:
            lane.remove(ticket)
        except ValueError:
            return False
        if not lane:
            del self.lanes[ticket.priority]
        return True


class QueryScheduler:
    """The admission controller + fair dispatcher.  One condition
    variable guards all state; dispatch is event-driven (runs inside
    ``submit``/``release``/queued-cancel removal — there is no
    scheduler thread to leak or deadlock).

    Lock order: ``self._cv`` may be held while touching a
    ``CancelToken`` (``check``/``add_waiter``/``request_suspend``/
    ``resume``) — safe because the token lock is a leaf (token
    cancel/suspend paths notify waiter CVs OUTSIDE the token lock),
    and the only foreign CV those notifications take
    (``DeviceSemaphore._cv``) is never held by any thread that wants
    ``self._cv`` — the semaphore layer never calls into the
    scheduler.  The scheduler never takes the memory-manager lock
    while holding ``self._cv`` (the pressure probes read plain
    attributes), and the memory arbiter's
    ``request_tenant_preemption`` upcall must likewise be made
    without the memory lock held.
    """

    def __init__(self, conf=None):
        from spark_rapids_tpu import conf as C
        self._cv = threading.Condition()
        self._conf = conf
        if conf is not None:
            self.max_concurrent = int(conf.get(C.SCHED_MAX_CONCURRENT))
            self.max_queued = int(conf.get(C.SCHED_MAX_QUEUED))
            self.shed_queue_depth = int(conf.get(C.SCHED_SHED_QUEUE_DEPTH))
            self.shed_spill_ratio = float(conf.get(C.SCHED_SHED_SPILL_RATIO))
            self.shed_sem_saturation = float(
                conf.get(C.SCHED_SHED_SEM_SATURATION))
            self._default_weight = float(conf.get(C.SCHED_TENANT_WEIGHT))
            self._default_in_flight = int(
                conf.get(C.SCHED_TENANT_MAX_IN_FLIGHT))
            self._default_queued = int(conf.get(C.SCHED_TENANT_MAX_QUEUED))
            self._default_hbm_share = float(
                conf.get(C.SCHED_TENANT_HBM_SHARE))
            self.preempt_enabled = bool(conf.get(C.SCHED_PREEMPT_ENABLED))
            self.preempt_grace_s = float(
                conf.get(C.SCHED_PREEMPT_GRACE_MS)) / 1000.0
            self.preempt_min_run_s = float(
                conf.get(C.SCHED_PREEMPT_MIN_RUN_MS)) / 1000.0
            self._default_slo_ms = int(
                conf.get(C.SCHED_TENANT_SLO_P99_MS))
            self.slo_window = int(conf.get(C.SCHED_SLO_WINDOW))
        else:
            self.max_concurrent = C.SCHED_MAX_CONCURRENT.default
            self.max_queued = C.SCHED_MAX_QUEUED.default
            self.shed_queue_depth = C.SCHED_SHED_QUEUE_DEPTH.default
            self.shed_spill_ratio = C.SCHED_SHED_SPILL_RATIO.default
            self.shed_sem_saturation = C.SCHED_SHED_SEM_SATURATION.default
            self._default_weight = C.SCHED_TENANT_WEIGHT.default
            self._default_in_flight = C.SCHED_TENANT_MAX_IN_FLIGHT.default
            self._default_queued = C.SCHED_TENANT_MAX_QUEUED.default
            self._default_hbm_share = C.SCHED_TENANT_HBM_SHARE.default
            self.preempt_enabled = C.SCHED_PREEMPT_ENABLED.default
            self.preempt_grace_s = C.SCHED_PREEMPT_GRACE_MS.default / 1000.0
            self.preempt_min_run_s = (
                C.SCHED_PREEMPT_MIN_RUN_MS.default / 1000.0)
            self._default_slo_ms = C.SCHED_TENANT_SLO_P99_MS.default
            self.slo_window = C.SCHED_SLO_WINDOW.default
        self.queue_shaping = QUEUE_SHAPING
        self._tenants: Dict[str, TenantState] = {}
        self._rr_order: deque = deque()  # round-robin tie-break rotation
        self._tickets: Dict[int, Ticket] = {}
        self._suspended: List[Ticket] = []  # oldest suspension first
        self.queued_total = 0
        self.running_total = 0

    # -- tenants -----------------------------------------------------------

    def _tenant_override(self, name: str, suffix: str, default):
        if self._conf is None:
            return default
        raw = self._conf.get_raw(f"{_TENANT_PREFIX}{name}.{suffix}")
        if raw is None:
            return default
        try:
            return type(default)(raw)
        except (TypeError, ValueError):
            raise QueryRejected(
                "bad_tenant_conf", tenant=name,
                detail=f"{_TENANT_PREFIX}{name}.{suffix}={raw!r} is not "
                       f"a valid {type(default).__name__}")

    def _tenant_locked(self, name: str) -> TenantState:
        t = self._tenants.get(name)
        if t is None:
            t = TenantState(
                name,
                weight=self._tenant_override(
                    name, "weight", self._default_weight),
                max_in_flight=self._tenant_override(
                    name, "maxInFlight", self._default_in_flight),
                max_queued=self._tenant_override(
                    name, "maxQueued", self._default_queued),
                hbm_share=self._tenant_override(
                    name, "hbmShare", self._default_hbm_share),
                max_concurrent=self.max_concurrent,
                slo_p99_ms=self._tenant_override(
                    name, "sloP99Ms", self._default_slo_ms),
                slo_window=self.slo_window)
            self._tenants[name] = t
            self._rr_order.append(name)
        return t

    # -- admission ---------------------------------------------------------

    def _shed_reason(self) -> Optional[tuple]:
        """(reason, detail) if a service-wide watermark is breached.
        Reads live pressure signals; never creates runtime state."""
        depth = self.queued_total + self.running_total
        if depth >= self.shed_queue_depth:
            return ("shed_queue_depth",
                    f"{depth} queued+running >= shed.queueDepth="
                    f"{self.shed_queue_depth}")
        from spark_rapids_tpu.runtime import memory
        mgr = memory.peek_manager()
        if mgr is not None:
            pressure = mgr.spill_pressure()
            if pressure >= self.shed_spill_ratio:
                return ("shed_spill_pressure",
                        f"host spill tier {pressure:.2f} full >= "
                        f"shed.spillRatio={self.shed_spill_ratio} — "
                        "shedding before the disk tier thrashes")
        sem = peek_semaphore()
        if sem is not None and sem.permits > 0:
            saturation = (sem.holders + sem.waiting) / sem.permits
            if saturation >= self.shed_sem_saturation:
                return ("shed_semaphore_saturation",
                        f"(holders+waiting)/permits={saturation:.2f} >= "
                        "shed.semaphoreSaturation="
                        f"{self.shed_sem_saturation}")
        return None

    def _effective_max_queued_locked(self, t: TenantState) -> int:
        """The tenant's EFFECTIVE queued cap: with queue shaping on,
        its weight share of the global queue budget (so one hot
        tenant's standing queue cannot monopolise admission and bury
        every other tenant's latency behind it), never above its own
        static ``maxQueued``."""
        if not self.queue_shaping:
            return t.max_queued
        total_w = sum(x.weight for x in self._tenants.values())
        share = math.ceil((t.weight / max(total_w, t.weight))
                          * self.max_queued)
        return min(t.max_queued, max(1, share))

    @staticmethod
    def _observed_p99_ms_locked(t: TenantState) -> Optional[float]:
        """Nearest-rank p99 over the tenant's sliding completion
        window (ms); None below the 8-sample confidence floor."""
        if len(t.slo_window) < 8:
            return None
        walls = sorted(w for w, _b in t.slo_window)
        idx = max(0, math.ceil(0.99 * len(walls)) - 1)
        return walls[idx] * 1000.0

    def submit(self, query_id: int, tenant: str = "default",
               priority: int = 0, token=None) -> Ticket:
        """Admit or reject one submission.  Returns a QUEUED ``Ticket``
        (pass it to ``acquire`` from the thread that will run the
        query) or raises ``QueryRejected(reason=...)``.  Never blocks
        beyond the scheduler lock."""
        priority = check_priority(priority, tenant)
        shed = None
        reason = None
        detail = ""
        ticket = None
        with self._cv:
            t = self._tenant_locked(tenant)
            shed = self._shed_reason()
            eff_cap = self._effective_max_queued_locked(t)
            slo_cut = t.slo_breached and t.slo_p99_ms > 0
            if slo_cut:
                # queue-depth shaping while the tenant's p99 breaches
                # its SLO: halve the effective cap so the backlog the
                # breach feeds on drains instead of growing
                eff_cap = max(1, eff_cap // 2)
            if shed is not None:
                reason, detail = shed
                t.shed += 1
                t.rejected += 1
            elif t.cluster_shed:
                reason = "shed_cluster"
                detail = (f"tenant {tenant} shed by cluster arbiter "
                          "directive (over cluster share, nothing left "
                          "to preempt)")
                t.shed += 1
                t.rejected += 1
            elif t.queued >= eff_cap:
                if slo_cut:
                    reason = "shed_slo"
                    detail = (f"tenant p99 SLO breached "
                              f"(target={t.slo_p99_ms}ms) — queue cap "
                              f"shaped to {eff_cap}, {t.queued} queued")
                    t.shed += 1
                else:
                    reason = "tenant_queue_full"
                    detail = (f"{t.queued} queued >= effective cap "
                              f"{eff_cap} (tenant maxQueued="
                              f"{t.max_queued}"
                              + (", weight-shaped" if self.queue_shaping
                                 else "") + ")")
                t.rejected += 1
            elif self.queued_total >= self.max_queued:
                reason = "queue_full"
                detail = (f"{self.queued_total} queued >= "
                          f"maxQueuedQueries={self.max_queued}")
                t.rejected += 1
            else:
                ticket = Ticket(query_id, tenant, int(priority), token)
                t.lanes.setdefault(ticket.priority,
                                   deque()).append(ticket)
                t.queued += 1
                t.submitted += 1
                self.queued_total += 1
                self._tickets[query_id] = ticket
                self._dispatch_locked()
        if reason is not None:
            _TM_REJECTED.inc(reason)
            if reason in SHED_REASONS:
                _TM_SHED.inc(tenant)
                TM.REGISTRY.record_health({
                    "severity": "WARN", "check": "admission_shed",
                    "value": 1, "threshold": 0, "query_id": query_id,
                    "detail": f"tenant={tenant} {detail}"})
            raise QueryRejected(reason, tenant=tenant, detail=detail)
        _TM_SUBMITTED.inc(tenant)
        return ticket

    # -- dispatch ----------------------------------------------------------

    def _dispatch_locked(self) -> None:
        """Grant free run slots: suspended tickets resume FIRST (they
        already won a slot once — preemption borrowed it, it was not
        revoked), then queued tickets are granted fairest-first.
        Tickets flip to RUNNING here (the grant is the state change —
        the acquiring thread merely observes it), so a grant holds even
        if the acquirer is slow to wake."""
        granted = False
        for k in list(self._suspended):
            if self.running_total >= self.max_concurrent:
                break
            if k.remote_hold:
                # a cluster directive parked it — a free LOCAL slot
                # must not resume it (that would undo the cluster
                # share enforcement one heartbeat after it landed)
                continue
            vt = self._tenants[k.tenant]
            if vt.running >= vt.run_cap:
                continue
            self._suspended.remove(k)
            k.state = RUNNING
            k.granted_at = time.monotonic()
            vt.running += 1
            vt.suspended -= 1
            self.running_total += 1
            granted = True
            if k.token is not None:
                # safe under self._cv: resume() only sets the token's
                # resume event — it never notifies foreign CVs
                k.token.resume()
        while (self.running_total < self.max_concurrent
               and self.queued_total > 0):
            ticket = self._next_ticket_locked()
            if ticket is None:
                break
            t = self._tenants[ticket.tenant]
            t.queued -= 1
            t.running += 1
            self.queued_total -= 1
            self.running_total += 1
            ticket.state = RUNNING
            ticket.granted_at = time.monotonic()
            granted = True
        if granted:
            self._cv.notify_all()

    def _next_ticket_locked(self) -> Optional[Ticket]:
        """Deficit weighted round-robin: each full pass over backlogged
        tenants without a grant refills every backlogged tenant's
        deficit by its weight; a grant costs 1.0.  Weight >= 0.01, so
        at most ~100 refill rounds reach a grant — the loop is bounded,
        not heuristic."""
        if not any(t.backlogged() for t in self._tenants.values()):
            return None
        for _round in range(102):
            for _ in range(len(self._rr_order)):
                name = self._rr_order[0]
                self._rr_order.rotate(-1)
                t = self._tenants[name]
                if t.backlogged() and t.deficit >= 1.0:
                    t.deficit -= 1.0
                    return t.pop_ticket()
            for t in self._tenants.values():
                if t.backlogged():
                    t.deficit += t.weight
                else:
                    # an idle tenant must not bank unbounded credit and
                    # later monopolize the device in a burst
                    t.deficit = min(t.deficit, t.weight)
        return None

    # -- preemption arbiter ------------------------------------------------

    def _suspend_locked(self, victim: Ticket, now: float) -> None:
        victim.state = SUSPENDED
        victim.suspended_at = now
        vt = self._tenants[victim.tenant]
        vt.running -= 1
        vt.preempted += 1
        vt.suspended += 1
        self.running_total -= 1
        self._suspended.append(victim)
        _TM_PREEMPTED.inc(victim.tenant)

    def _grant_locked(self, ticket: Ticket, now: float) -> None:
        t = self._tenants[ticket.tenant]
        t.remove_ticket(ticket)
        t.queued -= 1
        t.running += 1
        self.queued_total -= 1
        self.running_total += 1
        ticket.state = RUNNING
        ticket.granted_at = now

    def _maybe_preempt_locked(self, ticket: Ticket,
                              waiting_since: float) -> Optional[Ticket]:
        """The arbiter: when ``ticket`` has starved past
        ``preempt.graceMs`` and no slot can free up on its own, pick a
        victim (largest-runtime query of the most over-share tenant —
        same-tenant victims only on strict priority, cross-tenant only
        when the victim's tenant is more over its fair share than the
        waiter's or the waiter outranks it), suspend it — ticket state
        AND token request in one locked step, so a concurrent dispatch
        can never resume a ticket whose token has not yet heard of the
        suspend — and hand its slot to the waiter atomically.  Returns
        the victim or None."""
        if not self.preempt_enabled:
            return None
        now = time.monotonic()
        if now - waiting_since < self.preempt_grace_s:
            return None
        t = self._tenants[ticket.tenant]
        tenant_capped = t.running >= t.run_cap
        if not tenant_capped and self.running_total < self.max_concurrent:
            return None  # a slot is free — normal dispatch will grant
        waiter_score = t.running / t.weight
        cands = []
        for k in self._tickets.values():
            if k.state != RUNNING or k.token is None:
                continue
            if k.token.cancelled() or k.token.preempt_pending():
                continue
            if (k.granted_at is None
                    or now - k.granted_at < self.preempt_min_run_s):
                continue  # anti-thrash floor: let it make progress
            if k.tenant == ticket.tenant:
                if k.priority >= ticket.priority:
                    continue
            else:
                if tenant_capped:
                    continue  # only evicting our own frees quota room
                kt = self._tenants[k.tenant]
                if (kt.running / kt.weight <= waiter_score
                        and k.priority >= ticket.priority):
                    continue
            cands.append(k)
        if not cands:
            return None

        def _score(k: Ticket):
            kt = self._tenants[k.tenant]
            return (kt.running / kt.weight, now - (k.granted_at or now))

        victim = max(cands, key=_score)
        victim.token.request_suspend(
            f"preempted by query {ticket.query_id} "
            f"(tenant={ticket.tenant}, priority={ticket.priority})")
        self._suspend_locked(victim, now)
        self._grant_locked(ticket, now)
        self._cv.notify_all()
        return victim

    def request_tenant_preemption(self, tenant: str,
                                  exclude_query_id: Optional[int] = None
                                  ) -> bool:
        """HBM-arbiter hook: a tenant breached its byte budget and
        spilling its own residency was not enough — suspend the
        tenant's largest-runtime OTHER running query so its residency
        spills and its reservations unwind.  Call WITHOUT holding the
        memory-manager lock (this takes the scheduler lock).  The
        freed run slot is deliberately NOT re-dispatched here — an
        immediate dispatch would resume the victim straight back into
        it; the next submit/release event hands the slot out."""
        with self._cv:
            if not self.preempt_enabled:
                return False
            now = time.monotonic()
            cands = [
                k for k in self._tickets.values()
                if k.state == RUNNING and k.tenant == tenant
                and k.query_id != exclude_query_id
                and k.token is not None
                and not k.token.cancelled()
                and not k.token.preempt_pending()
                and k.granted_at is not None
                and now - k.granted_at >= self.preempt_min_run_s]
            if not cands:
                return False
            victim = min(cands, key=lambda k: k.granted_at)
            victim.token.request_suspend(
                f"tenant {tenant} HBM budget breach")
            self._suspend_locked(victim, now)
        return True

    # -- cluster tenancy (runtime/tenancy.py drives these) -----------------

    def remote_suspend(self, query_id: int, detail: str = "",
                       ttl_s: Optional[float] = None) -> bool:
        """Suspend one RUNNING query on a cluster arbiter directive.
        Unlike local arbitration this does not need preempt.enabled —
        the operator armed the cluster protocol explicitly.  The token
        suspend is leased (``ttl_s``): if the coordinator stops
        renewing (executor loss, coordinator restart) the token
        force-resumes itself and ``notify_force_resumed`` repairs the
        slot accounting.  Cancel always wins: a cancelled or
        already-pending token refuses the suspend."""
        with self._cv:
            k = self._tickets.get(query_id)
            if k is None or k.state != RUNNING or k.token is None:
                return False
            if k.token.cancelled() or k.token.preempt_pending():
                return False
            if not k.token.request_suspend(detail, ttl_s=ttl_s):
                return False
            k.token._suspend_owner = weakref.ref(self)
            self._suspend_locked(k, time.monotonic())
            k.remote_hold = True
            # hand the freed slot out NOW: unlike the HBM-breach path
            # there may be no later submit/release event on this
            # executor to run dispatch, and the starved waiter this
            # directive exists for is sitting in acquire().  The
            # victim itself cannot bounce back — dispatch skips
            # remote_hold tickets.
            self._dispatch_locked()
            self._cv.notify_all()
        _TM_REMOTE_SUSPENDED.inc(k.tenant)
        return True

    def remote_resume(self, query_id: int) -> bool:
        """Lift a remote hold (cluster 'resume' directive) and let
        normal dispatch resume the ticket when a slot frees."""
        with self._cv:
            k = self._tickets.get(query_id)
            if k is None or not k.remote_hold:
                return False
            k.remote_hold = False
            if k.state == SUSPENDED:
                self._dispatch_locked()
                self._cv.notify_all()
            return True

    def notify_force_resumed(self, query_id: int) -> None:
        """The wedge guard fired: a suspended token's lease expired
        unrenewed and it self-resumed.  Follow it in the ticket
        accounting — the query is running again whether or not a slot
        was free (liveness beats strict capacity; the one-slot
        overshoot drains at the next release)."""
        with self._cv:
            k = self._tickets.get(query_id)
            if k is None or k.state != SUSPENDED:
                return
            k.remote_hold = False
            try:
                self._suspended.remove(k)
            except ValueError:
                pass
            k.state = RUNNING
            k.granted_at = time.monotonic()
            vt = self._tenants[k.tenant]
            vt.running += 1
            vt.suspended -= 1
            self.running_total += 1
            self._cv.notify_all()

    def set_cluster_shed(self, tenant: str, shed: bool) -> None:
        """Apply/lift a cluster 'shed'/'unshed' directive for a
        tenant; shed submissions reject with reason='shed_cluster'."""
        with self._cv:
            self._tenant_locked(tenant).cluster_shed = bool(shed)

    def record_latency(self, tenant: str, wall_s: float,
                       buckets: Optional[dict] = None,
                       query_id: Optional[int] = None
                       ) -> Optional[dict]:
        """Feed one completed query's submit-to-done wall time (and
        its attribution bucket seconds) into the tenant's SLO
        estimator.  Returns a breach record on the un-breached ->
        breached transition (the caller black-box dumps it); None
        otherwise."""
        dominant = ""
        if buckets:
            dominant = max(buckets, key=lambda b: buckets[b])
        breach = None
        with self._cv:
            t = self._tenant_locked(tenant)
            t.slo_window.append((max(0.0, float(wall_s)), dominant))
            if t.slo_p99_ms <= 0:
                return None
            p99 = self._observed_p99_ms_locked(t)
            if p99 is None:
                return None
            if p99 > t.slo_p99_ms:
                if not t.slo_breached:
                    t.slo_breached = True
                    t.slo_breaches += 1
                    doms = [b for _w, b in t.slo_window if b]
                    offending = (max(set(doms), key=doms.count)
                                 if doms else "unattributed")
                    breach = {"tenant": tenant,
                              "observed_p99_ms": round(p99, 3),
                              "slo_p99_ms": t.slo_p99_ms,
                              "dominant_bucket": offending,
                              "window": len(t.slo_window),
                              "query_id": query_id}
            else:
                t.slo_breached = False
        if breach is not None:
            _TM_SLO_BREACH.inc(tenant)
            TM.REGISTRY.record_health({
                "severity": "WARN", "check": "slo_breach",
                "value": breach["observed_p99_ms"],
                "threshold": breach["slo_p99_ms"],
                "query_id": query_id,
                "detail": (f"tenant={tenant} p99 "
                           f"{breach['observed_p99_ms']:.0f}ms > slo "
                           f"{breach['slo_p99_ms']}ms, dominant bucket "
                           f"{breach['dominant_bucket']}")})
        return breach

    def local_tenancy_report(self) -> dict:
        """The per-tenant state an executor piggybacks on its
        rendezvous heartbeat: in-flight/queued depth, starvation age,
        and the largest-runtime running query (the cluster arbiter's
        preferred victim on this executor)."""
        with self._cv:
            now = time.monotonic()
            tenants = {}
            for name, t in self._tenants.items():
                oldest = None
                for lane in t.lanes.values():
                    for k in lane:
                        if oldest is None or k.submitted_at < oldest:
                            oldest = k.submitted_at
                largest_qid = None
                largest_run = 0.0
                for k in self._tickets.values():
                    if (k.tenant != name or k.state != RUNNING
                            or k.token is None or k.token.cancelled()
                            or k.token.preempt_pending()
                            or k.granted_at is None):
                        continue
                    run_s = now - k.granted_at
                    if run_s < self.preempt_min_run_s:
                        continue  # anti-thrash floor holds remotely too
                    if largest_qid is None or run_s > largest_run:
                        largest_qid, largest_run = k.query_id, run_s
                tenants[name] = {
                    "weight": t.weight,
                    "running": t.running,
                    "queued": t.queued,
                    "suspended": t.suspended,
                    "oldest_wait_s": (round(now - oldest, 6)
                                      if oldest is not None else None),
                    "largest_qid": largest_qid,
                    "largest_run_s": round(largest_run, 6),
                }
            return {"slots": self.max_concurrent, "tenants": tenants}

    # -- the worker side ---------------------------------------------------

    def acquire(self, ticket: Ticket) -> float:
        """Block the calling (worker) thread until the ticket is
        granted a run slot; returns seconds spent queued.  The wait is
        cancellable and deadline-aware via the ticket's ``CancelToken``
        — cancel/expiry while still QUEUED raises ``QueryCancelled``
        within ~one poll interval, removes the ticket from its lane,
        and counts ``tpuq_scheduler_cancelled_queued_total``.

        Each poll tick also consults the preemption arbiter: once the
        wait exceeds ``preempt.graceMs`` and no slot can free on its
        own, a running victim is suspended and its slot transferred to
        this ticket in one locked step."""
        tok = ticket.token
        registered = False
        waiting_since = time.monotonic()
        try:
            with self._cv:
                try:
                    while ticket.state == QUEUED:
                        if tok is not None:
                            tok.check()
                            if not registered:
                                tok.add_waiter(self._cv)
                                registered = True
                            timeout = tok.wait_interval()
                        else:
                            timeout = 0.1
                        if self._maybe_preempt_locked(
                                ticket, waiting_since) is not None:
                            continue  # slot transferred — loop exits
                        self._cv.wait(timeout=timeout)
                except BaseException:
                    if ticket.state == QUEUED:
                        self._remove_queued_locked(ticket)
                    raise
        finally:
            if registered:
                tok.remove_waiter(self._cv)
        waited = (ticket.granted_at or time.monotonic()) \
            - ticket.submitted_at
        _TM_QUEUE_WAIT.observe(max(0.0, waited))
        return max(0.0, waited)

    def _remove_queued_locked(self, ticket: Ticket) -> None:
        t = self._tenants.get(ticket.tenant)
        if t is not None and t.remove_ticket(ticket):
            t.queued -= 1
            t.cancelled_queued += 1
            self.queued_total -= 1
            ticket.state = CANCELLED
            self._tickets.pop(ticket.query_id, None)
            _TM_CANCELLED_QUEUED.inc()

    def release(self, ticket: Ticket) -> None:
        """Return the run slot (worker's ``finally``).  Idempotent for
        tickets that never ran (cancelled while queued)."""
        completed = False
        with self._cv:
            if ticket.state == RUNNING:
                ticket.state = DONE
                t = self._tenants[ticket.tenant]
                t.running -= 1
                t.completed += 1
                self.running_total -= 1
                self._tickets.pop(ticket.query_id, None)
                completed = True
                self._dispatch_locked()
                self._cv.notify_all()
            elif ticket.state == SUSPENDED:
                # worker bailed while suspended (cancel/deadline fired
                # in the park) — the suspension already returned the
                # run slot, so only the bookkeeping unwinds here
                ticket.state = DONE
                t = self._tenants[ticket.tenant]
                t.completed += 1
                t.suspended -= 1
                try:
                    self._suspended.remove(ticket)
                except ValueError:
                    pass
                self._tickets.pop(ticket.query_id, None)
                completed = True
                self._cv.notify_all()
            elif ticket.state == QUEUED:
                # worker bailed without acquire() ever raising
                self._remove_queued_locked(ticket)
                self._cv.notify_all()
        if completed:
            _TM_COMPLETED.inc(ticket.tenant)

    # -- introspection -----------------------------------------------------

    def active_queries(self, tenant: Optional[str] = None) -> List[int]:
        """Query ids currently queued or running, optionally filtered
        by tenant, oldest submission first."""
        with self._cv:
            tickets = [k for k in self._tickets.values()
                       if tenant is None or k.tenant == tenant]
        tickets.sort(key=lambda k: k.submitted_at)
        return [k.query_id for k in tickets]

    def ticket_state(self, query_id: int) -> Optional[str]:
        with self._cv:
            ticket = self._tickets.get(query_id)
            return ticket.state if ticket is not None else None

    def stats(self) -> Dict[str, dict]:
        """Per-tenant accounting snapshot — the bench driver records
        this (shed/reject counts per tenant) into every
        TPCH_SF1_CONCURRENCY record."""
        with self._cv:
            return {name: {"weight": t.weight,
                           "run_cap": t.run_cap,
                           "running": t.running,
                           "queued": t.queued,
                           "submitted": t.submitted,
                           "completed": t.completed,
                           "rejected": t.rejected,
                           "shed": t.shed,
                           "cancelled_queued": t.cancelled_queued,
                           "preempted": t.preempted,
                           "suspended": t.suspended,
                           "effective_max_queued":
                               self._effective_max_queued_locked(t),
                           "slo_p99_ms": t.slo_p99_ms,
                           "observed_p99_ms":
                               self._observed_p99_ms_locked(t),
                           "slo_breached": t.slo_breached,
                           "slo_breaches": t.slo_breaches,
                           "cluster_shed": t.cluster_shed}
                    for name, t in self._tenants.items()}


# -- process singleton (mirrors semaphore.py) ------------------------------

_scheduler: Optional[QueryScheduler] = None
_sched_lock = threading.Lock()


def get_scheduler(conf=None) -> QueryScheduler:
    """The process scheduler, created on first use.  A later conf only
    re-tunes the service-wide limits/watermarks in place (existing
    tenants keep the quotas they were created with — tenant state must
    not reset under live queries)."""
    from spark_rapids_tpu import conf as C
    global _scheduler
    with _sched_lock:
        if _scheduler is None:
            _scheduler = QueryScheduler(conf)
        elif conf is not None:
            s = _scheduler
            with s._cv:
                s._conf = conf
                s.max_concurrent = int(conf.get(C.SCHED_MAX_CONCURRENT))
                s.max_queued = int(conf.get(C.SCHED_MAX_QUEUED))
                s.shed_queue_depth = int(
                    conf.get(C.SCHED_SHED_QUEUE_DEPTH))
                s.shed_spill_ratio = float(
                    conf.get(C.SCHED_SHED_SPILL_RATIO))
                s.shed_sem_saturation = float(
                    conf.get(C.SCHED_SHED_SEM_SATURATION))
                s._default_weight = float(conf.get(C.SCHED_TENANT_WEIGHT))
                s._default_in_flight = int(
                    conf.get(C.SCHED_TENANT_MAX_IN_FLIGHT))
                s._default_queued = int(
                    conf.get(C.SCHED_TENANT_MAX_QUEUED))
                s._default_hbm_share = float(
                    conf.get(C.SCHED_TENANT_HBM_SHARE))
                s.preempt_enabled = bool(
                    conf.get(C.SCHED_PREEMPT_ENABLED))
                s.preempt_grace_s = float(
                    conf.get(C.SCHED_PREEMPT_GRACE_MS)) / 1000.0
                s.preempt_min_run_s = float(
                    conf.get(C.SCHED_PREEMPT_MIN_RUN_MS)) / 1000.0
                s._default_slo_ms = int(
                    conf.get(C.SCHED_TENANT_SLO_P99_MS))
                s.slo_window = int(conf.get(C.SCHED_SLO_WINDOW))
                s._dispatch_locked()
                s._cv.notify_all()
        return _scheduler


def peek_scheduler() -> Optional[QueryScheduler]:
    """The process scheduler if one exists — never creates (telemetry
    and session introspection must not instantiate runtime state)."""
    return _scheduler


def reset_scheduler() -> None:
    global _scheduler
    with _sched_lock:
        _scheduler = None


@contextlib.contextmanager
def device_hold(conf=None, waited_out: Optional[list] = None):
    """THE sanctioned ``DeviceSemaphore`` acquisition path.  Every
    device-admission hold in the engine goes through here so admission
    control, saturation accounting, and the scheduler's pressure
    signals all see the same traffic; the ``scheduler-bypass`` lint
    rule fails any other module that calls ``get_semaphore``."""
    sem = get_semaphore(conf)
    with sem.hold(waited_out=waited_out):
        yield sem


TM.REGISTRY.gauge(
    "tpuq_scheduler_queue_depth",
    "queries currently waiting for a run slot, all tenants",
    fn=lambda: _scheduler.queued_total if _scheduler is not None else 0)
TM.REGISTRY.gauge(
    "tpuq_scheduler_running",
    "queries currently holding a run slot, all tenants",
    fn=lambda: _scheduler.running_total if _scheduler is not None else 0)
