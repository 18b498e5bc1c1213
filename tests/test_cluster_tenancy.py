"""Cluster-wide tenancy enforcement: the cross-process half of the
preemptive-tenancy plane (runtime/tenancy.py + the rendezvous
TenancyArbiter) plus its SLO guardrails and failure domains.

Four groups:

* **directive matrix** — idempotency (a duplicate suspend is a lease
  renewal, a duplicate resume a no-op), stale-epoch drops, and the
  cancel-wins race, driven straight through ``TenancyAgent``/
  ``QueryScheduler`` with no network.
* **wedge guard** — a suspend whose requester dies (lease never
  renewed) force-resumes within the TTL: never a token stuck in
  SUSPEND_REQUESTED/SUSPENDED, and the scheduler's slot accounting
  follows the self-resume.
* **queue shaping + SLO estimator** — the per-tenant effective queue
  cap is the tenant's weight share of the global queue budget; a p99
  SLO breach is recorded (never silent), halves the cap, sheds with
  ``shed_slo``, and recovers when the window drains.
* **the cluster soak** — >= 2 thread-hosted executors, each with its
  own scheduler/server/agent, heartbeating a REAL TCP coordinator;
  executor loss and coordinator restart injected mid-soak; all-green
  verdicts (SLO met-or-shed, zero wedged tokens, zero leaks, ledgers
  closed) and directive fan-out inside 2x the heartbeat period.
"""

import threading
import time

import pytest

from spark_rapids_tpu.runtime import cancel as CN
from spark_rapids_tpu.runtime import memory as M
from spark_rapids_tpu.runtime import resilience as R
from spark_rapids_tpu.runtime import scheduler as SCH
from spark_rapids_tpu.runtime import semaphore as SEM
from spark_rapids_tpu.runtime import tenancy as TN
from spark_rapids_tpu.runtime.scheduler import QueryRejected
from spark_rapids_tpu.utils.harness import run_cluster_tenancy_soak

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_service_state():
    R.INJECTOR.reset()
    CN.reset()
    SCH.reset_scheduler()
    SEM.reset_semaphore()
    M.reset_manager()
    TN.reset_agent()
    yield
    R.INJECTOR.reset()
    CN.reset()
    SCH.reset_scheduler()
    SEM.reset_semaphore()
    M.reset_manager()
    TN.reset_agent()


# ---------------------------------------------------------------------------
# plumbing helpers (no network, no session)
# ---------------------------------------------------------------------------

def _mk_sched(**over):
    sched = SCH.QueryScheduler()
    sched.max_concurrent = over.pop("max_concurrent", 1)
    sched.max_queued = over.pop("max_queued", 8)
    sched.shed_queue_depth = over.pop("shed_queue_depth", 1000)
    for k, v in over.items():
        setattr(sched, k, v)
    return sched


def _running(sched, qid, tenant="hog", poll_ms=5.0):
    tok = CN.CancelToken(qid, poll_ms=poll_ms)
    CN.register(tok)
    ticket = sched.submit(qid, tenant=tenant, token=tok)
    sched.acquire(ticket)   # slot is free -> returns immediately
    assert ticket.state == SCH.RUNNING
    return tok, ticket


def _mk_agent(sched):
    """Agent with cluster enforcement armed (the conf default is off —
    these tests exercise the enabled protocol path)."""
    agent = TN.TenancyAgent(sched)
    agent.enabled = True
    return agent


def _directive(did, epoch, kind, qid=None, tenant="hog",
               ttl_ms=5000.0):
    return {"id": did, "epoch": epoch, "kind": kind, "tenant": tenant,
            "query_id": qid, "ttl_ms": ttl_ms, "detail": "test",
            "issued_wall": time.time()}


# ---------------------------------------------------------------------------
# directive matrix: idempotent / stale-epoch / cancel-wins
# ---------------------------------------------------------------------------

def test_directive_suspend_idempotent_and_resume():
    sched = _mk_sched()
    agent = _mk_agent(sched)
    agent.on_heartbeat({"ok": True, "tenancy_epoch": 7,
                        "directives": []})
    tok, ticket = _running(sched, 41)
    d = _directive("7-1", 7, "suspend", qid=41)
    assert agent.apply_directive(d)
    assert sched.ticket_state(41) == SCH.SUSPENDED
    assert tok.preempt_pending()
    assert agent.applied["suspend"] == 1
    # the SAME directive again is a lease renewal, not a second apply
    assert agent.apply_directive(dict(d))
    assert agent.applied["suspend"] == 1
    assert sched.ticket_state(41) == SCH.SUSPENDED
    # resume lifts the hold and local dispatch re-grants the slot
    r = _directive("7-2", 7, "resume", qid=41)
    assert agent.apply_directive(r)
    assert sched.ticket_state(41) == SCH.RUNNING
    assert not tok.preempt_pending()
    # duplicate resume: no-op
    assert not agent.apply_directive(dict(r))
    sched.release(ticket)


def test_directive_stale_epoch_dropped():
    sched = _mk_sched()
    agent = _mk_agent(sched)
    agent.on_heartbeat({"ok": True, "tenancy_epoch": 7,
                        "directives": []})
    tok, ticket = _running(sched, 42)
    stale = _directive("6-9", 6, "suspend", qid=42)
    assert not agent.apply_directive(stale)
    assert sched.ticket_state(42) == SCH.RUNNING
    assert not tok.preempt_pending()
    assert agent.stale == 1
    sched.release(ticket)


def test_directive_cancel_wins_race():
    sched = _mk_sched()
    agent = _mk_agent(sched)
    agent.on_heartbeat({"ok": True, "tenancy_epoch": 3,
                        "directives": []})
    tok, ticket = _running(sched, 43)
    tok.cancel("user", "raced the directive")
    d = _directive("3-1", 3, "suspend", qid=43)
    assert not agent.apply_directive(d)
    assert not tok.preempt_pending()
    assert agent.applied["suspend"] == 0
    assert agent.stale == 1   # counted as targeting a dead query
    sched.release(ticket)


def test_directive_shed_and_unshed_shape_admission():
    sched = _mk_sched()
    agent = _mk_agent(sched)
    agent.on_heartbeat({"ok": True, "tenancy_epoch": 2,
                        "directives": []})
    assert agent.apply_directive(_directive("2-1", 2, "shed",
                                            tenant="hog"))
    with pytest.raises(QueryRejected) as ei:
        sched.submit(44, tenant="hog")
    assert ei.value.reason == "shed_cluster"
    assert agent.apply_directive(_directive("2-2", 2, "unshed",
                                            tenant="hog"))
    ticket = sched.submit(45, tenant="hog")
    sched.release(ticket)


def test_epoch_change_resyncs_applied_memory():
    sched = _mk_sched()
    agent = _mk_agent(sched)
    agent.on_heartbeat({"ok": True, "tenancy_epoch": 1,
                        "directives": []})
    tok, ticket = _running(sched, 46)
    d = _directive("1-1", 1, "suspend", qid=46)
    assert agent.apply_directive(d)
    # coordinator restart: new generation -> resync clears the
    # idempotency memory; the restarted arbiter's directives apply
    # fresh while old-generation ones drop
    agent.on_heartbeat({"ok": True, "tenancy_epoch": 2,
                        "directives": []})
    assert agent.resyncs == 1
    assert not agent.apply_directive(_directive("1-2", 1, "resume",
                                                qid=46))
    assert agent.apply_directive(_directive("2-1", 2, "resume",
                                            qid=46))
    assert sched.ticket_state(46) == SCH.RUNNING
    sched.release(ticket)


# ---------------------------------------------------------------------------
# wedge guard: a dead requester never wedges the token
# ---------------------------------------------------------------------------

def test_suspended_token_force_resumes_on_lease_expiry():
    """Requester dies mid-SUSPENDED: renewals stop, the parked query
    self-resumes within the TTL (2x graceMs by default) and never
    wedges."""
    tok = CN.CancelToken(51, poll_ms=5.0)
    CN.register(tok)
    ttl = 0.08
    assert tok.request_suspend("dying requester", ttl_s=ttl)
    t0 = time.monotonic()
    worker = threading.Thread(target=tok.preempt_point, daemon=True)
    worker.start()
    worker.join(timeout=5.0)
    parked = time.monotonic() - t0
    assert not worker.is_alive(), "query wedged in the suspend park"
    assert tok.preempt_state == CN.PREEMPT_RESUMED
    assert parked < 2 * ttl + 0.5, (
        f"force-resume took {parked:.3f}s for a {ttl}s lease")
    assert CN._TM_PREEMPT_FORCE_RESUMED.value >= 1


def test_suspend_requested_expiry_never_parks():
    """The lease can die before the query ever reaches a preempt
    point — SUSPEND_REQUESTED with an expired TTL must resume on
    arrival, not park."""
    tok = CN.CancelToken(52, poll_ms=5.0)
    CN.register(tok)
    assert tok.request_suspend("gone already", ttl_s=0.01)
    time.sleep(0.05)
    t0 = time.monotonic()
    tok.preempt_point()   # must return immediately
    assert time.monotonic() - t0 < 1.0
    assert tok.preempt_state == CN.PREEMPT_RESUMED


def test_remote_suspend_lease_expiry_repairs_scheduler_accounting():
    sched = _mk_sched()
    tok, ticket = _running(sched, 53)
    assert sched.remote_suspend(53, "cluster directive", ttl_s=0.06)
    assert sched.ticket_state(53) == SCH.SUSPENDED
    assert sched.running_total == 0
    worker = threading.Thread(target=tok.preempt_point, daemon=True)
    worker.start()
    worker.join(timeout=5.0)
    assert not worker.is_alive()
    assert tok.preempt_state == CN.PREEMPT_RESUMED
    # notify_force_resumed followed the self-resume: ticket RUNNING
    # again, slot accounting restored
    assert sched.ticket_state(53) == SCH.RUNNING
    assert sched.running_total == 1
    sched.release(ticket)
    assert sched.running_total == 0


def test_remote_hold_not_resumed_by_local_dispatch():
    """A cluster-suspended ticket must NOT be resumed just because a
    local slot freed — only remote_resume (or lease expiry) lifts the
    hold."""
    sched = _mk_sched()
    tok, ticket = _running(sched, 54)
    assert sched.remote_suspend(54, ttl_s=60.0)
    # the freed slot goes to a queued ticket, not back to the hold
    t2 = sched.submit(55, tenant="latency")
    sched.acquire(t2)
    assert t2.state == SCH.RUNNING
    sched.release(t2)
    # slot free again — the held ticket still must not resume
    assert sched.ticket_state(54) == SCH.SUSPENDED
    assert sched.remote_resume(54)
    assert sched.ticket_state(54) == SCH.RUNNING
    sched.release(ticket)


# ---------------------------------------------------------------------------
# satellite: weight-shaped per-tenant queue caps (hot vs cold)
# ---------------------------------------------------------------------------

def test_queue_shaping_two_tenant_hot_cold():
    """A hot tenant's standing queue is capped at its weight share of
    the global queue budget, so the cold tenant still gets admission
    room behind it."""
    sched = _mk_sched(max_concurrent=1, max_queued=8,
                      queue_shaping=True)
    hog_run = sched.submit(60, tenant="hog")      # takes the slot
    sched.submit(61, tenant="latency")            # materialize + queue
    # equal weights, 8 global slots -> effective cap 4 each
    assert sched.stats()["hog"]["effective_max_queued"] == 4
    admitted = 0
    with pytest.raises(QueryRejected) as ei:
        for i in range(10):
            sched.submit(62 + i, tenant="hog")
            admitted += 1
    assert ei.value.reason == "tenant_queue_full"
    assert "weight-shaped" in ei.value.detail
    assert admitted == 4, (
        f"hot tenant queued {admitted}, expected its 4-slot share")
    # the cold tenant still has queue room the hog could not consume
    for i in range(3):
        sched.submit(80 + i, tenant="latency")
    assert sched.stats()["latency"]["queued"] == 4
    # shaping off -> the static per-tenant cap is back in force
    sched.queue_shaping = False
    assert (sched.stats()["hog"]["effective_max_queued"]
            == sched._tenant_locked("hog").max_queued)
    sched.release(hog_run)


# ---------------------------------------------------------------------------
# satellite: SLO estimator — breach recorded, cap halved, recovery
# ---------------------------------------------------------------------------

def test_slo_breach_recorded_sheds_and_recovers():
    sched = _mk_sched(max_concurrent=1, max_queued=4,
                      queue_shaping=True)
    sched._default_slo_ms = 50
    sched.slo_window = 16
    for _ in range(9):
        assert sched.record_latency("t", 0.010) is None
    breach = None
    for i in range(12):
        b = sched.record_latency("t", 0.200,
                                 buckets={"execute": 0.15,
                                          "transfer": 0.01},
                                 query_id=100 + i)
        breach = breach or b
    assert breach is not None, "p99 4x over target never breached"
    assert breach["tenant"] == "t"
    assert breach["observed_p99_ms"] > 50
    assert breach["dominant_bucket"] == "execute"
    st = sched.stats()["t"]
    assert st["slo_breached"] and st["slo_breaches"] == 1
    # while breached the effective queue cap is halved: occupy the
    # slot, then overflow the shaped cap -> shed_slo (not queue_full)
    run = sched.submit(200, tenant="t")
    eff = sched.stats()["t"]["effective_max_queued"]
    half = max(1, eff // 2)
    with pytest.raises(QueryRejected) as ei:
        for i in range(half + 1):
            sched.submit(201 + i, tenant="t")
    assert ei.value.reason == "shed_slo"
    assert sched.stats()["t"]["shed"] >= 1
    # recovery: fast completions refill the window, breach clears
    for _ in range(16):
        sched.record_latency("t", 0.001)
    assert not sched.stats()["t"]["slo_breached"]
    sched.release(run)


# ---------------------------------------------------------------------------
# the tentpole: multi-executor fault-injected cluster soak
# ---------------------------------------------------------------------------

def _assert_cluster_verdicts(rec):
    assert rec["zero_deadlock"], (
        f"cluster soak deadlocked: outcomes={rec['outcomes']} "
        f"sched={rec['sched_stats']}")
    assert rec["wedged_tokens"] == 0, (
        f"{rec['wedged_tokens']} tokens wedged in suspend after the "
        f"soak drained — the lease/TTL guard failed")
    assert rec["zero_leak"], "soak leaked spillables/permits/spill files"
    assert rec["ledgers_closed"], (
        "a query's attribution ledger failed to close across the "
        "executor fleet")
    assert rec["outcomes"]["error"] == 0, f"errors: {rec['errors']}"
    for name, v in rec["slo"].items():
        assert v["met_or_shed"], (
            f"tenant {name} breached its SLO silently: {v} — a breach "
            "must be recorded and shed, never unobserved")
    for name, t in rec["tenants"].items():
        assert t["completed"] + t["errors"] == t["submitted"], (
            f"tenant {name} lost a submission: {t}")


def test_cluster_tenancy_soak_smoke():
    """Tier-1: two executors, a real TCP coordinator, executor loss
    AND coordinator restart injected mid-soak, plus a chaos fault in
    the directive-apply path — and still all-green verdicts with
    cross-executor suspends inside the fan-out bound."""
    rec = run_cluster_tenancy_soak(
        duration_s=2.5, executors=2, in_flight=8, seed=5,
        timeout_s=90.0, heartbeat_s=0.05)
    _assert_cluster_verdicts(rec)
    assert rec["faults"]["executor_lost"] is not None
    assert rec["faults"]["coordinator_restarted"]
    assert rec["cluster"]["applied"]["suspend"] >= 1, (
        f"no cluster suspend directive ever applied: {rec['cluster']}")
    # breach -> remote suspend must land within 2x the heartbeat
    # period (directives ride the heartbeat response)
    assert rec["cluster"]["max_fanout_s"] < 2 * rec["heartbeat_s"], (
        f"directive fan-out {rec['cluster']['max_fanout_s']:.3f}s "
        f">= 2x heartbeat ({rec['heartbeat_s']}s)")
    # the coordinator outage tripped degraded local-only mode and the
    # restart re-synced the surviving agents
    assert rec["cluster"]["degraded_entries"] >= 1
    assert rec["cluster"]["resyncs"] >= 1
    total = sum(t["completed"] for t in rec["tenants"].values())
    assert total >= 10, f"cluster soak barely ran: {total} completions"


@pytest.mark.slow
def test_cluster_tenancy_soak_sustained():
    """The long-soak shape: more executors, deeper in-flight, minutes
    of wall."""
    rec = run_cluster_tenancy_soak(
        duration_s=30.0, executors=3, in_flight=18, seed=17,
        timeout_s=300.0, heartbeat_s=0.05)
    _assert_cluster_verdicts(rec)
    assert rec["cluster"]["applied"]["suspend"] >= 3
    assert rec["cluster"]["applied"]["resume"] >= 1
    total = sum(t["completed"] for t in rec["tenants"].values())
    assert total >= 100, f"sustained soak throughput too low: {total}"
