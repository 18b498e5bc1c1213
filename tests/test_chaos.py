"""Chaos harness: fault-injection schedules across every failure domain.

[REF: spark-rapids-jni faultinj + the reference's retry/OOM injection
 integration tests; SURVEY §5.3] — the engine-wide invariant under test
(see utils/harness.py :: assert_chaos_invariant):

* transient faults → results bit-identical to a clean run;
* terminal faults in a degradable domain → recorded host-degraded
  result matching the clean run;
* terminal faults elsewhere → clean domain-tagged failure;
* a bare ``InjectedDeviceError`` NEVER escapes the engine.

Deterministic per-domain smokes run in tier 1; the seed-randomized
soak is marked ``slow``.
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.runtime.resilience import INJECTOR
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.column import col
from spark_rapids_tpu.utils.harness import (
    assert_chaos_invariant, random_chaos_schedule, run_chaos,
    run_rendezvous_chaos)

pytestmark = pytest.mark.chaos

_HOST_SHUFFLE = {"spark.rapids.shuffle.mode": "MULTITHREADED"}
_ICI = {"spark.rapids.shuffle.mode": "ICI"}


@pytest.fixture(autouse=True)
def _disarm():
    INJECTOR.reset()
    yield
    INJECTOR.reset()


def table(n=800, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 17, n).astype(np.int32)),
        "v": pa.array(rng.normal(size=n)),
    })


_T = table()


def q_agg(s):
    """TPC-H-style mini query: filter → hash aggregate."""
    return (s.createDataFrame(_T).filter(col("v") > -3.0)
            .groupBy("k").agg(F.sum("v").alias("sv"),
                              F.count("*").alias("c")))


def q_minmax(s):
    """Distinct kernel shapes from q_agg — the ``compile`` smoke needs
    a guaranteed cache MISS even after earlier tests in this module
    populated the kernel cache."""
    return (s.createDataFrame(_T).filter(col("v") < 3.0)
            .groupBy("k").agg(F.min("v").alias("mn"),
                              F.max("v").alias("mx")))


def q_shuffle(s):
    """Repartition through the host shuffle files, then aggregate."""
    return (s.createDataFrame(_T).repartition(6, "k")
            .groupBy("k").agg(F.sum("v").alias("sv")))


# ---------------------------------------------------------------------------
# deterministic smokes: transient fault in each domain → bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inject,builder,conf", [
    ({"execute": (2, 1)}, q_agg, None),
    ({"transfer": (1, 1)}, q_agg, None),
    ({"compile": (1, 1)}, q_minmax, None),
    ({"alloc": (2, 1)}, q_agg, None),
    ({"shuffle_ser": (1, 1)}, q_shuffle, _HOST_SHUFFLE),
    ({"shuffle_exchange": (1, 1)}, q_shuffle, _HOST_SHUFFLE),
    ({"collective": (1, 1)}, q_agg, _ICI),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_transient_fault_recovers_bit_identical(inject, builder, conf):
    rec = assert_chaos_invariant(builder, inject, conf=conf)
    assert rec["status"] == "ok"
    res = (rec["entry"] or {}).get("resilience") or {}
    assert not res.get("degraded_ops"), (
        "transient schedule must recover on-device, not degrade")


# ---------------------------------------------------------------------------
# terminal faults: degradable domains degrade + record; others fail clean
# ---------------------------------------------------------------------------

def test_terminal_execute_degrades_and_records():
    rec = assert_chaos_invariant(q_agg, {"execute": (2, 0)})
    assert rec["status"] == "ok"
    res = rec["entry"]["resilience"]
    assert res["breaker_trips"] >= 1
    assert any(d["domain"] == "execute" for d in res["degraded_ops"])
    health = rec["entry"].get("health") or []
    assert any(h["check"] == "host_degraded" for h in health)


def test_terminal_collective_degrades_to_host_shuffle():
    rec = assert_chaos_invariant(q_agg, {"collective": (1, 0)},
                                 conf=_ICI)
    assert rec["status"] == "ok"
    res = rec["entry"]["resilience"]
    assert any(d["domain"] == "collective" for d in res["degraded_ops"])


def test_terminal_execute_without_degrade_fails_clean():
    rec = run_chaos(
        q_agg, {"execute": (2, 0)},
        conf={"spark.rapids.tpu.retry.hostDegrade.enabled": False})
    assert rec["status"] == "failed"
    assert rec["domain"] == "execute"


def test_terminal_shuffle_exchange_fails_domain_tagged():
    rec = run_chaos(q_shuffle, {"shuffle_exchange": (1, 0)},
                    conf=_HOST_SHUFFLE)
    assert rec["status"] == "failed"
    assert rec["domain"] == "shuffle_exchange"


# ---------------------------------------------------------------------------
# accounting: retry counters match the injected fire schedule
# ---------------------------------------------------------------------------

def test_retry_counters_match_injected_fires():
    # execute armed at call 1 with a transient budget of 3: exactly 3
    # fires, each ridden out by one retry, then the domain disarms
    rec = run_chaos(q_agg, {"execute": (1, 3)})
    assert rec["status"] == "ok"
    deltas = rec["entry"]["telemetry"]
    assert deltas.get('tpuq_retry_total{domain="execute"}') == 3
    assert deltas.get('tpuq_faults_injected_total{domain="execute"}') == 3
    res = rec["entry"]["resilience"]
    assert res["retries"] == {"execute": 3}
    assert res["retries_total"] == 3
    assert res["retry_exhausted"] == 0


def test_retry_budget_caps_retries_per_query():
    # a 2-retry budget exhausts a 5-fire transient schedule early
    rec = run_chaos(
        q_agg, {"execute": (1, 5)},
        conf={"spark.rapids.tpu.retry.budgetPerQuery": 2,
              "spark.rapids.tpu.retry.hostDegrade.enabled": False})
    assert rec["status"] == "failed"
    assert rec["domain"] == "execute"
    res = rec["entry"]["resilience"]
    assert res["retries_total"] == 2
    assert res["retry_exhausted"] >= 1


def test_a_query_that_joins_a_resilience_scope_closes_its_share():
    """A query that runs while another holds the scope (a served
    neighbour, a nested execution) joins it; when both are done the
    depth is 0 again, or no later query of the process would own a
    scope, reset its budget or record its retries (what made the
    tests above depend on the files run before them)."""
    from spark_rapids_tpu.runtime import resilience as R
    from spark_rapids_tpu.utils.harness import tpu_session
    assert R._STATE.depth == 0
    outer = R.begin_query(-1)            # the neighbour in flight
    try:
        q_agg(tpu_session({})).toArrow()  # joins: its begin returns None
        assert R._STATE.depth == 1
    finally:
        R.finish_query(outer)
    assert R._STATE.depth == 0
    rec = run_chaos(q_agg, {"execute": (1, 1)})
    assert rec["entry"]["resilience"]["retries_total"] == 1


# ---------------------------------------------------------------------------
# distributed domains: rendezvous / peer_loss over the thread-level
# rendezvous harness (N client threads + a real coordinator)
# ---------------------------------------------------------------------------

_LEASE_S = 0.4


@pytest.mark.distributed
def test_chaos_peer_loss_survivors_fail_together_fast():
    """peer_loss invariant: the victim goes silent, and EVERY survivor
    raises the same peer-tagged ``TerminalDeviceError`` within ~2× the
    lease — no full-deadline waits, no hangs, no stage leak."""
    out = run_rendezvous_chaos({"peer_loss": (1, 0)}, nprocs=3,
                               lease_s=_LEASE_S, stage_timeout=30.0)
    dead = [r for r in out["records"] if r["died"]]
    survivors = [r for r in out["records"] if not r["died"]]
    assert len(dead) == 1 and len(survivors) == 2
    victim = dead[0]["pid"]
    for r in out["records"]:
        assert r["status"] == "failed"
        assert r["domain"] == "peer_loss"
    for r in survivors:
        assert r["peer"] == victim
        # well under the 30 s stage deadline: lease detection + fan-out
        assert r["elapsed"] < 2 * _LEASE_S + 0.5, (
            f"survivor {r['pid']} took {r['elapsed']:.2f}s")
    assert out["live_stages"] == {}


@pytest.mark.distributed
def test_chaos_transient_rendezvous_recovers_next_epoch():
    """rendezvous invariant: one transient fault → every participant
    re-enters at epoch+1 under the shared policy and the stage completes
    with results identical to a clean run."""
    from spark_rapids_tpu.parallel import rendezvous as RD

    base = RD.counters_snapshot()["epoch_retries"]
    out = run_rendezvous_chaos({"rendezvous": (1, 1)}, nprocs=3,
                               lease_s=_LEASE_S)
    for r in out["records"]:
        assert r["status"] == "ok", r["error"]
        assert r["result"] == out["expected"]
    assert RD.counters_snapshot()["epoch_retries"] > base
    assert out["live_stages"] == {}


# ---------------------------------------------------------------------------
# randomized soak (slow tier): seeds × random schedules, same invariant
# ---------------------------------------------------------------------------

_SOAK_DOMAINS = ["execute", "transfer", "alloc", "compile",
                 "shuffle_ser", "shuffle_exchange"]


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(10))
def test_randomized_chaos_soak(seed):
    sched = random_chaos_schedule(seed, domains=_SOAK_DOMAINS)
    rec = assert_chaos_invariant(q_shuffle, sched, conf=_HOST_SHUFFLE)
    if rec["status"] == "failed":
        # only the non-degradable IO domains may fail terminally
        assert rec["domain"] in ("shuffle_ser", "shuffle_exchange")


@pytest.mark.slow
@pytest.mark.distributed(timeout=120)
@pytest.mark.parametrize("seed", range(8))
def test_randomized_rendezvous_chaos_soak(seed):
    """Seed-randomized soak over the distributed domains: whatever the
    schedule, every participant either completes with the full payload
    set or fails with a clean domain-tagged error — never a hang, never
    a bare ``InjectedDeviceError``, never a leaked stage."""
    sched = random_chaos_schedule(seed,
                                  domains=["rendezvous", "peer_loss"])
    out = run_rendezvous_chaos(sched, nprocs=3, lease_s=_LEASE_S)
    for r in out["records"]:
        if r["status"] == "ok":
            assert r["result"] == out["expected"]
        else:
            assert r["domain"] in ("rendezvous", "peer_loss")
    # one participant dying must fail the others; all-ok otherwise
    st = {r["status"] for r in out["records"]}
    if any(r["died"] for r in out["records"]):
        assert st == {"failed"}
    assert out["live_stages"] == {}
