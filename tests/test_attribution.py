"""Attribution ledger + flight recorder + black box + `profile why`.

The acceptance bounds of the attribution plane: exclusive buckets that
close against end-to-end wall within the tolerance with the gap
reported explicitly, a black box for every query that dies, and the
CLI verdict over every artifact kind.
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.runtime import attribution
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils.harness import tpu_session


def _t(n=2000, seed=1):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 30, n)),
        "v": pa.array(rng.uniform(-10, 10, n)),
    })


class _FakeSpan:
    def __init__(self, op, stage, t0, t1):
        self.op, self.stage, self.t0, self.t1 = op, stage, t0, t1


# ---------------------------------------------------------------------------
# ledger fold unit tests
# ---------------------------------------------------------------------------

def test_buckets_are_exclusive_and_sum_to_e2e():
    """Overlapping spans across threads charge each instant once, by
    priority; buckets + unaccounted == e2e exactly."""
    spans = [
        _FakeSpan("PumpTask", "pumpTask", 0.0, 10.0),
        _FakeSpan("TpuProject", "opTime", 1.0, 5.0),
        # a compile overlapping the op on another thread: compile wins
        _FakeSpan("Kernel", "compile", 2.0, 4.0),
        _FakeSpan("DeviceSemaphore", "semaphoreWait", 6.0, 8.0),
    ]
    att = attribution.attribute(spans=spans, e2e_s=12.0, tolerance=0.5)
    b = att["buckets"]
    assert b["compile"] == pytest.approx(2.0)
    assert b["kernel_dispatch"] == pytest.approx(2.0)  # 1-2 + 4-5
    assert b["semaphore_wait"] == pytest.approx(2.0)
    assert b["pump_idle"] == pytest.approx(4.0)  # 0-1, 5-6, 8-10
    assert att["unaccounted_s"] == pytest.approx(2.0)  # 10-12
    total = sum(b.values())
    assert total == pytest.approx(att["e2e_s"])


def test_unaccounted_reported_never_absorbed():
    """A half-instrumented query is NOT closed at 10% tolerance and the
    gap is explicit — in the buckets, the field, and the verdict."""
    spans = [_FakeSpan("TpuSort", "opTime", 0.0, 5.0)]
    att = attribution.attribute(spans=spans, e2e_s=10.0, tolerance=0.10)
    assert not att["closed"]
    assert att["unaccounted_s"] == pytest.approx(5.0)
    assert att["buckets"]["unaccounted"] == pytest.approx(5.0)
    assert "NOT CLOSED" in att["verdict"]
    # ... and at a tolerance covering the gap, the same fold closes
    att2 = attribution.attribute(spans=spans, e2e_s=10.0, tolerance=0.6)
    assert att2["closed"]
    assert att2["unaccounted_s"] == pytest.approx(5.0)  # still reported


def test_root_execute_span_not_charged():
    """The query-root envelope must not absorb uninstrumented time —
    else closure would be vacuously true."""
    spans = [_FakeSpan("Query", "execute", 0.0, 10.0)]
    att = attribution.attribute(spans=spans, e2e_s=10.0, tolerance=0.10)
    assert att["unaccounted_s"] == pytest.approx(10.0)
    assert not att["closed"]


def test_verdict_names_dominant_bucket():
    spans = [
        _FakeSpan("TpuIciShuffleExchangeExec", "collectiveTime",
                  0.0, 7.1),
        _FakeSpan("TpuProject", "opTime", 7.1, 10.0),
    ]
    att = attribution.attribute(spans=spans, e2e_s=10.0)
    assert att["dominant"] == "exchange_collective"
    assert att["verdict"].startswith("exchange-bound:")
    assert "exchange_collective" in att["verdict"]
    assert att["dominant_share"] == pytest.approx(0.71, abs=0.01)


def test_queue_wait_extras_extend_e2e():
    """The server's queue-side scalar joins the ledger as its own
    bucket and extends e2e rather than competing with spans."""
    att = attribution.attribute(spans=(), e2e_s=0.0,
                                extras={"queue_wait": 3.0})
    assert att["buckets"]["queue_wait"] == pytest.approx(3.0)
    assert att["e2e_s"] == pytest.approx(3.0)
    assert att["dominant"] == "queue_wait"
    assert att["verdict"].startswith("queue-bound:")
    assert att["closed"]


def test_cpu_pump_spans_are_host_fallback():
    spans = [_FakeSpan("CpuProjectExec", "pump", 0.0, 4.0),
             _FakeSpan("TpuProject", "opTime", 4.0, 5.0)]
    att = attribution.attribute(spans=spans, e2e_s=5.0)
    assert att["buckets"]["host_fallback"] == pytest.approx(4.0)
    assert att["dominant"] == "host_fallback"


def test_stage_buckets_cover_declared_buckets():
    """Every mapped stage lands in a declared bucket; every declared
    bucket except unaccounted is reachable from some stage or extras."""
    reachable = {b for b in attribution.STAGE_BUCKETS.values()
                 if b is not None}
    assert reachable <= set(attribution.BUCKETS)
    assert set(attribution.BUCKET_PRIORITY) <= set(attribution.BUCKETS)
    assert set(attribution.BUCKET_VERDICTS) == set(attribution.BUCKETS)


# ---------------------------------------------------------------------------
# end-to-end closure on real queries
# ---------------------------------------------------------------------------

def test_attribution_closes_q1_shaped(tmp_path):
    """Filter + groupBy + multi-agg (the q1 shape): the books close
    within the default tolerance and the gap is explicit."""
    s = tpu_session({"spark.rapids.tpu.attribution.blackboxPath":
                     str(tmp_path)})
    df = (s.createDataFrame(_t(4000))
          .filter(F.col("v") > -5)
          .groupBy("k")
          .agg(F.sum("v").alias("sv"), F.avg("v").alias("av"),
               F.count("v").alias("cv")))
    df.toArrow()
    entry = s.query_history()[-1]
    att = entry["attribution"]
    assert att["closed"], att
    assert "unaccounted_s" in att
    assert "unaccounted" in att["buckets"]
    total = sum(att["buckets"].values())
    assert total == pytest.approx(att["e2e_s"], rel=0.01, abs=0.005)
    assert att["verdict"]
    # tracing was off: the ledger must not leak trace artifacts
    assert "op_rollup" not in entry
    assert "wall_s" not in entry
    assert "trace_file" not in entry


def test_attribution_closes_q3_shaped(tmp_path):
    """Join + groupBy + sort (the q3 shape)."""
    s = tpu_session({"spark.rapids.tpu.attribution.blackboxPath":
                     str(tmp_path)})
    left = s.createDataFrame(_t(3000))
    right = s.createDataFrame(pa.table({
        "k": pa.array(list(range(30))),
        "w": pa.array([float(i) * 2 for i in range(30)])}))
    df = (left.join(right, "k", "inner")
          .groupBy("k").agg(F.sum("v").alias("sv")))
    df.toArrow()
    att = s.query_history()[-1]["attribution"]
    assert att["closed"], att
    assert att["e2e_s"] > 0
    assert att["dominant"] in attribution.BUCKETS


def test_trace_enabled_keeps_rollup_and_attribution(tmp_path):
    s = tpu_session({"spark.rapids.sql.trace.enabled": True,
                     "spark.rapids.tpu.attribution.blackboxPath":
                     str(tmp_path)})
    # same shape as the q3-shaped test above: warm kernel cache
    df = s.createDataFrame(_t(3000)).groupBy("k").agg(
        F.sum("v").alias("sv"))
    df.toArrow()
    entry = s.query_history()[-1]
    assert "op_rollup" in entry
    assert "attribution" in entry
    assert entry["attribution"]["closed"]


def test_attribution_disabled_no_entry(tmp_path):
    s = tpu_session({"spark.rapids.tpu.attribution.enabled": False})
    df = s.createDataFrame(_t(500)).select("k")
    df.toArrow()
    entry = s.query_history()[-1]
    assert "attribution" not in entry
    assert "op_rollup" not in entry  # tracing off too


def test_attribution_in_stats_profile(tmp_path):
    s = tpu_session({"spark.rapids.tpu.stats.enabled": True,
                     "spark.rapids.tpu.attribution.blackboxPath":
                     str(tmp_path)})
    df = s.createDataFrame(_t(3000)).groupBy("k").agg(
        F.sum("v").alias("sv"))
    df.toArrow()
    prof = s.last_query_profile()
    assert prof is not None
    assert "attribution" in prof
    assert prof["attribution"]["verdict"]


# ---------------------------------------------------------------------------
# flight recorder + black box
# ---------------------------------------------------------------------------

def test_blackbox_on_deadline(tmp_path):
    """A deadline-killed query leaves a black box naming a dominant
    bucket, with the cancel event in the ring."""
    from spark_rapids_tpu.runtime.cancel import QueryCancelled
    bb = str(tmp_path / "bb")
    s = tpu_session({"spark.rapids.tpu.attribution.blackboxPath": bb})
    df = s.createDataFrame(_t(50000)).groupBy("k").agg(
        F.sum("v").alias("sv"), F.avg("v").alias("av"))
    with pytest.raises(QueryCancelled):
        df.toArrow(timeout_ms=5)
    entry = s.query_history()[-1]
    assert entry["status"] == "cancelled"
    path = entry.get("blackbox")
    assert path and os.path.exists(path)
    box = json.load(open(path))
    assert box["record"] == "blackbox"
    assert box["trigger"] == "timeout"
    assert box["verdict"]
    att = box["attribution"]
    assert att["dominant"] in attribution.BUCKETS
    fr = box["flight_recorder"]
    assert any(ev["kind"] == "cancel" for ev in fr["events"])


def test_blackbox_on_error(tmp_path):
    """An erroring query leaves a trigger=error box."""
    bb = str(tmp_path / "bb")
    s = tpu_session({"spark.rapids.tpu.attribution.blackboxPath": bb,
                     "spark.rapids.sql.test.enabled": False})
    bad = F.udf(lambda x: 1 // 0, returnType="int")
    df = s.createDataFrame(_t(200)).select(bad(F.col("k")).alias("z"))
    with pytest.raises(BaseException):
        df.toArrow()
    entry = s.query_history()[-1]
    assert entry["status"] == "error"
    path = entry.get("blackbox")
    assert path and os.path.exists(path)
    box = json.load(open(path))
    assert box["trigger"] == "error"
    assert box.get("error")


def test_ring_is_bounded():
    rec = attribution.FlightRecorder(1, ring_size=16)
    for i in range(200):
        rec.record_span(_FakeSpan("Op", "opTime", float(i), i + 1.0))
        rec.record_event("retry", {"domain": "kernel", "i": i})
    snap = rec.snapshot()
    assert len(snap["recent_spans"]) == 16
    assert len(snap["events"]) == 16
    # newest survive
    assert snap["events"][-1]["i"] == 199


def test_nested_query_rides_owner():
    """Ownership is by thread of execution: a second start on the
    owner's thread rides it; another thread owns a recorder of its own
    meanwhile, and an event lands in the ring of the thread that
    recorded it."""
    rec = attribution.start_query(101, ring_size=32)
    try:
        assert rec is not None
        assert attribution.start_query(102) is None
        attribution.record_event("health", {"check": "x"})
        assert len(rec.snapshot()["events"]) == 1
        theirs = []

        def other():
            assert attribution.current() is None
            mine = attribution.start_query(103, ring_size=32)
            try:
                attribution.record_event("health", {"check": "y"})
                theirs.append(mine)
            finally:
                attribution.end_query(mine)
            assert attribution.current() is None
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive() and theirs[0] is not rec
        assert [e["check"] for e in theirs[0].snapshot()["events"]] == ["y"]
        assert [e["check"] for e in rec.snapshot()["events"]] == ["x"]
        assert attribution.current() is rec
    finally:
        attribution.end_query(rec)
    assert attribution.current() is None


def test_dump_atomic_bounded_concurrent(tmp_path):
    """Concurrent dumps into one dir: every surviving file is whole
    JSON, the count is bounded with oldest-first eviction, and no tmp
    litter remains."""
    d = str(tmp_path / "boxes")
    att = attribution.attribute(spans=(), e2e_s=1.0)

    def dump_many(base):
        for i in range(8):
            attribution.dump_blackbox(d, base + i, "cancel",
                                      attribution=att, max_dumps=5)

    threads = [threading.Thread(target=dump_many, args=(b,))
               for b in (100, 200, 300)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    files = glob.glob(os.path.join(d, "*.blackbox.json"))
    assert 0 < len(files) <= 5
    for f in files:
        box = json.load(open(f))  # never torn
        assert box["record"] == "blackbox"
    assert not glob.glob(os.path.join(d, ".*tmp*"))  # no tmp litter


def test_dump_eviction_oldest_first(tmp_path):
    d = str(tmp_path / "boxes")
    for i in range(7):
        attribution.dump_blackbox(d, i, "error", max_dumps=3)
        os.utime(attribution.blackbox_path(d, i), (i + 1, i + 1))
    attribution.dump_blackbox(d, 99, "error", max_dumps=3)
    names = sorted(os.path.basename(p) for p in
                   glob.glob(os.path.join(d, "*.blackbox.json")))
    assert "query-000099.blackbox.json" in names
    assert len(names) == 3
    assert "query-000000.blackbox.json" not in names


# ---------------------------------------------------------------------------
# overhead guard
# ---------------------------------------------------------------------------

def test_attribution_overhead_within_bound():
    """Attribution + recorder (default on) adds <= 5% wall vs disabled
    on a q1-shaped query (min-of-N, interleaved so drift hits both)."""
    s_on = tpu_session({})
    s_off = tpu_session({"spark.rapids.tpu.attribution.enabled": False})
    t = _t(4000)

    def run(sess):
        # exact q1-closure shape: the kernel cache is warm from
        # test_attribution_closes_q1_shaped, so reps time dispatch
        df = (sess.createDataFrame(t).filter(F.col("v") > -5)
              .groupBy("k").agg(F.sum("v").alias("sv"),
                                F.avg("v").alias("av"),
                                F.count("v").alias("cv")))
        t0 = time.perf_counter()
        df.toArrow()
        return time.perf_counter() - t0

    run(s_on)   # warm compile caches for both paths
    run(s_off)
    on = min(run(s_on) for _ in range(3))
    off = min(run(s_off) for _ in range(3))
    # 5% relative plus an absolute floor: at millisecond scale the
    # bound must not fail on scheduler jitter alone
    assert on <= off * 1.05 + 0.025, (on, off)


# ---------------------------------------------------------------------------
# profile why CLI
# ---------------------------------------------------------------------------

def _att_fixture(dom="exchange_collective", e2e=23.3):
    buckets = {b: 0.0 for b in attribution.BUCKETS}
    buckets[dom] = 16.5
    buckets["kernel_dispatch"] = 6.0
    buckets["unaccounted"] = 0.8
    return {"buckets": buckets, "e2e_s": e2e, "unaccounted_s": 0.8,
            "closed": True, "tolerance": 0.1, "dominant": dom,
            "dominant_share": 0.71,
            "verdict": "exchange-bound: 71% of 23.3 s in "
                       "exchange_collective"}


def test_profile_why_event_log(tmp_path, capsys):
    from spark_rapids_tpu.utils import profile as P
    log = tmp_path / "qlog.jsonl"
    entries = [
        {"query_id": 1, "status": "ok", "plan": "*TpuProject",
         "attribution": _att_fixture()},
        {"query_id": 2, "status": "ok", "plan": "*TpuSort"},
    ]
    log.write_text("".join(json.dumps(e) + "\n" for e in entries))
    rc = P.main(["why", str(log)])
    out = capsys.readouterr().out
    assert rc == P.EXIT_OK
    assert "exchange-bound: 71% of 23.3 s in exchange_collective" in out
    assert "exchange_collective" in out
    assert "16.5" in out


def test_profile_why_blackbox_of_timed_out_query(tmp_path, capsys):
    """The timed-out-query fixture: a black box renders its verdict,
    trigger, and the last ring events."""
    from spark_rapids_tpu.utils import profile as P
    rec = attribution.FlightRecorder(7, ring_size=8)
    rec.record_span(_FakeSpan("TpuIciShuffleExchangeExec",
                              "collectiveTime", 0.0, 16.5))
    rec.record_event("cancel", {"reason": "deadline"})
    path = attribution.dump_blackbox(
        str(tmp_path), 7, "timeout", attribution=_att_fixture(),
        recorder=rec, extra={"status": "cancelled"})
    rc = P.main(["why", path])
    out = capsys.readouterr().out
    assert rc == P.EXIT_OK
    assert "[cancelled]" in out
    assert "trigger=timeout" in out
    assert "cancel" in out
    assert "collectiveTime" in out


def test_profile_knows_three_kinds_not_the_old_scoreboard(tmp_path, capsys):
    """The fourth kind went with the file that wrote it (PR 31): a
    scoreboard-shaped object is unrecognized input, like any other."""
    from spark_rapids_tpu.utils import profile as P
    old = {"metric": "tpch_sf1",
           "tpch_sf1_attribution": {"q3": _att_fixture()},
           "tpch_sf1_op_rollup": {"q3": {"TpuScanExec": {"self_s": 1.0}}}}
    with pytest.raises(ValueError, match="unrecognized input"):
        P.detect_kind([old])
    p = tmp_path / "scoreboard.json"
    p.write_text(json.dumps(old))
    for cmd in ("why", "top", "storms"):
        with pytest.raises(SystemExit) as e:
            P.main([cmd, str(p)])
        assert e.value.code == P.EXIT_BAD_INPUT
    assert "unrecognized input" in capsys.readouterr().err


def test_profile_why_no_attribution_is_bad_input(tmp_path, capsys):
    from spark_rapids_tpu.utils import profile as P
    log = tmp_path / "qlog.jsonl"
    log.write_text(json.dumps({"query_id": 1, "plan": "x"}) + "\n")
    rc = P.main(["why", str(log)])
    assert rc == P.EXIT_BAD_INPUT


def test_real_blackbox_renders_via_cli(tmp_path, capsys):
    """End to end: deadline kill -> black box -> `profile why` renders
    a verdict naming a bucket."""
    from spark_rapids_tpu.runtime.cancel import QueryCancelled
    from spark_rapids_tpu.utils import profile as P
    bb = str(tmp_path / "bb")
    s = tpu_session({"spark.rapids.tpu.attribution.blackboxPath": bb})
    # same shape as test_blackbox_on_deadline: warm kernel cache
    df = s.createDataFrame(_t(50000)).groupBy("k").agg(
        F.sum("v").alias("sv"), F.avg("v").alias("av"))
    with pytest.raises(QueryCancelled):
        df.toArrow(timeout_ms=5)
    path = s.query_history()[-1]["blackbox"]
    rc = P.main(["why", path])
    out = capsys.readouterr().out
    assert rc == P.EXIT_OK
    assert "trigger=timeout" in out
    assert any(lbl in out for lbl in attribution.BUCKET_VERDICTS.values())


# ---------------------------------------------------------------------------
# lint rule fixtures
# ---------------------------------------------------------------------------

def _lint_findings(src):
    from spark_rapids_tpu.utils.lint import SourceModule, run_lint
    from spark_rapids_tpu.utils.lint.bucket_accounting import (
        BucketAccountingRule)
    mod = SourceModule("/x/spark_rapids_tpu/exec/fake.py",
                       "spark_rapids_tpu/exec/fake.py", text=src)
    return run_lint(rules=[BucketAccountingRule()], modules=[mod])


def test_lint_flags_unmapped_stage():
    src = ("def pump(self):\n"
           "    with self.timer(\"mysteryTime\"):\n"
           "        pass\n")
    fs = _lint_findings(src)
    assert len(fs) == 1
    assert fs[0].rule == "bucket-accounting"
    assert "mysteryTime" in fs[0].message


def test_lint_clean_on_mapped_stages():
    src = ("def pump(self, tr):\n"
           "    with self.timer(\"opTime\"):\n"
           "        pass\n"
           "    with self.timer():\n"
           "        pass\n"
           "    sp = tr.begin(\"Kernel\", \"compile\")\n")
    assert _lint_findings(src) == []


def test_lint_honors_attribution_exempt():
    src = ("def pump(self):\n"
           "    # attribution-exempt: measured out of band\n"
           "    with self.timer(\"mysteryTime\"):\n"
           "        pass\n")
    assert _lint_findings(src) == []
    # ... but an exemption without a reason is itself a finding
    src2 = ("def pump(self):\n"
            "    # attribution-exempt\n"
            "    with self.timer(\"mysteryTime\"):\n"
            "        pass\n")
    fs = _lint_findings(src2)
    assert any(f.rule == "exemption" for f in fs)


def test_docs_drift_gate_attribution():
    from spark_rapids_tpu.utils import docs_gen
    assert docs_gen.check_attribution_documented() == []
