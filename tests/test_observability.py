"""LORE dump/replay, leak tracker, per-query profiler capture.

[REF: lore/, cudf MemoryCleaner, spark-rapids-jni profiler]
"""

import glob
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils.harness import tpu_session


def _t(n=2000, seed=1):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 30, n)),
        "v": pa.array(rng.uniform(-10, 10, n)),
    })


def test_lore_dump_and_replay_aggregate(tmp_path):
    """A tagged aggregate's inputs dump to parquet; replay re-runs the
    exec offline and reproduces the query's result (r2 verdict #9's
    'seeded failing operator reproduced offline' criterion)."""
    t = _t()
    dump = str(tmp_path / "lore")
    s = tpu_session({"spark.rapids.sql.lore.tag": "TpuHashAggregateExec",
                     "spark.rapids.sql.lore.dumpPath": dump})
    df = s.createDataFrame(t).groupBy("k").agg(F.sum("v").alias("sv"))
    expected = sorted(map(repr, df.toArrow().to_pylist()))
    dirs = sorted(glob.glob(os.path.join(dump, "TpuHashAggregateExec-*")))
    assert dirs, "no LORE dump written"
    d = dirs[0]
    assert os.path.exists(os.path.join(d, "meta.json"))
    assert glob.glob(os.path.join(d, "child0-part*.parquet"))

    from spark_rapids_tpu.utils import lore
    replayed = lore.replay(d)
    got = sorted(map(repr, replayed.to_pylist()))
    assert got == expected


def test_lore_dump_join_inputs(tmp_path):
    t = _t(500)
    r = pa.table({"k": pa.array([1, 2, 3]), "w": pa.array([10, 20, 30])})
    dump = str(tmp_path / "lore2")
    s = tpu_session({"spark.rapids.sql.lore.tag": "TpuSortMergeJoinExec",
                     "spark.rapids.sql.lore.dumpPath": dump,
                     "spark.sql.autoBroadcastJoinThreshold": 0})
    df = s.createDataFrame(t).join(s.createDataFrame(r), "k", "inner")
    expected = sorted(map(repr, df.toArrow().to_pylist()))
    d = sorted(glob.glob(os.path.join(dump, "TpuSortMergeJoinExec-*")))[0]
    # both join children dumped
    assert glob.glob(os.path.join(d, "child0-part*.parquet"))
    assert glob.glob(os.path.join(d, "child1-part*.parquet"))
    from spark_rapids_tpu.utils import lore
    got = sorted(map(repr, lore.replay(d).to_pylist()))
    assert got == expected


def test_leak_tracker_reports_unclosed(tmp_path):
    from spark_rapids_tpu.runtime.memory import (
        DeviceMemoryManager, SpillableBatch)
    from spark_rapids_tpu.columnar.column import host_to_device
    mgr = DeviceMemoryManager(budget=1 << 30, debug=True)
    b = host_to_device(_t(100))
    sp = SpillableBatch(b, mgr)
    leaks = mgr.leaked()
    assert len(leaks) == 1
    assert "test_observability" in leaks[0][1]  # creation stack recorded
    assert mgr.report_leaks() == 1
    sp.close()
    assert mgr.leaked() == []


def test_leak_tracker_excludes_scan_cache():
    from spark_rapids_tpu.runtime import memory as M
    M.reset_manager()
    s = tpu_session({"spark.rapids.memory.gpu.debug": "STDOUT"})
    df = s.createDataFrame(_t(1000)).groupBy("k").count()
    df.toArrow()
    mgr = M.get_manager()
    # scan-cache registrations are pinned, not leaks
    assert mgr.leaked() == []
    M.reset_manager()


def test_profiler_capture_writes_trace(tmp_path):
    prof = str(tmp_path / "prof")
    s = tpu_session({"spark.rapids.profile.enabled": True,
                     "spark.rapids.profile.path": prof})
    df = s.createDataFrame(_t(500)).filter(F.col("v") > 0).groupBy(
        "k").count()
    out = df.toArrow()
    assert out.num_rows > 0
    captured = glob.glob(os.path.join(prof, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in captured), captured


def test_fallback_summary_metric():
    """The fallback budget as a metric (ExplainPlanImpl condensed):
    device/fallback op counts + reasons [VERDICT r3 #10]."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.utils.harness import tpu_session
    t = pa.table({"k": pa.array(np.arange(50) % 5),
                  "v": pa.array(np.arange(50.0))})
    s = tpu_session({})
    df = s.createDataFrame(t).groupBy("k").agg(F.sum("v").alias("sv"))
    df.toArrow()
    fs = df.fallback_summary()
    assert fs["fallback_ops"] == 0
    assert fs["device_fraction"] == 1.0
    assert fs["device_ops"] >= 2
    # a lazily-planned frame gets a summary without execution
    df2 = s.createDataFrame(t).select("k")
    fs2 = df2.fallback_summary()
    assert fs2["device_ops"] >= 1


# -- span tracing + query event log -----------------------------------------


def test_metric_level_filtering_is_nested():
    """ESSENTIAL ⊂ MODERATE ⊂ DEBUG, per node."""
    s = tpu_session({})
    df = s.createDataFrame(_t(500)).groupBy("k").agg(
        F.sum("v").alias("sv"))
    df.toArrow()
    by_level = {lvl: dict(df.metrics(level=lvl))
                for lvl in ("ESSENTIAL", "MODERATE", "DEBUG")}
    for lo, hi in (("ESSENTIAL", "MODERATE"), ("MODERATE", "DEBUG")):
        for op, vals in by_level[lo].items():
            assert set(vals) <= set(by_level[hi][op]), (lo, hi, op)
    ess = by_level["ESSENTIAL"]
    assert all(set(v) <= {"numOutputRows", "numOutputBatches"}
               for v in ess.values())
    # something more exists at MODERATE (opTime at least)
    assert any(set(by_level["MODERATE"][op]) - set(ess[op])
               for op in ess)


def test_span_nesting_across_pool_threads():
    """Per-thread span stacks: concurrent threads nest independently;
    a child's duration subtracts from its parent's self-time on the
    SAME thread only."""
    import threading
    import time as _time
    from spark_rapids_tpu.runtime import trace
    tr = trace.Tracer(query_id=99)

    def work():
        with tr.span("Outer", "pump"):
            with tr.span("Inner", "opTime"):
                _time.sleep(0.02)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.finish()
    outers = [sp for sp in tr.events if sp.op == "Outer"]
    inners = [sp for sp in tr.events if sp.op == "Inner"]
    assert len(outers) == len(inners) == 4
    assert {sp.tid for sp in outers} == {sp.tid for sp in inners}
    assert len({sp.tid for sp in outers}) == 4
    for sp in inners:
        assert sp.parent_op == "Outer"
        assert sp.dur >= 0.02
    for sp in outers:
        assert sp.parent_op is None
        # child time accounted: outer self-time excludes the sleep
        assert sp.child_time >= 0.02
        assert sp.self_time < sp.dur
    roll = tr.rollup()
    assert roll["Inner"]["total_s"] >= 4 * 0.02
    assert roll["Outer"]["self_s"] < roll["Outer"]["total_s"]


def test_same_op_nested_spans_do_not_double_count():
    from spark_rapids_tpu.runtime import trace
    tr = trace.Tracer(query_id=98)
    with tr.span("A", "pump"):
        with tr.span("A", "opTime"):
            pass
    roll = tr.rollup()
    outer = [sp for sp in tr.events if sp.stage == "pump"][0]
    # total counts the outer span only; inner same-op span excluded
    assert roll["A"]["spans"] == 2
    assert abs(roll["A"]["total_s"] - round(outer.dur, 6)) < 1e-5


def test_query_log_round_trip(tmp_path):
    """Query runs → JSONL entry parses; fallback report matches the
    frame's own summary; metrics match collect_metrics; rollup
    self-time sums to the traced wall time (the acceptance bound)."""
    import json
    log = str(tmp_path / "qlog.jsonl")
    s = tpu_session({"spark.rapids.sql.trace.enabled": True,
                     "spark.rapids.sql.queryLog.path": log})
    df = s.createDataFrame(_t(2000)).groupBy("k").agg(
        F.sum("v").alias("sv"))
    out = df.toArrow()
    with open(log) as f:
        lines = f.read().splitlines()
    assert len(lines) == 1
    entry = json.loads(lines[0])
    assert entry["status"] == "ok"
    assert entry == s.query_history()[-1] or entry["query_id"] == (
        s.query_history()[-1]["query_id"])
    assert entry["fallback"] == df.fallback_summary()
    # every metric collect_metrics reports appears in the entry at the
    # same value (DEBUG = everything)
    logged = {m["op"]: m["metrics"] for m in entry["metrics"]}
    for op, vals in df.metrics(level="DEBUG"):
        for name, v in vals.items():
            lv = logged[op][name]["value"]
            assert lv == (round(v, 6) if isinstance(v, float) else v)
    # plan tree recorded with device markers
    assert "*Tpu" in entry["plan"]
    # self-time rollup partitions the traced wall time (10% bound)
    self_sum = sum(r["self_s"] for r in entry["op_rollup"].values())
    assert abs(self_sum - entry["wall_s"]) <= 0.1 * entry["wall_s"], (
        self_sum, entry["wall_s"])
    assert out.num_rows > 0


def test_query_history_records_untraced_queries():
    s = tpu_session({})
    df = s.createDataFrame(_t(300)).select("k")
    df.toArrow()
    df.toArrow()
    h = s.query_history()
    assert len(h) == 2
    assert h[0]["query_id"] != h[1]["query_id"]
    assert all(e["status"] == "ok" for e in h)
    assert "op_rollup" not in h[0]  # tracing was off
    assert s.query_history(1) == [h[-1]]


def test_explain_metrics_mode(capsys):
    s = tpu_session({"spark.rapids.sql.trace.enabled": True})
    df = s.createDataFrame(_t(300)).groupBy("k").count()
    df.explain("metrics")
    assert "no execution yet" in capsys.readouterr().out
    df.toArrow()
    df.explain("metrics")
    out = capsys.readouterr().out
    assert "numOutputRows" in out
    assert "per-op time attribution" in out


def test_profiler_capture_names_dump_after_query_id(tmp_path):
    prof = str(tmp_path / "prof")
    s = tpu_session({"spark.rapids.profile.enabled": True,
                     "spark.rapids.profile.path": prof})
    df = s.createDataFrame(_t(300)).groupBy("k").count()
    df.toArrow()
    entry = s.query_history()[-1]
    d = entry["profile_dir"]
    assert d.startswith(prof)
    assert os.path.basename(d) == f"query-{entry['query_id']:06d}"
    assert os.path.isdir(d)


def test_tracer_event_cap_counts_dropped():
    from spark_rapids_tpu.runtime import trace
    tr = trace.Tracer(query_id=97, max_events=5)
    for _ in range(9):
        with tr.span("A", "pump"):
            pass
    assert len(tr.events) == 5
    assert tr.dropped == 4


def test_all_metric_names_documented():
    """Metric drift fails fast: every metric created in the package
    appears in docs/observability.md."""
    from spark_rapids_tpu.utils.docs_gen import check_metrics_documented
    assert check_metrics_documented() == []


def test_concat_empty_batch_list_returns_empty():
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.exec.basic import (
        _concat_compacted_fast, concat_device_batches)
    schema = T.StructType((T.StructField("a", T.LongT, True),))
    for fn in (concat_device_batches, _concat_compacted_fast):
        b = fn(schema, [])
        assert b.num_rows_host() == 0
        assert len(b.columns) == 1
