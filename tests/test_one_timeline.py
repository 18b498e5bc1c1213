"""One timeline: the program's spans on the profiler's clock, kernels
with names, the time books extended to plan, launch, result and
epilogue, and the ring the benchmark's per-layer metrics read."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.runtime import attribution, kernel_cache, trace
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils.harness import tpu_session


def _t(n=3000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 30, n)),
        "v": pa.array(rng.uniform(-10, 10, n)),
    })


def _agg(session, table=None):
    return (session.createDataFrame(table if table is not None else _t())
            .filter(F.col("v") > -5).groupBy("k")
            .agg(F.sum("v").alias("sv")))


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats, line index) of ``/host:CPU``."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1, found
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats), i))
    return out


# ---------------------------------------------------------------------------
# 1. the mirror
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mirrored(tmp_path_factory):
    """One small aggregate under a caller's profiler session."""
    s = tpu_session({})
    df = _agg(s)
    df.toArrow()                      # compile outside the trace
    d = str(tmp_path_factory.mktemp("xplane"))
    with jax.profiler.trace(d):
        df.toArrow()
    qid = s.query_history()[-1]["query_id"]
    return qid, _host_events(d)


@pytest.mark.parametrize("prefix", [
    "tpuq.Plan:optimize", "tpuq.Plan:physicalPlan", "tpuq.Plan:overrides",
    "tpuq.Kernel.", "tpuq.Result:resultD2H", "tpuq.Result:resultConcat",
    "tpuq.Query:record"])
def test_spans_are_host_events_on_the_profilers_clock(mirrored, prefix):
    qid, events = mirrored
    roots = [e for e in events if e[0] == "tpuq.Query:execute"]
    assert len(roots) == 1 and roots[0][3]["query_id"] == qid
    _, r0, r1, _, rline = roots[0]
    mine = [e for e in events if e[0].startswith(prefix)]
    assert mine, sorted({e[0] for e in events if e[0].startswith("tpuq.")})
    for name, s, e, stats, line in mine:
        assert stats["query_id"] == qid
        if prefix == "tpuq.Kernel.":
            assert name.endswith(":kernelLaunch"), name
        if prefix == "tpuq.Query:record":
            assert s >= r1           # the epilogue, after the wall
        elif line == rline:          # same thread: nested in the root
            assert r0 <= s and e <= r1, (name, s, e, r0, r1)


def test_kernel_shows_by_name_on_the_host_plane(mirrored):
    _, events = mirrored
    names = {e[0] for e in events}
    assert not any(n == "PjitFunction(run)" for n in names)
    assert any(n.startswith("PjitFunction(tpuq_") for n in names), (
        sorted(n for n in names if n.startswith("PjitFunction")))


def test_partition_is_a_stat_where_the_span_has_one(mirrored):
    _, events = mirrored
    pumps = [e for e in events if e[0] == "tpuq.PumpTask:pumpTask"]
    assert pumps and all("partition" in e[3] for e in pumps)


def test_profile_conf_yields_one_xplane_with_program_spans(tmp_path):
    prof = str(tmp_path / "prof")
    s = tpu_session({"spark.rapids.profile.enabled": True,
                     "spark.rapids.profile.path": prof})
    _agg(s).toArrow()
    entry = s.query_history()[-1]
    names = {e[0] for e in _host_events(entry["profile_dir"])}
    assert "tpuq.Query:execute" in names
    assert "tpuq.Plan:overrides" in names
    assert any(n.startswith("tpuq.Kernel.") for n in names)


def test_a_served_querys_wait_and_serve_spans_are_on_the_timeline(
        tmp_path):
    """``QueryServer:queueWait`` and ``QueryServer:serve`` are opened
    on the served query's own tracer and mirrored like the rest, under
    its id; the serve span holds the query's root."""
    from spark_rapids_tpu.sql.server import QueryServer
    s = tpu_session({})
    _agg(s).toArrow()                 # compile outside the trace
    server = QueryServer(s)
    try:
        with jax.profiler.trace(str(tmp_path)):
            handle = server.submit(lambda: _agg(s), tenant="t")
            server.result(handle, timeout_s=120)
    finally:
        server.shutdown()
    mine = {e[0]: e for e in _host_events(str(tmp_path))
            if e[0].startswith("tpuq.")
            and e[3].get("query_id") == handle.query_id}
    assert {"tpuq.QueryServer:queueWait", "tpuq.QueryServer:serve",
            "tpuq.QueryServer:buildPlan", "tpuq.Query:execute"} <= set(mine)
    _, w0, w1, _, wline = mine["tpuq.QueryServer:queueWait"]
    _, s0, s1, _, sline = mine["tpuq.QueryServer:serve"]
    _, r0, r1, _, rline = mine["tpuq.Query:execute"]
    assert wline == sline == rline    # the worker thread
    assert w1 <= s0 <= r0 and r1 <= s1


class _CountingAnnotation:
    made = 0
    enabled = False
    open_names = []

    def __init__(self, name, **stats):
        type(self).made += 1
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        type(self).open_names.append(self.name)
        return self

    def __exit__(self, *exc):
        type(self).open_names.remove(self.name)
        return False


@pytest.fixture
def counting(monkeypatch):
    _CountingAnnotation.made = 0
    _CountingAnnotation.enabled = False
    _CountingAnnotation.open_names = []
    monkeypatch.setattr(trace, "TraceAnnotation", _CountingAnnotation)
    return _CountingAnnotation


def test_no_profiler_no_annotation_object(counting):
    s = tpu_session({})
    _agg(s).toArrow()
    assert s.query_history()[-1]["attribution"]["launches"] >= 1
    assert counting.made == 0


def test_is_enabled_is_asked_once_a_query_and_mirrors_every_span(counting):
    counting.enabled = True
    tr = trace.start_query(4242)
    try:
        assert tr.mirror
        counting.enabled = False     # asked at the start, not a span
        with tr.span("Plan", "optimize"):
            with tr.span("Kernel.x", "kernelLaunch", {"partition": 3}):
                assert counting.open_names == [
                    "tpuq.Plan:optimize", "tpuq.Kernel.x:kernelLaunch"]
    finally:
        trace.end_query(tr)
    assert counting.made == 2 and counting.open_names == []


def test_leaked_child_annotation_closes_with_its_parent(counting):
    tr = trace.Tracer(7, mirror=True)
    outer = tr.begin("A", "pump")
    tr.begin("B", "pump")             # never ended: a dropped generator
    assert len(counting.open_names) == 2
    tr.end(outer)
    assert counting.open_names == []


# ---------------------------------------------------------------------------
# 2. kernels with names
# ---------------------------------------------------------------------------

def test_cached_kernel_lowers_to_a_named_module():
    def run(x):
        return (x * 2).sum()

    jfn = kernel_cache._build_wrapper(("agg_reduce", "fp", 1), lambda: run)
    text = jfn.lower(jnp.ones(8)).as_text(debug_info=True)
    assert "module @jit_tpuq_agg_reduce" in text
    assert "agg_reduce" in text.split("module @jit_tpuq_agg_reduce")[1]
    assert run.__name__ == "run"      # the builder's function is not renamed


def test_prejitted_builder_keeps_its_name_and_donation():
    def exchange_step(x):
        return x + 1

    pre = jax.jit(exchange_step, donate_argnums=(0,))
    jfn = kernel_cache._build_wrapper(("exchange", "fp"), lambda: pre)
    assert jfn is pre
    text = jfn.lower(jnp.ones(8)).as_text()
    assert "module @jit_exchange_step" in text
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text


@pytest.mark.parametrize("key,label", [
    (("agg_reduce", 1), "agg_reduce"),
    (("join mat/2",), "join_mat_2"),
    ((("tuple", 1),), "__tuple___1_"),
    ((), "kernel")])
def test_op_label_is_an_identifier(key, label):
    assert kernel_cache._op_label(key) == label


# ---------------------------------------------------------------------------
# 3. the books
# ---------------------------------------------------------------------------

@pytest.fixture
def traced_query(monkeypatch):
    """A warm aggregate's tracer and ledger."""
    kept = []
    orig = trace.end_query

    def end_query(tr):
        kept.append(tr)
        orig(tr)

    monkeypatch.setattr(trace, "end_query", end_query)
    s = tpu_session({})
    df = _agg(s)
    df.toArrow()
    df.toArrow()
    return kept[-1], s.query_history()[-1]["attribution"]


def test_ledger_has_plan_launch_result_and_closes(traced_query):
    _, att = traced_query
    b = att["buckets"]
    assert b["plan"] > 0 and b["kernel_launch"] > 0 and b["result_d2h"] > 0
    assert att["closed"], att
    assert sum(b.values()) == pytest.approx(att["e2e_s"], abs=2e-5)


def test_launch_time_is_not_counted_again_in_dispatch(traced_query):
    """Every launch sits inside an exec's timer span: the sweep gives
    the inside to ``kernel_launch`` and takes it out of the stage
    around it, so the other buckets hold exactly that much less than
    they would without the launch spans."""
    tr, att = traced_query
    launch = sum(sp.dur for sp in tr.events if sp.stage == "kernelLaunch")
    without = attribution.attribute(
        spans=[sp for sp in tr.events if sp.stage != "kernelLaunch"],
        e2e_s=att["e2e_s"])
    b, b0 = att["buckets"], without["buckets"]
    assert b0["kernel_launch"] == 0
    assert b["kernel_launch"] == pytest.approx(launch, abs=2e-5)
    rest = lambda bk: sum(v for k, v in bk.items()
                          if k not in ("kernel_launch", "unaccounted"))
    assert rest(b) == pytest.approx(rest(b0) - launch, abs=5e-5)
    assert b["kernel_dispatch"] < b0["kernel_dispatch"]


def test_priority_sweep_splits_a_launch_from_its_stage():
    class Sp:
        def __init__(self, op, stage, t0, t1):
            self.op, self.stage, self.t0, self.t1 = op, stage, t0, t1

    att = attribution.attribute(spans=[
        Sp("PumpTask", "pumpTask", 0.0, 10.0),
        Sp("TpuHashAggregateExec", "opTime", 1.0, 7.0),
        Sp("Kernel.agg_reduce", "kernelLaunch", 2.0, 3.0),
        Sp("Kernel.agg_reduce", "compile", 4.0, 6.0),
        Sp("DeviceToHostExec", "transferTime", 7.0, 9.0),
        Sp("Kernel.compact", "kernelLaunch", 7.5, 8.0),
    ], e2e_s=10.0)
    b = att["buckets"]
    assert b["kernel_launch"] == pytest.approx(1.5)
    assert b["compile"] == pytest.approx(2.0)
    assert b["kernel_dispatch"] == pytest.approx(3.0)
    assert b["result_d2h"] == pytest.approx(1.5)
    assert b["pump_idle"] == pytest.approx(2.0)
    assert att["launches"] == 3


@pytest.mark.parametrize("op,stage,bucket", [
    ("Plan", "optimize", "plan"),
    ("Plan", "physicalPlan", "plan"),
    ("Plan", "overrides", "plan"),
    ("Kernel.agg_reduce", "kernelLaunch", "kernel_launch"),
    ("TpuScanExec", "h2dTime", "scan_h2d"),
    ("TpuParquetScanExec", "scanTime", "scan_h2d"),
    ("CpuParquetScanExec", "scanTime", "host_fallback"),
    ("DeviceToHostExec", "transferTime", "result_d2h"),
    ("HostToDeviceExec", "transferTime", "kernel_dispatch"),
    ("Result", "resultD2H", "result_d2h"),
    ("Result", "resultConcat", "result_d2h"),
    ("Query", "execute", None),
    ("Query", "record", None)])
def test_stage_lands_in_its_bucket(op, stage, bucket):
    assert attribution.span_bucket(op, stage) == bucket
    if bucket is not None:
        assert bucket in attribution.BUCKETS
        assert bucket in attribution.BUCKET_PRIORITY
        assert bucket in attribution.BUCKET_VERDICTS
    assert (attribution.BUCKET_PRIORITY.index("kernel_launch")
            < attribution.BUCKET_PRIORITY.index("kernel_dispatch"))


def test_launches_is_the_count_of_launch_spans(traced_query):
    tr, att = traced_query
    spans = [sp for sp in tr.events if sp.stage == "kernelLaunch"]
    assert spans and all(sp.op.startswith("Kernel.") for sp in spans)
    assert att["launches"] == len(spans)


def test_launch_counter_moves_with_the_tracer_off(traced_query):
    _, att = traced_query
    counter = TM.REGISTRY.counter("tpuq_program_launches_total")
    s = tpu_session({"spark.rapids.tpu.attribution.enabled": False})
    df = _agg(s)
    df.toArrow()
    before = counter.value
    n_before = len(attribution.recent())
    df.toArrow()
    assert trace.current() is None
    assert counter.value - before == att["launches"]
    assert len(attribution.recent()) == n_before   # no ledger published


def test_recent_is_bounded_ordered_and_on_monotonic():
    s = tpu_session({})
    df = _agg(s)
    marks = []
    for _ in range(3):
        t0 = time.monotonic()
        df.toArrow()
        marks.append((t0, time.monotonic()))
    books = attribution.recent()[-3:]
    ids = [e["query_id"] for e in s.query_history()[-3:]]
    assert [b["query_id"] for b in books] == ids
    for b, (t0, t1) in zip(books, marks):
        assert t0 <= b["t0_mono"] <= b["t1_mono"] <= t1
        assert b["t1_mono"] - b["t0_mono"] == pytest.approx(b["e2e_s"],
                                                            abs=2e-6)
        assert b["record_s"] is not None and b["record_s"] > 0
        # the epilogue is outside the wall the ledger closes on
        assert b["t1_mono"] + b["record_s"] <= t1 + 1e-4
        assert b is not None and "buckets" in b and "launches" in b
    ring = attribution._RECENT
    assert ring.maxlen == attribution.RECENT_MAX == 4096
    fake = trace.Tracer(1)
    fake.finish()
    for _ in range(attribution.RECENT_MAX + 5):
        attribution.publish(attribution.attribute(fake), fake)
    assert len(attribution.recent()) == attribution.RECENT_MAX


def test_planning_error_leaves_no_tracer_and_no_entry():
    s = tpu_session({})
    df = _agg(s)
    n = len(s.query_history())
    boom = RuntimeError("no plan")

    def fail():
        raise boom

    df._execute_plan = fail
    with pytest.raises(RuntimeError):
        df.toArrow()
    assert trace.current() is None and attribution.current() is None
    assert len(s.query_history()) == n
    del df._execute_plan
    assert df.toArrow().num_rows > 0


# ---------------------------------------------------------------------------
# 4. the scan
# ---------------------------------------------------------------------------

def test_scan_cache_counters_over_two_runs_of_one_table():
    hits = TM.REGISTRY.counter("tpuq_scan_cache_hits_total")
    misses = TM.REGISTRY.counter("tpuq_scan_cache_misses_total")
    h2d = TM.REGISTRY.counter("tpuq_h2d_bytes_total")
    s = tpu_session({})
    table = _t(seed=11)
    df = _agg(s, table)
    h0, m0, b0 = hits.value, misses.value, h2d.value
    df.toArrow()
    first = s.query_history()[-1]["attribution"]["buckets"]
    assert misses.value - m0 >= 1 and hits.value == h0
    assert h2d.value - b0 == table.nbytes
    assert first["scan_h2d"] > 0
    h1, m1, b1 = hits.value, misses.value, h2d.value
    df.toArrow()
    second = s.query_history()[-1]["attribution"]["buckets"]
    assert hits.value - h1 == m1 - m0 and misses.value == m1
    assert h2d.value == b1 and second["scan_h2d"] == 0


# ---------------------------------------------------------------------------
# 5. the gates
# ---------------------------------------------------------------------------

def test_lint_and_docs_gates_pass_with_the_new_stages():
    from spark_rapids_tpu.utils import docs_gen
    from spark_rapids_tpu.utils.lint import run_lint
    from spark_rapids_tpu.utils.lint.bucket_accounting import (
        BucketAccountingRule)
    assert [f for f in run_lint(rules=[BucketAccountingRule()])
            if f.rule == "bucket-accounting"] == []
    assert docs_gen.check_attribution_documented() == []
    assert docs_gen.check_telemetry_documented() == []
    assert docs_gen.check_metrics_documented() == []


def test_conf_has_no_trace_path_and_no_own_clock_exporter():
    from spark_rapids_tpu import conf as C
    assert not hasattr(C, "TRACE_PATH")
    assert not hasattr(trace.Tracer, "to_chrome_trace")
    assert not hasattr(trace, "write_chrome_trace")
    with pytest.raises(Exception):
        tpu_session({"spark.rapids.sql.trace.path": "/tmp/x"})
