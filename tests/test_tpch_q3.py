"""TPC-H Q3 (shipping priority) on the normal path, against the
benchmark's plain reference, and the books by operator it brought.

``benchmark/queries/q3.py`` holds the plan (three scans, two joins, a
group-by of about 0.4 groups a row, a top-10), dbgen's ship dates for
``lineitem`` and the numpy reference; the engine has to return the reference's ten rows: keys,
order and row count exactly, ``revenue`` to 1e-9.  With a tiny
``join.targetRows`` both joins stream their big side in bounded groups
(``_broadcast_streamed``, planned and by the runtime's pick) and the
aggregate merges several partials.  The ledger of such a query keeps
its exclusive sweep by ``<op>:<stage>`` (``stages_s``) and what its
operators counted on the host (``counts``).  CPU platform, SF0.01, seeded tables,
no assertion on seconds."""

import importlib.util
import os
import sys

import numpy as np
import pytest

from spark_rapids_tpu.exec import join as J
from spark_rapids_tpu.runtime import attribution, trace
from spark_rapids_tpu.utils.harness import tpu_session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF, SEED = 0.01, 33
BINDINGS = [{"segment": "BUILDING", "date": "1995-03-15"},
            {"segment": "MACHINERY", "date": "1995-03-29"}]
# customer (about 30 KB) may be broadcast, orders (360 KB) and lineitem
# not: join 1 is planned broadcast=left with orders streamed, join 2
# unplanned, as at SF1; 2 048 rows hold join 1's output (about 1 400)
# and neither orders' (about 6 900 live) nor lineitem's (about 34 000)
STREAMED = {"spark.rapids.tpu.join.targetRows": 2048,
            "spark.rapids.tpu.batchRows": 4096,
            "spark.sql.autoBroadcastJoinThreshold": 100_000}
CONFS = {"default": {}, "streamed": STREAMED}
COUNTS = ["joinProbeGroups", "joinSlotsProbed", "joinLiveRowsStreamed",
          "sortBackend.jnp"]


def _bench(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        f"q3_test_{name.replace('/', '_')}", os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def q3():
    return _bench("queries/q3")


@pytest.fixture(scope="module")
def compare():
    return _bench("compare")


@pytest.fixture(scope="module")
def tables(q3):
    return _bench("tpch_gen").gen_tables(SF, SEED, q3.TABLES)


@pytest.fixture(scope="module")
def ran(q3, tables):
    """(conf name, binding index) -> what one run left: the answer, the
    DataFrame, its ledger and the match-kernel calls the test counted."""
    out = {}
    for name, conf in CONFS.items():
        session = tpu_session(conf)
        for bi, b in enumerate(BINDINGS):
            calls = []
            real = J.TpuSortMergeJoinExec._match_ranges

            def counted(self, lb, rb, probe, _real=real, _calls=calls):
                _calls.append((lb.capacity, rb.capacity))
                return _real(self, lb, rb, probe)
            J.TpuSortMergeJoinExec._match_ranges = counted
            try:
                df = q3.build(session, tables, b)
                table = df.toArrow()
            finally:
                J.TpuSortMergeJoinExec._match_ranges = real
            out[name, bi] = {"table": table, "df": df, "calls": calls,
                             "book": attribution.recent()[-1]}
    return out


def _sizes(q3, tables, b):
    """The sizes of Q3's intermediate results, by numpy."""
    from refutil import days, lookup, strings
    cust, orders, li = tables["customer"], tables["orders"], q3.lineitem(tables)
    date = days(q3._date(b))
    seg = strings(cust, "c_mktsegment") == b["segment"]
    o_date = days(orders, "o_orderdate") < date
    in_seg = lookup(cust.column("c_custkey").to_numpy()[seg],
                    orders.column("o_custkey").to_numpy()) >= 0
    o_key = orders.column("o_orderkey").to_numpy()[o_date & in_seg]
    l_live = days(li, "l_shipdate") > date
    pos = lookup(o_key, li.column("l_orderkey").to_numpy()[l_live])
    return {"customers": int(seg.sum()), "orders": int(o_date.sum()),
            "join1": len(o_key), "lineitems": int(l_live.sum()),
            "join2": int((pos >= 0).sum()),
            "groups": len(np.unique(pos[pos >= 0]))}


def _joins(node, out=None):
    out = [] if out is None else out
    if type(node).__name__ == "TpuSortMergeJoinExec":
        out.append(node)
    for c in node.children:
        _joins(c, out)
    return out


@pytest.mark.parametrize("bi", [0, 1])
@pytest.mark.parametrize("conf", list(CONFS))
def test_q3_equals_the_reference(ran, q3, compare, tables, conf, bi):
    got = ran[conf, bi]["table"]
    want = q3.reference(tables, BINDINGS[bi])
    assert got.num_rows == want.num_rows == q3.LIMIT
    assert got.schema.equals(want.schema), (got.schema, want.schema)
    c = compare.compare_tables(got, want)
    assert c["exact_mismatches"] == 0, c["what"]
    assert c["max_rel_err"] <= 1e-9
    assert (got.column("o_orderkey").to_pylist()
            == want.column("o_orderkey").to_pylist())


@pytest.mark.parametrize("conf", list(CONFS))
def test_q3_runs_on_the_device(ran, conf):
    for bi in range(len(BINDINGS)):
        summary = ran[conf, bi]["df"].fallback_summary()
        assert summary["fallback_ops"] == 0, summary


@pytest.mark.parametrize("bi", [0, 1])
def test_the_float32_reference_differs(q3, compare, tables, bi):
    c = compare.compare_tables(
        q3.reference(tables, BINDINGS[bi], np.float32),
        q3.reference(tables, BINDINGS[bi]))
    assert c["max_rel_err"] > 1e-9 or c["exact_mismatches"] >= 1


@pytest.mark.parametrize("bi", [0, 1])
def test_both_joins_stream_and_the_aggregate_merges_partials(ran, bi):
    plan = ran["streamed", bi]["df"]._last_plan
    top, bottom = _joins(plan)
    assert bottom.broadcast == "left" and top.broadcast is None
    for j in (top, bottom):
        assert j.metrics["streamedJoins"].value == 1
        assert j.metrics["joinProbeGroups"].value >= 2
    book = ran["streamed", bi]["book"]
    # every probe group's output is a partial the aggregate merges
    for stage in ("partialTime", "mergeTime"):
        assert book["stages_s"][f"TpuHashAggregateExec:{stage}"] > 0


def stages_add_up_to_the_buckets(book, ops):
    """The books by operator of one ledger: every stage maps to a
    bucket, a bucket's stages add up to it, all of them and the
    unaccounted rest to the wall, and every operator of ``ops`` has a
    stage."""
    by_bucket = {}
    for key, secs in book["stages_s"].items():
        bucket = attribution.span_bucket(*key.split(":", 1))
        assert bucket is not None, key
        by_bucket[bucket] = by_bucket.get(bucket, 0.0) + secs
    slack = 1e-6 * (len(book["stages_s"]) + 1)
    for bucket, secs in book["buckets"].items():
        if bucket != "unaccounted":
            assert by_bucket.get(bucket, 0.0) == pytest.approx(
                secs, abs=slack), bucket
    assert (sum(book["stages_s"].values()) + book["unaccounted_s"]
            == pytest.approx(book["e2e_s"], abs=slack))
    for op in ops:
        assert any(k.startswith(op + ":") for k in book["stages_s"]), op


@pytest.mark.parametrize("bi", [0, 1])
@pytest.mark.parametrize("conf", list(CONFS))
def test_stages_add_up_to_the_buckets(ran, conf, bi):
    book = ran[conf, bi]["book"]
    stages_add_up_to_the_buckets(book, (
        "TpuSortMergeJoinExec", "TpuHashAggregateExec", "TpuTopNExec"))
    assert "TpuTopNExec:concatTime" in book["stages_s"]


@pytest.mark.parametrize("conf", list(CONFS))
def test_probe_groups_are_the_match_kernel_calls(ran, conf):
    for bi in range(len(BINDINGS)):
        r = ran[conf, bi]
        counts = r["book"]["counts"]
        assert counts["joinProbeGroups"] == len(r["calls"])
        assert counts["joinProbeGroups"] == sum(
            j.metrics["joinProbeGroups"].value
            for j in _joins(r["df"]._last_plan))
    assert ran["default", 0]["book"]["counts"]["joinProbeGroups"] == 2
    assert ran["streamed", 0]["book"]["counts"]["joinProbeGroups"] > 4


@pytest.mark.parametrize("name", COUNTS)
def test_count(ran, q3, tables, name):
    """Every count of the ledger against what numpy says of the data
    (default conf, where each join probes once: the streamed side is
    the left, the customers and then join 1's output)."""
    from spark_rapids_tpu.columnar.column import live_bucket
    for bi, b in enumerate(BINDINGS):
        n = _sizes(q3, tables, b)
        got = ran["default", bi]["book"]["counts"][name]
        streamed = [n["customers"], n["join1"]]
        want = {
            "joinProbeGroups": 2,
            "joinSlotsProbed": sum(live_bucket(k, 1 << 20)
                                   for k in streamed),
            "joinLiveRowsStreamed": sum(streamed),
            "sortBackend.jnp": 2,          # all groups, then the winners
        }[name]
        assert got == want, (name, bi, got, want, n)
        # streamed in groups: the same rows, more slots and more groups
        more = ran["streamed", bi]["book"]["counts"]
        if name == "joinLiveRowsStreamed":
            assert more[name] == n["orders"] + n["lineitems"]
        elif name == "joinSlotsProbed":
            assert more[name] >= n["orders"] + n["lineitems"]
        elif name == "sortBackend.jnp":
            assert more[name] == want


def test_the_sweep_charges_the_innermost_span_of_the_winning_bucket():
    class Sp:
        def __init__(self, op, stage, t0, t1):
            self.op, self.stage, self.t0, self.t1 = op, stage, t0, t1
    spans = [Sp("A", "opTime", 0.0, 10.0),          # kernel_dispatch
             Sp("B", "pump", 1.0, 9.0),             # pump_idle: loses
             Sp("B", "opTime", 2.0, 6.0),           # innermost dispatch
             Sp("Kernel.k", "kernelLaunch", 3.0, 4.0)]
    att = attribution.attribute(spans=spans, e2e_s=12.0)
    assert att["stages_s"] == {"A:opTime": 6.0, "B:opTime": 3.0,
                               "Kernel.k:kernelLaunch": 1.0}
    assert att["buckets"]["kernel_dispatch"] == 9.0
    assert att["buckets"]["kernel_launch"] == 1.0
    assert att["unaccounted_s"] == 2.0
    assert att["counts"] == {}


def test_tracer_counts_add_up_and_only_for_a_query():
    tr = trace.Tracer(0)
    tr.count("n", 2)
    tr.count("n", 3)
    assert tr.counts == {"n": 5}
    trace.count("nobody", 1)        # no query on this thread: dropped


@pytest.mark.parametrize("caps,lives,want", [
    # under the cap together: one group
    ([1024, 1024], [1000, 7], [([1024, 1024], 1007)]),
    # a batch over the cap is sliced; its live rows are a prefix
    ([8192], [5000], [([2048], 2048), ([2048], 2048), ([2048], 904),
                      ([2048], 0)]),
    # a group closes when the next batch would pass the cap
    ([1024, 2048, 512, 512], [10, 2048, 0, 512],
     [([1024], 10), ([2048], 2048), ([512, 512], 512)]),
    # nothing streamed: one empty group (the join still runs once)
    ([], [], [([], 0)]),
])
def test_bounded_groups_know_their_live_rows(caps, lives, want):
    """``_bounded_groups`` cuts by capacity and counts by the gather's
    live counts: the slots and the live rows of a probe group are
    counted together, a call."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.column import DeviceBatch, DeviceColumn

    schema = T.StructType([T.StructField("k", T.LongType())])

    def batch(cap, n):
        col = DeviceColumn(T.LongType(), jnp.arange(cap, dtype=jnp.int64))
        return DeviceBatch(schema, (col,), jnp.arange(cap) < n)

    class Node:
        sub_partition_rows = 2048
    got = J.TpuSortMergeJoinExec._bounded_groups(
        Node(), [batch(c, n) for c, n in zip(caps, lives)], lives)
    assert [([b.capacity for b in g], n) for g, n in got] == want
    for g, n in got:
        assert sum(int(b.sel.sum()) for b in g) == n
