"""The books by plan node: every span and count of a query carries the
pre-order path of its operator in the plan (``"0"``, ``"0.1"``: the
stats plane's ``plan_paths``), and the ledger keeps them as ``nodes``
beside the flat ``stages_s`` and ``counts`` they add up to.

On a Q18 at test size (``benchmark/queries/q18.py`` under
``tests/test_tpch_q18.py``'s streamed conf: two aggregates, a left-semi
join and two more joins, the sub-aggregate's repartition merge), on a
served query, on a fused region that fell open, and on the tracer
alone.  CPU platform, no assertion on seconds."""

import re

import pytest
from test_tpch_q3 import _bench
from test_tpch_q18 import BINDINGS, SEED, SF, STREAMED

from spark_rapids_tpu.runtime import attribution, trace
from spark_rapids_tpu.runtime.stats import plan_paths
from spark_rapids_tpu.utils.harness import tpu_session

AGG, JOIN = "TpuHashAggregateExec", "TpuSortMergeJoinExec"
QUERY_LEVEL = ("Query", "Plan", "Result", "PumpTask", "QueryServer")


def _run_keeping_the_tracer(df, mp):
    """Run ``df`` and return (its tracer, its ledger)."""
    kept = []
    orig = trace.end_query

    def end_query(tr):
        kept.append(tr)
        orig(tr)

    mp.setattr(trace, "end_query", end_query)
    df.toArrow()
    mp.undo()
    return kept[-1], df._last_query_entry["attribution"]


@pytest.fixture(scope="module")
def q18():
    """A warm Q18's plan, tracer and ledger, and the ledger of the same
    query run again with nothing bound."""
    q = _bench("queries/q18")
    tables = _bench("tpch_gen").gen_tables(SF, SEED, q.TABLES)
    session = tpu_session(STREAMED)
    q.build(session, tables, BINDINGS[0]).toArrow()       # compile
    with pytest.MonkeyPatch.context() as mp:
        df = q.build(session, tables, BINDINGS[0])
        tracer, book = _run_keeping_the_tracer(df, mp)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace.Tracer, "bind", lambda self, plan: None)
        again = q.build(session, tables, BINDINGS[0])
        again.toArrow()
        unbound = again._last_query_entry["attribution"]
    return {"plan": df._last_plan, "tracer": tracer, "book": book,
            "unbound": unbound}


def _of(q18, op):
    """The plan paths of the nodes of class ``op``, root first."""
    return [p for n, p in plan_paths(q18["plan"]) if n.name == op]


def test_two_aggregates_and_three_joins_each_have_their_own_books(q18):
    nodes = q18["book"]["nodes"]
    aggs, joins = _of(q18, AGG), _of(q18, JOIN)
    assert len(aggs) == 2 and len(joins) == 3
    for p in aggs + joins:
        assert p in nodes, p
        assert nodes[p]["op"] == (AGG if p in aggs else JOIN)
    by_path = {p: n for n, p in plan_paths(q18["plan"])}
    for p in joins:
        assert nodes[p]["counts"]["joinProbeGroups"] == (
            by_path[p].metrics["joinProbeGroups"].value)
    final, sub = aggs
    assert nodes[final]["counts"]["aggPartials"] == (
        by_path[final].metrics["aggPartials"].value)
    assert nodes[sub]["counts"]["aggRepartitionBuckets"] == (
        by_path[sub].metrics["aggRepartitionBuckets"].value)
    assert nodes["query"]["op"] is None
    assert all(re.fullmatch(r"0(\.\d+)*", p) for p in nodes if p != "query")


def test_stages_close_over_the_nodes(q18):
    book = q18["book"]
    for key, secs in book["stages_s"].items():
        over = sum(n["stages_s"].get(key, 0.0)
                   for n in book["nodes"].values())
        assert over == pytest.approx(secs, abs=1e-6), key
    for n in book["nodes"].values():
        assert set(n["stages_s"]) <= set(book["stages_s"])


def test_counts_close_over_the_nodes(q18):
    book = q18["book"]
    for name, value in book["counts"].items():
        assert sum(n["counts"].get(name, 0)
                   for n in book["nodes"].values()) == value, name
    for n in book["nodes"].values():
        assert set(n["counts"]) <= set(book["counts"])


def test_a_launch_books_under_the_node_open_when_it_ran(q18):
    """A ``Kernel.<label>:kernelLaunch`` span is opened with no node:
    it takes the innermost span open on its thread, so the split's cuts
    are the sub-aggregate's and the match kernel's calls the joins'."""
    events = q18["tracer"].events
    launches = [sp for sp in events if sp.stage == "kernelLaunch"]
    assert launches
    for sp in launches:
        # the span it opened under: on its thread, around it, of the op
        # that was on top of the thread's stack
        under = [o for o in events if o is not sp and o.tid == sp.tid
                 and o.t0 <= sp.t0 and sp.t1 <= o.t1
                 and o.op == sp.parent_op and o.node == sp.node]
        assert sp.node is not None and under, (sp.op, sp.parent_op)
    _, sub = _of(q18, AGG)
    assert {sp.node for sp in launches
            if sp.op == "Kernel.split_cut"} == {sub}
    assert {sp.node for sp in launches
            if sp.op == "Kernel.join_match"} == set(_of(q18, JOIN))


def test_the_splits_counts_book_under_the_sub_aggregate(q18):
    """``splitChunks`` and the slices are counted by
    ``split_to_spillables`` with no node: the merge's span around it
    names the node."""
    book = q18["book"]
    _, sub = _of(q18, AGG)
    for name in ("splitChunks", "spillableSlices", "spillableBytes"):
        assert book["counts"][name] > 0
        assert book["nodes"][sub]["counts"][name] == book["counts"][name]


def test_query_level_spans_book_under_the_query(q18):
    for sp in q18["tracer"].events:
        if sp.op in QUERY_LEVEL:
            assert sp.node is None, (sp.op, sp.stage, sp.node)
        elif not sp.op.startswith(("Kernel.", "Spill", "DeviceSemaphore")):
            assert sp.node is not None, (sp.op, sp.stage)
    assert any(k.startswith("Plan:")
               for k in q18["book"]["nodes"]["query"]["stages_s"])


def test_the_flat_books_are_the_same_without_nodes(q18):
    """The same spans swept with every node taken away give the same
    ``stages_s``, buckets and counts."""
    tr, book = q18["tracer"], q18["book"]
    saved = [(sp, sp.node) for sp in tr.events]
    try:
        for sp, _ in saved:
            sp.node = None
        flat = attribution.attribute(tr)
    finally:
        for sp, node in saved:
            sp.node = node
    assert flat["stages_s"] == book["stages_s"]
    assert flat["buckets"] == book["buckets"]
    assert flat["counts"] == book["counts"]
    assert flat["nodes"]["query"]["stages_s"] == pytest.approx(
        book["stages_s"], abs=1e-6)


def test_a_query_run_with_nothing_bound_books_it_all_under_the_query(q18):
    unbound, book = q18["unbound"], q18["book"]
    assert unbound["counts"] == book["counts"]
    assert set(unbound["nodes"]) == {"query"}
    assert unbound["nodes"]["query"]["counts"] == unbound["counts"]


def test_the_node_metrics_add_up_to_the_books_by_operator(q18):
    """What the benchmark's node metrics read: the aggregates' own
    stages add up to the operator's, and one join's own to at most all
    three joins'."""
    book = q18["book"]

    def own(path, op):
        return sum(s for k, s in book["nodes"][path]["stages_s"].items()
                   if k.split(":", 1)[0] == op)

    def flat(op):
        return sum(s for k, s in book["stages_s"].items()
                   if k.split(":", 1)[0] == op)
    aggs, joins = _of(q18, AGG), _of(q18, JOIN)
    assert sum(own(p, AGG) for p in aggs) == pytest.approx(
        flat(AGG), abs=1e-5)
    assert own(joins[0], JOIN) <= flat(JOIN)


# ---------------------------------------------------------------------------
# a served query, a fused region, the tracer alone
# ---------------------------------------------------------------------------

def _grouped(session):
    import pyarrow as pa

    from spark_rapids_tpu.sql import functions as F
    t = pa.table({"k": pa.array([i % 7 for i in range(500)]),
                  "v": pa.array([float(i) for i in range(500)])})
    return (session.createDataFrame(t).filter(F.col("v") > 3.0)
            .groupBy("k").agg(F.sum("v").alias("sv")))


def test_a_served_querys_ledger_has_its_own_nodes():
    from spark_rapids_tpu.sql.server import QueryServer
    s = tpu_session({})
    _grouped(s).toArrow()                         # compile
    server = QueryServer(s)
    dfs = []

    def build():
        dfs.append(_grouped(s))
        return dfs[-1]
    try:
        handles = [server.submit(build, tenant=f"t{i}") for i in range(2)]
        for h in handles:
            server.result(h, timeout_s=120)
    finally:
        server.shutdown()
    books = {b["query_id"]: b for b in attribution.recent()}
    for h in handles:
        book = books[h.query_id]
        df = next(d for d in dfs
                  if d._last_query_entry["query_id"] == h.query_id)
        want = {p: n.name for n, p in plan_paths(df._last_plan)}
        nodes = book["nodes"]
        assert nodes["query"]["op"] is None
        assert any(k.startswith("QueryServer:")
                   for k in nodes["query"]["stages_s"])
        for p, n in nodes.items():
            if p != "query":
                assert n["op"] == want[p], p
        assert {n["op"] for n in nodes.values()} >= {AGG}
        for key, secs in book["stages_s"].items():
            assert sum(n["stages_s"].get(key, 0.0)
                       for n in nodes.values()) == pytest.approx(
                           secs, abs=1e-6)


def test_a_fused_member_keeps_its_pre_fusion_path(monkeypatch):
    """A region that fell open pumps its members: their spans book
    under the paths they had before fusion, the paths the region's
    ``fusion_members`` (and the stats plane's member records) carry."""
    import pyarrow as pa

    from spark_rapids_tpu.exec.fused import FusedStageExec
    from spark_rapids_tpu.runtime import kernel_cache
    from spark_rapids_tpu.sql.column import col
    t = pa.table({"k": pa.array(list(range(400))),
                  "v": pa.array([float(i) for i in range(400)])})
    kernel_cache.clear()

    def boom(self):
        raise ValueError("forced region build failure")
    monkeypatch.setattr(FusedStageExec, "_composed", boom)
    s = tpu_session({"spark.rapids.tpu.fusion.enabled": True})
    df = (s.createDataFrame(t).filter(col("k") % 3 != 1)
          .select((col("k") % 7).alias("k7"), col("v"))
          .filter(col("k7") > 1))
    tracer, book = _run_keeping_the_tracer(df, monkeypatch)
    region, = [n for n, _ in plan_paths(df._last_plan)
               if isinstance(n, FusedStageExec)]
    assert region._fell_open
    for m, sig in zip(region.members, region.fusion_members):
        assert tracer.paths[id(m)] == sig["path"]
    ran = {(sp.op, sp.node) for sp in tracer.events if sp.stage == "opTime"}
    for op in ("TpuFilterExec", "TpuProjectExec"):
        want = {sig["path"] for sig in region.fusion_members
                if sig["op"] == op}
        assert {p for o, p in ran if o == op} == want, op
    assert book["nodes"][region.fusion_members[0]["path"]]["stages_s"]


class _Node:
    def __init__(self, *children):
        self.children = children

    @property
    def name(self):
        return "Fake"


def test_a_node_built_while_the_query_runs_books_under_the_open_one():
    inner = _Node()
    plan = _Node(_Node(), _Node(inner))
    tr = trace.Tracer(1)
    tr.bind(plan)
    assert tr.paths[id(inner)] == "0.1.0"
    late = _Node()                                  # not in the plan
    outer = tr.begin("Fake", "opTime", node=inner)
    sp = tr.begin("Fake", "opTime", node=late)
    tr.count("made", 2, late)
    trace_free = tr.begin("Kernel.x", "kernelLaunch")
    for done in (trace_free, sp, outer):
        tr.end(done)
    tr.count("alone", 1)                            # nothing open
    assert outer.node == sp.node == trace_free.node == "0.1.0"
    assert tr.node_counts == {"0.1.0": {"made": 2}, None: {"alone": 1}}
    assert tr.counts == {"made": 2, "alone": 1}
    tr.finish()
    att = attribution.attribute(tr)
    assert att["nodes"]["0.1.0"]["op"] == "Fake"
    assert att["nodes"]["query"]["counts"] == {"alone": 1}


def test_a_span_held_open_across_a_yield_leaves_the_stack_as_it_is():
    """A generator's timer around its ``yield`` is popped with the pump
    span it opened under when the batch is handed over; when it closes
    later, what is open on the thread stays open, and what opens next
    still books under the right node."""
    consumer_node, producer_node = _Node(), _Node()
    tr = trace.Tracer(1)
    tr.bind(_Node(consumer_node, producer_node))
    root = tr.begin("Query", "execute")
    consumer = tr.begin("A", "opTime", node=consumer_node)
    pump = tr.begin("B", "pump", node=producer_node)
    held = tr.begin("B", "opTime", node=producer_node)
    tr.end(pump)                       # the batch leaves the generator
    assert tr._stack() == [root, consumer]
    tr.end(held)                       # the generator resumes and closes
    assert tr._stack() == [root, consumer]
    launch = tr.begin("Kernel.k", "kernelLaunch")
    tr.end(launch)
    assert launch.node == "0.0" and launch.parent_op == "A"
