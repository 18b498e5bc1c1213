"""Every query in flight owns its books (runtime/inflight.py).

Two client threads go through ``QueryServer`` as the benchmark's cell
``server.throughput`` does — cycling TPC-H Q6, Q12, Q1 with the cell's
bindings, at SF0.01 on the CPU — and every served query has to come
back with the reference's answer, a ledger of its own in
``attribution.recent()`` that adds up, and the spans of that query and
of no other.  Then the rules of ownership one by one: a nested
execution rides its owner, a helper thread writes into the books it
was handed, a thread nobody bound has none.

No assertion here is on seconds.
"""

import json
import os
import sys
import threading

import pytest

from spark_rapids_tpu.runtime import attribution, inflight, stats, trace
from spark_rapids_tpu.runtime import telemetry as TM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF, SEED, PASSES = 0.01, 2147483659, 2
JOIN_S = 600.0
# spans a served query has and the same query run alone has not: the
# server's own, and a wait for a device permit (only when one is taken)
SERVED_ONLY = {"QueryServer", "DeviceSemaphore"}


def _bench_modules():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import compare
    import run
    import tpch_gen
    return run, tpch_gen, compare


def _names(entry) -> dict:
    """op -> (spans, stages) of one execution's rollup, without the
    spans only a served query has."""
    return {op: (r["spans"], sorted(r["stages"]))
            for op, r in entry["op_rollup"].items()
            if op not in SERVED_ONLY}


@pytest.fixture(scope="module")
def served():
    """Each (query, binding) of the cell alone on this thread, twice
    (the second run has no compile in it), then two client threads
    through one ``QueryServer``; what every request saw."""
    from spark_rapids_tpu.sql.server import QueryServer
    from spark_rapids_tpu.sql.session import TpuSession
    run, tpch_gen, compare = _bench_modules()
    _, _, config, traffic, queries = run.load_cell("server.throughput")
    bindings = traffic["bindings"]
    tables = tpch_gen.gen_tables(SF, SEED, run.tables_needed(queries))
    # the cell's conf, with the rollup switched on so that a query's
    # span names can be read from its event-log entry
    session = TpuSession(dict(config["conf"],
                              **{"spark.rapids.sql.trace.enabled": True}))
    alone = {}
    for q, bs in bindings.items():
        for bi, b in enumerate(bs):
            for _ in range(2):
                df = queries[q].build(session, tables, b)
                df.toArrow()
            alone[(q, bi)] = df._last_query_entry

    # the tracers the server opens, by query id: the ``serve`` span
    # closes after the entry's rollup was taken
    from spark_rapids_tpu.sql import dataframe as dataframe_mod
    tracers, real_open = {}, dataframe_mod.open_books

    def open_books(conf, qid):
        got = real_open(conf, qid)
        tracers[qid] = got[0]
        return got
    patch = pytest.MonkeyPatch()
    patch.setattr(dataframe_mod, "open_books", open_books)
    server = QueryServer(session)
    published0 = TM.BOOKS_PUBLISHED.value
    ridden0 = TM.BOOKS_RIDDEN.value
    requests, lock = [], threading.Lock()

    def client(group):
        for p in range(PASSES):
            for q in group["queries"]:
                bi = (p + group["queries"].index(q)) % len(bindings[q])
                box = {}

                def make(q=q, bi=bi, box=box):
                    box["df"] = queries[q].build(session, tables,
                                                 bindings[q][bi])
                    return box["df"]
                handle = server.submit(make, tenant=group["tenant"])
                table = server.result(handle, timeout_s=JOIN_S)
                with lock:
                    requests.append({
                        "q": q, "binding": bi, "handle": handle,
                        "table": table,
                        "entry": box["df"]._last_query_entry})

    threads = [threading.Thread(target=client, args=(g,), daemon=True)
               for g in traffic["streams"]]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=JOIN_S)
    assert not any(t.is_alive() for t in threads)
    server.shutdown()
    patch.undo()
    return {"tracers": tracers, "requests": requests, "alone": alone,
            "config": config, "queries": queries, "bindings": bindings,
            "tables": tables, "compare": compare,
            "published": TM.BOOKS_PUBLISHED.value - published0,
            "ridden": TM.BOOKS_RIDDEN.value - ridden0,
            "recent": attribution.recent()}


def test_both_streams_were_served_in_whole_passes(served):
    by_q = {}
    for r in served["requests"]:
        by_q[r["q"]] = by_q.get(r["q"], 0) + 1
    assert by_q == {"q6": 2 * PASSES, "q12": 2 * PASSES, "q1": 2 * PASSES}


@pytest.mark.parametrize("q", ["q6", "q12", "q1"])
def test_every_served_answer_equals_the_reference(served, q):
    g = served["config"]["guarantees"]
    mine = [r for r in served["requests"] if r["q"] == q]
    assert mine
    for r in mine:
        want = served["queries"][q].reference(
            served["tables"], served["bindings"][q][r["binding"]])
        c = served["compare"].compare_tables(r["table"], want)
        assert c["exact_mismatches"] <= g["exact_mismatches"], c["what"]
        assert c["max_rel_err"] <= g["double_rtol"]


def test_every_served_query_closed_one_ledger_of_its_own(served):
    ids = [b["query_id"] for b in served["recent"]]
    for r in served["requests"]:
        assert ids.count(r["handle"].query_id) == 1, r["q"]
        assert r["entry"]["query_id"] == r["handle"].query_id
        assert r["entry"]["attribution"]["query_id"] == r["handle"].query_id
    # one book a served query, none ridden: nothing nested was run
    assert served["published"] == len(served["requests"])
    assert served["ridden"] == 0


def test_a_served_ledger_adds_up_with_its_waits_in_it(served):
    for r in served["requests"]:
        att = r["entry"]["attribution"]
        assert {"queue_wait", "semaphore_wait"} <= set(att["buckets"])
        assert att["buckets"]["queue_wait"] > 0.0
        assert (sum(att["buckets"].values())
                == pytest.approx(att["e2e_s"], abs=1e-4))
        # how small ``unaccounted`` is depends on the host's load: it is
        # read on the chip (``books_unaccounted_pct.server``), not here
        assert att["buckets"]["unaccounted"] == att["unaccounted_s"]
        # the ledger's wall lies inside the request, on the client's clock
        h = r["handle"]
        assert h.submitted_at <= att["t0_mono"] <= att["t1_mono"]
        assert att["t1_mono"] - att["t0_mono"] <= h.wall_s + 1e-3


def test_a_served_query_has_the_servers_spans_on_its_own_tracer(served):
    for r in served["requests"]:
        tracer = served["tracers"][r["handle"].query_id]
        mine = [(sp.stage, sp.tid) for sp in tracer.events
                if sp.op == "QueryServer"]
        assert sorted(st for st, _ in mine) == ["buildPlan", "queueWait",
                                                "serve"]
        # all on the worker thread, which is where the query ran
        root = next(sp for sp in tracer.events
                    if (sp.op, sp.stage) == ("Query", "execute"))
        assert {tid for _, tid in mine} == {root.tid}
        assert tracer.query_id == r["handle"].query_id


@pytest.mark.parametrize("q", ["q6", "q12", "q1"])
def test_served_launches_and_spans_equal_the_same_query_alone(served, q):
    """No stranger's span: op by op the served query has as many spans,
    in the same stages, as the same query and binding run alone."""
    mine = [r for r in served["requests"] if r["q"] == q]
    assert mine
    for r in mine:
        alone = served["alone"][(q, r["binding"])]
        assert (r["entry"]["attribution"]["launches"]
                == alone["attribution"]["launches"])
        assert _names(r["entry"]) == _names(alone)


def _own_thread(fn):
    box = {}

    def go():
        try:
            box["value"] = fn()
        except BaseException as e:   # handed to the caller's assert
            box["error"] = e
    t = threading.Thread(target=go, daemon=True)
    t.start()
    t.join(timeout=JOIN_S)
    assert not t.is_alive()
    if "error" in box:
        raise box["error"]
    return box["value"]


def test_two_threads_own_a_tracer_each_at_the_same_time():
    mine = trace.start_query(9101)
    try:
        assert mine is not None and trace.current() is mine

        def other():
            tr = trace.start_query(9102)
            try:
                assert tr is not None and tr is not mine
                assert trace.current() is tr
                with trace.span("Other", "opTime"):
                    pass
                return tr
            finally:
                trace.end_query(tr)
        theirs = _own_thread(other)
        assert [sp.op for sp in theirs.events] == ["Other"]
        assert mine.events == []
        assert trace.current() is mine
        assert inflight.in_flight_peak() >= 2
    finally:
        trace.end_query(mine)
    assert trace.current() is None


def test_many_threads_open_and_close_books_without_a_lost_update():
    """More threads than cores, a shortened switch interval: every
    thread's spans land in its own tracer and in no other, and the count
    of queries in flight comes back to where it started."""
    n_threads, rounds = 32, 40
    in_flight0 = inflight._in_flight
    start = threading.Barrier(n_threads, timeout=JOIN_S)
    wrong, lock = [], threading.Lock()

    def worker(i):
        start.wait()
        for r in range(rounds):
            qid = 9600 + i * rounds + r
            tr = trace.start_query(qid)
            try:
                with trace.span(f"W{i}", "opTime"):
                    with trace.span(f"W{i}", "pump"):
                        pass
                if (tr is None or trace.current() is not tr
                        or [sp.op for sp in tr.events] != [f"W{i}"] * 2):
                    with lock:
                        wrong.append(qid)
            finally:
                trace.end_query(tr)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=JOIN_S)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    assert inflight._in_flight == in_flight0
    assert inflight.in_flight_peak() > in_flight0


def test_a_nested_execution_on_the_owners_thread_rides_it():
    from spark_rapids_tpu.utils.harness import tpu_session
    import pyarrow as pa
    s = tpu_session({})
    from spark_rapids_tpu.sql.column import col
    df = s.createDataFrame(pa.table({"a": list(range(64))})).filter(
        col("a") > 3)
    df.toArrow()                      # warm: no compile span below
    owner = trace.start_query(9201)
    rec = attribution.start_query(9201)
    try:
        before = len(attribution.recent())
        ridden = TM.BOOKS_RIDDEN.value
        assert trace.start_query(9202) is None
        assert attribution.start_query(9202) is None
        assert TM.BOOKS_RIDDEN.value == ridden + 1
        assert df.toArrow().num_rows == 60   # a whole nested execution
        assert TM.BOOKS_RIDDEN.value == ridden + 2
        # it closed no ledger and left the owner where it was ...
        assert len(attribution.recent()) == before
        assert trace.current() is owner and attribution.current() is rec
        # ... and its spans are in the owner's tracer
        ops = {sp.op for sp in owner.events}
        assert "Plan" in ops and any(o.startswith("Kernel.") for o in ops)
    finally:
        trace.end_query(owner)
        attribution.end_query(rec)


def test_a_helper_threads_span_lands_in_its_querys_tracer():
    from spark_rapids_tpu.parallel.executor import run_pump_tasks
    owner = trace.start_query(9301)
    st = stats.start_query(9301)
    rec = attribution.start_query(9301)
    barrier = threading.Barrier(3, timeout=JOIN_S)
    try:
        def task(p):
            barrier.wait()             # three pool threads at once
            with trace.span("Helper", "opTime", {"partition": p}):
                attribution.record_event("retry", {"p": p})
                return (threading.get_ident(), trace.current(),
                        stats.current(), attribution.current())
        seen = run_pump_tasks(task, [0, 1, 2], max_workers=3)
        assert {s[0] for s in seen}.isdisjoint({threading.get_ident()})
        assert all(s[1:] == (owner, st, rec) for s in seen)
        helpers = [sp for sp in owner.events if sp.op == "Helper"]
        assert sorted(sp.args["partition"] for sp in helpers) == [0, 1, 2]
        assert len(rec.snapshot()["events"]) == 3
        # a plain thread is handed the books with carry()

        def plain():
            with trace.span("Carried", "opTime"):
                return trace.current()
        assert _own_thread(inflight.carry(plain)) is owner
        assert "Carried" in {sp.op for sp in owner.events}
    finally:
        trace.end_query(owner)
        stats.end_query(st)
        attribution.end_query(rec)


def test_a_pool_thread_gets_its_own_books_back():
    """``bind`` restores what the thread had: a reused pool thread does
    not keep the last query's books."""
    owner = trace.start_query(9401)
    try:
        held = inflight.held()
    finally:
        trace.end_query(owner)

    def on_pool_thread():
        assert trace.current() is None
        with inflight.bind(held):
            assert trace.current() is owner
        return trace.current()
    assert _own_thread(on_pool_thread) is None


def test_span_on_an_unowned_thread_is_the_null_span():
    owner = trace.start_query(9501)
    st = stats.start_query(9501)
    rec = attribution.start_query(9501)
    try:
        def unowned():
            attribution.record_event("health", {"check": "x"})
            return (trace.span("Stray", "opTime"), trace.current(),
                    stats.current(), attribution.current())
        assert _own_thread(unowned) == (trace._NULL, None, None, None)
        assert owner.events == [] and rec.snapshot()["events"] == []
    finally:
        trace.end_query(owner)
        stats.end_query(st)
        attribution.end_query(rec)
    assert trace.span("Stray", "opTime") is trace._NULL


def test_the_new_metrics_are_in_the_registry():
    names = set(TM.REGISTRY.names())
    assert {"tpuq_query_books_published_total",
            "tpuq_query_books_ridden_total",
            "tpuq_queries_in_flight_peak"} <= names
    assert (TM.REGISTRY.snapshot()["tpuq_queries_in_flight_peak"]
            == inflight.in_flight_peak())


def test_the_cells_files_name_what_the_test_drove():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "server.throughput")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf1_server", "throughput_streams", 1)
    served_metrics = [m["name"] for m in bench["per_layer"]
                      if m.get("workloads") == ["server.throughput"]]
    assert len(served_metrics) == 7
    assert all(m.endswith(".server") for m in served_metrics)
