"""The cell PR 33 added, as ``BENCHMARK.json`` has it: ``session.q3``
(TPC-H Q3 through one session, ship dates by dbgen's rule, with the
five ``.q3`` metrics read from the ledger's books by operator),
rehearsed end to end; the ``.q3`` readers on a ledger that lacks the new
keys; a planted fault in a Q3 answer."""

import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from conftest import ROOT

Q3_METRICS = {"join_probe_groups.q3", "join_slots_per_live_row.q3",
              "join_host_ms.q3", "agg_host_ms.q3", "topn_host_ms.q3"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
# the cell reports one of the two seconds-a-query metrics (ISSUE 33:
# query_s if its runs spread under half of that bound, else
# scan_query_s) and the twelve shared per-layer metrics that move it
E2E, = [m["name"] for m in BENCH["end_to_end"]
        if m["name"] != "setup_s" and "session.q3" in m["workloads"]]
SUFFIX = {"query_s": ".session", "scan_query_s": ".scan"}[E2E]
ROOFLINE = "hbm_roofline_pct" + SUFFIX   # needs a peak: none in a rehearsal
SHARED = {q + SUFFIX for q in (
    "device_idle_pct", "launches_per_query", "launch_gap_ms",
    "compiles_in_window", "plan_ms", "launch_host_ms", "pump_host_ms",
    "result_d2h_ms", "epilogue_ms", "cached_launches_per_query",
    "books_unaccounted_pct")}


def _rehearse(cell, trace, seconds=4):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]


@pytest.fixture(scope="module")
def q3_traced():
    return _rehearse("session.q3", 1)


def test_the_entries_are_as_the_issue_names_them():
    bench = BENCH
    by_name = {w["name"]: w for w in bench["workloads"]}
    q3 = by_name["session.q3"]
    assert (q3["config"], q3["traffic"], q3["chips"]) == (
        "tpch_sf1_q3_session", "q3_stream", 1)
    assert "server.dash_only" not in by_name
    config = next(c for c in bench["configs"] if c["name"] == q3["config"])
    assert config["reduced"] == ["scale_factor", "bindings", "generator"]
    with open(os.path.join(ROOT, config["file"])) as f:
        on_disk = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs/tpch_sf1_session.json")) as f:
        session = json.load(f)
    assert on_disk["source"] == config["source"]
    assert set(on_disk["reduced"]) == set(config["reduced"])
    assert on_disk["guarantees"] == session["guarantees"]
    assert on_disk["entry"] == "session" and on_disk["conf"] == {
        "spark.rapids.sql.enabled": True}
    assert {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == ["session.q3"]} == Q3_METRICS
    assert all(m["moves"] == E2E for m in bench["per_layer"]
               if "session.q3" in m["workloads"])
    assert {m["name"] for m in bench["per_layer"]
            if "session.q3" in m["workloads"]} == (
        SHARED | Q3_METRICS | {ROOFLINE})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e[E2E]["workloads"][-1] == "session.q3"
    with open(os.path.join(ROOT, "benchmark/traffic/q3_stream.json")) as f:
        mix = json.load(f)
    assert mix["bindings"]["q3"] == [
        {"segment": "BUILDING", "date": "1995-03-15"},
        {"segment": "MACHINERY", "date": "1995-03-29"}]


def test_session_q3_rehearses_correct_with_its_metrics(q3_traced):
    result, phases = q3_traced
    assert result["correct"] is True
    assert set(result["metrics"]) == SHARED | Q3_METRICS
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["join_probe_groups.q3"] == 2        # SF0.01: one probe a join
    assert m["join_slots_per_live_row.q3"] >= 1
    for name in ("join_host_ms.q3", "agg_host_ms.q3", "topn_host_ms.q3"):
        assert m[name] > 0
    assert m["compiles_in_window" + SUFFIX] == 0


def test_session_q3_untraced_line_has_the_two_end_to_end_metrics():
    result, _ = _rehearse("session.q3", 0, seconds=2)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", E2E}


def test_ship_dates_follow_the_order_by_dbgens_rule():
    """``queries/q3.py::lineitem``: every ship date 1 .. 121 days after
    its order's date, seeded, and nothing else of the table moved; the
    join output is then about a tenth of the independent draw's."""
    import run
    import tpch_gen
    from refutil import days
    q3 = run.load_module("queries", "q3")
    tables = tpch_gen.gen_tables(0.01, 2147483659, q3.TABLES)
    li = q3.lineitem(tables)
    assert q3.lineitem(tables) is li            # made once a run
    assert tables["lineitem"].num_rows == li.num_rows
    for c in ("l_orderkey", "l_extendedprice", "l_discount"):
        assert li.column(c).equals(tables["lineitem"].column(c))
    after = (days(li, "l_shipdate") - days(tables["orders"], "o_orderdate")[
        li.column("l_orderkey").to_numpy()])
    assert after.min() == 1 and after.max() == q3.SHIP_DAYS
    assert len(np.unique(after)) == q3.SHIP_DAYS
    again = tpch_gen.gen_tables(0.01, 2147483659, q3.TABLES)
    assert q3.lineitem(again).equals(li)
    other = tpch_gen.gen_tables(0.01, 7, q3.TABLES)
    assert not q3.lineitem(other).column("l_shipdate").equals(
        li.column("l_shipdate"))
    b = {"segment": "BUILDING", "date": "1995-03-15"}
    date = days(q3._date(b))
    assert ((days(li, "l_shipdate") > date).sum()
            > 0.5 * li.num_rows)                # still a half-live stream
    # an order before DATE with a line item after it: 121 days' worth
    o_date = days(tables["orders"], "o_orderdate")[
        li.column("l_orderkey").to_numpy()]
    both = ((o_date < date) & (days(li, "l_shipdate") > date)).mean()
    assert 0.01 < both < 0.04


def test_q3_metrics_read_nothing_from_a_ledger_without_the_new_keys():
    import run
    old = {"buckets": {"kernel_dispatch": 1.0}, "e2e_s": 1.0,
           "unaccounted_s": 0.0, "launches": 3}
    new = dict(old, stages_s={"TpuSortMergeJoinExec:opTime": 0.5,
                              "TpuSortMergeJoinExec:gatherTime": 0.25,
                              "TpuHashAggregateExec:mergeTime": 0.125,
                              "TpuTopNExec:opTime": 0.0625,
                              "Kernel.sort:kernelLaunch": 0.01},
               counts={"joinProbeGroups": 25, "joinSlotsProbed": 600,
                       "joinLiveRowsStreamed": 400})
    want = {"join_probe_groups.q3": 25, "join_slots_per_live_row.q3": 1.5,
            "join_host_ms.q3": 750.0, "agg_host_ms.q3": 125.0,
            "topn_host_ms.q3": 62.5}
    for name in sorted(Q3_METRICS):
        read = run.load_module("metrics", name).read
        assert read({"_books": [old]}) is None
        assert read({"_books": []}) is None
        assert read({"_books": [new, new, old]}) == want[name]


def test_a_swapped_pair_of_the_ten_rows_is_not_correct(rehearse,
                                                       monkeypatch):
    from spark_rapids_tpu.sql.dataframe import DataFrame
    real = DataFrame.toArrow
    state = {"n": 0}

    def swapped(self, *a, **kw):
        out = real(self, *a, **kw)
        state["n"] += 1
        if state["n"] % 5 or out.num_rows < 2:
            return out
        order = [1, 0] + list(range(2, out.num_rows))
        return out.take(pa.array(order))

    monkeypatch.setattr(DataFrame, "toArrow", swapped)
    code, result, err = rehearse("session.q3", seconds=3)
    assert code == 0 and result["correct"] is False
    assert result["checks"]["exact_mismatches"]["value"] >= 1
    assert err.strip().splitlines()[-1] == "correct: False"
