"""The cells PR 35 added, as ``BENCHMARK.json`` has them: ``session.q18``
(TPC-H Q18 through one session, with the six ``.q18`` metrics read from
the ledger's books by operator) and ``session.q1_sf10`` (Q1 over the
SF10 configuration), rehearsed end to end; the ``.q18`` readers on a
ledger that lacks the new keys; planted faults in a Q18 answer."""

import json
import os
import subprocess
import sys

import pyarrow as pa
import pytest

from conftest import ROOT

Q18_METRICS = {"agg_repartition_buckets.q18", "agg_partial_rows.q18",
               "agg_repartition_ms.q18", "agg_host_ms.q18",
               "join_host_ms.q18", "join_probe_groups.q18"}
# a rehearsal's 60 000 line items are one batch and one partial: its
# merge pulls no counts and has nothing to repartition
# (tests/test_tpch_q18.py runs both at test size)
AT_SF001 = {"agg_host_ms.q18", "join_host_ms.q18", "join_probe_groups.q18"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
SUFFIXES = {"query_s": ".session", "scan_query_s": ".scan"}


def _e2e(cell):
    """The one seconds-a-query metric the cell reports (ISSUE 35, by
    ISSUE 33's rule: query_s if its runs spread under half of that
    bound, else scan_query_s)."""
    name, = [m["name"] for m in BENCH["end_to_end"]
             if m["name"] != "setup_s" and cell in m["workloads"]]
    return name


def _shared(cell):
    return {q + SUFFIXES[_e2e(cell)] for q in (
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
def q18_traced():
    return _rehearse("session.q18", 1)


@pytest.mark.parametrize("cell,config,traffic", [
    ("session.q18", "tpch_sf1_q18_session", "q18_stream"),
    ("session.q1_sf10", "tpch_sf10_session", "q1_stream")])
def test_the_cells_are_as_the_issue_names_them(cell, config, traffic):
    by_name = {w["name"]: w for w in BENCH["workloads"]}
    w = by_name[cell]
    assert (w["config"], w["traffic"], w["chips"]) == (config, traffic, 1)
    e2e = _e2e(cell)
    mine = {m["name"] for m in BENCH["per_layer"] if cell in m["workloads"]}
    own = Q18_METRICS if cell == "session.q18" else set()
    assert mine == _shared(cell) | own | {
        "hbm_roofline_pct" + SUFFIXES[e2e]}
    assert all(m["moves"] == e2e for m in BENCH["per_layer"]
               if cell in m["workloads"])
    assert "server.dash_only" not in by_name


def test_the_q18_configuration_and_traffic_are_as_the_issue_names_them():
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "tpch_sf1_q18_session")
    assert config["reduced"] == ["scale_factor", "bindings", "generator"]
    with open(os.path.join(ROOT, config["file"])) as f:
        on_disk = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/configs/tpch_sf1_q3_session.json")) as f:
        q3 = json.load(f)
    assert on_disk["source"] == config["source"]
    assert "cl. 2.4.18" in config["source"]
    assert set(on_disk["reduced"]) == set(config["reduced"])
    assert set(on_disk) == set(q3)
    assert on_disk["guarantees"] == q3["guarantees"]
    assert on_disk["entry"] == "session" and on_disk["conf"] == {
        "spark.rapids.sql.enabled": True}
    assert (on_disk["scale_factor"], on_disk["chips"]) == (1.0, 1)
    assert {m["name"] for m in BENCH["per_layer"]
            if m.get("workloads") == ["session.q18"]} == Q18_METRICS
    assert all(m["source"] == "program_counter" for m in BENCH["per_layer"]
               if m["name"] in Q18_METRICS)
    with open(os.path.join(ROOT, "benchmark/traffic/q18_stream.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed"
    assert mix["streams"] == [
        {"cls": "query", "count": 1, "queries": ["q18"]}]
    assert mix["bindings"]["q18"] == [{"quantity": 313}, {"quantity": 315}]
    assert mix["trace_slice"] == {"start_s": 1.0, "min_s": 2.0,
                                  "whole_queries": 1, "max_s": 20.0}


def test_session_q18_rehearses_correct_with_its_metrics(q18_traced):
    result, phases = q18_traced
    assert result["correct"] is True
    assert set(result["metrics"]) == _shared("session.q18") | AT_SF001
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["join_probe_groups.q18"] == 3       # SF0.01: one probe a join
    for name in ("join_host_ms.q18", "agg_host_ms.q18"):
        assert m[name] > 0
    assert m["compiles_in_window" + SUFFIXES[_e2e("session.q18")]] == 0


@pytest.mark.parametrize("cell", ["session.q18", "session.q1_sf10"])
def test_the_untraced_line_has_the_two_end_to_end_metrics(cell):
    result, _ = _rehearse(cell, 0, seconds=2)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", _e2e(cell)}


def test_session_q1_sf10_rehearses_correct_with_its_metrics():
    result, _ = _rehearse("session.q1_sf10", 1)
    assert result["correct"] is True
    assert set(result["metrics"]) == _shared("session.q1_sf10")


def test_q18_metrics_read_nothing_from_a_ledger_without_the_new_keys():
    import run
    old = {"buckets": {"kernel_dispatch": 1.0}, "e2e_s": 1.0,
           "unaccounted_s": 0.0, "launches": 3}
    # the parent's ledger of a Q18: books by operator, none of the
    # merge's counts, the split's time inside mergeTime
    parent = dict(old, stages_s={"TpuSortMergeJoinExec:opTime": 0.5,
                                 "TpuHashAggregateExec:mergeTime": 0.375},
                  counts={"joinProbeGroups": 30})
    new = dict(old, stages_s={"TpuSortMergeJoinExec:opTime": 0.5,
                              "TpuSortMergeJoinExec:gatherTime": 0.25,
                              "TpuHashAggregateExec:mergeTime": 0.125,
                              "TpuHashAggregateExec:repartitionTime": 0.25,
                              "Kernel.split_sort:kernelLaunch": 0.01},
               counts={"joinProbeGroups": 30, "aggPartialRows": 4366000,
                       "aggRepartitionBuckets": 5})
    want = {"agg_repartition_buckets.q18": 5,
            "agg_partial_rows.q18": 4366000,
            "agg_repartition_ms.q18": 250.0, "agg_host_ms.q18": 375.0,
            "join_host_ms.q18": 750.0, "join_probe_groups.q18": 30}
    on_parent = {"agg_host_ms.q18": 375.0, "join_host_ms.q18": 500.0,
                 "join_probe_groups.q18": 30}
    for name in sorted(Q18_METRICS):
        read = run.load_module("metrics", name).read
        assert read({"_books": [old]}) is None
        assert read({"_books": []}) is None
        assert read({"_books": [parent]}) == on_parent.get(name)
        assert read({"_books": [new, new, old]}) == want[name]


def _tampered(monkeypatch, alter):
    """Every fifth answer of the window goes through ``alter``."""
    from spark_rapids_tpu.sql.dataframe import DataFrame
    real = DataFrame.toArrow
    state = {"n": 0}

    def tampered(self, *a, **kw):
        out = real(self, *a, **kw)
        state["n"] += 1
        return out if state["n"] % 5 or out.num_rows < 2 else alter(out)

    monkeypatch.setattr(DataFrame, "toArrow", tampered)


def test_a_swapped_pair_of_the_hundred_rows_is_not_correct(rehearse,
                                                           monkeypatch):
    _tampered(monkeypatch, lambda out: out.take(
        pa.array([1, 0] + list(range(2, out.num_rows)))))
    code, result, err = rehearse("session.q18", seconds=3)
    assert code == 0 and result["correct"] is False
    assert result["checks"]["exact_mismatches"]["value"] >= 1
    assert err.strip().splitlines()[-1] == "correct: False"


def test_a_sum_altered_by_a_millionth_is_not_correct(rehearse, monkeypatch):
    def alter(out):
        qty = out.column("sum_qty").to_pylist()
        qty[-1] *= 1 + 1e-6
        at = out.schema.get_field_index("sum_qty")
        return out.set_column(at, "sum_qty", pa.array(qty, pa.float64()))

    _tampered(monkeypatch, alter)
    code, result, err = rehearse("session.q18", seconds=3)
    assert code == 0 and result["correct"] is False
    assert result["checks"]["exact_mismatches"]["value"] == 0
    assert 5e-7 < result["checks"]["max_rel_err"]["value"] < 2e-6
    assert err.strip().splitlines()[-1] == "correct: False"
