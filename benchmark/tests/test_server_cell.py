"""The served cell, ``server.throughput``, as ``BENCHMARK.json`` has it:
TPC-H's throughput test through ``QueryServer``, rehearsed end to end
(``tests/data/added/`` holds the proposal it grew from; that directory
and ``test_added_files.py`` are as they were)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELL = "server.throughput"
SERVED = {"device_idle_pct.server", "compiles_in_window.server",
          "queue_wait_ms.server", "semaphore_wait_ms.server",
          "books_per_answer_pct.server", "books_unaccounted_pct.server",
          "q6_served_p95_ms.server"}


def _rehearse(trace):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "4", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]


@pytest.fixture(scope="module")
def untraced():
    return _rehearse(0)


@pytest.fixture(scope="module")
def traced():
    return _rehearse(1)


def test_the_entries_are_as_the_issue_names_them():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf1_server", "throughput_streams", 1)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["scale_factor", "query_set", "bindings",
                                 "generator"]
    with open(os.path.join(ROOT, config["file"])) as f:
        on_disk = json.load(f)
    assert on_disk["source"] == config["source"]
    assert set(on_disk["reduced"]) == set(config["reduced"])
    assert on_disk["entry"] == "server" and on_disk["conf"] == {
        "spark.rapids.sql.enabled": True}
    assert {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]} == SERVED
    assert all(m["moves"] == "scan_query_s" for m in bench["per_layer"]
               if m["name"] in SERVED)
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "scan_query_s")["workloads"]


def test_untraced_line_has_the_two_end_to_end_metrics(untraced):
    result, phases = untraced
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "scan_query_s"}
    assert result["checks"]["unanswered"]["value"] == 0
    assert (result["checks"]["answers_compared"]["value"]
            == result["attempted"] > 0)
    setup = next(p for p in phases if p["phase"] == "setup")
    # every binding of the mix under each of the two tenants
    assert setup["warm_requests"] == 2 * (8 + 2 + 2)


def test_both_streams_count_whole_passes(untraced):
    _, phases = untraced
    window = next(p for p in phases if p["phase"] == "window")
    assert set(window["by_query"]) == {"q1", "q6", "q12"}
    assert len(set(window["by_query"].values())) == 1
    streams = [p for p in phases if p["phase"] == "stream"]
    assert len(streams) == 2
    assert all(s["requests"] % 3 == 0 and s["requests"] for s in streams)


def test_traced_line_has_every_served_metric(traced):
    result, _ = traced
    assert result["correct"] is True
    assert set(result["metrics"]) == SERVED
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["compiles_in_window.server"] == 0
    assert m["queue_wait_ms.server"] >= 0 and m["semaphore_wait_ms.server"] >= 0
    assert 0 <= m["device_idle_pct.server"] <= 100
    assert m["q6_served_p95_ms.server"] > 0


def test_every_answer_has_a_book_of_its_own_that_adds_up(traced):
    result, _ = traced
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["books_per_answer_pct.server"] == 100.0
    # SF0.01: a book is a few milliseconds long, so its gaps weigh more
    assert 0 <= m["books_unaccounted_pct.server"] < 10
