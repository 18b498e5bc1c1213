"""The five metrics read from the ledger's books by plan node
(``node_books.py``) on synthetic ledgers of a Q18 and a Q3: each picks
its node by its place in the plan, the two aggregates add up to
``agg_host_ms.q18``, and every one reads None from a ledger without
``nodes``, which is what a program without the books by node gives."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

import book_readers
from conftest import BENCH

AGG, JOIN = "TpuHashAggregateExec", "TpuSortMergeJoinExec"


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(ledgers, monkeypatch):
    monkeypatch.setattr(book_readers, "_recent", lambda: ledgers)
    return {"requests": [SimpleNamespace(t_submit=0.0, t_done=100.0)]}


def _q18(t0, final_buckets=12, scale=1.0):
    """A Q18's ledger as the program keeps it: the flat books and the
    same by node (the final aggregate at ``0.0.0``, the sub-aggregate
    under the semi join; the joins at ``0.0.0.0`` (``lineitem``),
    ``0.0.0.0.0`` and ``0.0.0.0.0.1`` (the semi join))."""
    nodes = {
        "query": {"op": None, "stages_s": {"Plan:optimize": 0.001},
                  "counts": {}},
        "0.0.0": {"op": AGG, "counts": {
            "aggRepartitionBuckets": final_buckets},
            "stages_s": {f"{AGG}:mergeTime": 0.100 * scale,
                         f"{AGG}:repartitionTime": 0.030 * scale,
                         "Kernel.agg_merge:kernelLaunch": 0.005}},
        "0.0.0.0.0.1.1.0.0": {"op": AGG, "counts": {
            "aggRepartitionBuckets": 5, "splitChunks": 7},
            "stages_s": {f"{AGG}:mergeTime": 0.600 * scale,
                         f"{AGG}:partialTime": 0.280 * scale,
                         f"{AGG}:repartitionTime": 0.150 * scale,
                         "Kernel.split_cut:kernelLaunch": 0.050}},
        "0.0.0.0": {"op": JOIN, "counts": {"joinProbeGroups": 24},
                    "stages_s": {f"{JOIN}:opTime": 0.280 * scale,
                                 f"{JOIN}:gatherTime": 0.020 * scale}},
        "0.0.0.0.0": {"op": JOIN, "counts": {"joinProbeGroups": 1},
                      "stages_s": {f"{JOIN}:opTime": 0.010 * scale}},
        "0.0.0.0.0.1": {"op": JOIN, "counts": {"joinProbeGroups": 6},
                        "stages_s": {f"{JOIN}:opTime": 0.060 * scale}},
    }
    stages, counts = {}, {}
    for n in nodes.values():
        for k, s in n["stages_s"].items():
            stages[k] = stages.get(k, 0.0) + s
        for k, c in n["counts"].items():
            counts[k] = counts.get(k, 0) + c
    return {"t0_mono": t0, "t1_mono": t0 + 1.0, "stages_s": stages,
            "counts": counts, "nodes": nodes}


def _without_nodes(b):
    return {k: v for k, v in b.items() if k != "nodes"}


@pytest.mark.parametrize("name,want", [
    ("agg_sub_ms.q18", 1030.0),
    ("agg_final_ms.q18", 130.0),
    ("agg_final_repartition_buckets.q18", 12),
    ("join_lineitem_ms.q18", 300.0),
])
def test_q18_node_metric_reads_its_node(name, want, monkeypatch):
    run = _run([_q18(1.0), _q18(3.0), _q18(5.0, final_buckets=11,
                                             scale=2.0)], monkeypatch)
    assert _metric(name)(run) == pytest.approx(want)


def test_the_two_aggregates_add_up_to_agg_host_ms(monkeypatch):
    for b in (_q18(1.0), _q18(1.0, scale=1.7)):
        run = _run([b], monkeypatch)
        assert (_metric("agg_sub_ms.q18")(run)
                + _metric("agg_final_ms.q18")(run)) == pytest.approx(
                    _metric("agg_host_ms.q18")(run))
        assert (_metric("join_lineitem_ms.q18")(run)
                <= _metric("join_host_ms.q18")(run))


def test_a_final_aggregate_that_did_not_repartition_reads_zero(
        monkeypatch):
    b = _q18(1.0)
    del b["nodes"]["0.0.0"]["counts"]["aggRepartitionBuckets"]
    run = _run([b], monkeypatch)
    assert _metric("agg_final_repartition_buckets.q18")(run) == 0


def test_q3_join_nearest_the_root(monkeypatch):
    nodes = {
        "0.0.0.0": {"op": JOIN, "counts": {"joinProbeGroups": 22},
                    "stages_s": {f"{JOIN}:opTime": 0.31,
                                 "Kernel.join_match:kernelLaunch": 0.04}},
        "0.0.0.0.0": {"op": JOIN, "counts": {"joinProbeGroups": 3},
                      "stages_s": {f"{JOIN}:opTime": 0.05}},
    }
    b = {"t0_mono": 1.0, "t1_mono": 2.0, "nodes": nodes,
         "stages_s": {f"{JOIN}:opTime": 0.36,
                      "Kernel.join_match:kernelLaunch": 0.04}}
    run = _run([b], monkeypatch)
    assert _metric("join_lineitem_ms.q3")(run) == pytest.approx(310.0)
    assert _metric("join_host_ms.q3")(run) == pytest.approx(360.0)


def test_a_tie_in_depth_goes_to_the_first_in_pre_order(monkeypatch):
    nodes = {p: {"op": JOIN, "counts": {},
                 "stages_s": {f"{JOIN}:opTime": s}}
             for p, s in (("0.1", 0.2), ("0.0", 0.1), ("0.0.1", 0.4))}
    run = _run([{"t0_mono": 1.0, "t1_mono": 2.0, "nodes": nodes}],
               monkeypatch)
    assert _metric("join_lineitem_ms.q3")(run) == pytest.approx(100.0)


@pytest.mark.parametrize("name", [
    "agg_sub_ms.q18", "agg_final_ms.q18",
    "agg_final_repartition_buckets.q18", "join_lineitem_ms.q18",
    "join_lineitem_ms.q3"])
def test_a_ledger_without_nodes_reads_none(name, monkeypatch):
    run = _run([_without_nodes(_q18(1.0)), _without_nodes(_q18(3.0))],
               monkeypatch)
    assert _metric(name)(run) is None
    # and a ledger whose plan has no node of the class
    run = _run([{"t0_mono": 1.0, "t1_mono": 2.0, "nodes": {
        "query": {"op": None, "stages_s": {}, "counts": {}}}}],
        monkeypatch)
    assert _metric(name)(run) is None
