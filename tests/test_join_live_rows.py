"""A broadcast join sizes its probe by the streamed side's LIVE rows.

The streamed side of a broadcast join honours ``join.targetRows``: a
filtered stream keeps its scan buckets, so its capacity says nothing
about the work.  Over the cap by capacity and under it by live rows, the
join shrinks every batch to its live bucket and probes once, in-core
(``liveRowInCoreJoins``); over it by live rows it streams in bounded
groups as before (``streamedJoins``).  CPU platform, small tables, no
assertion on seconds."""

import os
import sys
import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import column as C
from spark_rapids_tpu.exec import join as J
from spark_rapids_tpu.sql.column import col
from spark_rapids_tpu.utils.harness import (
    assert_tpu_and_cpu_are_equal_collect, tpu_session)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CAP = 4096
N, NKEYS = 40_000, 300
# ten 4096-slot scan batches: 40 960 slots of capacity against a cap of
# 4 096 rows
CONF = {"spark.rapids.tpu.join.targetRows": CAP,
        "spark.rapids.tpu.batchRows": 4096}
# fact is 960 KB, dim 4.8 KB: between them only dim may be broadcast,
# whichever side it is on
CONF_LEFT = dict(CONF, **{"spark.sql.autoBroadcastJoinThreshold": 100_000})
# (join type, the broadcast side)
SHAPES = [("inner", "right"), ("left", "right"), ("left_semi", "right"),
          ("left_anti", "right"), ("inner", "left")]


def _tables(seed=30):
    """fact: N rows, a fifth of whose keys miss dim; ``tag`` is uniform
    over 0..99, so ``tag < t`` keeps t % of the rows."""
    rng = np.random.default_rng(seed)
    fact = pa.table({
        "k": pa.array(rng.integers(0, NKEYS + NKEYS // 4, N)),
        "v": pa.array(rng.uniform(-5, 5, N)),
        "tag": pa.array(rng.integers(0, 100, N))})
    dim = pa.table({"k": pa.array(np.arange(NKEYS, dtype=np.int64)),
                    "w": pa.array(rng.integers(0, 9, NKEYS))})
    return fact, dim


def _join(s, fact, dim, how, side, keep):
    f = s.createDataFrame(fact).filter(col("tag") < keep)
    d = s.createDataFrame(dim)
    return f.join(d, "k", how) if side == "right" else d.join(f, "k", how)


def _conf(side):
    return CONF if side == "right" else CONF_LEFT


def _find(node, name="TpuSortMergeJoinExec"):
    if type(node).__name__ == name:
        return node
    for c in node.children:
        got = _find(c, name)
        if got is not None:
            return got
    return None


def _group_slots(buckets):
    """Slots a group of ``_broadcast_streamed`` holds: the pow-2 bucket
    of its batches' capacities' sum, greedily under the cap."""
    out, acc = [], 0
    for k in buckets:
        if acc and acc + k > CAP:
            out.append(acc)
            acc = 0
        acc += k
    out.append(acc)
    return [C.round_up_pow2(a) for a in out]


def _counters(j):
    return {k: j.metric(k).value
            for k in ("liveRowInCoreJoins", "streamedJoins",
                      "subPartitionJoins")}


@pytest.fixture
def probes(monkeypatch):
    """(left capacity, right capacity) of every ``_merge_join``."""
    seen = []
    real = J.TpuSortMergeJoinExec._merge_join

    def spy(self, lb, rb, jt, probe):
        seen.append((lb.capacity, rb.capacity))
        return real(self, lb, rb, jt, probe)

    monkeypatch.setattr(J.TpuSortMergeJoinExec, "_merge_join", spy)
    return seen


@pytest.mark.parametrize("how,side", SHAPES)
def test_thinly_live_stream_joins_in_core_once(how, side, probes):
    fact, dim = _tables()
    # 5 % of 40 000: ~2 000 live rows in 40 960 slots
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _join(s, fact, dim, how, side, 5),
        conf=_conf(side), ignore_order=True, approx_float=True)
    del probes[:]
    s = tpu_session(_conf(side))
    df = _join(s, fact, dim, how, side, 5)
    df.toArrow()
    j = _find(df._last_plan)
    assert j.broadcast == side
    assert _counters(j) == {"liveRowInCoreJoins": 1, "streamedJoins": 0,
                            "subPartitionJoins": 0}
    # one group: one probe, the streamed side at its live bucket
    assert len(probes) == 1, probes
    streamed = probes[0][0 if side == "right" else 1]
    live = int(np.sum(fact.column("tag").to_numpy() < 5))
    assert live <= streamed <= CAP, (live, probes)


@pytest.mark.parametrize("how,side", SHAPES)
def test_stream_live_over_the_cap_still_streams(how, side, probes):
    fact, dim = _tables(seed=31)
    # half of 40 000 rows stay: 20 000 live rows against a cap of 4 096
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _join(s, fact, dim, how, side, 50),
        conf=_conf(side), ignore_order=True, approx_float=True)
    del probes[:]
    s = tpu_session(_conf(side))
    df = _join(s, fact, dim, how, side, 50)
    plan = df._execute_plan()
    j = _find(plan)
    assert j.broadcast == side
    caps = [b.capacity for p in range(j.num_partitions())
            for b in j.execute(p)]
    assert _counters(j) == {"liveRowInCoreJoins": 0, "streamedJoins": 1,
                            "subPartitionJoins": 0}
    # the groups of the capacity rule, cut from what the gather hands
    # on since PR 32: ten batches at their live buckets (2048 or 4096
    # slots for ~2 000 rows of 4 096), a group closed where the next
    # batch would pass the cap
    keep = fact.column("tag").to_numpy() < 50
    buckets = [C.live_bucket(int(keep[lo:lo + 4096].sum()), 4096)
               for lo in range(0, N, 4096)]
    groups = len(_group_slots(buckets))
    assert 5 < groups < 10 and set(buckets) == {2048, 4096}, buckets
    assert len(probes) == groups, probes
    assert sorted(p[0 if side == "right" else 1] for p in probes) == \
        sorted(_group_slots(buckets)), probes
    assert max(max(p) for p in probes) <= CAP, probes
    if how == "inner":
        # as test_broadcast_streamed_output_capacities_capped pins
        assert len(caps) > 1
        assert max(caps) <= CAP, caps


@pytest.mark.parametrize("how,side", SHAPES)
def test_all_dead_stream(how, side, probes):
    """Capacity over the cap and not one live row: one in-core join of
    an empty side, not ten probes of nothing."""
    fact, dim = _tables(seed=32)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _join(s, fact, dim, how, side, 0),
        conf=_conf(side), ignore_order=True, approx_float=True)
    del probes[:]
    s = tpu_session(_conf(side))
    df = _join(s, fact, dim, how, side, 0)
    assert df.toArrow().num_rows == 0
    j = _find(df._last_plan)
    assert _counters(j) == {"liveRowInCoreJoins": 1, "streamedJoins": 0,
                            "subPartitionJoins": 0}
    assert len(probes) == 1 and max(probes[0]) <= CAP, probes


@pytest.mark.parametrize("how,side", SHAPES)
def test_empty_stream(how, side):
    """No row and no capacity: the in-core path as it always was."""
    fact, dim = _tables(seed=33)
    fact = fact.slice(0, 0)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _join(s, fact, dim, how, side, 5),
        conf=_conf(side), ignore_order=True, approx_float=True)
    s = tpu_session(_conf(side))
    df = _join(s, fact, dim, how, side, 5)
    assert df.toArrow().num_rows == 0
    assert _counters(_find(df._last_plan)) == {
        "liveRowInCoreJoins": 0, "streamedJoins": 0, "subPartitionJoins": 0}


@pytest.mark.parametrize("how", ["inner", "left_anti"])
def test_lone_batch_is_cut_to_its_live_bucket(how, probes):
    """One scan batch over the cap: the concat would hand it back at its
    scan bucket, so the join cuts it to its live bucket itself."""
    fact, dim = _tables(seed=34)
    conf = dict(CONF, **{"spark.rapids.tpu.batchRows": 65536})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _join(s, fact, dim, how, "right", 5),
        conf=conf, ignore_order=True, approx_float=True)
    del probes[:]
    s = tpu_session(conf)
    df = _join(s, fact, dim, how, "right", 5)
    df.toArrow()
    j = _find(df._last_plan)
    assert _counters(j)["liveRowInCoreJoins"] == 1
    live = int(np.sum(fact.column("tag").to_numpy() < 5))
    assert len(probes) == 1 and live <= probes[0][0] <= CAP, probes


def test_thin_stream_is_not_sent_to_the_hash_split():
    """The in-core join reached by live rows reserves what it will hold
    (live buckets), not the scan capacity of slots it never gathers: a
    pool that holds the live rows many times over but not twice the
    scan buckets still joins in-core."""
    from spark_rapids_tpu.runtime import memory as M
    fact, dim = _tables(seed=35)
    # the ten scan batches are ~1 MB at capacity, ~60 KB at their live
    # buckets
    conf = dict(CONF, **{"spark.rapids.tpu.memory.poolSize": 1 << 20})
    M.reset_manager()
    try:
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: _join(s, fact, dim, "inner", "right", 5),
            conf=conf, ignore_order=True, approx_float=True)
        s = tpu_session(conf)
        df = _join(s, fact, dim, "inner", "right", 5)
        df.toArrow()
        assert _counters(_find(df._last_plan)) == {
            "liveRowInCoreJoins": 1, "streamedJoins": 0,
            "subPartitionJoins": 0}
    finally:
        M.reset_manager()


def test_counters_show_in_the_plan_metrics():
    fact, dim = _tables()
    s = tpu_session(CONF)
    df = _join(s, fact, dim, "inner", "right", 5)
    df.toArrow()
    got = dict(df._last_plan.collect_metrics(level="MODERATE"))
    assert got["TpuSortMergeJoinExec"]["liveRowInCoreJoins"] == 1


# -- the cell's query ---------------------------------------------------

def _bench_modules():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import compare
    import run
    import tpch_gen
    return run, tpch_gen, compare


@pytest.mark.parametrize("bi", [0, 1])
@pytest.mark.parametrize("scaled", [False, True])
def test_q14_with_the_cells_bindings(bi, scaled):
    """Q14 at SF0.01 against the benchmark's plain reference at 1e-9:
    under the cell's conf (one 65 536-slot batch, under the cap: the
    changed block is not reached) and with the cap and the batches
    scaled down with the tables, so that the month's ~700 rows sit in
    eight 8 192-slot batches as the cell's 75 k sit in six of 1 M."""
    run, tpch_gen, compare = _bench_modules()
    q = run.load_module("queries", "q14")
    b = run.load_json("traffic", "q14_stream.json")["bindings"]["q14"][bi]
    tables = tpch_gen.gen_tables(0.01, 2147483659, q.TABLES)
    conf = {"spark.rapids.sql.enabled": True}
    if scaled:
        conf.update({"spark.rapids.tpu.join.targetRows": 4096,
                     "spark.rapids.tpu.batchRows": 8192})
    df = q.build(tpu_session(conf), tables, b)
    c = compare.compare_tables(df.toArrow(), q.reference(tables, b))
    assert c["exact_mismatches"] == 0, c
    assert c["max_rel_err"] <= 1e-9, c
    assert df.fallback_summary()["fallback_ops"] == 0
    j = _find(df._last_plan)
    assert j.broadcast == "right"
    assert _counters(j) == {"liveRowInCoreJoins": int(scaled),
                            "streamedJoins": 0, "subPartitionJoins": 0}


# -- two queries at once ------------------------------------------------

def _rows(t: pa.Table):
    return sorted(zip(*(t.column(n).to_pylist() for n in t.column_names)))


def test_q14_shaped_beside_q12_shaped_join():
    """Two threads through one session: the broadcast join of a thinly
    live stream (Q14's shape) and a join no side of which may be
    broadcast, its fully live side streamed in groups against the
    filtered one (Q12's shape), give the answers they give alone."""
    fact, dim = _tables(seed=36)
    rng = np.random.default_rng(37)
    # Q12's shape: `orders` (fully live, 480 KB, over the broadcast
    # threshold and over the cap) against a filtered fact table
    orders = pa.table({"k": pa.array(np.arange(30_000, dtype=np.int64)),
                       "p": pa.array(rng.integers(0, 5, 30_000))})
    items = pa.table({"k": pa.array(rng.integers(0, 30_000, N)),
                      "tag": pa.array(rng.integers(0, 100, N))})
    s = tpu_session(CONF_LEFT)

    def q14():
        return _join(s, fact, dim, "inner", "right", 5)

    def q12():
        return (s.createDataFrame(items).filter(col("tag") < 5)
                .join(s.createDataFrame(orders), "k", "inner"))

    alone = {}
    for name, build in (("q14", q14), ("q12", q12)):
        df = build()
        alone[name] = _rows(df.toArrow())
        j = _find(df._last_plan)
        assert j.broadcast == ("right" if name == "q14" else None)
        assert _counters(j) == {
            "liveRowInCoreJoins": int(name == "q14"),
            "streamedJoins": int(name == "q12"), "subPartitionJoins": 0}
    assert alone["q14"] and alone["q12"]

    got, errors = {"q14": [], "q12": []}, []
    start = threading.Barrier(2)

    def client(name, build):
        try:
            start.wait(60)
            for _ in range(3):
                df = build()
                got[name].append((_rows(df.toArrow()),
                                  _counters(_find(df._last_plan))))
        except Exception as e:   # surfaced on the test's thread
            errors.append((name, e))

    threads = [threading.Thread(target=client, args=a)
               for a in (("q14", q14), ("q12", q12))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    for name in ("q14", "q12"):
        assert len(got[name]) == 3
        for rows, counters in got[name]:
            assert rows == alone[name]
            assert counters == {
                "liveRowInCoreJoins": int(name == "q14"),
                "streamedJoins": int(name == "q12"),
                "subPartitionJoins": 0}
