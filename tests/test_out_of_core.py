"""Out-of-core sort and join sub-partitioning under a tight budget.

VERDICT r2 #6 'done' criterion: operator tests pass with poolSize forced
below working-set size, actually exercising spill
(spillToHostBytes > 0).  [REF: GpuOutOfCoreSortIterator,
GpuSubPartitionHashJoin]
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.runtime import memory as M
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.column import col
from spark_rapids_tpu.utils.harness import (
    assert_tpu_and_cpu_are_equal_collect, tpu_session)


@pytest.fixture(autouse=True)
def _fresh_manager():
    M.reset_manager()
    from spark_rapids_tpu.exec.basic import clear_scan_cache
    clear_scan_cache()
    yield
    M.reset_manager()
    clear_scan_cache()


def _sort_table(n=60_000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "a": pa.array(rng.integers(-10**6, 10**6, n)),
        "b": pa.array(rng.uniform(-1000, 1000, n)),
    })


def _find(node, name):
    if type(node).__name__ == name:
        return node
    for c in node.children:
        r = _find(c, name)
        if r is not None:
            return r
    return None


def test_out_of_core_sort_matches_oracle_and_spills():
    t = _sort_table()
    # table ~960 KB; budget 400 KB forces the range-partitioned path
    pool = 400 << 10
    conf = {"spark.rapids.tpu.memory.poolSize": pool,
            "spark.rapids.tpu.batchRows": 8192}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy("a", "b"),
        conf=conf, approx_float=True)
    mgr = M.get_manager()
    assert mgr.metrics["spillToHostBytes"] > 0, mgr.metrics


def test_out_of_core_sort_streams_multiple_batches():
    t = _sort_table(40_000, seed=5)
    s = tpu_session({"spark.rapids.tpu.memory.poolSize": 300 << 10,
                     "spark.rapids.tpu.batchRows": 8192})
    df = s.createDataFrame(t).orderBy("a")
    out = df.toArrow()
    assert out.column("a").to_pylist() == sorted(t.column("a").to_pylist())
    sort_node = _find(df._last_plan, "TpuSortExec")
    assert sort_node.metric("outOfCoreSorts").value == 1
    assert sort_node.metric("numOutputBatches").value > 1


def test_in_core_sort_unchanged_with_room():
    t = _sort_table(5000, seed=6)
    s = tpu_session({})
    df = s.createDataFrame(t).orderBy("a")
    df.toArrow()
    sort_node = _find(df._last_plan, "TpuSortExec")
    assert sort_node.metric("outOfCoreSorts").value == 0
    assert sort_node.metric("numOutputBatches").value == 1


def _join_tables(n=40_000, m=20_000, seed=9):
    rng = np.random.default_rng(seed)
    left = pa.table({
        "k": pa.array(rng.integers(0, 5000, n)),
        "v": pa.array(rng.uniform(-10, 10, n)),
    })
    right = pa.table({
        "k": pa.array(rng.integers(0, 6000, m)),
        "w": pa.array(rng.integers(-100, 100, m)),
    })
    return left, right


@pytest.mark.parametrize("how", ["inner", "left", "full", "left_semi",
                                 "left_anti"])
def test_sub_partitioned_join_matches_oracle(how):
    l, r = _join_tables()
    conf = {"spark.rapids.tpu.memory.poolSize": 500 << 10,
            "spark.sql.autoBroadcastJoinThreshold": 0,
            "spark.rapids.tpu.batchRows": 8192}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k",
                                            how),
        conf=conf, ignore_order=True, approx_float=True)


def test_sub_partitioned_join_spills_and_counts():
    l, r = _join_tables(seed=11)
    s = tpu_session({"spark.rapids.tpu.memory.poolSize": 500 << 10,
                     "spark.sql.autoBroadcastJoinThreshold": 0,
                     "spark.rapids.tpu.batchRows": 8192})
    df = s.createDataFrame(l).join(s.createDataFrame(r), "k", "inner")
    out = df.toArrow()
    assert out.num_rows > 0
    j = _find(df._last_plan, "TpuSortMergeJoinExec")
    assert j.metric("subPartitionJoins").value == 1
    mgr = M.get_manager()
    assert mgr.metrics["spillToHostBytes"] > 0, mgr.metrics


def test_sub_partitioned_right_join():
    l, r = _join_tables(seed=13)
    conf = {"spark.rapids.tpu.memory.poolSize": 500 << 10,
            "spark.sql.autoBroadcastJoinThreshold": 0}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k",
                                            "right"),
        conf=conf, ignore_order=True, approx_float=True)


# -- proactive (size-driven) sub-partitioning + output re-batching ----------
# [REF: GpuSubPartitionHashJoin — the reference's trigger is build-size
# driven; VERDICT r3 #1: never compile a sort/join kernel above the cap]

@pytest.mark.parametrize("how", ["inner", "left", "full", "right"])
def test_proactive_sub_partition_join_matches_oracle(how):
    l, r = _join_tables(n=30_000, m=24_000, seed=21)
    conf = {"spark.sql.autoBroadcastJoinThreshold": 0,
            "spark.rapids.tpu.join.targetRows": 4096,
            "spark.rapids.tpu.batchRows": 8192}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k",
                                            how),
        conf=conf, ignore_order=True, approx_float=True)


def test_proactive_trigger_is_row_driven_not_oom():
    """With a roomy memory pool, the row cap alone must route the join
    through sub-partitioning (q10's 75-min compile had no OOM)."""
    l, r = _join_tables(n=50_000, m=40_000, seed=22)
    s = tpu_session({"spark.sql.autoBroadcastJoinThreshold": 0,
                     "spark.rapids.tpu.join.targetRows": 8192,
                     "spark.rapids.tpu.batchRows": 8192})
    df = s.createDataFrame(l).join(s.createDataFrame(r), "k", "inner")
    out = df.toArrow()
    assert out.num_rows > 0
    j = _find(df._last_plan, "TpuSortMergeJoinExec")
    assert j.metric("subPartitionJoins").value == 1
    mgr = M.get_manager()
    assert mgr.metrics["spillToHostBytes"] == 0, (
        "row-driven trigger must not require memory pressure")


def test_join_output_rebatched_to_batch_rows():
    """A high-multiplicity join's expanded output arrives as
    batchRows-bucket chunks, not one giant bucket."""
    rng = np.random.default_rng(23)
    n = 20_000
    left = pa.table({"k": pa.array(rng.integers(0, 50, n)),
                     "v": pa.array(rng.uniform(-1, 1, n))})
    right = pa.table({"k": pa.array(np.arange(50).repeat(8)),
                      "w": pa.array(np.arange(400, dtype=np.int64))})
    s = tpu_session({"spark.sql.autoBroadcastJoinThreshold": 0,
                     "spark.rapids.tpu.batchRows": 16384})
    ldf = s.createDataFrame(left)
    rdf = s.createDataFrame(right)
    df = ldf.join(rdf, "k", "inner")
    plan = df._execute_plan()
    j = _find(plan, "TpuSortMergeJoinExec")
    caps = [b.capacity for p in range(j.num_partitions())
            for b in j.execute(p)]
    # ~160k output rows: must arrive as 16k-capacity chunks
    assert len(caps) > 1
    assert max(caps) <= 16384, caps
    out = df.toArrow()
    cpu = tpu_session({"spark.rapids.sql.enabled": False})
    exp = (cpu.createDataFrame(left).join(cpu.createDataFrame(right),
                                          "k", "inner").toArrow())
    assert out.num_rows == exp.num_rows


@pytest.mark.parametrize("how", ["inner", "left", "left_semi",
                                 "left_anti"])
def test_streamed_join_small_right_side(how):
    """Runtime strategy pick: left exceeds targetRows, right fits —
    stream the left in bounded groups against the fully-present right.
    Regression: the group loop consulted ``self.broadcast`` (None on
    these plans) instead of the per-side override, so the 'broadcast'
    batch was built from the STREAMED side's list against the other
    side's schema — the TPC-H q7 SF1 IndexError."""
    l, r = _join_tables(n=30_000, m=3_000, seed=41)
    conf = {"spark.sql.autoBroadcastJoinThreshold": 0,
            "spark.rapids.tpu.join.targetRows": 4096,
            "spark.rapids.tpu.batchRows": 8192}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k",
                                            how),
        conf=conf, ignore_order=True, approx_float=True)


def test_streamed_join_small_left_side():
    l, r = _join_tables(n=3_000, m=30_000, seed=43)
    s = tpu_session({"spark.sql.autoBroadcastJoinThreshold": 0,
                     "spark.rapids.tpu.join.targetRows": 4096,
                     "spark.rapids.tpu.batchRows": 8192})
    df = s.createDataFrame(l).join(s.createDataFrame(r), "k", "inner")
    out = df.toArrow()
    j = _find(df._last_plan, "TpuSortMergeJoinExec")
    assert j.metric("streamedJoins").value == 1
    cpu = tpu_session({"spark.rapids.sql.enabled": False})
    exp = (cpu.createDataFrame(l).join(cpu.createDataFrame(r), "k",
                                       "inner").toArrow())
    assert out.num_rows == exp.num_rows


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
def test_semi_stream_right_oversized_right_side(how):
    """Regression: semi/anti with a small left and an oversized right
    routes to ``_semi_stream_right``, which was referenced but never
    defined (AttributeError on TPC-H q4 SF1).  The streamed path must
    OR-accumulate matches across bounded right groups and agree with
    the in-core oracle."""
    l, r = _join_tables(n=3_000, m=30_000, seed=47)
    conf = {"spark.sql.autoBroadcastJoinThreshold": 0,
            "spark.rapids.tpu.join.targetRows": 4096,
            "spark.rapids.tpu.batchRows": 8192}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k",
                                            how),
        conf=conf, ignore_order=True, approx_float=True)
    s = tpu_session(conf)
    df = s.createDataFrame(l).join(s.createDataFrame(r), "k", how)
    df.toArrow()
    j = _find(df._last_plan, "TpuSortMergeJoinExec")
    assert j.metric("streamedJoins").value == 1


def test_skewed_sub_partition_recurses_and_matches():
    """Low-cardinality keys defeat one split level; the re-split with a
    fresh seed (and, for a single hot key, the bounded-depth in-core
    fallback) must stay correct."""
    rng = np.random.default_rng(31)
    n = 20_000
    for nkeys in (1, 3):  # 1 = unsplittable hot key; 3 = skew-spreads
        left = pa.table({"k": pa.array(rng.integers(0, nkeys, n)),
                         "v": pa.array(rng.uniform(-1, 1, n))})
        right = pa.table({"k": pa.array(np.arange(nkeys, dtype=np.int64)),
                          "w": pa.array(np.arange(nkeys, dtype=np.int64))})
        conf = {"spark.sql.autoBroadcastJoinThreshold": 0,
                "spark.rapids.tpu.join.targetRows": 4096,
                "spark.rapids.tpu.batchRows": 8192}
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: s.createDataFrame(left).join(
                s.createDataFrame(right), "k", "inner"),
            conf=conf, ignore_order=True, approx_float=True)


def _merge_tables(kind, n=40_000, seed=71):
    rng = np.random.default_rng(seed)
    k = rng.permutation(n) // 2                  # two rows a key
    cols = {"v": pa.array(rng.uniform(1, 50, n)),
            "w": pa.array(rng.integers(0, 100, n))}
    if kind == "long":
        return pa.table({"k": pa.array(k), **cols}), ["k"]
    if kind == "string":
        names = np.char.add("Customer#", np.char.zfill(k.astype(str), 9))
        return pa.table({"name": pa.array(names.tolist()), **cols}), ["name"]
    return pa.table({"name": pa.array([f"c{i % 7}" for i in k]),
                     "k": pa.array(k), **cols}), ["name", "k"]


@pytest.mark.parametrize("kind", ["long", "string", "string_and_long"])
def test_merge_bounded_one_concat_equals_the_repartition_fallback(
        kind, monkeypatch):
    """``_merge_bounded``'s two branches on the same partials: merged by
    one concat (what it does while they fit twice a batch) and by the
    repartition fallback (what it does here, over that), they give the
    same groups, sums and counts; the fallback's books say how it cut
    them."""
    from spark_rapids_tpu.columnar.column import device_to_host
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import concat_device_batches
    from spark_rapids_tpu.runtime import attribution
    t, keys = _merge_tables(kind)
    real = TpuHashAggregateExec._merge_bounded
    seen = {}

    def both(self, partials, merge_fn):
        seen["partials"] = len(partials)
        seen["one"] = device_to_host(merge_fn(concat_device_batches(
            self._buffer_schema(), list(partials))))
        out = real(self, partials, merge_fn)
        seen["many"] = pa.concat_tables([device_to_host(b) for b in out])
        return out

    monkeypatch.setattr(TpuHashAggregateExec, "_merge_bounded", both)
    s = tpu_session({"spark.rapids.tpu.batchRows": 4096})
    df = (s.createDataFrame(t).groupBy(*keys)
          .agg(F.sum("v").alias("sv"), F.sum("w").alias("sw"),
               F.count("*").alias("c")))
    out = df.toArrow()
    agg = _find(df._last_plan, "TpuHashAggregateExec")
    counts = attribution.recent()[-1]["counts"]
    # 40 000 rows in ten batches: the first keeps 0.95 groups a row,
    # over the ratio at which passes are skipped, so the nine after it
    # hand the merge their rows as they are
    assert seen["partials"] == counts["aggPartials"] == 10
    assert counts["skippedAggPasses"] == 1
    first = len(set(t.column(keys[-1]).to_pylist()[:4096]))
    assert 0.9 * 4096 < first < 4096
    assert counts["aggPartialRows"] == first + 40_000 - 4096
    k = -(-counts["aggPartialRows"] // 4096)
    assert agg.metric("repartitionMerges").value == 1
    assert counts["aggRepartitionBuckets"] == k
    assert counts["splitChunks"] == 1
    assert counts["spillableSlices"] == k
    order = [(name, "ascending") for name in keys]
    one, many = seen["one"].sort_by(order), seen["many"].sort_by(order)
    assert one.num_rows == many.num_rows == out.num_rows == 20_000
    for name in one.column_names:
        if name == "sv":        # two addends a group, in either order
            np.testing.assert_allclose(one.column(name).to_numpy(),
                                       many.column(name).to_numpy(),
                                       rtol=1e-15)
        else:
            assert one.column(name).equals(many.column(name)), name


def test_what_the_arbiter_spills_inside_a_query_is_in_its_books():
    from spark_rapids_tpu.runtime import attribution
    t = _sort_table()
    s = tpu_session({"spark.rapids.tpu.memory.poolSize": 400 << 10,
                     "spark.rapids.tpu.batchRows": 8192})
    assert s.createDataFrame(t).orderBy("a", "b").toArrow().num_rows == 60_000
    spilled = M.get_manager().metrics["spillToHostBytes"]
    assert spilled > 0
    assert attribution.recent()[-1]["counts"]["spilledBytes"] == spilled
