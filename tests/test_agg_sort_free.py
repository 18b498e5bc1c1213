"""The grouped aggregate's sort-free partial pass.

When the first batch's partial holds at most ``SORT_FREE_MAX_GROUPS``
groups, every later batch folds into a running accumulator of those
groups by masked reductions (``sort_free_groupby``), with no sort and no
row movement; rows whose key the first batch lacked take the sort path
and join the merge.  Each case runs the same batches three ways: the
CPU oracle, the sort path (``SORT_FREE_MAX_GROUPS`` 0) and the sort-free
pass, and reads the pass's counts from the query's books.
"""

import decimal
import re

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec import aggregate as AG
from spark_rapids_tpu.runtime import attribution
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.column import col
from spark_rapids_tpu.utils.asserts import assert_tables_equal
from spark_rapids_tpu.utils.harness import cpu_session, tpu_session

ROWS = 4096  # a batch: the least that establishes a group count
CONF = {"spark.rapids.tpu.batchRows": ROWS}


def _collect(table, build):
    out = build(tpu_session(CONF).createDataFrame(table)).toArrow()
    return out, attribution.recent()[-1]["counts"]


def _same(a: pa.Table, b: pa.Table):
    """Keys, counts and null/NaN places equal; doubles to 1e-12 (the
    two paths add in another order)."""
    assert a.column_names == b.column_names and a.num_rows == b.num_rows
    for name in a.column_names:
        x, y = a.column(name).to_pylist(), b.column(name).to_pylist()
        if pa.types.is_floating(a.schema.field(name).type):
            assert [v is None for v in x] == [v is None for v in y], name
            np.testing.assert_allclose(
                np.array([np.nan if v is None else v for v in x]),
                np.array([np.nan if v is None else v for v in y]),
                rtol=1e-12, err_msg=name)
        else:
            assert x == y, name


def _three_ways(table, build, monkeypatch):
    """(sort-free answer, its counts), checked against the oracle and
    against the sort path on the same batches."""
    free, counts = _collect(table, build)
    oracle = build(cpu_session(CONF).createDataFrame(table)).toArrow()
    assert_tables_equal(oracle, free, approx_float=True, rel=1e-9)
    with monkeypatch.context() as m:
        m.setattr(AG, "SORT_FREE_MAX_GROUPS", 0)
        sort, sort_counts = _collect(table, build)
    assert "aggSortFreeBatches" not in sort_counts
    _same(sort, free)
    return free, counts


def _q1_shaped(n=5 * ROWS, seed=39):
    rng = np.random.default_rng(seed)
    q = rng.uniform(1, 50, n)
    return pa.table({
        "flag": rng.choice(["A", "N", "R"], n).tolist(),
        "status": rng.choice(["F", "O"], n).tolist(),
        "qty": pa.array(q, mask=rng.random(n) < 0.1),
        "price": rng.uniform(900, 1e5, n),
        "disc": rng.uniform(0, 0.1, n),
        "id": np.arange(n)})


def _q1_agg(df):
    dp = col("price") * (1 - col("disc"))
    return (df.groupBy("flag", "status")
            .agg(F.sum("qty").alias("sum_qty"), F.sum(dp).alias("sum_disc"),
                 F.avg("qty").alias("avg_qty"), F.avg("disc").alias("avg_d"),
                 F.count("qty").alias("n_qty"), F.count("*").alias("cnt"))
            .orderBy("flag", "status"))


def _null_key():
    t = _q1_shaped()
    rng = np.random.default_rng(5)
    flag = pa.array(t.column("flag").to_pylist(),
                    mask=rng.random(t.num_rows) < 0.2)
    return t.set_column(0, "flag", flag), _q1_agg


def _nan_and_negative_zero():
    n = 4 * ROWS
    rng = np.random.default_rng(11)
    keys = np.array([np.nan, -0.0, 0.0, 1.5, 2.5])[rng.integers(0, 5, n)]
    v = rng.choice([np.nan, -0.0, 0.0, -3.25, 7.0], n)
    v[keys == 2.5] = np.nan  # a group whose every value is NaN
    t = pa.table({"k": pa.array(keys, mask=rng.random(n) < 0.1),
                  "v": pa.array(v, mask=rng.random(n) < 0.1),
                  "i": rng.integers(-9, 9, n)})

    def build(df):
        return (df.groupBy("k")
                .agg(F.min("v").alias("mn"), F.max("v").alias("mx"),
                     F.min("i").alias("mi"), F.max("i").alias("xi"),
                     F.sum("v").alias("s"), F.count("*").alias("c"))
                .orderBy("k"))
    return t, build


def _a_batch_filtered_out():
    # the third batch keeps no row: it folds nothing, and counts
    def build(df):
        return _q1_agg(df.filter((col("id") < 2 * ROWS)
                                 | (col("id") >= 3 * ROWS)))
    return _q1_shaped(), build


CASES = {"q1_shaped": lambda: (_q1_shaped(), _q1_agg),
         "null_key": _null_key,
         "nan_and_negative_zero": _nan_and_negative_zero,
         "a_batch_filtered_out": _a_batch_filtered_out}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sort_free_pass_equals_oracle_and_sort_path(case, monkeypatch):
    table, build = CASES[case]()
    _, counts = _three_ways(table, build, monkeypatch)
    batches = -(-table.num_rows // ROWS)
    assert counts["aggSortFreeBatches"] == batches - 1
    assert "aggSortFreeFallbacks" not in counts
    assert counts["aggPartials"] == 1  # the accumulator, nothing beside


def test_a_key_the_first_batch_lacked_takes_the_sort_path(monkeypatch):
    t = _q1_shaped()
    flag = t.column("flag").to_pylist()
    flag[3 * ROWS + 17] = "Z"  # in the fourth batch only
    t = t.set_column(0, "flag", pa.array(flag))
    out, counts = _three_ways(t, _q1_agg, monkeypatch)
    assert counts["aggSortFreeBatches"] == 4
    assert counts["aggSortFreeFallbacks"] == 1
    assert counts["aggPartials"] == 2  # accumulator + the one row's partial
    assert out.column("flag").to_pylist().count("Z") == 1


def test_a_batch_whose_keys_are_wider_takes_the_sort_path(monkeypatch):
    # a 12-byte key in the fourth batch makes its key column 16 bytes
    # wide; its row is filtered out, so every live key is a known one
    t = _q1_shaped()
    flag = t.column("flag").to_pylist()
    flag[3 * ROWS + 5] = "LONGER_THAN8"
    t = t.set_column(0, "flag", pa.array(flag))

    def build(df):
        return _q1_agg(df.filter(col("id") != 3 * ROWS + 5))
    _, counts = _three_ways(t, build, monkeypatch)
    assert counts["aggSortFreeBatches"] == 3
    assert counts["aggSortFreeFallbacks"] == 1
    assert counts["aggPartials"] == 2


@pytest.mark.parametrize("over", [0, 1])
def test_the_group_cap_decides(over, monkeypatch):
    g = AG.SORT_FREE_MAX_GROUPS + over
    n = 3 * ROWS
    t = pa.table({"k": np.arange(n) % g,
                  "v": np.random.default_rng(g).uniform(0, 1, n)})

    def build(df):
        return (df.groupBy("k").agg(F.sum("v").alias("s"),
                                    F.max("v").alias("m"),
                                    F.count("*").alias("c")).orderBy("k"))
    free, counts = _collect(t, build)
    oracle = build(cpu_session(CONF).createDataFrame(t)).toArrow()
    assert_tables_equal(oracle, free, approx_float=True, rel=1e-9)
    assert free.num_rows == g
    if over:
        assert "aggSortFreeBatches" not in counts
        assert counts["aggPartials"] == 3
    else:
        assert counts["aggSortFreeBatches"] == 2
        assert counts["aggPartials"] == 1


def test_a_first_batch_filtered_out_keeps_the_sort_path():
    # the filter keeps no row of the first batch: no group to fold into
    t = _q1_shaped(3 * ROWS)

    def build(df):
        return _q1_agg(df.filter(col("id") >= ROWS))
    out, counts = _collect(t, build)
    oracle = build(cpu_session(CONF).createDataFrame(t)).toArrow()
    assert_tables_equal(oracle, out, approx_float=True, rel=1e-9)
    assert "aggSortFreeBatches" not in counts
    assert counts["aggPartials"] == 3


def _first_agg(df):
    return (df.groupBy("flag").agg(F.first("price").alias("f"),
                                   F.sum("qty").alias("s"))
            .orderBy("flag"))


def _decimal_sum(df):
    return (df.groupBy("flag").agg(F.sum("dec").alias("s"))
            .orderBy("flag"))


@pytest.mark.parametrize("build", [_first_agg, _decimal_sum],
                         ids=["first", "decimal128_sum"])
def test_buffers_it_does_not_reduce_keep_the_sort_path(build):
    t = _q1_shaped(3 * ROWS)
    # decimal(12, 2) sums into decimal(22, 2): a 128-bit buffer
    dec = pa.array([decimal.Decimal(int(x)).scaleb(-2) for x in
                    np.random.default_rng(3).integers(-10**9, 10**9,
                                                      t.num_rows)],
                   type=pa.decimal128(12, 2))
    t = t.append_column("dec", dec)
    out, counts = _collect(t, build)
    assert "aggSortFreeBatches" not in counts
    assert counts["aggPartials"] == 3
    if build is _decimal_sum:  # first() of a batch order is not Spark's
        oracle = build(cpu_session(CONF).createDataFrame(t)).toArrow()
        assert_tables_equal(oracle, out)


def test_the_decision_pulls_no_count_of_its_own(monkeypatch):
    """The pass decides on the group count the skip ratio already
    pulled: ``_partial_stream`` makes as many live-count round trips
    either way, and the merge fewer (one partial, not one a batch)."""
    import sys
    from spark_rapids_tpu.exec import basic
    real = basic._overlapped_live_counts
    calls = []

    def counting(batches):
        calls.append(sys._getframe(1).f_code.co_name)
        return real(batches)

    monkeypatch.setattr(basic, "_overlapped_live_counts", counting)
    table = _q1_shaped()
    _collect(table, _q1_agg)
    free = list(calls)
    calls.clear()
    with monkeypatch.context() as m:
        m.setattr(AG, "SORT_FREE_MAX_GROUPS", 0)
        _collect(table, _q1_agg)
    sort = list(calls)
    deciding = ("_partial_stream", "_first_groups")
    assert ([c for c in free if c in deciding]
            == [c for c in sort if c in deciding]
            == ["_partial_stream", "_first_groups"])
    assert len(free) < len(sort)


def test_staged_partial_mode_ships_the_accumulator(monkeypatch):
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.column import (
        device_to_host, host_to_device)
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.ops.aggregates import CountStar, Min, Sum
    from spark_rapids_tpu.ops.expressions import BoundReference as R

    class Batches(TpuExec):
        def __init__(self, batches):
            super().__init__(batches[0].schema)
            self.batches = batches

        def execute(self, partition):
            yield from self.batches

    rng = np.random.default_rng(8)
    n = 4 * ROWS
    t = pa.table({"k": rng.integers(0, 12, n), "v": rng.uniform(-5, 5, n)})
    inputs = [host_to_device(t.slice(i, ROWS), ROWS)
              for i in range(0, n, ROWS)]
    grouping = [R(0, T.LongT)]
    fns = [Sum(R(1, T.DoubleT)), Min(R(1, T.DoubleT)),
           CountStar(R(0, T.LongT))]
    schema = T.StructType((T.StructField("k", T.LongT),
                           T.StructField("s", T.DoubleT),
                           T.StructField("m", T.DoubleT),
                           T.StructField("c", T.LongT)))

    def run():
        part = AG.TpuHashAggregateExec(grouping, fns, None, Batches(inputs),
                                       mode="partial", bucket_rows=0,
                                       skip_ratio=0.9)
        part.schema = part._buffer_schema()
        bufs = list(part.execute(0))
        final = AG.TpuHashAggregateExec(grouping, fns, schema,
                                        Batches(bufs), mode="final")
        out = pa.concat_tables([device_to_host(b)
                                for b in final.execute(0)])
        return out.sort_by("k"), part, bufs

    free, part, bufs = run()
    assert part.metric("aggSortFreeBatches").value == 3
    # 12 groups in a 16-slot accumulator (a shape policy may pad it)
    assert len(bufs) == 1 and bufs[0].capacity < ROWS
    with monkeypatch.context() as m:
        m.setattr(AG, "SORT_FREE_MAX_GROUPS", 0)
        sort, part, bufs = run()
    assert part.metric("aggSortFreeBatches").value == 0
    assert len(bufs) == 1
    _same(sort, free)
    want = t.group_by("k").aggregate([("v", "sum"), ("v", "min"),
                                      ("k", "count")]).sort_by("k")
    np.testing.assert_allclose(free.column("s").to_numpy(),
                               want.column("v_sum").to_numpy(), rtol=1e-12)
    assert free.column("m").to_pylist() == want.column("v_min").to_pylist()
    assert free.column("c").to_pylist() == want.column("k_count").to_pylist()


# -- the kernel alone, in Q1's shape ---------------------------------------

def q1_shaped_sort_free(rows, gcap):
    """``sort_free_groupby`` as a function of flat arrays, in Q1's shape:
    two string keys, 5 ``double`` + 1 ``long`` buffers, a dictionary of
    ``gcap`` groups (``test_chip_compile.py`` compiles it for the
    described v5e).  Returns (function, argument shapes)."""
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.column import DeviceColumn as DC

    def fn(sel, k1, l1, k2, l2, d0, d1, d2, d3, d4, cnt,
           dk1, dl1, dk2, dl2, dsel, *acc):
        keys = [DC(T.StringT, k1, None, l1), DC(T.StringT, k2, None, l2)]
        dkeys = [DC(T.StringT, dk1, None, dl1), DC(T.StringT, dk2, None, dl2)]
        vals = ([(DC(T.DoubleT, d), "sum") for d in (d0, d1, d2, d3, d4)]
                + [(DC(T.LongT, cnt), "sum")])
        accs = [DC(c.dtype, a, v)
                for (c, _), a, v in zip(vals, acc[::2], acc[1::2])]
        out, unmatched, n = AG.sort_free_groupby(keys, sel, vals, dkeys,
                                                 dsel, accs)
        return [(c.data, c.validity) for c in out], unmatched, n

    def block(n):
        return ([((n,), jnp.bool_), ((n, 8), jnp.uint8), ((n,), jnp.int32),
                 ((n, 8), jnp.uint8), ((n,), jnp.int32)])

    acc = [((gcap,), d) for t in [jnp.float64] * 5 + [jnp.int64]
           for d in (t, jnp.bool_)]
    shapes = (block(rows)[:5] + [((rows,), jnp.float64)] * 5
              + [((rows,), jnp.int64)] + block(gcap)[1:] + block(gcap)[:1]
              + acc)
    return fn, shapes


def test_the_kernel_sorts_gathers_and_scatters_nothing():
    n = 384  # no other dimension of the program has this size
    fn, shapes = q1_shaped_sort_free(n, 8)
    hlo = jax.jit(fn).lower(*[jax.ShapeDtypeStruct(s, d)
                              for s, d in shapes]).compile().as_text()
    for op in ("sort", "gather", "scatter"):
        assert not re.search(rf" {op}\(", hlo), op
