"""Sort and join CPU-vs-TPU oracle tests.

[REF: integration_tests/src/main/python/sort_test.py, join_test.py]
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.column import col
from spark_rapids_tpu.utils import datagen as dg
from spark_rapids_tpu.utils.harness import (
    assert_tpu_and_cpu_are_equal_collect, assert_tpu_fallback_collect)


def gen_table(seed=0, n=400):
    rng = np.random.default_rng(seed)
    return pa.table({
        "i": dg.IntegerGen(min_val=-50, max_val=50).generate(rng, n),
        "l": dg.LongGen().generate(rng, n),
        "d": dg.DoubleGen().generate(rng, n),
        "s": dg.StringGen().generate(rng, n),
        "k": pa.array((np.arange(n) % 11).astype(np.int32)),
    })


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

def test_orderby_int_asc():
    t = gen_table(0)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy("i", "l"))


def test_orderby_desc_and_nulls():
    t = gen_table(1)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy(col("i").desc(), col("l")))


def test_orderby_double_nan():
    t = pa.table({"d": pa.array([1.0, float("nan"), None, -0.0, 0.0,
                                 float("-inf"), float("inf"), 2.5]),
                  "x": pa.array(list(range(8)))})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy("d", "x"))
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy(col("d").desc(), col("x")))


def test_orderby_string():
    t = gen_table(2)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy("s", "i"))


def test_orderby_multi_partition():
    t = gen_table(3)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy("k", col("i").desc()),
        conf={"spark.default.parallelism": 3})


def test_sort_then_limit_topn():
    t = gen_table(4)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(t).orderBy("l").limit(13))


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

def two_tables(seed=0, nl=300, nr=200, nullable=True):
    rng = np.random.default_rng(seed)
    kl = dg.IntegerGen(min_val=0, max_val=40,
                       null_ratio=0.1 if nullable else 0).generate(rng, nl)
    kr = dg.IntegerGen(min_val=0, max_val=40,
                       null_ratio=0.1 if nullable else 0).generate(rng, nr)
    left = pa.table({
        "k": kl,
        "lv": dg.LongGen().generate(rng, nl),
        "ls": dg.StringGen().generate(rng, nl),
    })
    right = pa.table({
        "k": kr,
        "rv": dg.DoubleGen().generate(rng, nr),
    })
    return left, right


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_join_int_key(how):
    l, r = two_tables(5)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k", how),
        ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_string_key(how):
    rng = np.random.default_rng(7)
    l = pa.table({"g": dg.StringGen(max_len=12).generate(rng, 150),
                  "x": dg.IntegerGen().generate(rng, 150)})
    r = pa.table({"g": dg.StringGen(max_len=12).generate(rng, 120),
                  "y": dg.LongGen().generate(rng, 120)})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "g", how),
        ignore_order=True)


def test_join_multi_key():
    rng = np.random.default_rng(8)
    l = pa.table({"a": dg.IntegerGen(min_val=0, max_val=5).generate(rng, 200),
                  "b": dg.StringGen(max_len=4).generate(rng, 200),
                  "x": dg.LongGen().generate(rng, 200)})
    r = pa.table({"a": dg.IntegerGen(min_val=0, max_val=5).generate(rng, 150),
                  "b": dg.StringGen(max_len=4).generate(rng, 150),
                  "y": dg.DoubleGen().generate(rng, 150)})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(
            s.createDataFrame(r), ["a", "b"], "inner"),
        ignore_order=True)


def test_cross_join():
    l = pa.table({"x": pa.array([1, 2, 3])})
    r = pa.table({"y": pa.array(["a", "b"])})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).crossJoin(s.createDataFrame(r)),
        ignore_order=True)


def test_join_empty_side():
    l, r = two_tables(9)
    empty = r.slice(0, 0)
    for how in ("inner", "left", "left_anti"):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: s.createDataFrame(l).join(
                s.createDataFrame(empty), "k", how),
            ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_double_key(how):
    # Spark NormalizeFloatingNumbers: NaN == NaN, -0.0 == 0.0 as join keys
    special = [float("nan"), -0.0, 0.0, float("inf"), float("-inf"), None]
    rng = np.random.default_rng(10)
    lv = list(rng.integers(-5, 5, 40).astype(float)) + special
    rv = list(rng.integers(-5, 5, 30).astype(float)) + special
    l = pa.table({"d": pa.array(lv, type=pa.float64()),
                  "x": pa.array(list(range(len(lv))))})
    r = pa.table({"d": pa.array(rv, type=pa.float64()),
                  "y": pa.array(list(range(len(rv))))})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "d", how),
        ignore_order=True)


def test_join_float32_key():
    special = [float("nan"), -0.0, 0.0, None]
    rng = np.random.default_rng(12)
    lv = list(rng.integers(-5, 5, 40).astype(np.float32)) + special
    rv = list(rng.integers(-5, 5, 30).astype(np.float32)) + special
    l = pa.table({"f": pa.array(lv, type=pa.float32()),
                  "x": pa.array(list(range(len(lv))))})
    r = pa.table({"f": pa.array(rv, type=pa.float32()),
                  "y": pa.array(list(range(len(rv))))})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "f"),
        ignore_order=True)


def test_join_mixed_int_width_key():
    # int32 key joined against int64 key: canonical 64-bit encoding
    rng = np.random.default_rng(13)
    l = pa.table({"k": pa.array(rng.integers(0, 20, 60), type=pa.int32()),
                  "x": pa.array(list(range(60)))})
    r = pa.table({"k": pa.array(rng.integers(0, 20, 40), type=pa.int64()),
                  "y": pa.array(list(range(40)))})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k"),
        ignore_order=True)
    # right/full would coalesce int32+int64 key data into one column —
    # stays on CPU
    assert_tpu_fallback_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k",
                                            "full"),
        "Join", ignore_order=True)


def test_join_then_aggregate():
    l, r = two_tables(11)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: (s.createDataFrame(l)
                   .join(s.createDataFrame(r), "k", "inner")
                   .groupBy("k").agg(F.count("*").alias("c"),
                                     F.sum("lv").alias("sl"))),
        ignore_order=True)


def test_join_skewed_duplicate_keys():
    # many-to-many expansion
    l = pa.table({"k": pa.array([1] * 50 + [2] * 3 + [3]),
                  "x": pa.array(list(range(54)))})
    r = pa.table({"k": pa.array([1] * 40 + [3] * 2),
                  "y": pa.array(list(range(42)))})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.createDataFrame(l).join(s.createDataFrame(r), "k"),
        ignore_order=True)


def _expand_counts_bisected(counts):
    """``exec.join._expand_counts`` as it stood before the merge rank:
    the row of every output slot by ``jnp.searchsorted``."""
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.basic import round_up_pow2
    cum = jnp.cumsum(counts.astype(jnp.int64))
    total = int(cum[-1])
    bucket = round_up_pow2(max(total, 1))
    j = jnp.arange(bucket, dtype=jnp.int64)
    i = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    i_c = jnp.clip(i, 0, max(counts.shape[0] - 1, 0))
    start = jnp.take(cum, i_c) - jnp.take(counts.astype(jnp.int64), i_c)
    return bucket, i_c, (j - start).astype(jnp.int32), total


def _count_vectors():
    rng = np.random.default_rng(34)
    over = np.zeros(50, np.int32)
    over[[3, 17, 40]] = [100, 20, 9]  # total 129: just over 128
    return {
        "random_with_zeros": rng.poisson(0.8, 300).astype(np.int32),
        "all_zero": np.zeros(64, np.int32),
        "one_row": np.array([5], np.int32),
        "one_row_no_match": np.array([0], np.int32),
        "total_just_over_pow2": over,
        "selective": (rng.random(1024) < 0.01).astype(np.int32),
    }


@pytest.mark.parametrize("case", sorted(_count_vectors()))
def test_expand_counts_equals_bisection(case):
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.join import _expand_counts
    from spark_rapids_tpu.runtime.device import ensure_initialized
    ensure_initialized()  # x64 on, as every session has it
    counts = jnp.asarray(_count_vectors()[case])
    bucket, i_c, off, total = _expand_counts(counts)
    bucket0, i_c0, off0, total0 = _expand_counts_bisected(counts)
    assert (bucket, total) == (bucket0, total0)
    assert i_c.dtype == i_c0.dtype and off.dtype == off0.dtype
    assert np.array_equal(np.asarray(i_c), np.asarray(i_c0))
    assert np.array_equal(np.asarray(off), np.asarray(off0))
