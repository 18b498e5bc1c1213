"""Compaction moves the live rows, not the batch.

``compact(b, rows=n)`` sorts the dead flag as before, gathers only the
first ``live_bucket(n, capacity)`` entries of that order, and moves
every leaf through one packed row gather (``ops.ordering.take_rows``):
the first ``bucket`` rows of what the one-take-a-column compaction
gave, leaf for leaf and bit for bit, the dead rows past the count
included.  A join counts its children's live rows first (one round
trip) and compacts at the live buckets; a projection hands on
``compacted``, so a fully live side launches nothing.  CPU platform,
no assertion on seconds."""

import decimal
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu.columnar import column as C
from spark_rapids_tpu.exec import basic as B
from spark_rapids_tpu.runtime import kernel_cache as KC
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.sql.column import col
from spark_rapids_tpu.utils.harness import (
    assert_tpu_and_cpu_are_equal_collect, tpu_session)
from test_join_live_rows import _find

CAP = 1 << 17
KINDS = ["int32", "int64", "float64", "bool", "string", "decimal128",
         "list"]
ROWS = [0, 1, 65_536, 65_537, CAP - 1, CAP]


def _old_compact(batch):
    """``_compact_impl`` as it stood before PR 32: one ``jnp.take`` of
    all B indices for every leaf of every column."""
    order = jnp.argsort((~batch.sel).astype(jnp.int8), stable=True)
    cols = tuple(c.gather(order) for c in batch.columns)
    count = jnp.sum(batch.sel.astype(jnp.int32))
    sel = jnp.arange(batch.capacity, dtype=jnp.int32) < count
    return C.DeviceBatch(batch.schema, cols, sel, compacted=True)


def _values(kind, rng, n):
    if kind == "int32":
        return pa.array(rng.integers(-2**31, 2**31, n), pa.int32())
    if kind == "int64":
        return pa.array(rng.integers(-2**62, 2**62, n), pa.int64())
    if kind == "float64":
        v = rng.normal(size=n) * 1e6
        v[::97], v[1::97], v[2::97] = np.nan, -0.0, np.inf
        return pa.array(v, pa.float64())
    if kind == "bool":
        return pa.array(rng.random(n) < 0.5, pa.bool_())
    if kind == "string":
        words = np.array(["", "a", "MAIL", "REG AIR", "deliver in person",
                          "ünï", "x" * 23])
        return pa.array(words[rng.integers(0, len(words), n)], pa.string())
    if kind == "decimal128":
        hi = rng.integers(-10**17, 10**17, n)
        lo = rng.integers(0, 10**10, n)
        return pa.array([decimal.Decimal(int(h) * 10**10 + int(x))
                         .scaleb(-4) for h, x in zip(hi, lo)],
                        pa.decimal128(30, 4))
    assert kind == "list"
    lens = rng.integers(0, 5, n)
    offs = np.zeros(n + 1, np.int32)
    np.cumsum(lens, out=offs[1:])
    flat = rng.integers(-2**40, 2**40, int(offs[-1]))
    # null elements inside the lists: the evalid plane moves too
    elems = pa.array(flat, pa.int64(), mask=rng.random(len(flat)) < 0.1)
    return pa.ListArray.from_arrays(pa.array(offs), elems)


_BATCHES = {}


def _batch(kind, nulls):
    """One CAP-row device batch a (kind, nulls), made once a module."""
    key = (kind, nulls)
    if key not in _BATCHES:
        rng = np.random.default_rng(32 + len(_BATCHES))
        arr = _values(kind, rng, CAP)
        if nulls:
            mask = pa.array(rng.random(CAP) < 0.2)
            arr = pc.if_else(mask, pa.scalar(None, arr.type), arr)
        _BATCHES[key] = C.host_to_device(pa.table({"v": arr}))
        assert _BATCHES[key].capacity == CAP
    return _BATCHES[key]


def _with_live(batch, n, seed=0):
    live = np.zeros(batch.capacity, bool)
    live[np.random.default_rng(seed).permutation(batch.capacity)[:n]] = True
    return batch.with_sel(jnp.asarray(live))


def _bits(x):
    a = np.ascontiguousarray(np.asarray(x))
    return a.view(np.uint8).reshape(a.shape[0], -1) if a.size else a


def _assert_prefix_equal(new, old, bucket):
    assert new.capacity == bucket and new.compacted
    ln, lo = jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old)
    assert len(ln) == len(lo)
    for x, y in zip(ln, lo):
        assert x.dtype == y.dtype and x.shape[1:] == y.shape[1:]
        assert x.shape[0] == bucket
        assert np.array_equal(_bits(x), _bits(y)[:bucket]), x.dtype


def _launches():
    return TM.REGISTRY.counter("tpuq_program_launches_total").value


@pytest.mark.parametrize("nulls", [False, True], ids=["plain", "nulls"])
@pytest.mark.parametrize("n", ROWS)
@pytest.mark.parametrize("kind", KINDS)
def test_live_bucket_is_the_prefix_of_the_old_compaction(kind, n, nulls):
    b = _with_live(_batch(kind, nulls), n, seed=n)
    before = _launches()
    got = C.compact(b, rows=n)
    if n == CAP:
        # every slot live: compacted by definition, nothing launched
        assert _launches() == before
        assert got.compacted and got.capacity == CAP
        assert got.sel is b.sel
        assert all(x is y for x, y in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(b)))
        return
    bucket = C.live_bucket(n, CAP)
    assert bucket == {0: 8, 1: 8, 65_536: 65_536}.get(n, CAP)
    assert _launches() == before + 2      # the order, then the gather
    _assert_prefix_equal(got, _old_compact(b), bucket)
    assert int(np.asarray(got.sel).sum()) == n
    assert np.asarray(got.sel)[:n].all()


@pytest.mark.parametrize("nulls", [False, True], ids=["plain", "nulls"])
@pytest.mark.parametrize("kind", KINDS)
def test_compact_without_a_count_is_what_it_was(kind, nulls):
    """Every caller but the join's gather: the same rows in the same
    order at the same capacity."""
    b = _with_live(_batch(kind, nulls), 40_000, seed=7)
    _assert_prefix_equal(C.compact(b), _old_compact(b), CAP)


def test_a_compacted_batch_is_handed_back_as_it_is():
    b = C.compact(_with_live(_batch("int64", True), 100, seed=3), rows=100)
    before = _launches()
    assert C.compact(b) is b and C.compact(b, rows=100) is b
    assert _launches() == before


def test_every_column_moves_in_one_packed_gather():
    """A Q12-shaped batch (long, string, three dates, a double): one
    word matrix and one double matrix, not a take a leaf."""
    rng = np.random.default_rng(5)
    n = 4096
    t = pa.table({
        "k": _values("int64", rng, n), "mode": _values("string", rng, n),
        "d1": _values("int32", rng, n), "d2": _values("int32", rng, n),
        "d3": _values("int32", rng, n), "x": _values("float64", rng, n)})
    b = _with_live(C.host_to_device(t), 300, seed=1)
    order, count = C._compact_order(b.sel)
    hlo = jax.jit(C._compact_take(512)).lower(
        b, order, count).compile().as_text()
    assert hlo.count(" gather(") == 2, hlo
    _assert_prefix_equal(C.compact(b, rows=300), _old_compact(b), 512)


def test_the_sort_is_one_program_a_capacity():
    """Neither a schema nor a bucket may multiply the program that
    holds the sort (tens of seconds of compile at 1 M rows)."""
    KC.clear()
    for kind in ("int32", "string"):
        for n in (5, 500, 70_000, None):
            b = _with_live(_batch(kind, False), n or 9, seed=2)
            C.compact(b, rows=n)
    small = _with_live(C.host_to_device(
        pa.table({"v": pa.array(np.arange(2000))})), 10)
    C.compact(small, rows=10)
    keys = [k for k in KC._CACHE if k[0].startswith("compact")]
    assert [k for k in keys if k[0] == "compact_order"] == [
        ("compact_order",)]
    takes = {k[2] for k in keys if k[0] == "compact_take"}
    assert takes == {8, 512, CAP, 16}
    assert len([k for k in keys if k[0] == "compact_take"]) == 7
    # one trace a capacity (CAP and the small batch's 2 048)
    assert KC._CACHE[("compact_order",)].__kwdefaults__[
        "__jfn"]._cache_size() == 2


# -- the projection -----------------------------------------------------

def _batches(df):
    """The device batches under the plan's root (the D2H transfer)."""
    plan = df._execute_plan()
    assert type(plan).__name__ == "DeviceToHostExec", plan
    top = plan.children[0]
    return [b for p in range(top.num_partitions())
            for b in top.execute(p)]


def test_projection_hands_on_compacted():
    t = pa.table({"a": pa.array(np.arange(3000)),
                  "b": pa.array(np.arange(3000) * 0.5)})
    s = tpu_session()
    df = s.createDataFrame(t)
    kept = _batches(df.select((col("a") + 1).alias("a1"), col("b")))
    assert kept and all(b.compacted for b in kept)
    # a filter breaks the promise, and the projection over it says so
    cut = _batches(df.filter(col("a") % 3 == 0).select(col("b")))
    assert cut and not any(b.compacted for b in cut)


# -- the joins ----------------------------------------------------------

JCAP = 4096
JCONF = {"spark.rapids.tpu.join.targetRows": JCAP,
         "spark.rapids.tpu.batchRows": 4096}
N = 40_000


@pytest.fixture
def round_trips(monkeypatch):
    """(calling function, batches counted) of every count round trip."""
    seen = []
    real = B._overlapped_live_counts

    def spy(batches):
        seen.append((sys._getframe(1).f_code.co_name, len(batches)))
        return real(batches)

    monkeypatch.setattr(B, "_overlapped_live_counts", spy)
    return seen


def _q14_shaped(s, fact, dim):
    """A thinly live stream under a broadcast join against a fully
    live, projected dimension."""
    return (s.createDataFrame(fact).filter(col("tag") < 2)
            .join(s.createDataFrame(dim).select(
                col("k"), (col("w") * 2).alias("w2")), "k", "inner"))


def _q12_shaped(s, fact, dim):
    """No side may be broadcast: the fully live one (over the cap) is
    streamed in groups against the filtered one."""
    return (s.createDataFrame(fact).filter(col("tag") < 2)
            .join(s.createDataFrame(dim).select(col("k"), col("w")),
                  "k", "inner"))


def _join_tables(shape):
    rng = np.random.default_rng(14 if shape == "q14" else 12)
    nkeys = 300 if shape == "q14" else 30_000
    fact = pa.table({"k": pa.array(rng.integers(0, nkeys, N)),
                     "v": pa.array(rng.uniform(-5, 5, N)),
                     "tag": pa.array(rng.integers(0, 100, N))})
    dim = pa.table({"k": pa.array(np.arange(nkeys, dtype=np.int64)),
                    "w": pa.array(rng.integers(0, 9, nkeys))})
    return fact, dim


@pytest.mark.parametrize("shape", ["q14", "q12"])
def test_join_compacts_at_the_live_buckets(shape, round_trips):
    fact, dim = _join_tables(shape)
    build = _q14_shaped if shape == "q14" else _q12_shaped
    conf = (JCONF if shape == "q14" else
            dict(JCONF, **{"spark.sql.autoBroadcastJoinThreshold": 100_000}))
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: build(s, fact, dim), conf=conf, ignore_order=True,
        approx_float=True)
    del round_trips[:]
    in0 = TM.REGISTRY.counter("tpuq_compact_slots_in_total").value
    moved0 = TM.REGISTRY.counter("tpuq_compact_slots_moved_total").value
    s = tpu_session(conf)
    df = build(s, fact, dim)
    assert df.toArrow().num_rows > 0
    j = _find(df._last_plan)
    assert j.broadcast == ("right" if shape == "q14" else None)
    assert j.metric("liveRowInCoreJoins").value == int(shape == "q14")
    assert j.metric("streamedJoins").value == int(shape == "q12")
    # ten 4096-slot batches of ~80 live rows each: a 128-slot bucket a
    # batch is gathered; the other side (fully live, projected: it
    # came compacted) moves nothing
    keep = fact.column("tag").to_numpy() < 2
    buckets = [C.live_bucket(int(keep[lo:lo + 4096].sum()), 4096)
               for lo in range(0, N, 4096)]
    assert max(buckets) <= 256
    # (300 rows in a 1 024-slot bucket; 30 000 in seven 4 096-slot
    # batches and one of 2 048)
    other = 1024 if shape == "q14" else 7 * 4096 + 2048
    slots_in = j.metric("compactSlotsIn").value
    moved = j.metric("compactSlotsMoved").value
    assert slots_in == 10 * 4096 + other
    assert moved == sum(buckets)
    assert moved / slots_in < 0.05
    # the process counters: this join's, and the broadcast exchange's
    # gather of its (compacted) child, which moves nothing either
    bc = 1024 if shape == "q14" else 0
    assert (TM.REGISTRY.counter("tpuq_compact_slots_in_total").value
            - in0) == slots_in + bc
    assert (TM.REGISTRY.counter("tpuq_compact_slots_moved_total").value
            - moved0) == moved
    # one count round trip for both of the join's children, and none
    # pulled again by execute or by the in-core concats
    mine = [c for c in round_trips if c[0] == "_compact_counted"]
    assert mine[-1][1] == (11 if shape == "q14" else 18)
    assert len(mine) == (2 if shape == "q14" else 1)
    assert not [c for c in round_trips
                if c[0] in ("execute", "_concat_compacted_fast",
                            "concat_device_batches")
                and shape == "q14"], round_trips
    assert not [c for c in round_trips if c[0] == "execute"]


def test_a_filter_that_keeps_everything_moves_every_slot():
    fact, dim = _join_tables("q14")
    s = tpu_session(JCONF)
    df = (s.createDataFrame(fact).filter(col("tag") < 1000)
          .join(s.createDataFrame(dim), "k", "inner"))
    assert df.toArrow().num_rows > 0
    j = _find(df._last_plan)
    # nine batches of 4 096 live rows in 4 096 slots are compacted by
    # definition (nothing launched); the tenth holds 3 136 and moves
    # its whole 4 096-slot bucket
    assert j.metric("compactSlotsIn").value == 10 * 4096 + 1024
    assert j.metric("compactSlotsMoved").value == 4096


def test_a_lone_batch_that_came_compacted_is_still_cut():
    """Over the cap by the capacity it came at, compacted already (a
    scan's own bucket): the gather has nothing to move, so the join
    cuts the batch to its live bucket itself."""
    rng = np.random.default_rng(3)
    fact = pa.table({"k": pa.array(rng.integers(0, 300, 3000)),
                     "v": pa.array(rng.uniform(-5, 5, 3000))})
    dim = pa.table({"k": pa.array(np.arange(300, dtype=np.int64)),
                    "w": pa.array(rng.integers(0, 9, 300))})
    conf = dict(JCONF, **{"spark.rapids.tpu.batchRows": 65_536,
                          "spark.rapids.tpu.minBucketRows": 16_384})

    def build(s):
        return s.createDataFrame(fact).join(s.createDataFrame(dim), "k")

    assert_tpu_and_cpu_are_equal_collect(
        build, conf=conf, ignore_order=True, approx_float=True)
    from spark_rapids_tpu.exec import join as J
    seen = []
    real = J.TpuSortMergeJoinExec._merge_join

    def spy(self, lb, rb, jt, probe):
        seen.append((lb.capacity, rb.capacity))
        return real(self, lb, rb, jt, probe)

    J.TpuSortMergeJoinExec._merge_join = spy
    try:
        df = build(tpu_session(conf))
        df.toArrow()
    finally:
        J.TpuSortMergeJoinExec._merge_join = real
    j = _find(df._last_plan)
    assert j.metric("liveRowInCoreJoins").value == 1
    assert j.metric("compactSlotsMoved").value == 0
    assert seen == [(4096, 16_384)], seen
