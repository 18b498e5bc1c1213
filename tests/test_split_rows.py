"""``parallel/shuffle.py::split_to_spillables`` against a plain
reference: numpy's stable sort of the live rows by bucket id, leaf by
leaf.  Live rows bit for bit and in input order inside a bucket,
``sel`` a prefix, ``live_rows`` the count, whatever the schema and
however the chunk was cut."""

import decimal

import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.column import host_to_device
from spark_rapids_tpu.parallel.shuffle import split_to_spillables
from spark_rapids_tpu.runtime import device, trace
from spark_rapids_tpu.runtime.memory import get_manager

NBUCKETS = 4


def _column(kind: str, n: int, nullable: bool, rng) -> pa.Array:
    """``n`` values of one kind; every seventh null where ``nullable``
    (so each batch of a case carries the same leaves)."""
    i = np.arange(n)
    if kind == "long":
        vals = rng.integers(-2**62, 2**62, n).tolist()
        typ = pa.int64()
    elif kind == "int":
        vals = rng.integers(-2**31, 2**31, n).tolist()
        typ = pa.int32()
    elif kind == "short":
        vals = rng.integers(-2**15, 2**15, n).tolist()
        typ = pa.int16()
    elif kind == "bool":
        vals = (rng.integers(0, 2, n) == 1).tolist()
        typ = pa.bool_()
    elif kind == "double":
        vals = rng.standard_normal(n)
        vals[i % 11 == 3] = np.nan
        vals[i % 13 == 5] = -0.0
        vals, typ = vals.tolist(), pa.float64()
    elif kind == "float":
        vals = rng.standard_normal(n).astype(np.float32)
        vals[i % 11 == 3] = np.inf
        vals, typ = vals.tolist(), pa.float32()
    elif kind == "string":
        # every batch of 20 rows or more holds the widest string
        vals = ["r%d" % j + "x" * (j % 20) for j in i]
        typ = pa.string()
    elif kind == "decimal128":
        vals = [decimal.Decimal(int(v)) * 10**15 / 100
                for v in rng.integers(-2**62, 2**62, n)]
        typ = pa.decimal128(38, 2)
    else:
        assert kind == "array", kind
        vals = [[int(j) * 3 + k if (not nullable or (j + k) % 5)
                 else None for k in range(j % 4)] for j in i]
        typ = pa.list_(pa.int64())
    if nullable:
        vals = [None if j % 7 == 2 else v for j, v in zip(i, vals)]
    return pa.array(vals, type=typ)


KINDS = ["long", "int", "short", "bool", "double", "float", "string",
         "decimal128", "array"]


def _batches(kinds, sizes, nullable, rng, dead=None):
    """One batch a size, its bucket ids riding the first column."""
    out = []
    for n in sizes:
        cols = {"pid": pa.array(rng.integers(0, NBUCKETS, n),
                                type=pa.int32())}
        for k in kinds:
            cols[k] = _column(k, n, nullable, rng)
        b = host_to_device(pa.table(cols), min_bucket=8)
        if dead is not None:
            b = b.with_sel(b.sel & (jax.numpy.arange(b.capacity) % dead
                                    != 1))
        out.append(b)
    return out


def _leaves(batch, widths=None):
    """Every leaf of a batch as numpy, strings padded to ``widths``."""
    leaves = [np.asarray(x)
              for x in jax.tree_util.tree_leaves(batch.columns)]
    if widths is not None:
        leaves = [np.pad(x, ((0, 0), (0, w - x.shape[1])))
                  if x.ndim == 2 and x.shape[1] < w else x
                  for x, w in zip(leaves, widths)]
    return leaves


def _bits(x):
    """Floats by their bits: NaN equals NaN, -0.0 differs from 0.0."""
    if x.dtype.kind == "f":
        return x.view("u%d" % x.dtype.itemsize)
    return x


def _pid_column(b, aux):
    return b.columns[0].data


def _pid_skewed(b, aux):
    # bucket 0 keeps a tenth, the last bucket the rest: its cut's
    # pow-2 window passes the end of the chunk
    return jax.numpy.where(b.columns[0].data == 0, 0, NBUCKETS - 1)


def _pid_no_bucket_1(b, aux):
    p = b.columns[0].data
    return jax.numpy.where(p == 1, 2, p)


def _pid_by_bounds(b, aux):
    # the out-of-core sort's way: range ids from bounds that ride aux
    return jax.numpy.searchsorted(
        aux, b.columns[1].data, side="right").astype("int32")


# a case: the columns' kinds, a batch a size, every seventh value null,
# every ``dead``-th row dead, the bucket ids and what rides ``aux``
_ALL = dict(kinds=KINDS, sizes=[900], nullable=True, dead=None,
            ids_fn=_pid_column, aux=None, chunk_rows=1 << 20)
CASES = {
    **{f"{k}-{'nullable' if nl else 'not-null'}":
       dict(_ALL, kinds=[k], sizes=[300], nullable=nl)
       for k in KINDS for nl in (True, False)},
    "every-kind-nullable": _ALL,
    "every-kind-not-null": dict(_ALL, nullable=False),
    "dead-rows-in-the-middle": dict(_ALL, dead=3),
    "an-empty-bucket": dict(_ALL, ids_fn=_pid_no_bucket_1),
    "batches-coalesced-into-one-chunk": dict(_ALL, sizes=[200, 500, 90],
                                             dead=5),
    "a-chunk-a-batch": dict(_ALL, sizes=[200, 500, 90],
                            chunk_rows=256),
    "a-chunk-of-one-row": dict(_ALL, sizes=[1]),
    "range-ids-riding-aux": dict(
        _ALL, kinds=["long", "string", "double"], ids_fn=_pid_by_bounds,
        aux=np.array([-2**61, 0, 2**61], dtype=np.int64)),
    "a-cut-past-the-chunks-capacity": dict(_ALL, sizes=[1000],
                                           ids_fn=_pid_skewed),
}


@pytest.mark.parametrize("case", list(CASES))
def test_split_matches_stable_sort_by_bucket(case):
    c = CASES[case]
    device.ensure_initialized()
    rng = np.random.default_rng(len(case) * 7919 + 36)
    batches = _batches(c["kinds"], c["sizes"], c["nullable"], rng,
                       c["dead"])
    aux = None if c["aux"] is None else jax.numpy.asarray(c["aux"])
    # the reference, before the split drains the list: live rows of
    # every batch in order, their ids by the same function
    raw = [_leaves(b) for b in batches]
    nleaves = len(raw[0])
    widths = [max(x.shape[1] if x.ndim == 2 else 0 for x in xs)
              for xs in zip(*raw)]
    live = np.concatenate([np.asarray(b.sel) for b in batches])
    pid = np.concatenate([np.asarray(c["ids_fn"](b, aux))
                          for b in batches])[live]
    rows = [np.concatenate(xs)[live] for xs in zip(
        *(_leaves(b, widths) for b in batches))]
    order = np.argsort(pid, kind="stable")
    want_counts = np.bincount(pid, minlength=NBUCKETS)
    starts = np.concatenate([[0], np.cumsum(want_counts)])

    capacities = [b.capacity for b in batches]
    slices = split_to_spillables(
        batches, c["ids_fn"], NBUCKETS, get_manager(),
        ("test_split_rows", case), aux=aux, chunk_rows=c["chunk_rows"])
    assert batches == []            # consumed in place
    try:
        nchunks = (len(capacities) if c["chunk_rows"] < sum(capacities)
                   else 1)
        for i in range(NBUCKETS):
            got_n = 0
            got = [[] for _ in range(nleaves)]
            assert len(slices[i]) <= nchunks
            for sp in slices[i]:
                part = sp.get()
                n = sp.live_rows
                assert n > 0 and part.compacted
                assert part.capacity == max(8, 1 << (n - 1).bit_length())
                np.testing.assert_array_equal(
                    np.asarray(part.sel), np.arange(part.capacity) < n)
                for j, x in enumerate(_leaves(part, widths)):
                    got[j].append(x[:n])
                got_n += n
            assert got_n == want_counts[i], (i, got_n, want_counts)
            if not got_n:
                continue
            at = order[starts[i]:starts[i + 1]]
            for j in range(nleaves):
                have = np.concatenate(got[j])
                assert have.dtype == rows[j].dtype
                np.testing.assert_array_equal(
                    _bits(have), _bits(rows[j][at]),
                    err_msg=f"bucket {i}, leaf {j}")
    finally:
        for ss in slices:
            for sp in ss:
                sp.close()


def test_the_split_counts_the_slots_it_gathers():
    """Inside a query the split says how many indices went through a
    gather: a chunk's capacity, nothing for the cuts."""
    device.ensure_initialized()
    rng = np.random.default_rng(36)
    batches = _batches(["long", "double"], [600, 100], True, rng)
    tracer = trace.start_query(trace.next_query_id())
    assert tracer is not None
    try:
        slices = split_to_spillables(
            batches, _pid_column, NBUCKETS, get_manager(),
            ("test_split_rows", "counts"))
    finally:
        trace.end_query(tracer)
    counts = tracer.counts
    for ss in slices:
        for sp in ss:
            sp.close()
    assert counts["splitChunks"] == 1
    assert counts["spillableSlices"] == NBUCKETS
    # 700 rows coalesced at their 1 024-slot bucket
    assert counts["splitSlotsGathered"] == 1024
