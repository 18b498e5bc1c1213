"""Hash-aggregate execs (CPU oracle + TPU sort-based groupby).

[REF: sql-plugin/../GpuAggregateExec.scala :: GpuHashAggregateExec,
 AggHelper, GpuAggregateIterator] — the reference drives cuDF's hash
groupby; here the device groupby is **sort-based** (SURVEY.md §7 phase 3:
"XLA sort-based groupby first — lax.sort + segment-reduce — hash tables in
Pallas later"):

  encode keys as uint64 limbs (ops/ordering.py) → one stable
  ``lax.sort`` → group boundaries → ``segment_sum/min/max`` with a static
  segment count = the batch bucket → group representatives scattered to
  the front.

Everything is static-shape: a (schema, bucket) pair compiles once.  The
partial/merge/final split mirrors the reference exactly — partial buffers
(sum+count, min, max, first) are themselves columns, merged by the same
segment reduction keyed on ``AggregateFunction.buffer_kinds``, so
multi-batch and (later) post-shuffle final aggregation reuse one kernel.

Where the first batch's partial holds few groups, the later batches of
its stream skip the sort altogether (``sort_free_groupby``): masked
reductions against those known groups into a running accumulator, the
grouped form of the global aggregate's ``_reduce_batch``.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import host as H
from spark_rapids_tpu.columnar.column import DeviceBatch, DeviceColumn
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec.base import CpuExec, TpuExec
from spark_rapids_tpu.exec.basic import concat_device_batches
from spark_rapids_tpu.ops import ordering as ORD
from spark_rapids_tpu.ops.aggregates import (
    AggregateFunction, ApproxPercentile, Average, CollectList,
    CollectSet, Count, CountStar, First, Max, Min, Percentile, Sum,
    _VarianceBase)
from spark_rapids_tpu.ops.expressions import Expression
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.runtime import telemetry as TM

_TM_REPART_BUCKETS = TM.REGISTRY.counter(
    "tpuq_agg_repartition_buckets_total",
    "buckets the aggregates' repartition merges cut their partials "
    "into (the sum of aggRepartitionBuckets)")


# ---------------------------------------------------------------------------
# Orderable encode/decode for single-limb types (min/max reductions ride
# uint64 so NaN/sign semantics match Spark's total order exactly)
# ---------------------------------------------------------------------------

def encode_orderable(data: jnp.ndarray, dt: T.DataType) -> jnp.ndarray:
    """Non-float column → order-preserving uint64 (floats stay raw — the
    TPU x64-rewrite cannot compile 64-bit bitcasts, so float reductions
    use the NaN-aware float path instead of orderable bits)."""
    assert not isinstance(dt, (T.FloatType, T.DoubleType))
    if isinstance(dt, T.BooleanType):
        return data.astype(jnp.uint64)
    return ORD._i_to_u64(data)


def decode_orderable(u: jnp.ndarray, dt: T.DataType) -> jnp.ndarray:
    assert not isinstance(dt, (T.FloatType, T.DoubleType))
    if isinstance(dt, T.BooleanType):
        return u.astype(jnp.bool_)
    signed = (u ^ jnp.uint64(1 << 63)).astype(jnp.int64)
    return signed.astype(T.to_numpy_dtype(dt))


def _is_float(dt: T.DataType) -> bool:
    return isinstance(dt, (T.FloatType, T.DoubleType))


# ---------------------------------------------------------------------------
# The device groupby kernel
# ---------------------------------------------------------------------------

def segmented_scan(op, values: jnp.ndarray, boundary: jnp.ndarray
                   ) -> jnp.ndarray:
    """Inclusive segmented scan: row i gets op-reduction of its segment's
    rows [segment_start..i].

    THE TPU-idiom replacement for segment_sum/min/max over sorted data:
    XLA lowers scatter (which jax.ops.segment_* use) to a *serial* loop on
    TPU — catastrophic at batch sizes (measured: minutes at 128k rows).
    ``associative_scan`` is log-depth slices+concats, which the TPU
    vectorizes."""
    def comb(a, bb):
        va, fa = a
        vb, fb = bb
        return jnp.where(fb, vb, op(va, vb)), fa | fb

    v, _ = jax.lax.associative_scan(comb, (values, boundary))
    return v


def segmented_scan_dec128(values2: jnp.ndarray, boundary: jnp.ndarray
                          ) -> jnp.ndarray:
    """Inclusive segmented 128-bit sum over int64[B,2] (hi, lo) values
    — the carry-aware twin of ``segmented_scan(jnp.add, ...)``."""
    from spark_rapids_tpu.ops import decimal128 as D128

    def comb(a, bb):
        ah, al, fa = a
        bh, bl, fb = bb
        s = D128.add(D128.pack(ah, al), D128.pack(bh, bl))
        return (jnp.where(fb, bh, D128.hi(s)),
                jnp.where(fb, bl, D128.lo(s)), fa | fb)

    h, l, _ = jax.lax.associative_scan(
        comb, (values2[:, 0], values2[:, 1], boundary))
    return jnp.stack([h, l], axis=-1)


def segment_groupby(
    key_cols: Sequence[DeviceColumn],
    sel: jnp.ndarray,
    value_cols: Sequence[Tuple[DeviceColumn, str]],
    has_nans: bool = True,
    backend: str = "jnp",
) -> Tuple[List[DeviceColumn], List[DeviceColumn], jnp.ndarray,
           Optional[jnp.ndarray]]:
    """Group rows by keys; reduce values by kind ('sum'|'min'|'max'|'first').

    Returns (out_key_cols, out_value_cols, out_sel, ok) — groups
    compacted to the front, capacity unchanged (static shape).
    Scatter-free: one stable sort, segmented scans, and a second sort
    that compacts each group's END row (which holds the full-segment
    scan result) to the front in group order.  Rows move once a
    permutation, all columns together (``ORD.sort_rows``), never one
    ``jnp.take`` a column.

    ``backend`` selects the group-layout kernel: the non-jnp rungs
    (kernels.hash_agg) sort ONE 64-bit hash limb instead of the full
    fused key encoding — group order becomes hash order (undefined in
    Spark for a hash aggregate), content is identical.  ``ok`` follows
    the kernel-plane dispatch protocol: None when the reference layout
    ran; a device bool (False = 64-bit hash collision between distinct
    keys, caller must fall back) from the fused rungs.
    """
    b = int(sel.shape[0])
    limbs, key_limbs = ORD.group_sort_limbs(list(key_cols), sel)
    # everything that must follow the rows into group order
    payload = [sel]
    for c in key_cols:
        payload += [c.data, c.validity, c.lengths]
    for c, _ in value_cols:
        payload += [c.data, c.validity]
    okf = None
    res = None
    if backend != "jnp":
        from spark_rapids_tpu.kernels import hash_agg as KNA
        res = KNA.group_layout_fused(
            key_limbs, use_pallas=(backend == "pallas"), payload=payload)
    if res is not None:
        _, sorted_limbs, boundary, okf, moved = res
    else:
        sorted_limbs, _, moved = ORD.sort_rows(limbs, payload)
        diff = jnp.zeros((b,), jnp.bool_)
        for l in sorted_limbs:
            diff = diff | ORD.limb_neq(
                l, jnp.concatenate([l[:1], l[:-1]]))
        boundary = diff.at[0].set(True)  # row 0 always starts a group
    moved = iter(moved)
    live_sorted = next(moved)
    keys_s = [(next(moved), next(moved), next(moved)) for _ in key_cols]
    vals_s = [(next(moved), next(moved)) for _ in value_cols]
    num_groups = jnp.sum((boundary & live_sorted).astype(jnp.int32))

    # Two-phase value reduction: per-column segmented scans are ENQUEUED
    # first so requests over the same logical input run ONCE (a q1-shaped
    # aggregate asks for the identical live-row count scan 8 times).
    # XLA:TPU compile time is dominated by scan count — see _ScanBatcher
    # for why dedup (not stacking) is the right reduction.
    batcher = _ScanBatcher(boundary)
    all_valid = jnp.ones((b,), jnp.bool_)
    plans = []
    shared = {}  # a column asked for twice (sum(x), avg(x)) is one plan
    for ci, ((c, kind), (data_s, valid_s)) in enumerate(
            zip(value_cols, vals_s)):
        pkey = (id(data_s), id(valid_s), kind)
        if pkey in shared:
            plans.append(shared[pkey])
            continue
        if valid_s is None:
            valid_s, contrib = all_valid, live_sorted
            ckey = "live"  # shared count scan for all non-null inputs
        else:
            contrib = valid_s & live_sorted
            ckey = ("col", ci)
        e = {"c": c, "kind": kind, "data_s": data_s, "valid_s": valid_s}
        e["n_contrib"] = batcher.add("add", contrib.astype(jnp.int32),
                                     key=ckey)
        if kind == "sum" and data_s.ndim == 2:
            # decimal128 buffers: carry-aware scan outside the batcher
            e["agg128"] = segmented_scan_dec128(
                jnp.where(contrib[:, None], data_s,
                          jnp.zeros((), data_s.dtype)), boundary)
        elif kind == "sum":
            e["agg"] = batcher.add("add", jnp.where(
                contrib, data_s, jnp.zeros((), data_s.dtype)))
        elif kind in ("min", "max"):
            if _is_float(c.dtype) and not has_nans:
                # spark.rapids.sql.hasNans=false: skip NaN bookkeeping
                inf = jnp.asarray(np.inf, data_s.dtype)
                sent = inf if kind == "min" else -inf
                e["agg"] = batcher.add(
                    kind, jnp.where(contrib, data_s, sent))
            elif _is_float(c.dtype):
                # Spark float total order: NaN greatest.  No 64-bit
                # bitcasts on TPU, so reduce raw floats with NaN masked
                # out and reinstate NaN per the order semantics.
                isn = jnp.isnan(data_s)
                real = contrib & ~isn
                e["float_nan"] = True
                e["n_real"] = batcher.add("add", real.astype(jnp.int32))
                inf = jnp.asarray(np.inf, data_s.dtype)
                if kind == "min":
                    e["agg"] = batcher.add(
                        "min", jnp.where(real, data_s, inf))
                else:
                    e["agg"] = batcher.add(
                        "max", jnp.where(real, data_s, -inf))
                    e["any_nan"] = batcher.add(
                        "add", (contrib & isn).astype(jnp.int32))
            else:
                u = encode_orderable(data_s, c.dtype)
                sentinel = jnp.uint64(
                    0xFFFFFFFFFFFFFFFF if kind == "min" else 0)
                e["orderable"] = True
                e["agg"] = batcher.add(
                    kind, jnp.where(contrib, u, sentinel))
        elif kind == "first":
            # keep-leftmost scan: end row sees the start value
            e["agg"] = batcher.add("first", data_s)
            e["vfirst"] = batcher.add("first", valid_s)
            # a group with no LIVE rows (the forced global-aggregate
            # row over empty input) must be null, not a dead row's
            # validity bit
            e["nlive"] = batcher.add("add", live_sorted.astype(jnp.int32),
                                     key="nlive")
        else:
            raise ValueError(f"unknown reduction kind {kind}")
        shared[pkey] = e
        plans.append(e)
    batcher.run()

    # group END rows hold the completed segment reductions.  Compaction:
    # a second sort brings the ends of live groups to the front, in
    # group order, and carries every output column with it.
    outputs = [a for arrs in keys_s for a in arrs]
    for e in plans:
        if "out" in e:  # a shared plan's columns ride once
            outputs += e["out"]
            continue
        c, kind = e["c"], e["kind"]
        n_contrib = batcher.get(e["n_contrib"])
        validity = n_contrib > 0
        agg = (e["agg128"] if "agg128" in e
               else batcher.get(e["agg"]))
        if kind in ("min", "max") and e.get("float_nan"):
            nan = jnp.asarray(np.nan, e["data_s"].dtype)
            if kind == "min":
                n_real = batcher.get(e["n_real"])
                agg = jnp.where((n_real == 0) & (n_contrib > 0), nan,
                                agg)
            else:
                agg = jnp.where(batcher.get(e["any_nan"]) > 0, nan, agg)
        elif kind in ("min", "max") and e.get("orderable"):
            agg = decode_orderable(agg, c.dtype)
        elif kind == "first":
            validity = (batcher.get(e["vfirst"])
                        & (batcher.get(e["nlive"]) > 0))
        e["out"] = [agg, validity]
        outputs += e["out"]

    is_end = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    rank = (~(is_end & live_sorted)).astype(jnp.uint8)
    front = iter(ORD.sort_rows([rank], outputs)[2])
    out_keys = [DeviceColumn(c.dtype, next(front), next(front),
                             next(front)) for c in key_cols]
    out_vals = [DeviceColumn(c.dtype, next(front), next(front), None)
                for c, _ in value_cols]
    out_sel = jnp.arange(b, dtype=jnp.int32) < num_groups
    return out_keys, out_vals, out_sel, okf


class _ScanBatcher:
    """Deduplicates segmented scans over identical inputs.

    Scan COUNT dominates XLA:TPU compile time (~5 s per f64[n] scan;
    stacking into [n, k] measured WORSE — 2-D associative scans compile
    ~11× slower per op on this backend, so requests run individually).
    The win is sharing: a q1-shaped aggregate requests the same
    live-row count scan for every one of its 8 functions — one compiled
    scan serves them all.  ``add`` enqueues with an optional logical
    input key and returns a handle; ``get`` returns the result."""

    @staticmethod
    def _op(tag: str):
        return {"add": jnp.add, "min": jnp.minimum,
                "max": jnp.maximum, "first": _keep_first}[tag]

    def __init__(self, boundary):
        self.boundary = boundary
        self._reqs: List[list] = []  # [tag, array, result]
        self._dedupe = {}

    def add(self, tag: str, arr, key=None) -> int:
        if key is not None:
            k = (tag, key)
            if k in self._dedupe:
                return self._dedupe[k]
        self._reqs.append([tag, arr, None])
        i = len(self._reqs) - 1
        if key is not None:
            self._dedupe[(tag, key)] = i
        return i

    def run(self) -> None:
        for req in self._reqs:
            tag, arr, _ = req
            req[2] = segmented_scan(self._op(tag), arr, self.boundary)

    def get(self, i: int):
        return self._reqs[i][2]


def _keep_first(a, bb):
    return a


def segment_max_group_count(key_cols, sel, contribs) -> jnp.ndarray:
    """Max per-group contrib count over any contrib mask — the collect
    matrix width probe (phase-1 kernel, one host sync at the call site,
    same pattern as the exchange's count program)."""
    b = int(sel.shape[0])
    parts = [ORD._flag_part(~sel)] + ORD.batch_group_parts(list(key_cols))
    limbs = ORD.fuse_parts(parts)
    sorted_limbs, _, contribs_s = ORD.sort_rows(
        limbs, [contrib & sel for contrib in contribs])
    diff = jnp.zeros((b,), jnp.bool_)
    for l in sorted_limbs:
        diff = diff | ORD.limb_neq(l, jnp.concatenate([l[:1], l[:-1]]))
    boundary = diff.at[0].set(True)
    out = jnp.zeros((), jnp.int32)
    for cs in contribs_s:
        n = segmented_scan(jnp.add, cs.astype(jnp.int32), boundary)
        out = jnp.maximum(out, jnp.max(n))
    return out


def _sorted_group_layout(key_cols, sel, value_col: DeviceColumn,
                         value_order: bool):
    """Shared skeleton of the holistic aggregates: stable sort on
    (exclusion, keys, value-invalid[, value]), per-group starts/valid
    counts compacted to group order via the END-rows-to-front trick.

    Returns (values_sorted, contrib_sorted, sorted_limbs, boundary,
    start_scan, rank) — sorting by ``rank`` (``_group_rows``) maps
    compacted group g to its end row (same group order as
    ``segment_groupby``)."""
    b = int(sel.shape[0])
    contrib = sel & value_col.valid_mask()
    tail_parts = [ORD._flag_part(~contrib)]
    if value_order:
        tail_parts = tail_parts + ORD.column_order_parts(
            value_col, True, True, distinguish_neg_zero=False)
    limbs, key_limbs = ORD.group_sort_limbs(list(key_cols), sel,
                                            tail_parts)
    # boundaries over the KEY limbs only (trailing contrib/value parts
    # must NOT split groups)
    sorted_limbs, _, moved = ORD.sort_rows(
        limbs, [sel, contrib, value_col.data] + key_limbs)
    live_sorted, contrib_sorted, values_sorted = moved[:3]
    diff = jnp.zeros((b,), jnp.bool_)
    for l in moved[3:]:
        diff = diff | ORD.limb_neq(l, jnp.concatenate([l[:1], l[:-1]]))
    boundary = diff.at[0].set(True)
    is_end = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    rank = (~(is_end & live_sorted)).astype(jnp.uint8)
    iota = jnp.arange(b, dtype=jnp.int32)
    start_scan = segmented_scan(_keep_first, iota, boundary)
    return (values_sorted, contrib_sorted, sorted_limbs, boundary,
            start_scan, rank)


def _group_rows(rank, per_row):
    """Each live group's END-row entries of ``per_row``, compacted to
    the front in group order (the compaction of ``segment_groupby``)."""
    return ORD.sort_rows([rank], per_row)[2]


def segment_collect(key_cols, sel, value_col: DeviceColumn, cap: int,
                    distinct: bool = False
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """collect_list/collect_set over sorted groups → (matrix [B, cap],
    lengths [B]) in the SAME compacted group order as
    ``segment_groupby``.

    Scatter-free: a stable sort on (exclusion, keys, value-invalid)
    makes each group's valid values contiguous from its group start, so
    list g is one shifted gather.  Null values are skipped (Spark
    collect semantics).  ``distinct`` additionally sorts by value,
    keeps only each run's first row, and re-packs kept rows to the
    group front with one more stable sort (set order = value order)."""
    b = int(sel.shape[0])
    (values_sorted, contrib_sorted, sorted_limbs, boundary, start_scan,
     rank) = _sorted_group_layout(key_cols, sel, value_col,
                                  value_order=distinct)
    keep = contrib_sorted
    if distinct:
        full_diff = jnp.zeros((b,), jnp.bool_)
        for l in sorted_limbs:
            full_diff = full_diff | ORD.limb_neq(
                l, jnp.concatenate([l[:1], l[:-1]]))
        keep = contrib_sorted & full_diff.at[0].set(True)
        # re-pack kept rows to the group front (group blocks stay at
        # the same positions: the group ordinal is the primary key and
        # group sizes don't change, so `boundary`/`start_scan` hold)
        grp_ord = jnp.cumsum(boundary.astype(jnp.int64)).astype(
            jnp.uint64)
        limbs3 = ORD.fuse_parts(
            [(grp_ord, 64), ORD._flag_part(~keep)])
        _, _, (values_sorted, keep) = ORD.sort_rows(
            limbs3, [values_sorted, keep])
    n_keep = segmented_scan(jnp.add, keep.astype(jnp.int32), boundary)
    starts_g, counts_g = _group_rows(rank, [start_scan, n_keep])
    idx = starts_g[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    mat = jnp.take(values_sorted, jnp.clip(idx, 0, b - 1).reshape(-1),
                   axis=0).reshape((b, cap) + values_sorted.shape[1:])
    mask = jnp.arange(cap, dtype=jnp.int32)[None, :] < counts_g[:, None]
    zero = jnp.zeros((), values_sorted.dtype)
    mat = jnp.where(mask, mat, zero)
    return mat, counts_g.astype(jnp.int32)


def _needs_sorted_extreme(dt: T.DataType) -> bool:
    """Min/Max/First inputs whose values cannot ride a single-uint64
    buffer through the partial/merge protocol (multi-limb encodings):
    handled on the holistic single-kernel path instead."""
    from spark_rapids_tpu.ops import decimal128 as D128
    return isinstance(dt, (T.StringType, T.BinaryType)) or D128.is128(dt)


def is_holistic_fn(f: AggregateFunction) -> bool:
    """Functions that require the single-kernel gathered path (no
    partial/final split): collect/percentile, and min/max/first over
    multi-limb dtypes.  The ONE definition — the exec's routing, the
    collect kernel's classification, and the planner's exchange gating
    all call this."""
    if isinstance(f, (CollectList, Percentile)):
        return True
    return (isinstance(f, (Min, Max, First)) and f.child is not None
            and _needs_sorted_extreme(f.input_dtype))


def segment_extreme(key_cols, sel, value_col: DeviceColumn, kind: str
                    ) -> DeviceColumn:
    """min/max/first of ``value_col`` per group for ANY orderable dtype
    (strings and decimal128 included) — the holistic twin of
    ``segment_groupby``'s single-limb reductions: one stable sort on
    (exclusion, keys[, null-flag, value]) and the answer is a single
    row gather per group (min = first valid row, max = last valid row,
    first = first LIVE row, nulls included — Spark First semantics).
    Output in the same compacted group order as ``segment_groupby``."""
    b = int(sel.shape[0])
    if kind == "first":
        contrib = sel
        tail: list = []
    else:
        contrib = sel & value_col.valid_mask()
        tail = [ORD._flag_part(~contrib)] + ORD.column_order_parts(
            value_col, True, True, distinguish_neg_zero=False)
    limbs, key_limbs = ORD.group_sort_limbs(list(key_cols), sel, tail)
    # boundaries over the KEY limbs only (trailing null-flag/value parts
    # must NOT split groups; tail bits may share the last key limb)
    moved = ORD.sort_rows(
        limbs, [sel, contrib, value_col.data, value_col.lengths,
                value_col.validity] + key_limbs)[2]
    live_sorted, contrib_sorted, data_s, lengths_s, validity_s = moved[:5]
    diff = jnp.zeros((b,), jnp.bool_)
    for l in moved[5:]:
        diff = diff | ORD.limb_neq(l, jnp.concatenate([l[:1], l[:-1]]))
    boundary = diff.at[0].set(True)
    is_end = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    rank = (~(is_end & live_sorted)).astype(jnp.uint8)
    iota = jnp.arange(b, dtype=jnp.int32)
    start_scan = segmented_scan(_keep_first, iota, boundary)
    n_contrib = segmented_scan(jnp.add, contrib_sorted.astype(jnp.int32),
                               boundary)
    starts_g, counts_g = _group_rows(rank, [start_scan, n_contrib])
    idx = (starts_g + counts_g - 1) if kind == "max" else starts_g
    idx = jnp.clip(idx, 0, b - 1)
    row_data = jnp.take(data_s, idx, axis=0)
    lengths = None
    if lengths_s is not None:
        lengths = jnp.take(lengths_s, idx)
    if kind == "first":
        base = (jnp.take(validity_s, idx) if validity_s is not None
                else jnp.ones((b,), jnp.bool_))
        validity = base & (counts_g > 0)  # empty group → null
    else:
        validity = counts_g > 0
    return DeviceColumn(value_col.dtype, row_data, validity, lengths)


def segment_percentile(key_cols, sel, value_col: DeviceColumn,
                       pct: float, interpolate: bool
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """percentile / approx_percentile over value-sorted groups →
    (values [B], validity [B]) in compacted group order.

    Exact path: Spark's rank = p·(n-1) with linear interpolation.
    Approx path: the nearest-rank ELEMENT (ceil(p·n)-1) — zero rank
    error, always an actual group element (see ApproxPercentile)."""
    b = int(sel.shape[0])
    (values_sorted, contrib_sorted, _limbs, boundary, start_scan,
     rank) = _sorted_group_layout(key_cols, sel, value_col,
                                  value_order=True)
    n_contrib = segmented_scan(jnp.add, contrib_sorted.astype(jnp.int32),
                               boundary)
    starts_g, counts_g = _group_rows(rank, [start_scan, n_contrib])
    nonempty = counts_g > 0
    if interpolate:
        r = jnp.float64(pct) * jnp.maximum(counts_g - 1, 0).astype(
            jnp.float64)
        lo = jnp.floor(r)
        vlo = jnp.take(values_sorted, jnp.clip(
            starts_g + lo.astype(jnp.int32), 0, b - 1)).astype(
                jnp.float64)
        vhi = jnp.take(values_sorted, jnp.clip(
            starts_g + jnp.ceil(r).astype(jnp.int32), 0, b - 1)).astype(
                jnp.float64)
        out = vlo + (r - lo) * (vhi - vlo)
        return out, nonempty
    idx = jnp.clip(jnp.ceil(jnp.float64(pct)
                            * counts_g.astype(jnp.float64))
                   .astype(jnp.int32) - 1, 0,
                   jnp.maximum(counts_g - 1, 0))
    out = jnp.take(values_sorted,
                   jnp.clip(starts_g + idx, 0, b - 1))
    return out, nonempty


def _reduce_column(data: jnp.ndarray, valid: jnp.ndarray,
                   live: jnp.ndarray, kind: str, dt: T.DataType,
                   has_nans: bool = True) -> DeviceColumn:
    """Whole-array masked reduction → 1-element column, honoring the same
    Spark semantics as ``segment_groupby`` (NaN greatest under total
    order, wrap-free sums of valid rows only, 'first' takes the first
    LIVE row's value including nulls)."""
    contrib = valid & live
    got = jnp.any(contrib)
    if kind == "sum":
        v = jnp.sum(jnp.where(contrib, data, jnp.zeros((), data.dtype)))
        out_v, out_valid = v, got
    elif kind in ("min", "max"):
        if _is_float(dt) and not has_nans:
            inf = jnp.asarray(np.inf, data.dtype)
            sent = inf if kind == "min" else -inf
            masked = jnp.where(contrib, data, sent)
            out_v = jnp.min(masked) if kind == "min" else jnp.max(masked)
        elif _is_float(dt):
            isn = jnp.isnan(data)
            real = contrib & ~isn
            inf = jnp.asarray(np.inf, data.dtype)
            sent = inf if kind == "min" else -inf
            masked = jnp.where(real, data, sent)
            v = jnp.min(masked) if kind == "min" else jnp.max(masked)
            has_nan = jnp.any(contrib & isn)
            has_real = jnp.any(real)
            make_nan = (has_nan & ~has_real) if kind == "min" else has_nan
            out_v = jnp.where(make_nan, jnp.asarray(np.nan, data.dtype), v)
        else:
            u = encode_orderable(data, dt)
            sentinel = jnp.uint64(
                0xFFFFFFFFFFFFFFFF if kind == "min" else 0)
            u = jnp.where(contrib, u, sentinel)
            v = jnp.min(u) if kind == "min" else jnp.max(u)
            out_v = decode_orderable(jnp.reshape(v, (1,)), dt)[0]
        out_valid = got
    elif kind == "first":
        has_row = jnp.any(live)
        idx = jnp.argmax(live)
        out_v = jnp.where(has_row, data[idx], jnp.zeros((), data.dtype))
        out_valid = valid[idx] & has_row
    else:
        raise ValueError(f"unknown reduction kind {kind}")
    return DeviceColumn(dt, jnp.reshape(out_v, (1,)),
                        jnp.reshape(out_valid, (1,)))


# Most groups a first partial may hold for the rest of its stream to be
# reduced sort-free: the largest dictionary measured (8 .. 256 rows) at
# which ``sort_free_groupby`` costs at most half of ``segment_groupby``
# on a 1 M-row Q1-shaped batch on the v5e: 14.9 against 57.4 ms at 256
# (docs/kernels.md "Reducing few groups").
SORT_FREE_MAX_GROUPS = 256

_TM_SORT_FREE = TM.REGISTRY.counter(
    "tpuq_agg_sort_free_batches_total",
    "batches the grouped aggregates folded into a running accumulator "
    "of known groups, sort-free (the sum of aggSortFreeBatches)")


def _dict_rows(n_groups: int) -> int:
    """Slots of a sort-free pass's dictionary: a power of two, ≥ 8."""
    return max(8, 1 << (n_groups - 1).bit_length())


def _input_shapes(b: DeviceBatch) -> tuple:
    """What of a batch's columns, besides its capacity, its key columns'
    shapes can depend on."""
    return tuple((c.data.shape[1:], c.data.dtype, c.validity is None,
                  c.lengths is None) for c in b.columns)


def sort_free_groupby(
    key_cols: Sequence[DeviceColumn],
    sel: jnp.ndarray,
    value_cols: Sequence[Tuple[DeviceColumn, str]],
    dict_keys: Sequence[DeviceColumn],
    dict_sel: jnp.ndarray,
    acc_cols: Sequence[DeviceColumn],
    has_nans: bool = True,
) -> Tuple[List[DeviceColumn], jnp.ndarray, jnp.ndarray]:
    """Fold a batch into a running accumulator of KNOWN groups, with no
    sort and no row movement: ``segment_groupby``'s answer where the
    groups are few and known in advance.

    ``dict_keys`` / ``dict_sel`` are the G groups (live rows marked),
    ``acc_cols`` their buffers so far, one per ``value_cols`` entry
    (kinds 'sum', 'min', 'max'; no decimal128).  Rows and groups are
    encoded by the one ``ORD.group_sort_limbs`` and compared with the
    grouping equivalence (``ORD.limb_neq``); each buffer is one masked
    [G, B] reduction, ``_reduce_column`` mapped over the groups, and
    combined into the accumulator by the same reduction over the pair
    (sums and counts add, min/max keep the extreme).
    No scatter: ``segment_sum`` is a serial loop on TPU.

    Returns (new accumulator columns, unmatched [B], unmatched count):
    the live rows whose key is none of the G, which the caller reduces
    by the sort path."""
    g = int(dict_sel.shape[0])
    _, row_limbs = ORD.group_sort_limbs(list(key_cols), sel)
    _, dict_limbs = ORD.group_sort_limbs(list(dict_keys), dict_sel)
    match = dict_sel[:, None] & sel[None, :]
    for r, d in zip(row_limbs, dict_limbs):
        match = match & ~ORD.limb_neq(r[None, :], d[:, None])
    # the dictionary's keys are distinct: a row matches one group at
    # most, so a sum of (index + 1) over its matches is its group + 1;
    # each reduction rebuilds its [G, B] mask from that one [B] vector,
    # so the mask is never stored
    iota = jnp.arange(g, dtype=jnp.int32)[:, None]
    gid = jnp.sum(jnp.where(match, iota + 1, 0), axis=0) - 1
    matched = gid >= 0
    member = gid[None, :] == iota

    def by_group(data, valid, rows, kind, dt, axis=None):
        # _reduce_column once a group: row g of rows marks its rows of
        # data (shared, axis None, or the group's own, axis 0)
        col = jax.vmap(lambda d, v, r: _reduce_column(d, v, r, kind, dt,
                                                      has_nans),
                       in_axes=(axis, axis, 0))(data, valid, rows)
        return col.data[:, 0], col.validity[:, 0]

    out: List[DeviceColumn] = []
    shared = {}  # an input asked for twice (sum(x), avg(x)) reduces once
    for (c, kind), a in zip(value_cols, acc_cols):
        pkey = (id(c.data), id(c.validity), kind)
        if pkey not in shared:
            shared[pkey] = by_group(c.data, c.valid_mask(), member, kind,
                                    c.dtype)
        v, got = shared[pkey]
        data, valid = by_group(jnp.stack([a.data, v], axis=1),
                               jnp.stack([a.valid_mask(), got], axis=1),
                               jnp.ones((g, 2), jnp.bool_), kind, c.dtype,
                               axis=0)
        out.append(DeviceColumn(a.dtype, data, valid))
    unmatched = sel & ~matched
    return out, unmatched, jnp.sum(unmatched.astype(jnp.int32))


def _one_row_batch(schema: T.StructType, cols: List[DeviceColumn],
                   bucket: int = 8) -> DeviceBatch:
    """Pad 1-row columns to the minimum bucket; row 0 live."""
    out = []
    for c in cols:
        data = jnp.pad(c.data, (0, bucket - 1))
        validity = (None if c.validity is None
                    else jnp.pad(c.validity, (0, bucket - 1)))
        out.append(DeviceColumn(c.dtype, data, validity))
    sel = jnp.arange(bucket, dtype=jnp.int32) < 1
    return DeviceBatch(schema, tuple(out), sel, compacted=True)


# ---------------------------------------------------------------------------
# Partial update / final projection per aggregate function
# ---------------------------------------------------------------------------

def _eval_child(fn: AggregateFunction, batch: DeviceBatch) -> DeviceColumn:
    return fn.child.eval_tpu(batch)


def update_value_cols(fns: Sequence[AggregateFunction], batch: DeviceBatch
                      ) -> List[Tuple[DeviceColumn, str]]:
    """Per-batch buffer inputs for the partial (update) pass."""
    out: List[Tuple[DeviceColumn, str]] = []
    counts = {}  # one 0/1 column a validity: equal inputs are one array

    def count_col(validity) -> DeviceColumn:
        k = id(validity)
        if k not in counts:
            counts[k] = DeviceColumn(T.LongT, (
                jnp.ones((batch.capacity,), jnp.int64)
                if validity is None else validity.astype(jnp.int64)))
        return counts[k]

    for fn in fns:
        if isinstance(fn, CountStar):
            out.append((count_col(None), "sum"))
            continue
        c = _eval_child(fn, batch)
        if isinstance(fn, Count):
            out.append((count_col(c.validity), "sum"))
        elif isinstance(fn, (Sum, Average)):
            from spark_rapids_tpu.ops import decimal128 as D128
            rdt = fn.buffer_dtypes()[0]
            if D128.is128(rdt):
                data = (c.data if D128.is128(c.dtype)
                        else D128.from_i64(c.data))
            else:
                data = c.data.astype(T.to_numpy_dtype(rdt))
            out.append((DeviceColumn(rdt, data, c.validity), "sum"))
            out.append((count_col(c.validity), "sum"))
        elif isinstance(fn, (Min, Max)):
            out.append((c, "min" if isinstance(fn, Min) else "max"))
        elif isinstance(fn, First):
            out.append((c, "first"))
        elif isinstance(fn, _VarianceBase):
            # variance children arrive pre-cast to double (analysis.py
            # wraps them), decimals included
            x = c.data.astype(jnp.float64)
            out.append((DeviceColumn(T.DoubleT, x, c.validity), "sum"))
            out.append((DeviceColumn(T.DoubleT, x * x, c.validity), "sum"))
            out.append((count_col(c.validity), "sum"))
        else:
            raise NotImplementedError(f"TPU aggregate {fn.name}")
    return out


def merge_kinds(fns: Sequence[AggregateFunction]) -> List[str]:
    kinds: List[str] = []
    for fn in fns:
        kinds.extend(fn.buffer_kinds)
    return kinds


def final_project(fns: Sequence[AggregateFunction],
                  bufs: List[DeviceColumn]) -> List[DeviceColumn]:
    out: List[DeviceColumn] = []
    i = 0
    for fn in fns:
        nb = len(fn.buffer_kinds)
        mine = bufs[i:i + nb]
        i += nb
        if isinstance(fn, (Count, CountStar)):
            out.append(DeviceColumn(T.LongT, mine[0].data, None))
        elif isinstance(fn, Sum):
            from spark_rapids_tpu.ops import decimal128 as D128
            s, cnt = mine
            validity = cnt.data > 0
            if D128.is128(fn.result_dtype):
                validity = validity & D128.fits_precision(
                    s.data, fn.result_dtype.precision)
            out.append(DeviceColumn(fn.result_dtype, s.data, validity))
        elif isinstance(fn, Average):
            s, cnt = mine
            denom = jnp.where(cnt.data > 0, cnt.data, 1)
            out.append(DeviceColumn(
                T.DoubleT, s.data / denom.astype(jnp.float64),
                cnt.data > 0))
        elif isinstance(fn, _VarianceBase):
            s1, s2, cnt = mine
            n = cnt.data.astype(jnp.float64)
            nsafe = jnp.where(cnt.data > 0, n, 1.0)
            # Σ(x-mean)² = Σx² - (Σx)²/n, clamped (cancellation)
            m2 = jnp.maximum(s2.data - s1.data * s1.data / nsafe, 0.0)
            denom = n - fn.ddof
            var = jnp.where(denom > 0, m2 / jnp.where(denom > 0, denom,
                                                      1.0),
                            jnp.float64(np.nan))  # var_samp(1 row) = NaN
            v = jnp.sqrt(var) if fn.sqrt_final else var
            out.append(DeviceColumn(T.DoubleT, v, cnt.data > 0))
        else:  # Min/Max/First: buffer is the result
            out.append(mine[0])
    return out


# ---------------------------------------------------------------------------
# TPU exec
# ---------------------------------------------------------------------------

class TpuHashAggregateExec(TpuExec):
    """Hash-aggregate exec in one of three modes, mirroring the
    reference's partial/final split [REF: GpuHashAggregateExec]:

    * ``complete`` — update per batch → merge partials → final project
      (single-partition plans; gathers all child partitions).
    * ``partial`` — per child partition: update + local merge, emitting
      buffer-schema batches (feeds a shuffle exchange keyed on k0..kn).
    * ``final`` — per child partition: merge received buffer batches +
      final project (downstream of a key-hash exchange, so each
      partition owns disjoint keys).
    """

    def __init__(self, grouping: Sequence[Expression],
                 fns: Sequence[AggregateFunction],
                 schema: T.StructType, child: TpuExec,
                 mode: str = "complete", has_nans: bool = True,
                 bucket_rows: int = 1 << 18, skip_ratio: float = 1.0):
        super().__init__(schema, child)
        self.grouping = list(grouping)
        self.fns = list(fns)
        assert mode in ("complete", "partial", "final")
        self.mode = mode
        # spark.rapids.sql.hasNans=false elides NaN total-order handling
        self.has_nans = has_nans
        # spark.rapids.tpu.agg.bucketRows: partial-pass input coalescing
        self.bucket_rows = bucket_rows
        # spark.rapids.sql.agg.skipAggPassReductionRatio
        self.skip_ratio = skip_ratio

    def node_string(self):
        keys = ", ".join(str(g) for g in self.grouping)
        aggs = ", ".join(fn.name for fn in self.fns)
        return (f"TpuHashAggregate [{self.mode} keys=[{keys}] "
                f"aggs=[{aggs}]]")

    def num_partitions(self) -> int:
        if self.mode == "complete":
            return 1
        return self.children[0].num_partitions()

    def _partial(self, batch: DeviceBatch, pre=None,
                 pre_key=()) -> DeviceBatch:
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        from spark_rapids_tpu import kernels as KN
        grouping, fns = self.grouping, self.fns
        buffer_schema = self._buffer_schema()
        has_nans = self.has_nans

        def build(backend):
            def run(b):
                if pre is not None:
                    b = pre(b)
                keys = [g.eval_tpu(b) for g in grouping]
                vals = update_value_cols(fns, b)
                ok, ov, sel, okf = segment_groupby(
                    keys, b.sel, vals, has_nans=has_nans,
                    backend=backend)
                return DeviceBatch(buffer_schema, tuple(ok + ov), sel,
                                   compacted=True), okf
            return run

        base_key = ("agg_partial", pre_key, has_nans,
                    fingerprint(grouping), fingerprint(fns))
        be = KN.resolve("agg")

        def runner(backend):
            # the jnp key stays the historical one so persistent cache
            # entries from older builds keep hitting
            key = (base_key if backend == "jnp"
                   else base_key + (backend,))
            fn = cached_kernel(key, lambda: build(backend))
            return lambda: fn(batch)

        return KN.dispatch("agg", be, runner, node=self)

    def _buffer_schema(self) -> T.StructType:
        fields = [T.StructField(f"k{i}", g.dtype)
                  for i, g in enumerate(self.grouping)]
        j = 0
        for fn in self.fns:
            for bd in fn.buffer_dtypes():
                fields.append(T.StructField(f"b{j}", bd))
                j += 1
        return T.StructType(tuple(fields))

    @property
    def _has_collect(self) -> bool:
        return any(is_holistic_fn(f) for f in self.fns)

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        if self.mode != "complete":
            yield from self._execute_staged(partition)
            return
        assert partition == 0
        from spark_rapids_tpu.exec.base import fuse_upstream
        src, pre, pre_key = fuse_upstream(self.children[0])
        with self.timer():
            if self._has_collect:
                outs = [self._execute_collect(src, pre, pre_key)]
            elif not self.grouping:
                outs = [self._execute_global(src, pre, pre_key)]
            else:
                outs = self._execute_grouped(src, pre, pre_key)
        for out in outs:
            self.metric("numOutputBatches").add(1)
            yield out

    def _execute_collect(self, src, pre, pre_key) -> DeviceBatch:
        """collect_list path: single kernel over the gathered input
        (variable-length buffers don't ride the partial/merge protocol —
        see CollectList docstring).  Two-phase like the exchange: a
        count kernel probes the largest group for the static matrix
        width, the main kernel groups + collects."""
        from spark_rapids_tpu.columnar.column import compact, empty_batch
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        from spark_rapids_tpu.runtime.memory import get_manager
        grouping, fns, schema = self.grouping, self.fns, self.schema
        has_nans = self.has_nans
        batches = [compact(b) for p in range(src.num_partitions())
                   for b in src.execute(p)]
        if not batches:
            batches = [empty_batch(src.schema)]
        merged = concat_device_batches(src.schema, batches)
        with get_manager().transient(2 * merged.nbytes()):
            base_key = (pre_key, has_nans, fingerprint(grouping),
                        fingerprint(fns), fingerprint(schema))

            has_lists = any(isinstance(f, CollectList) for f in fns)
            cap = 1
            if has_lists:
                def build_count():
                    def run(m):
                        if pre is not None:
                            m = pre(m)
                        keys = [g.eval_tpu(m) for g in grouping]
                        contribs = [
                            f.child.eval_tpu(m).valid_mask()
                            for f in fns if isinstance(f, CollectList)]
                        return segment_max_group_count(keys, m.sel,
                                                       contribs)
                    return run

                cnt_fn = cached_kernel(
                    ("agg_collect_count",) + base_key, build_count)
                cap = int(np.asarray(cnt_fn(merged)))
                cap = max(1, 1 << (cap - 1).bit_length()
                          if cap > 1 else 1)

            def build_main():
                def run(m):
                    if pre is not None:
                        m = pre(m)
                    keys = [g.eval_tpu(m) for g in grouping]
                    normal = [f for f in fns if not is_holistic_fn(f)]
                    vals = update_value_cols(normal, m)
                    # stays on the jnp layout: the sibling segment_*
                    # helpers key-sort independently and the output
                    # columns are zipped positionally — all layouts
                    # must agree on group order
                    ok, ov, sel, _ = segment_groupby(keys, m.sel, vals,
                                                     has_nans=has_nans)
                    normal_res = iter(final_project(normal, ov))
                    cols = list(ok)
                    for f in fns:
                        if isinstance(f, CollectList):
                            mat, lens = segment_collect(
                                keys, m.sel, f.child.eval_tpu(m), cap,
                                distinct=isinstance(f, CollectSet))
                            cols.append(DeviceColumn(
                                f.result_dtype, mat, None, lens))
                        elif isinstance(f, Percentile):
                            v, vv = segment_percentile(
                                keys, m.sel, f.child.eval_tpu(m),
                                f.pct,
                                interpolate=not isinstance(
                                    f, ApproxPercentile))
                            cols.append(DeviceColumn(
                                f.result_dtype, v, vv))
                        elif is_holistic_fn(f):
                            kind = ("min" if isinstance(f, Min) else
                                    "max" if isinstance(f, Max)
                                    else "first")
                            cols.append(segment_extreme(
                                keys, m.sel, f.child.eval_tpu(m), kind))
                        else:
                            cols.append(next(normal_res))
                    if not grouping:
                        # global holistic aggregate: exactly one output
                        # row even over an empty input (count-style
                        # validity already nulls the value columns)
                        sel = jnp.arange(m.capacity,
                                         dtype=jnp.int32) < 1
                    return DeviceBatch(schema, tuple(cols), sel,
                                       compacted=True)
                return run

            fn = cached_kernel(("agg_collect", cap) + base_key,
                               build_main)
            return fn(merged)

    def _execute_global(self, src, pre, pre_key) -> DeviceBatch:
        """Global aggregate: per-batch masked REDUCTION (no sort, no
        row movement — on the v5e the group-by's hash sort is 2.2 ms a
        1 M-row batch, but every gather that brings rows into its order
        is ~16 ms, ledger PR 26, against ~1 ms for the reduce), with
        upstream filter/project fused into the kernel.  Streamed: one
        input batch held at a time; the single-batch case fuses final
        projection into the same kernel (one dispatch total)."""
        from spark_rapids_tpu.runtime.memory import (
            RetryOOM, get_manager, with_retry)
        mgr = get_manager()
        stream = (b for p in range(src.num_partitions())
                  for b in src.execute(p))
        first = next(stream, None)
        if first is None:
            return self._reduce_merge_final([])
        second = next(stream, None)
        if second is None:
            try:
                with mgr.transient(first.nbytes()):
                    return self._reduce_batch(first, pre, pre_key,
                                              final=True)
            except RetryOOM:
                pass  # fall through to the splittable two-phase path

        def closure(b):
            with mgr.transient(b.nbytes()):
                return self._reduce_batch(b, pre, pre_key)

        def inputs():
            yield first
            if second is not None:
                yield second
            yield from stream

        partials = list(with_retry(
            inputs(), closure, max_attempts=mgr.retry_max_attempts,
            manager=mgr))
        return self._reduce_merge_final(partials)

    def _coalesced(self, stream) -> Iterator[DeviceBatch]:
        """Group input batches up to ``bucket_rows`` LIVE rows before the
        partial pass: each partial chain pays a fixed dispatch
        cost, so fewer/larger sorts win (the hash-capped key
        encoding keeps sort operands flat as the bucket grows).

        Count pulls are WINDOWED: live counts for up to 32 batches come
        back in ONE overlapped round trip and thread into the concats
        instead of one pull per concat (the window size has no chip
        measurement yet)."""
        cap = self.bucket_rows
        if not cap:
            yield from stream
            return
        from spark_rapids_tpu.columnar.column import compact
        from spark_rapids_tpu.exec.basic import _overlapped_live_counts

        def flush(window) -> Iterator[DeviceBatch]:
            if not window:
                return
            if len(window) == 1:
                yield window[0]
                return
            counts = _overlapped_live_counts(window)  # one round trip
            group: List[DeviceBatch] = []
            gcounts: List[int] = []
            acc = 0
            for b, n in zip(window, counts):
                if group and acc + n > cap:
                    yield self._emit_group(group, gcounts, compact)
                    group, gcounts, acc = [], [], 0
                group.append(b)
                gcounts.append(n)
                acc += n
            if group:
                yield self._emit_group(group, gcounts, compact)

        window: List[DeviceBatch] = []
        wcap = 0
        for b in stream:
            if b.capacity >= cap and not window:
                yield b
                continue
            window.append(b)
            wcap += b.capacity
            if len(window) >= 32 or wcap >= 8 * cap:
                yield from flush(window)
                window, wcap = [], 0
        yield from flush(window)

    def _emit_group(self, group, gcounts, compact) -> DeviceBatch:
        if len(group) == 1:
            return group[0]
        with self.timer("concatTime"):
            batches = [compact(b) for b in group]
            return concat_device_batches(batches[0].schema, batches,
                                         counts=gcounts)

    def _first_groups(self, outs1: List[DeviceBatch], n_in: int
                      ) -> Optional[int]:
        """The live groups of the first input batch's partial(s)
        (``outs1``, plural when the OOM-retry split it; ``n_in`` its
        live rows), pulled for the skip-agg-pass ratio [REF:
        GpuHashAggregateExec skipAggPassReductionRatio]; None where
        the ratio is off or the batch too small to establish it."""
        if self.skip_ratio >= 1.0:
            return None
        # small batches can't establish the ratio (64 rows → 60 groups
        # says nothing about 6M rows)
        if n_in < 4096:
            return None
        from spark_rapids_tpu.exec.basic import _overlapped_live_counts
        return sum(_overlapped_live_counts(outs1))

    def _sort_free_fits(self, outs1: List[DeviceBatch],
                        n_groups: Optional[int]) -> bool:
        """May the rest of the stream fold into the first partial's
        groups sort-free?  Few groups, one partial, and buffers that
        ``sort_free_groupby`` reduces (sum/min/max, no decimal128).
        Not where the fused filter kept no row of the first batch:
        every later row would miss the empty dictionary."""
        from spark_rapids_tpu.ops import decimal128 as D128
        if (not n_groups or len(outs1) != 1
                or n_groups > SORT_FREE_MAX_GROUPS
                or _dict_rows(n_groups) > outs1[0].capacity):
            return False
        return (all(k in ("sum", "min", "max")
                    for k in merge_kinds(self.fns))
                and not any(D128.is128(dt) for fn in self.fns
                            for dt in fn.buffer_dtypes()))

    def _partial_stream(self, stream, pre, pre_key, mgr
                        ) -> Tuple[Optional[List[DeviceBatch]], bool]:
        """Shared partial-pass driver (complete AND staged-partial
        modes): coalesce, run the first group's partial under retry,
        decide from its group count (skip-agg-pass when nearly every
        row is a group, the sort-free pass when the groups are few),
        stream the rest.  Returns (partials | None for an empty stream,
        skip)."""
        from spark_rapids_tpu.exec.basic import _overlapped_live_counts
        from spark_rapids_tpu.runtime.memory import with_retry
        stream = self._coalesced(stream)
        first = next(stream, None)
        if first is None:
            return None, False

        def closure_partial(b):
            with mgr.transient(b.nbytes()):
                return self._partial(b, pre, pre_key)

        with self.timer("decideTime"):
            n_in = (_overlapped_live_counts([first])[0]
                    if self.skip_ratio < 1.0 else 0)
            outs1 = list(with_retry(
                iter([first]), closure_partial,
                max_attempts=mgr.retry_max_attempts, manager=mgr))
            n_groups = self._first_groups(outs1, n_in)
            skip = (n_groups is not None
                    and n_groups / max(n_in, 1) > self.skip_ratio)
        if skip:
            self.count("skippedAggPasses", 1)
        elif self._sort_free_fits(outs1, n_groups):
            with self.timer("partialTime"):
                return self._sort_free_stream(
                    first, outs1[0], n_groups, stream, pre, pre_key,
                    mgr), False

        def closure(b):
            with mgr.transient(b.nbytes()):
                if skip:
                    return self._update_raw(b, pre, pre_key)
                return self._partial(b, pre, pre_key)

        with self.timer("partialTime"):
            partials = outs1 + list(with_retry(
                stream, closure, max_attempts=mgr.retry_max_attempts,
                manager=mgr))
        return partials, skip

    def _sort_free_stream(self, first: DeviceBatch, out1: DeviceBatch,
                          n_groups: int, stream, pre, pre_key, mgr
                          ) -> List[DeviceBatch]:
        """The sort-free partial pass: the first partial's groups are
        the dictionary, its buffers seed an accumulator of
        ``_dict_rows(n_groups)`` rows, and every later batch folds into
        it by ``sort_free_groupby``.  A batch's unmatched count is read
        one batch late, once the next batch is dispatched: where it is
        not 0, those rows alone take ``_partial`` and join the merge
        beside the accumulator.  A batch whose key columns are shaped
        unlike the dictionary's takes ``_partial`` whole.  Dictionary
        and accumulator are this execution's: two queries may run the
        node at once."""
        from spark_rapids_tpu.runtime.memory import with_retry
        acc = self._sort_free_seed(out1, _dict_rows(n_groups))
        fits = {_input_shapes(first): True}
        fallbacks: List[DeviceBatch] = []

        def partial(b):
            with mgr.transient(b.nbytes()):
                return self._partial(b, pre, pre_key)

        def fold(b):
            nonlocal acc
            shapes = _input_shapes(b)
            if shapes not in fits:
                fits[shapes] = self._keys_like(b, pre, acc)
            if not fits[shapes]:
                return partial(b)
            with mgr.transient(b.nbytes()):
                acc2, unmatched, n = self._sort_free(b, acc, pre, pre_key)
            acc = acc2
            n.copy_to_host_async()
            return b, unmatched, n

        def settle(b, unmatched, n):
            if int(np.asarray(n)):
                self.count("aggSortFreeFallbacks", 1)
                fallbacks.extend(with_retry(
                    iter([b.with_sel(unmatched)]), partial,
                    max_attempts=mgr.retry_max_attempts, manager=mgr))

        pending = None
        for res in with_retry(stream, fold,
                              max_attempts=mgr.retry_max_attempts,
                              manager=mgr):
            if pending is not None:
                settle(*pending)
                pending = None
            if isinstance(res, DeviceBatch):
                self.count("aggSortFreeFallbacks", 1)
                fallbacks.append(res)
            else:
                self.count("aggSortFreeBatches", 1)
                _TM_SORT_FREE.inc()
                pending = res
        if pending is not None:
            settle(*pending)
        return [acc] + fallbacks

    def _sort_free_seed(self, out1: DeviceBatch, rows: int) -> DeviceBatch:
        """The first partial's front ``rows`` slots (its groups are at
        the front): the dictionary's keys and the accumulator's seed."""
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel

        def build():
            def run(p):
                return jax.tree_util.tree_map(lambda x: x[:rows], p)
            return run

        return cached_kernel(("agg_sort_free_seed", rows), build)(out1)

    def _keys_like(self, batch: DeviceBatch, pre, acc: DeviceBatch
                   ) -> bool:
        """Do ``batch``'s key columns come out shaped as the
        dictionary's (string widths, null planes)?  Traced, not run."""
        nk = len(self.grouping)

        def keys(b):
            if pre is not None:
                b = pre(b)
            return [g.eval_tpu(b) for g in self.grouping]

        def trailing(cols):
            leaves, tree = jax.tree_util.tree_flatten(list(cols))
            return tree, [(x.shape[1:], x.dtype) for x in leaves]

        return (trailing(jax.eval_shape(keys, batch))
                == trailing(acc.columns[:nk]))

    def _sort_free(self, batch: DeviceBatch, acc: DeviceBatch, pre=None,
                   pre_key=()):
        """One batch into the accumulator: ``sort_free_groupby`` fused
        with the upstream filter/project, as ``_partial`` is.  Returns
        (accumulator, unmatched [B], unmatched count)."""
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        grouping, fns = self.grouping, self.fns
        nk = len(grouping)
        has_nans = self.has_nans

        def build():
            def run(b, a):
                if pre is not None:
                    b = pre(b)
                keys = [g.eval_tpu(b) for g in grouping]
                vals = update_value_cols(fns, b)
                bufs, unmatched, n = sort_free_groupby(
                    keys, b.sel, vals, a.columns[:nk], a.sel,
                    a.columns[nk:], has_nans=has_nans)
                return (DeviceBatch(a.schema, a.columns[:nk] + tuple(bufs),
                                    a.sel, compacted=True), unmatched, n)
            return run

        key = ("agg_sort_free", pre_key, has_nans, fingerprint(grouping),
               fingerprint(fns), acc.capacity)
        return cached_kernel(key, build)(batch, acc)

    def _execute_grouped(self, src, pre, pre_key) -> List[DeviceBatch]:
        """Update-per-batch under the OOM-retry framework: a RetryOOM
        spills the arbiter's pool and re-runs the batch; repeated
        pressure halves it by rows (partials merge regardless — the
        repartition-fallback-friendly shape [REF: withRetry +
        GpuAggregateIterator])."""
        from spark_rapids_tpu.runtime.memory import get_manager
        mgr = get_manager()
        # lazy: one upstream batch live at a time, so retry spills
        # actually free HBM instead of fighting a pinned input list
        partials, _skip = self._partial_stream(
            (b for p in range(src.num_partitions())
             for b in src.execute(p)), pre, pre_key, mgr)
        if partials is None:
            from spark_rapids_tpu.columnar.column import empty_batch
            partials = [self._partial(empty_batch(src.schema), pre,
                                      pre_key)]
        with self.timer("mergeTime"):
            return self._merge_bounded(partials, self._merge_final)

    def _update_raw(self, batch: DeviceBatch, pre=None,
                    pre_key=()) -> DeviceBatch:
        """Buffer-schema batch WITHOUT the per-batch reduction — the
        skip-agg-pass path: keys + per-row update buffers pass straight
        to the merge, whose single reduction then does all the work.
        Cheap elementwise kernel (no sort, no scans)."""
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        grouping, fns = self.grouping, self.fns
        buffer_schema = self._buffer_schema()

        def build():
            def run(b):
                if pre is not None:
                    b = pre(b)
                keys = [g.eval_tpu(b) for g in grouping]
                vals = [c for c, _ in update_value_cols(fns, b)]
                return DeviceBatch(buffer_schema, tuple(keys + vals),
                                   b.sel)
            return run

        fn = cached_kernel(
            ("agg_raw", pre_key, fingerprint(grouping),
             fingerprint(fns)), build)
        return fn(batch)

    def _merge_bounded(self, partials: List[DeviceBatch],
                       merge_fn) -> List[DeviceBatch]:
        """Concat + merge partial buffer batches, with the
        merge-explosion repartition fallback [REF: GpuAggregateExec
        repartition fallback]: when merged cardinality ≈ input (total
        live rows far exceed one batch bucket), one concat would build
        — and compile a merge kernel for — an exploded bucket; instead
        the partials re-hash-partition by grouping key and each bucket
        merges independently (equal keys share a bucket, so semantics
        hold per bucket)."""
        from spark_rapids_tpu.columnar.column import compact
        from spark_rapids_tpu.exec.basic import _overlapped_live_counts
        partials = [compact(p) for p in partials]
        self.count("aggPartials", len(partials))
        if len(partials) == 1:
            return [merge_fn(partials[0])]
        schema = self._buffer_schema()
        if len(partials) <= 2:
            return [merge_fn(concat_device_batches(schema, partials))]
        counts = _overlapped_live_counts(partials)
        total = sum(counts)
        self.count("aggPartialRows", total)
        cap = max(b.capacity for b in partials)
        if total <= 2 * cap:
            return [merge_fn(concat_device_batches(schema, partials,
                                                   counts=counts))]
        self.metric("repartitionMerges").add(1)
        from spark_rapids_tpu.ops.expressions import BoundReference
        from spark_rapids_tpu.parallel.shuffle import (
            make_pid_fn, split_to_spillables)
        from spark_rapids_tpu.runtime.kernel_cache import fingerprint
        from spark_rapids_tpu.runtime.memory import get_manager
        mgr = get_manager()
        k = int(min(64, max(2, -(-total // cap))))
        keys = [BoundReference(i, g.dtype)
                for i, g in enumerate(self.grouping)]
        # NOT the default shuffle seed: in final/staged mode the partials
        # arrived via a seed-42 hash-mod-nparts exchange, so re-hashing
        # with seed 42 would collapse every key into k/gcd(k,nparts)
        # buckets (often one) and re-create the exploded concat this
        # fallback exists to avoid — same reason the join sub-partition
        # path uses its own SUB_SEED.
        AGG_SEED = 0x41475242
        pid_fn = make_pid_fn(keys, k, seed=AGG_SEED)
        self.count("aggRepartitionBuckets", k)
        _TM_REPART_BUCKETS.inc(k)
        with self.timer("repartitionTime"):
            slices = split_to_spillables(
                partials, lambda b, aux: pid_fn(b), k, mgr,
                ("aggrepart", k, AGG_SEED, fingerprint(keys),
                 fingerprint(schema)))
        out = []
        for i in range(k):
            if not slices[i]:
                continue
            bs = [s.get() for s in slices[i]]
            bcounts = [s.live_rows for s in slices[i]]
            out.append(merge_fn(concat_device_batches(
                schema, bs, counts=bcounts)))
            for s in slices[i]:
                s.close()
        return out

    def _execute_staged(self, partition: int) -> Iterator[DeviceBatch]:
        """partial/final modes: operate on ONE child partition's stream
        (the stage-local halves of the distributed aggregate)."""
        from spark_rapids_tpu.columnar.column import compact, empty_batch
        from spark_rapids_tpu.exec.base import fuse_upstream
        child = self.children[0]
        with self.timer():
            if self.mode == "partial":
                from spark_rapids_tpu.runtime.memory import get_manager
                mgr = get_manager()
                src, pre, pre_key = fuse_upstream(child)
                partials, skip = self._partial_stream(
                    src.execute(partition), pre, pre_key, mgr)
                if partials is None:
                    yield empty_batch(self._buffer_schema())
                    return
                if len(partials) == 1 or skip:
                    # skip mode: a local combine would do exactly the
                    # reduction the ratio said is useless — ship raw
                    # buffers to the exchange; the final pass reduces
                    outs = partials
                else:
                    outs = self._merge_bounded(partials,
                                               self._merge_buffers)
            else:  # final
                batches = [compact(b) for b in child.execute(partition)]
                if not batches:
                    return
                outs = self._merge_bounded(batches, self._merge_final)
        for out in outs:
            self.metric("numOutputBatches").add(1)
            yield out

    def _merge_buffers(self, merged: DeviceBatch) -> DeviceBatch:
        """Merge buffer batches into one buffer batch (no final project):
        the partial-side local combine."""
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        from spark_rapids_tpu import kernels as KN
        grouping, fns = self.grouping, self.fns
        nk = len(grouping)
        buffer_schema = self._buffer_schema()
        has_nans = self.has_nans

        def build(backend):
            def run(m):
                keys = list(m.columns[:nk])
                bufs = list(m.columns[nk:])
                kinds = merge_kinds(fns)
                ok, ov, sel, okf = segment_groupby(
                    keys, m.sel, list(zip(bufs, kinds)),
                    has_nans=has_nans, backend=backend)
                return DeviceBatch(buffer_schema, tuple(ok + ov), sel,
                                   compacted=True), okf
            return run

        base_key = ("agg_merge_buffers", has_nans,
                    fingerprint(grouping), fingerprint(fns))
        be = KN.resolve("agg")

        def runner(backend):
            key = (base_key if backend == "jnp"
                   else base_key + (backend,))
            fn = cached_kernel(key, lambda: build(backend))
            return lambda: fn(merged)

        return KN.dispatch("agg", be, runner, node=self)

    def _merge_final(self, merged: DeviceBatch) -> DeviceBatch:
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        from spark_rapids_tpu import kernels as KN
        grouping, fns, schema = self.grouping, self.fns, self.schema
        nk = len(grouping)
        has_nans = self.has_nans

        def build(backend):
            def run(m):
                keys = list(m.columns[:nk])
                bufs = list(m.columns[nk:])
                kinds = merge_kinds(fns)
                ok, ov, sel, okf = segment_groupby(
                    keys, m.sel, list(zip(bufs, kinds)),
                    has_nans=has_nans, backend=backend)
                results = final_project(fns, ov)
                return DeviceBatch(schema, tuple(ok + results), sel,
                                   compacted=True), okf
            return run

        base_key = ("agg_merge", has_nans, fingerprint(grouping),
                    fingerprint(fns), fingerprint(schema))
        be = KN.resolve("agg")

        def runner(backend):
            key = (base_key if backend == "jnp"
                   else base_key + (backend,))
            fn = cached_kernel(key, lambda: build(backend))
            return lambda: fn(merged)

        return KN.dispatch("agg", be, runner, node=self)

    def _reduce_batch(self, batch: DeviceBatch, pre=None, pre_key=(),
                      final: bool = False) -> DeviceBatch:
        """Per-batch global-aggregate update: masked reduction of every
        buffer input to one row (capacity 8).  One jitted kernel (with
        upstream filter/project fused in); no sort, no scan — the whole
        batch collapses in a tree reduction.  ``final=True`` (the
        single-batch case) additionally fuses the final projection so
        the whole aggregate is one dispatch."""
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        fns = self.fns
        out_schema = self.schema if final else self._buffer_schema()
        has_nans = self.has_nans

        def build():
            def run(b):
                if pre is not None:
                    b = pre(b)
                vals = update_value_cols(fns, b)
                bufs = [
                    _reduce_column(c.data, c.valid_mask(), b.sel, kind,
                                   c.dtype, has_nans=has_nans)
                    for c, kind in vals]
                if final:
                    bufs = final_project(fns, bufs)
                return _one_row_batch(out_schema, bufs)
            return run

        fn = cached_kernel(
            ("agg_reduce", final, pre_key, has_nans, fingerprint(fns),
             fingerprint(out_schema)), build)
        return fn(batch)

    def _reduce_merge_final(self, partials: List[DeviceBatch]
                            ) -> DeviceBatch:
        """Merge per-batch reductions and final-project — one kernel."""
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        if not partials:
            from spark_rapids_tpu.columnar.column import empty_batch
            partials = [self._reduce_batch(
                empty_batch(self.children[0].schema))]
        fns, schema = self.fns, self.schema
        kinds = merge_kinds(fns)
        has_nans = self.has_nans

        def build():
            def run(ps):
                sel = jnp.concatenate([p.sel for p in ps])
                bufs = []
                for j, kind in enumerate(kinds):
                    data = jnp.concatenate([p.columns[j].data for p in ps])
                    valid = jnp.concatenate(
                        [p.columns[j].valid_mask() for p in ps])
                    bufs.append(_reduce_column(data, valid, sel, kind,
                                               ps[0].columns[j].dtype,
                                               has_nans=has_nans))
                results = final_project(fns, bufs)
                return _one_row_batch(schema, results)
            return run

        fn = cached_kernel(
            ("agg_reduce_merge", len(partials), has_nans,
             fingerprint(fns), fingerprint(schema)), build)
        return fn(partials)


# ---------------------------------------------------------------------------
# CPU oracle exec
# ---------------------------------------------------------------------------

class CpuAggregateExec(CpuExec):
    def __init__(self, grouping: Sequence[Expression],
                 fns: Sequence[AggregateFunction],
                 schema: T.StructType, child: CpuExec):
        super().__init__(schema, child)
        self.grouping = list(grouping)
        self.fns = list(fns)

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        child = self.children[0]
        groups = {}
        order: List[tuple] = []
        for p in range(child.num_partitions()):
            for b in child.execute(p):
                n = b.num_rows
                key_cols = [g.eval_cpu(b) for g in self.grouping]
                val_cols = [None if isinstance(fn, CountStar)
                            else fn.child.eval_cpu(b) for fn in self.fns]
                for i in range(n):
                    key = tuple(
                        None if (kc.validity is not None
                                 and not kc.validity[i])
                        else _norm_key(kc.data[i], kc.dtype)
                        for kc in key_cols)
                    st = groups.get(key)
                    if st is None:
                        st = [_new_acc(fn) for fn in self.fns]
                        groups[key] = st
                        order.append(key)
                    for acc, fn, vc in zip(st, self.fns, val_cols):
                        _acc_update(acc, fn, vc, i)
        if not self.grouping and not groups:
            groups[()] = [_new_acc(fn) for fn in self.fns]
            order.append(())
        rows = []
        for key in order:
            st = groups[key]
            rows.append(list(key) + [_acc_final(a, fn)
                                     for a, fn in zip(st, self.fns)])
        cols = list(zip(*rows)) if rows else [[] for _ in self.schema.fields]
        out_cols = []
        for vals, f in zip(cols, self.schema.fields):
            vals = list(vals)
            validity = np.array([v is not None for v in vals], bool)
            if isinstance(f.dtype, T.ArrayType):
                data = np.empty(len(vals), dtype=object)
                for i, v in enumerate(vals):
                    data[i] = v if v is not None else []
            elif isinstance(f.dtype, (T.StringType, T.BinaryType)):
                data = np.array([v if v is not None else "" for v in vals],
                                dtype=object)
            elif (isinstance(f.dtype, T.DecimalType)
                  and f.dtype.precision > T.DecimalType.MAX_LONG_DIGITS):
                data = np.empty(len(vals), dtype=object)
                for i, v in enumerate(vals):
                    data[i] = int(v) if v is not None else 0
            else:
                npdt = T.to_numpy_dtype(f.dtype)
                data = np.array([v if v is not None else 0 for v in vals])
                data = data.astype(npdt, copy=False)
            out_cols.append(H.HostCol(
                f.dtype, data, None if validity.all() else validity))
        yield H.HostBatch(self.schema, out_cols)


def _norm_key(v, dt):
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        f = float(v)
        if np.isnan(f):
            return "NaN"
        if f == 0.0:
            return 0.0  # -0.0 and 0.0 one group (Spark normalizes keys)
        return f
    if isinstance(dt, T.BooleanType):
        return bool(v)
    if isinstance(dt, (T.StringType, T.BinaryType)):
        return v
    return int(v)


def _new_acc(fn):
    return {"sum": 0, "count": 0, "min": None, "max": None, "first": None,
            "has_first": False, "mean": 0.0, "m2": 0.0, "list": []}


def _acc_update(acc, fn, vc, i):
    if isinstance(fn, CountStar):
        acc["count"] += 1
        return
    valid = vc.validity is None or bool(vc.validity[i])
    if isinstance(fn, First):
        if not acc["has_first"]:
            acc["first"] = vc.data[i] if valid else None
            acc["has_first"] = True
        return
    if not valid:
        return
    v = vc.data[i]
    if isinstance(fn, Count):
        acc["count"] += 1
    elif isinstance(fn, (Sum, Average)):
        acc["count"] += 1
        if isinstance(fn.child.dtype, T.DecimalType):
            # exact python-int accumulation: decimal sums widen to
            # p+10 digits (a decimal128 buffer on device)
            acc["sum"] = int(acc["sum"]) + int(v)
        elif T.is_integral(fn.child.dtype):
            with np.errstate(over="ignore"):  # Spark non-ANSI sum wraps
                acc["sum"] = np.int64(acc["sum"] + np.int64(v))
        else:
            acc["sum"] = float(acc["sum"]) + float(v)
    elif isinstance(fn, _VarianceBase):
        # Welford, exactly Spark's CentralMomentAgg update
        acc["count"] += 1
        delta = float(v) - acc["mean"]
        acc["mean"] += delta / acc["count"]
        acc["m2"] += delta * (float(v) - acc["mean"])
    elif isinstance(fn, (CollectList, Percentile)):
        acc["list"].append(vc.data[i])
    elif isinstance(fn, Min):
        acc["min"] = v if acc["min"] is None else _spark_min(acc["min"], v, fn)
    elif isinstance(fn, Max):
        acc["max"] = v if acc["max"] is None else _spark_max(acc["max"], v, fn)


def _total_key(v, dt):
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        f = float(v)
        if np.isnan(f):
            return (1, 0.0)
        return (0, f)
    return (0, v)


def _spark_min(a, b, fn):
    dt = fn.child.dtype
    return a if _total_key(a, dt) <= _total_key(b, dt) else b


def _spark_max(a, b, fn):
    dt = fn.child.dtype
    return a if _total_key(a, dt) >= _total_key(b, dt) else b


def _acc_final(acc, fn):
    if isinstance(fn, (Count, CountStar)):
        return int(acc["count"])
    if isinstance(fn, Sum):
        if acc["count"] == 0:
            return None
        if isinstance(fn.child.dtype, T.DecimalType):
            # mirror the 128-bit container wrap + overflow-to-null
            from spark_rapids_tpu.ops import decimal128 as D128
            w = D128.py_wrap128(acc["sum"])
            return (w if D128.py_fits(w, fn.result_dtype.precision)
                    else None)
        return acc["sum"]
    if isinstance(fn, Average):
        if acc["count"] == 0:
            return None
        return float(acc["sum"]) / acc["count"]
    if isinstance(fn, _VarianceBase):
        n = acc["count"]
        if n == 0:
            return None
        denom = n - fn.ddof
        var = acc["m2"] / denom if denom > 0 else float("nan")
        import math
        return math.sqrt(var) if fn.sqrt_final and var == var else (
            float("nan") if fn.sqrt_final else var)
    if isinstance(fn, CollectSet):
        dt = fn.input_dtype
        uniq = {}
        for v in acc["list"]:
            uniq.setdefault(_total_key(v, dt), v)
        return [_py_scalar(uniq[k], dt) for k in sorted(uniq)]
    if isinstance(fn, CollectList):
        return [_py_scalar(v, fn.input_dtype) for v in acc["list"]]
    if isinstance(fn, ApproxPercentile):
        vals = sorted(acc["list"],
                      key=lambda v: _total_key(v, fn.input_dtype))
        if not vals:
            return None
        import math
        idx = min(max(math.ceil(fn.pct * len(vals)) - 1, 0),
                  len(vals) - 1)
        return _py_scalar(vals[idx], fn.input_dtype)
    if isinstance(fn, Percentile):
        vals = sorted((float(v) for v in acc["list"]),
                      key=lambda x: _total_key(x, T.DoubleT))
        if not vals:
            return None
        import math
        r = fn.pct * (len(vals) - 1)
        lo = math.floor(r)
        hi = math.ceil(r)
        return vals[lo] + (r - lo) * (vals[hi] - vals[lo])
    if isinstance(fn, Min):
        return acc["min"]
    if isinstance(fn, Max):
        return acc["max"]
    if isinstance(fn, First):
        return acc["first"]
    raise NotImplementedError(fn.name)


def _py_scalar(v, dt):
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        return float(v)
    if isinstance(dt, T.BooleanType):
        return bool(v)
    if isinstance(dt, (T.StringType, T.BinaryType)):
        return v
    return int(v)


def plan_cpu_aggregate(node: L.Aggregate, child: CpuExec,
                       conf: RapidsConf) -> CpuExec:
    return CpuAggregateExec(node.grouping, node.aggregates, node.schema,
                            child)
