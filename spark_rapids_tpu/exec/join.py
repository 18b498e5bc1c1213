"""Join execs: CPU oracle hash join + TPU sort-merge equi-join.

[REF: sql-plugin/../GpuShuffledHashJoinExec.scala, joins/,
 GpuSortMergeJoinMeta] — the reference builds cuDF hash tables; the
TPU-first design is sort-merge (SURVEY §7 phase 5: "sort-merge first,
Pallas hash join second"):

  encode join keys as uint64 limbs → sort the build (right) side with one
  ``lax.sort`` → vectorized lexicographic binary search gives each left
  row its [lo, hi) match range → static-shape expansion (the only
  dynamic→static point: the output row count syncs to host once to pick
  the output bucket, the analog of cuDF's join output allocation).

Null keys never match (Spark equi-join semantics); rows with null keys
still surface for outer/anti outputs.

Key encoding is CANONICAL across sides: both sides must emit the exact
same limb layout or the fused-limb comparison is garbage (a right side
with no validity mask, a narrower string matrix, or an int32 vs int64 key
would otherwise encode differently).  So join keys always encode as:
integral family → 64-bit biased; strings → byte matrix padded to the
shared max width of both sides; f32 → orderable u32 bits; f64 → NaN flag
+ raw float limb.  Null/dead rows are excluded via the leading exclusion
flag, not via per-column null limbs.  Float keys follow Spark's
NormalizeFloatingNumbers semantics (NaN == NaN, -0.0 == 0.0 as keys).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import host as H
from spark_rapids_tpu.columnar.column import (
    DeviceBatch, DeviceColumn, live_bucket, round_up_pow2)
from spark_rapids_tpu.exec.base import CpuExec, TpuExec
from spark_rapids_tpu.exec.basic import (
    _compact_counted, concat_device_batches)
from spark_rapids_tpu.kernels import hash_layout as HL
from spark_rapids_tpu.ops import ordering as ORD
from spark_rapids_tpu.ops.expressions import Expression
from spark_rapids_tpu.runtime import telemetry as TM

_TM_PROBE_GROUPS = TM.REGISTRY.counter(
    "tpuq_join_probe_groups_total",
    "probe groups device joins ran: calls of the match kernel with a "
    "probe (the sum of joinProbeGroups)")


# ---------------------------------------------------------------------------
# helpers shared by both paths
# ---------------------------------------------------------------------------

def _pump_list(child, partition=None) -> List[DeviceBatch]:
    """Child batches as they come (all partitions or one)."""
    parts = (range(child.num_partitions()) if partition is None
             else [partition])
    return [b for p in parts for b in child.execute(p)]


def _gather_list(child, partition=None, node=None):
    """Child batches compacted at their live buckets, with their live
    counts and the capacities they came at (all partitions or one)."""
    return _compact_counted(_pump_list(child, partition), node)


def _concat_or_empty(schema, batches, counts=None):
    from spark_rapids_tpu.columnar.column import empty_batch
    if not batches:
        return empty_batch(schema)
    return concat_device_batches(schema, batches, counts=counts)


def _gather_all(child, schema, device: bool, partition=None):
    """Concat child batches to one batch — all partitions, or just one
    (the co-partitioned path downstream of a key-hash exchange)."""
    if device:
        batches, counts, _ = _gather_list(child, partition)
        return _concat_or_empty(schema, batches, counts=counts)
    from spark_rapids_tpu.exec.sort import _concat_host
    batches = _pump_list(child, partition)
    if not batches:
        return H.HostBatch(schema, [
            H.HostCol(f.dtype,
                      np.array([], dtype=object)
                      if isinstance(f.dtype, (T.StringType, T.BinaryType))
                      else np.zeros(0, T.to_numpy_dtype(f.dtype)), None)
            for f in schema.fields])
    return _concat_host(schema, batches)


# ---------------------------------------------------------------------------
# CPU oracle
# ---------------------------------------------------------------------------

class CpuJoinExec(CpuExec):
    def __init__(self, join_type: str, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 condition: Optional[Expression], schema: T.StructType,
                 left: CpuExec, right: CpuExec, using: bool = True):
        super().__init__(schema, left, right)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self.using = using

    def node_string(self):
        cond = f" cond={self.condition}" if self.condition else ""
        return f"Join [{self.join_type}{cond}]"

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        lb = _gather_all(self.children[0], self.children[0].schema, False)
        rb = _gather_all(self.children[1], self.children[1].schema, False)
        nl, nr = lb.num_rows, rb.num_rows
        jt = self.join_type

        def key_tuple(cols, i):
            out = []
            for c in cols:
                if c.validity is not None and not c.validity[i]:
                    return None
                v = c.data[i]
                if isinstance(c.dtype, (T.FloatType, T.DoubleType)):
                    f = float(v)
                    v = "NaN" if np.isnan(f) else (0.0 if f == 0.0 else f)
                elif isinstance(c.dtype, (T.StringType, T.BinaryType)):
                    pass
                else:
                    v = int(v)
                out.append(v)
            return tuple(out)

        # 1. candidate pairs from equi keys (or the full cross space)
        if jt == "cross" or not self.left_keys:
            cl = np.repeat(np.arange(nl, dtype=np.int64), nr)
            cr = np.tile(np.arange(nr, dtype=np.int64), nl)
        else:
            lk = [e.eval_cpu(lb) for e in self.left_keys]
            rk = [e.eval_cpu(rb) for e in self.right_keys]
            index = {}
            for j in range(nr):
                k = key_tuple(rk, j)
                if k is not None:
                    index.setdefault(k, []).append(j)
            cl_list, cr_list = [], []
            for i in range(nl):
                k = key_tuple(lk, i)
                for j in (index.get(k, []) if k is not None else []):
                    cl_list.append(i)
                    cr_list.append(j)
            cl = np.array(cl_list, dtype=np.int64)
            cr = np.array(cr_list, dtype=np.int64)

        # 2. residual condition filters candidates (null → drop), eval'd
        #    vectorized over the candidate pair batch in the
        #    left++right layout its refs were bound against
        if self.condition is not None and len(cl):
            pair_fields = tuple(self.children[0].schema.fields) + tuple(
                self.children[1].schema.fields)
            pair_cols = []
            for c in lb.columns:
                pair_cols.append(H.HostCol(
                    c.dtype, c.data[cl],
                    None if c.validity is None else c.validity[cl]))
            for c in rb.columns:
                pair_cols.append(H.HostCol(
                    c.dtype, c.data[cr],
                    None if c.validity is None else c.validity[cr]))
            pb = H.HostBatch(T.StructType(pair_fields), pair_cols)
            cv = self.condition.eval_cpu(pb)
            keep = cv.data.astype(bool)
            if cv.validity is not None:
                keep &= cv.validity
            cl, cr = cl[keep], cr[keep]

        # 3. join-type semantics over surviving pairs
        pairs: List[Tuple[int, int]] = []
        matched_l = np.zeros(nl, dtype=bool)
        matched_r = np.zeros(nr, dtype=bool)
        matched_l[cl] = True
        matched_r[cr] = True
        if jt == "left_semi":
            pairs = [(i, -1) for i in range(nl) if matched_l[i]]
        elif jt == "left_anti":
            pairs = [(i, -1) for i in range(nl) if not matched_l[i]]
        else:
            pairs = list(zip(cl.tolist(), cr.tolist()))
            if jt in ("left", "full"):
                # preserve left-row grouping order like the loop did
                extra = [(i, -1) for i in range(nl) if not matched_l[i]]
                merged: List[Tuple[int, int]] = []
                gi = 0
                ei = 0
                for i in range(nl):
                    while gi < len(pairs) and pairs[gi][0] == i:
                        merged.append(pairs[gi])
                        gi += 1
                    if not matched_l[i]:
                        merged.append((i, -1))
                pairs = merged + pairs[gi:]
            if jt == "right":
                pairs = [(i, j) for (i, j) in pairs if j >= 0]
                pairs += [(-1, j) for j in range(nr) if not matched_r[j]]
            elif jt == "full":
                pairs += [(-1, j) for j in range(nr) if not matched_r[j]]

        lidx = np.array([p[0] for p in pairs], dtype=np.int64)
        ridx = np.array([p[1] for p in pairs], dtype=np.int64)
        yield self._materialize(lb, rb, lidx, ridx)

    def _materialize(self, lb, rb, lidx, ridx) -> H.HostBatch:
        lkey_idx = [e.index for e in self.left_keys]
        rkey_idx = [e.index for e in self.right_keys]
        semi = self.join_type in ("left_semi", "left_anti")
        cross = self.join_type == "cross" or not self.using
        cols: List[H.HostCol] = []
        out_i = 0

        def gather(c: H.HostCol, idx) -> Tuple[np.ndarray, np.ndarray]:
            take = np.clip(idx, 0, max(len(c.data) - 1, 0))
            if len(c.data) == 0:
                data = np.zeros(len(idx), dtype=c.data.dtype)
            else:
                data = c.data[take]
            valid = (c.validity[take] if c.validity is not None
                     else np.ones(len(idx), bool)) if len(c.data) else \
                np.zeros(len(idx), bool)
            valid = valid & (idx >= 0)
            return data, valid

        if not cross:
            for ki in range(len(lkey_idx)):
                f = self.schema.fields[out_i]
                ld, lv = gather(lb.columns[lkey_idx[ki]], lidx)
                if self.join_type in ("right", "full"):
                    rd, rv = gather(rb.columns[rkey_idx[ki]], ridx)
                    data = np.where(lv, ld, rd)
                    valid = lv | rv
                else:
                    data, valid = ld, lv
                cols.append(H.HostCol(f.dtype, data,
                                      None if valid.all() else valid))
                out_i += 1
        for i in range(len(lb.columns)):
            if not cross and i in lkey_idx:
                continue
            f = self.schema.fields[out_i]
            data, valid = gather(lb.columns[i], lidx)
            cols.append(H.HostCol(f.dtype, data,
                                  None if valid.all() else valid))
            out_i += 1
        if not semi:
            for j in range(len(rb.columns)):
                if not cross and j in rkey_idx:
                    continue
                f = self.schema.fields[out_i]
                data, valid = gather(rb.columns[j], ridx)
                cols.append(H.HostCol(f.dtype, data,
                                      None if valid.all() else valid))
                out_i += 1
        return H.HostBatch(self.schema, cols)


# ---------------------------------------------------------------------------
# device search machinery
# ---------------------------------------------------------------------------

def _lex_search(sorted_limbs: List[jnp.ndarray],
                query_limbs: List[jnp.ndarray], side: str) -> jnp.ndarray:
    """Vectorized lexicographic binary search.

    Returns, per query row, the first index i in the sorted table where
    table[i] >= query ('left') or > query ('right').  All limbs uint64.
    """
    assert len(sorted_limbs) == len(query_limbs), (
        "join key limb layouts differ between sides: "
        f"{len(sorted_limbs)} vs {len(query_limbs)}")
    n = int(sorted_limbs[0].shape[0])
    nq = int(query_limbs[0].shape[0])
    lo = jnp.zeros((nq,), jnp.int32)
    hi = jnp.full((nq,), n, jnp.int32)
    steps = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    for _ in range(steps):
        active = lo < hi
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, n - 1)
        lt = jnp.zeros((nq,), jnp.bool_)
        eq = jnp.ones((nq,), jnp.bool_)
        for sl, ql in zip(sorted_limbs, query_limbs):
            tv = jnp.take(sl, midc)
            lt = lt | (eq & (tv < ql))
            eq = eq & (tv == ql)
        go_right = lt | (eq if side == "right" else jnp.zeros_like(eq))
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def _slot_rows(cum: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """The row of each output slot ``j``: its rank among the rows'
    running totals ``cum`` — ``searchsorted(cum, j, side="right")`` by
    the kernel plane's merge rank.  A total past the bucket ranks like
    the bucket, so 32-bit keys do: half the sort's operands."""
    bucket = int(j.shape[0])
    return HL.rank_sorted(jnp.minimum(cum, bucket).astype(jnp.int32),
                          j.astype(jnp.int32), side="right")


def _expand_counts(counts: jnp.ndarray) -> Tuple[int, jnp.ndarray,
                                                 jnp.ndarray, int]:
    """counts[B] → (bucket, row_idx[bucket], offset[bucket], total).

    The ONE host sync of the join: total match count picks the output
    bucket (pow-2), everything else stays on device with static shapes.
    """
    cum = jnp.cumsum(counts.astype(jnp.int64))
    total = int(cum[-1]) if counts.shape[0] else 0
    bucket = round_up_pow2(max(total, 1))
    from spark_rapids_tpu.exec.basic import warn_big_bucket
    warn_big_bucket("join expansion", bucket)
    j = jnp.arange(bucket, dtype=jnp.int64)
    from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
    i = cached_kernel(("join_expand_rank",), lambda: _slot_rows)(cum, j)
    i_c = jnp.clip(i, 0, max(counts.shape[0] - 1, 0))
    start = jnp.take(cum, i_c) - jnp.take(counts.astype(jnp.int64), i_c)
    off = (j - start).astype(jnp.int32)
    return bucket, i_c, off, total


_INT_FAMILY = (T.ByteType, T.ShortType, T.IntegerType, T.LongType)


def _join_key_family(dt: T.DataType) -> str:
    """Key-compatibility class: int family members may join each other
    (both canonicalize to 64-bit); everything else must match exactly."""
    if isinstance(dt, _INT_FAMILY):
        return "int"
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        return "float" + str(32 if isinstance(dt, T.FloatType) else 64)
    return dt.simple_name


def _canonical_key_parts(c: DeviceColumn, str_width: int
                         ) -> List["ORD.Part"]:
    """Equality-key parts with a layout that depends only on the key's
    family (and the shared string width) — never on validity presence,
    batch-local string width, or int width.  Null/dead rows are excluded
    by the caller's exclusion flag, so no null limbs are needed here."""
    dt = c.dtype
    if isinstance(dt, (T.StringType, T.BinaryType)):
        data = c.data
        w = int(data.shape[1])
        if w < str_width:
            data = jnp.pad(data, ((0, 0), (0, str_width - w)))
        return ORD._string_parts(data, c.lengths)
    if isinstance(dt, T.FloatType):
        # NaN canonicalized, -0.0 == 0.0 (Spark NormalizeFloatingNumbers)
        u = ORD._f32_orderable_u32(c.data, normalize_zero=True)
        return [(u.astype(jnp.uint64), 32)]
    if isinstance(dt, T.DoubleType):
        # no 64-bit bitcast on TPU: NaN rides a flag limb, the value
        # rides a RAW float limb (NaN zeroed; -0.0 == 0.0 holds under
        # both lax.sort's comparator and the ==/< of the binary search)
        isn = jnp.isnan(c.data)
        zero = jnp.zeros((), c.data.dtype)
        val = jnp.where(isn, zero, c.data)
        # -0.0 → +0.0: lax.sort's total-order comparator splits the two
        # zeros while the binary search's IEEE == does not — normalize so
        # both agree (and Spark joins the zeros as one key anyway)
        val = jnp.where(val == zero, zero, val)
        return [ORD._flag_part(isn), (val, "f64")]
    if isinstance(dt, T.BooleanType):
        return [(c.data.astype(jnp.uint64), 1)]
    if (isinstance(dt, T.DecimalType)
            and dt.precision > T.DecimalType.MAX_LONG_DIGITS):
        return [ORD._int_part(c.data[:, 0], 64, True),
                (c.data[:, 1].astype(jnp.uint64), 64)]
    # integral family, date, timestamp, decimal → 64-bit biased encoding
    return [ORD._int_part(c.data.astype(jnp.int64), 64, True)]


def _key_parts(batch: DeviceBatch, keys: Sequence[Expression],
               str_widths: Sequence[int]
               ) -> Tuple[List["ORD.Part"], jnp.ndarray]:
    """(canonical equality key parts, has_null_key) for a batch's keys."""
    has_null = jnp.zeros((batch.capacity,), jnp.bool_)
    parts: List[ORD.Part] = []
    for e, w in zip(keys, str_widths):
        c = e.eval_tpu(batch)
        if c.validity is not None:
            has_null = has_null | ~c.validity
        parts.extend(_canonical_key_parts(c, w))
    return parts, has_null


def _key_str_width(batch: DeviceBatch, e: Expression) -> int:
    """Static string width of a key expression's result on this batch.

    Column refs read the width off the batch; other string expressions
    trace once against a zero-capacity stand-in (shapes only, no data)."""
    if not isinstance(e.dtype, (T.StringType, T.BinaryType)):
        return 0
    if hasattr(e, "index"):
        return int(batch.columns[e.index].data.shape[1])
    shape = jax.eval_shape(lambda b: e.eval_tpu(b).data, batch)
    return int(shape.shape[1])


def _gather_col(c: DeviceColumn, idx: jnp.ndarray,
                valid_out: jnp.ndarray) -> DeviceColumn:
    g = c.gather(jnp.clip(idx, 0, c.capacity - 1))
    base = g.valid_mask()
    return DeviceColumn(c.dtype, g.data, base & valid_out, g.lengths)


class TpuBroadcastExchangeExec(TpuExec):
    """Gather the (small) child once; every stream partition reuses it.

    [REF: GpuBroadcastExchangeExec — host-serialized broadcast there;
    here the table is a single-process engine so the broadcast is the
    cached device batch itself]"""

    def __init__(self, child: TpuExec):
        super().__init__(child.schema, child)
        import threading
        self._lock = threading.Lock()
        self._cached: Optional[DeviceBatch] = None

    def node_string(self):
        return "TpuBroadcastExchange"

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        with self._lock:
            if self._cached is None:
                with self.timer("broadcastTime"):
                    self._cached = _gather_all(
                        self.children[0], self.schema, True)
                self.metric("numOutputBatches").add(1)
        yield self._cached


class TpuSortMergeJoinExec(TpuExec):
    """[REF: GpuShuffledHashJoinExec — same plan position, sort-merge
    algorithm per SURVEY §7; GpuBroadcastHashJoinExec when ``broadcast``
    is set; residual conditions = join-gather + fused mask (SURVEY N7 —
    no AST interpreter needed, XLA fuses the expression)]"""

    def __init__(self, join_type: str, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 condition: Optional[Expression], schema: T.StructType,
                 left: TpuExec, right: TpuExec,
                 partitioned: bool = False, using: bool = True,
                 broadcast: Optional[str] = None,
                 sub_partition_rows: int = 1 << 18,
                 out_batch_rows: Optional[int] = None,
                 skew_split=None):
        super().__init__(schema, left, right)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        # co-partitioned inputs (both sides exchanged on the same key
        # hash): join partition-by-partition like Spark reduce tasks
        self.partitioned = partitioned
        self.using = using
        # "right"/"left": that side is a TpuBroadcastExchangeExec; the
        # OTHER side streams partition-by-partition
        self.broadcast = broadcast
        # proactive sub-partition cap (spark.rapids.tpu.join.targetRows):
        # no sort/search kernel compiles above ~this row capacity
        self.sub_partition_rows = sub_partition_rows
        # join outputs re-batch to this bucket (spark.rapids.tpu.batchRows)
        # so downstream kernels never compile at the expanded bucket size
        self.out_batch_rows = out_batch_rows
        # AdaptivePolicy (or None): on a partitioned join, heal stream
        # skew by splitting hot exchange partitions into rank-interleaved
        # slices with the build partition replicated per slice
        self.skew_split = skew_split
        import threading
        self._split_lock = threading.Lock()
        self._split_specs: Optional[List[Tuple[int, int, int]]] = None
        self._split_planned = False
        # build partitions replicated across a hot partition's slices
        # gather ONCE and share (k slices would otherwise re-gather +
        # re-compact the same build partition k times)
        self._split_build_cache: dict = {}

    def __getstate__(self):
        # lore dumps pickle the exec skeleton (utils/lore.py): drop the
        # lock and the per-run split state, rebuilt on unpickle
        d = self.__dict__.copy()
        d["_split_lock"] = None
        d["_split_specs"] = None
        d["_split_planned"] = False
        d["_split_build_cache"] = {}
        return d

    def __setstate__(self, d):
        import threading
        self.__dict__.update(d)
        self._split_lock = threading.Lock()

    def node_string(self):
        part = " partitioned" if self.partitioned else ""
        bc = f" broadcast={self.broadcast}" if self.broadcast else ""
        cond = f" cond={self.condition}" if self.condition else ""
        return f"TpuSortMergeJoin [{self.join_type}{part}{bc}{cond}]"

    def num_partitions(self) -> int:
        if self.broadcast == "right":
            return self.children[0].num_partitions()
        if self.broadcast == "left":
            return self.children[1].num_partitions()
        if self.partitioned:
            specs = self._skew_specs()
            if specs is not None:
                return len(specs)
            return self.children[0].num_partitions()
        return 1

    def _skew_specs(self) -> Optional[List[Tuple[int, int, int]]]:
        """Adaptive skew-healing read plan for a partitioned join, or
        None for the 1:1 partition mapping.

        One ``(p, j, k)`` spec per output partition: slice j of k over
        stream-side exchange partition p (k == 1 reads the partition
        whole).  Hot partitions — per the exchange's RECORDED partition
        counts and the adaptive policy's skew threshold — split into
        rank-interleaved slices (exchange.execute_split), each joined
        against the build side's whole matching partition; every stream
        row still sees the full set of its key's build rows, the same
        correctness argument as ``_broadcast_streamed``, so this spreads
        a SINGLE hot key across slices — the one case the hash-split
        path (``_sub_partition_join``) provably cannot."""
        pol = self.skew_split
        if pol is None or not self.partitioned:
            return None
        lex = self.children[0]
        if not (hasattr(lex, "execute_split")
                and hasattr(lex, "aqe_partition_stats")):
            return None
        with self._split_lock:
            if self._split_planned:
                return self._split_specs
            self._split_planned = True
            from spark_rapids_tpu import adaptive as AD
            from spark_rapids_tpu.adaptive import replanner
            from spark_rapids_tpu.runtime import stats as stats_mod
            st = stats_mod.current()
            rec = st.partition_counts(lex) if st is not None else None
            unit, counts = (rec if rec is not None
                            else lex.aqe_partition_stats())
            if unit != "rows":
                return None
            planned = replanner.plan_skew_reads(pol, self.join_type,
                                                counts)
            if planned is None:
                return None
            specs, detail = planned
            self.metric("skewSplitJoins").add(len(detail["partitions"]))
            AD.record_decision(self, "skew-split", **detail)
            self._split_specs = specs
            return specs

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.memory import RetryOOM, get_manager
        jt = self.join_type
        if jt == "right":
            yield from self._execute_swapped(partition)
            return
        # each side: (batches compacted at their live buckets, their
        # live counts, the capacities they came at)
        left = right = None
        if self.broadcast == "right":
            lpart, rpart = partition, None
        elif self.broadcast == "left":
            lpart, rpart = None, partition
        elif self.partitioned:
            lpart = rpart = partition
            specs = self._skew_specs()
            if specs is not None:
                p, j, k = specs[partition]
                lpart = rpart = p
                if k > 1:
                    # hot partition: rank-interleaved stream slice
                    # joined against the replicated build partition
                    with self.timer("gatherTime"):
                        left = _compact_counted(
                            list(self.children[0].execute_split(p, j, k)),
                            self)
                        with self._split_lock:
                            right = self._split_build_cache.get(p)
                            if right is None:
                                right = _gather_list(
                                    self.children[1], rpart, self)
                                self._split_build_cache[p] = right
        else:
            lpart = rpart = None
        if left is None:
            with self.timer("gatherTime"):
                # both children's live counts in one round trip
                l_raw = _pump_list(self.children[0], lpart)
                r_raw = _pump_list(self.children[1], rpart)
                both = _compact_counted(l_raw + r_raw, self)
                left = tuple(x[:len(l_raw)] for x in both)
                right = tuple(x[len(l_raw):] for x in both)
        # copies: the sub-partition path drains its input lists in
        # place, and the split cache must keep its references for the
        # next slice
        l_list, l_counts, l_caps = map(list, left)
        r_list, r_counts, r_caps = map(list, right)
        nokey = jt == "cross" or not self.left_keys
        mgr = get_manager()
        total = (sum(b.nbytes() for b in l_list)
                 + sum(b.nbytes() for b in r_list))
        # proactive bound [REF: GpuSubPartitionHashJoin — there the
        # trigger is build-size driven, not OOM-reactive]: if either
        # side's gathered LIVE rows exceed the row cap, sub-partition
        # up front — an in-core attempt would compile sort/search
        # kernels at a bucket whose cold compile alone can exceed any
        # query budget.  Live counts (the gather's own, one round trip
        # for both sides) rather than capacities: a filtered side holds
        # few live rows in its scan buckets, and a capacity
        # trigger would sub-partition 3-23x more finely than the data
        # warrants (measured on TPC-H q10: 6M-capacity / 2M-live
        # lineitem).  Live rows — not capacities — decide every
        # downstream kernel's shape.
        side_live = None
        if not nokey and self.sub_partition_rows and not self.broadcast:
            l_live = sum(l_counts) or 1
            r_live = sum(r_counts) or 1
            side_live = max(l_live, r_live)
            cap = self.sub_partition_rows
            if side_live > cap:
                # runtime strategy pick (live counts, not estimates):
                # when ONE side fits in-core, stream the other in
                # bounded groups against it — no hash split, no
                # spillables, ~10x fewer dispatches than the
                # sub-partition path (measured: TPC-H q4's split cost
                # 4.5 s/run; the stream costs the match kernels alone)
                if (r_live <= cap
                        and jt in ("inner", "left", "left_semi",
                                   "left_anti")):
                    # right side fully present; streamed LEFT rows are
                    # each decided independently against it
                    self.metric("streamedJoins").add(1)
                    yield from self._broadcast_streamed(
                        l_list, r_list, jt, mgr, l_counts, side="right")
                    return
                if l_live <= cap and jt == "inner":
                    self.metric("streamedJoins").add(1)
                    yield from self._broadcast_streamed(
                        l_list, r_list, jt, mgr, r_counts, side="left")
                    return
                if (l_live <= cap
                        and jt in ("left_semi", "left_anti")):
                    self.metric("streamedJoins").add(1)
                    yield from self._semi_stream_right(
                        l_list, l_counts, r_list, r_counts, jt, mgr)
                    return
                self.metric("subPartitionJoins").add(1)
                yield from self._sub_partition_join(
                    l_list, r_list, jt, total, mgr,
                    live_rows=side_live)
                return
        # broadcast joins: the broadcast side is threshold-capped and
        # gathered once (re-splitting it per stream partition would
        # repeat identical work P times), but the STREAMED side still
        # honors the row cap — by its LIVE rows, as above: a filtered
        # stream comes in its scan buckets (TPC-H q14 at SF1: 6.3 M
        # slots holding ~75 k rows), and bounded groups cut by capacity
        # probe 24 chunks of which 18 hold no row.  Under the cap the
        # gather has shrunk each batch to its live bucket and the
        # in-core path below probes once; over it the stream needs no
        # hash split, since the other side is fully present: process it
        # in bounded groups, each group's rows decided independently
        # (inner/left/semi/anti)
        held = total
        if not nokey and self.sub_partition_rows and self.broadcast:
            right_bc = self.broadcast == "right"
            stream, counts, caps = ((l_list, l_counts, l_caps) if right_bc
                                    else (r_list, r_counts, r_caps))
            bc_list = r_list if right_bc else l_list
            # by the capacities the stream CAME at: what it holds now
            # are live buckets
            if sum(caps) > self.sub_partition_rows:
                if sum(counts) > self.sub_partition_rows:
                    self.metric("streamedJoins").add(1)
                    yield from self._broadcast_streamed(
                        l_list, r_list, jt, mgr, counts)
                    return
                self.metric("liveRowInCoreJoins").add(1)
                buckets = [live_bucket(n, b.capacity)
                           for b, n in zip(stream, counts)]
                if len(stream) == 1 and buckets[0] < stream[0].capacity:
                    # a lone batch that CAME compacted is still at the
                    # capacity it came at, and the concat hands a lone
                    # batch back as it is: cut it to its live bucket
                    # here (the live rows are a prefix)
                    from spark_rapids_tpu.parallel.shuffle import (
                        slice_batch)
                    stream[0] = slice_batch(stream[0], 0, buckets[0])
                # reserve what the concat will hold (live buckets), not
                # the capacity of a batch that came compacted: slots
                # that are never gathered must not send a thinly live
                # stream to the hash split
                held = (sum(b.nbytes() for b in bc_list)
                        + sum(b.nbytes() * k // b.capacity
                              for b, k in zip(stream, buckets)))
        try:
            # in-core: both sides + the expanded output live together
            # (counts, when the proactive check measured them, save the
            # concat its own sync round trip)
            with mgr.transient(2 * held):
                lb = _concat_or_empty(self.children[0].schema, l_list,
                                      counts=l_counts)
                rb = _concat_or_empty(self.children[1].schema, r_list,
                                      counts=r_counts)
                with self.timer():
                    if nokey:
                        cb, ctotal = self._cross(lb, rb)
                        cb = self._apply_condition(cb)
                        yield from self._rebatch(cb, ctotal)
                    else:
                        # in-core: the side that is not broadcast is
                        # the one that streams (the left, unplanned)
                        probe = (("right", sum(r_counts))
                                 if self.broadcast == "left"
                                 else ("left", sum(l_counts)))
                        yield from self._merge_join(lb, rb, jt, probe)
                return
        except RetryOOM:
            if nokey:
                raise  # nested loop can't hash-split; let retry handle
            self.metric("subPartitionJoins").add(1)
        yield from self._sub_partition_join(l_list, r_list, jt, total,
                                            mgr, live_rows=side_live)

    def _bounded_groups(self, stream, counts):
        """Cut a streamed side into groups of at most the row cap's
        slots: ``(batches, live rows)`` a group.  A single gathered
        batch can itself exceed the cap (the default batchRows bucket
        is larger than targetRows): it is row-sliced — batches here are
        compacted, so each pow-2 chunk keeps a contiguous live prefix,
        and ``counts`` (the gather's) says how long."""
        from spark_rapids_tpu.parallel.shuffle import slice_batch
        cap = self.sub_partition_rows
        groups: List[List[DeviceBatch]] = [[]]
        lives = [0]
        acc = 0
        for b, n in zip(stream, counts):
            for lo in range(0, max(b.capacity, 1), cap):
                c = b if b.capacity <= cap else slice_batch(b, lo, cap)
                if groups[-1] and acc + c.capacity > cap:
                    groups.append([])
                    lives.append(0)
                    acc = 0
                groups[-1].append(c)
                lives[-1] += min(max(n - lo, 0), c.capacity)
                acc += c.capacity
        return list(zip(groups, lives))

    def _broadcast_streamed(self, l_list, r_list, jt, mgr, counts,
                            side: Optional[str] = None
                            ) -> Iterator[DeviceBatch]:
        """Row-cap the streamed side of a broadcast join by joining it
        in bounded groups against the (small, fully-present) broadcast
        batch.  Correct for the join types the planner broadcasts
        (inner/left/left_semi/left_anti with broadcast=right; inner with
        broadcast=left): each streamed row's output depends only on the
        broadcast side, so groups are independent.  ``side`` overrides
        ``self.broadcast`` — the runtime strategy pick reuses this for
        non-broadcast plans whose measured small side fits in-core.
        ``counts`` are the streamed batches' live rows, as the gather
        counted them."""
        side = side or self.broadcast
        streamed = "left" if side == "right" else "right"
        # NOTE: side, not self.broadcast — the runtime strategy pick
        # passes side="right"/"left" on plans with broadcast=None, and
        # consulting self.broadcast here built the broadcast batch from
        # the STREAMED side's schema (IndexError on TPC-H q7 SF1)
        bc = _concat_or_empty(
            self.children[1 if side == "right" else 0].schema,
            r_list if side == "right" else l_list)
        for g, live in self._bounded_groups(
                l_list if side == "right" else r_list, counts):
            gb = _concat_or_empty(
                self.children[0 if side == "right" else 1].schema, g)
            lb, rb = (gb, bc) if side == "right" else (bc, gb)
            with mgr.transient(2 * (gb.nbytes() + bc.nbytes())):
                with self.timer():
                    yield from self._merge_join(lb, rb, jt,
                                                (streamed, live))

    def _semi_stream_right(self, l_list, l_counts, r_list, r_counts, jt,
                           mgr) -> Iterator[DeviceBatch]:
        """semi/anti with the LEFT side in-core and an oversized RIGHT:
        stream the right side in bounded groups, OR-accumulating the
        per-row match flag across groups.  Correct because a semi/anti
        row's verdict is "matched anywhere on the right" — group
        membership never changes it; null-key and dead left rows get
        m == 0 from every group, matching _merge_join's in-core
        semantics exactly."""
        lb = _concat_or_empty(self.children[0].schema, l_list,
                              counts=l_counts)
        matched = jnp.zeros((lb.capacity,), jnp.bool_)
        for g, live in self._bounded_groups(r_list, r_counts):
            if not g:
                continue
            rb = _concat_or_empty(self.children[1].schema, g)
            with mgr.transient(2 * (lb.nbytes() + rb.nbytes())):
                with self.timer():
                    m, lo, perm, l_null = self._match_ranges(
                        lb, rb, ("right", live))
                    matched = matched | (m > 0)
        keep = matched if jt == "left_semi" else ~matched
        out = lb.with_sel(lb.sel & keep)
        yield from self._rebatch(self._project_semi(out), out.capacity)

    def _sub_partition_join(self, l_list, r_list, jt, total, mgr,
                            depth: int = 0, live_rows: Optional[int] = None
                            ) -> Iterator[DeviceBatch]:
        """Oversized inputs: recursive hash split [REF:
        GpuSubPartitionHashJoin].  Both sides re-hash on the join keys
        with a DIFFERENT murmur3 seed (rows of one exchange partition
        must spread), each (batch × sub-partition) slice registers as a
        spillable, and sub-partition pairs join independently — peak HBM
        ≈ one pair.  Equal keys land in equal sub-partitions, so every
        join type's semantics hold per pair."""
        from spark_rapids_tpu.parallel.shuffle import (
            make_pid_fn, split_to_spillables)
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        # k satisfies BOTH ceilings: memory (pair fits the arbiter
        # budget) and rows (no kernel compiles above the row cap).
        # ``live_rows`` (when the caller measured it) sizes k by what a
        # pair's concat bucket will actually hold; capacity is the
        # sync-free fallback.
        k_mem = int(np.ceil(total / max(mgr.budget // 4, 1)))
        side_cap = live_rows if live_rows else max(
            sum(b.capacity for b in l_list) or 1,
            sum(b.capacity for b in r_list) or 1)
        k_rows = (int(np.ceil(side_cap / self.sub_partition_rows))
                  if self.sub_partition_rows else 1)
        k = max(2, min(256, max(k_mem, k_rows)))
        canon = tuple(
            type(le.dtype) is not type(re.dtype)
            and isinstance(le.dtype, _INT_FAMILY)
            for le, re in zip(self.left_keys, self.right_keys))
        # != Spark shuffle seed 42; varies per recursion level so a
        # skewed sub-partition's keys re-spread on the re-split
        SUB_SEED = 0x53504C54 + depth

        def split(batches, keys):
            pid_fn = make_pid_fn(keys, k, canon, seed=SUB_SEED)
            # drains ``batches`` in place so the originals free even
            # though execute()'s frame still references the lists;
            # the split's kernels are cached under the pid recipe
            return split_to_spillables(
                batches, lambda b, aux: pid_fn(b), k, mgr,
                ("subpart", SUB_SEED, canon, fingerprint(keys)))

        with self.timer("partitionTime"):
            l_slices = split(l_list, self.left_keys)
            r_slices = split(r_list, self.right_keys)
        for i in range(k):
            # inner/semi emit only matched left rows: an empty side means
            # an empty pair output (left/anti/full still must run to emit
            # their preserved side)
            if (jt in ("inner", "left_semi")
                    and (not l_slices[i] or not r_slices[i])):
                for s in l_slices[i] + r_slices[i]:
                    s.close()
                continue
            if not l_slices[i] and jt in ("left", "left_anti"):
                for s in r_slices[i]:
                    s.close()
                continue
            pair_bytes = (sum(s.nbytes for s in l_slices[i])
                          + sum(s.nbytes for s in r_slices[i]))
            # key skew can defeat one split level (a low-cardinality key
            # set hashing into one bucket): re-split the oversized pair
            # with the next seed.  Depth-capped — a single hot KEY can
            # never spread by key hash; past the cap the pair joins
            # in-core (bounded number of oversized compiles, documented
            # limitation) rather than recursing forever.  Capacity is
            # read off the spillable (no restore); the registrations
            # stay open until the recursion/join is done so the arbiter
            # keeps seeing (and can spill) the pair's bytes.
            if (self.sub_partition_rows and depth < 3
                    and max(sum(s.capacity for s in l_slices[i]) or 1,
                            sum(s.capacity for s in r_slices[i]) or 1)
                    > self.sub_partition_rows):
                yield from self._sub_partition_join(
                    [s.get() for s in l_slices[i]],
                    [s.get() for s in r_slices[i]],
                    jt, pair_bytes, mgr, depth + 1)
                for s in l_slices[i] + r_slices[i]:
                    s.close()
                continue
            # clamped: one pair can exceed a tiny budget after pow-2
            # padding; full-pool pressure is the reservation's ceiling
            with mgr.transient(min(2 * max(pair_bytes, 1), mgr.budget)):
                lb = _concat_or_empty(
                    self.children[0].schema,
                    [s.get() for s in l_slices[i]],
                    counts=[s.live_rows for s in l_slices[i]])
                rb = _concat_or_empty(
                    self.children[1].schema,
                    [s.get() for s in r_slices[i]],
                    counts=[s.live_rows for s in r_slices[i]])
                with self.timer():
                    yield from self._merge_join(
                        lb, rb, jt,
                        ("left", sum(s.live_rows for s in l_slices[i])))
                for s in l_slices[i] + r_slices[i]:
                    s.close()

    def _apply_condition(self, batch: DeviceBatch) -> DeviceBatch:
        """Residual condition as a fused mask over the join output (its
        refs were bound against the left++right layout = self.schema)."""
        if self.condition is None:
            return batch
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        cond = self.condition

        def build():
            def run(b):
                c = cond.eval_tpu(b)
                keep = c.data.astype(jnp.bool_)
                if c.validity is not None:
                    keep = keep & c.validity
                return b.with_sel(b.sel & keep)
            return run

        fn = cached_kernel(
            ("join_residual", fingerprint(cond),
             fingerprint(batch.schema)), build)
        return fn(batch)

    # -- core ---------------------------------------------------------------
    def _match_ranges(self, lb, rb, probe):
        """Sort right side; binary-search match ranges for left rows.

        ``probe`` is ``(side, live rows)``: which of the two the join
        streams through this call ("left" or "right") and how many of
        its rows are live.  Every call is one probe group of the
        ledger's ``counts``, counted here once the kernel has returned,
        so a call that never ran (a refused reservation) counts nothing.

        One cached jitted kernel per (keys, schemas, backend) triple.
        The fused/pallas rungs route through kernels.hash_join (one
        hash limb sorted, the probe merged into its order: no gather)
        and fall back to the exact lexicographic reference on a 64-bit
        collision; the (m, lo, perm, l_null) contract is unchanged —
        within a match range both layouts enumerate the same right rows
        in the same (original-index) order, so _merge_join's output is
        byte-identical."""
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        from spark_rapids_tpu import kernels as KN
        left_keys, right_keys = self.left_keys, self.right_keys
        # shared static string width per key pair: canonical layouts on
        # the two sides must match even when batch paddings differ
        widths = tuple(
            max(_key_str_width(lb, le), _key_str_width(rb, re))
            for le, re in zip(left_keys, right_keys))

        def build(backend):
            def run(lb, rb):
                r_parts, r_null = _key_parts(rb, right_keys, widths)
                r_excl = (~rb.sel) | r_null
                l_parts, l_null = _key_parts(lb, left_keys, widths)
                l_live = lb.sel & ~l_null
                if backend != "jnp":
                    from spark_rapids_tpu.kernels import hash_join as KNJ
                    res = KNJ.match_fused(
                        ORD.fuse_parts(l_parts), ORD.fuse_parts(r_parts),
                        r_excl, use_pallas=(backend == "pallas"))
                    if res is not None:
                        m, lo, perm, okf = res
                        m = jnp.where(l_live, m, 0)
                        return (m, lo, perm, l_null), okf
                    # unhashable keys (raw-f64 limb): reference runs
                    # inside this rung; ok=None ⇒ dispatch counts "jnp"
                sorted_limbs, perm = ORD.sort_by_keys(ORD.fuse_parts(
                    [ORD._flag_part(r_excl)] + r_parts))
                # canonical encoding ⇒ identical part widths on both
                # sides ⇒ identical fused limb layout, compare 1:1
                q_zero = ORD._flag_part(
                    jnp.zeros((lb.capacity,), jnp.bool_))
                q_limbs = ORD.fuse_parts([q_zero] + l_parts)
                lo = _lex_search(sorted_limbs, q_limbs, "left")
                hi = _lex_search(sorted_limbs, q_limbs, "right")
                m = jnp.where(l_live, hi - lo, 0)
                return (m, lo, perm, l_null), None
            return run

        base_key = ("join_match", widths, fingerprint(left_keys),
                    fingerprint(right_keys),
                    fingerprint(lb.schema), fingerprint(rb.schema))
        be = KN.resolve("join")

        def runner(backend):
            # the jnp key stays the historical one so persistent cache
            # entries from older builds keep hitting
            key = (base_key if backend == "jnp"
                   else base_key + (backend,))
            fn = cached_kernel(key, lambda: build(backend))
            return lambda: fn(lb, rb)

        out = KN.dispatch("join", be, runner, node=self)
        side, live = probe
        self.count("joinProbeGroups", 1)
        self.count("joinSlotsProbed",
                   (lb if side == "left" else rb).capacity)
        self.count("joinLiveRowsStreamed", live)
        _TM_PROBE_GROUPS.inc()
        return out

    def _merge_join(self, lb, rb, jt, probe):
        m, lo, perm, l_null = self._match_ranges(lb, rb, probe)

        if jt in ("left_semi", "left_anti"):
            keep = (m > 0) if jt == "left_semi" else (m == 0)
            out = lb.with_sel(lb.sel & keep)
            yield from self._rebatch(self._project_semi(out),
                                     out.capacity)
            return

        counts = m
        if jt in ("left", "full"):
            counts = jnp.where(lb.sel & (m == 0), 1, m)
        bucket, li, off, total = _expand_counts(counts)

        l_idx = li
        matched = jnp.take(m, li) > 0
        r_sorted_pos = jnp.take(lo, li) + off
        r_idx = jnp.take(perm, jnp.clip(r_sorted_pos, 0, rb.capacity - 1))
        out_live = jnp.arange(bucket, dtype=jnp.int64) < total
        r_valid = out_live & matched
        l_valid = out_live

        if jt == "full":
            # append unmatched live right rows after the left-join block
            matched_r = jnp.zeros((rb.capacity,), jnp.bool_).at[
                jnp.where(r_valid, r_idx, rb.capacity)].set(
                True, mode="drop")
            r_unmatched = rb.sel & ~matched_r
            n_extra = int(jnp.sum(r_unmatched.astype(jnp.int32)))
            full_bucket = round_up_pow2(max(total + n_extra, 1))
            # indices of unmatched right rows, compacted
            ridx_extra = jnp.nonzero(
                r_unmatched, size=rb.capacity, fill_value=rb.capacity)[0]
            pad = full_bucket - bucket
            if pad > 0:
                l_idx = jnp.pad(l_idx, (0, pad))
                r_idx = jnp.pad(r_idx, (0, pad))
                l_valid = jnp.pad(l_valid, (0, pad))
                r_valid = jnp.pad(r_valid, (0, pad))
                out_live = jnp.pad(out_live, (0, pad))
            j = jnp.arange(full_bucket, dtype=jnp.int64)
            in_extra = (j >= total) & (j < total + n_extra)
            extra_pos = jnp.clip(j - total, 0, rb.capacity - 1)
            r_idx = jnp.where(
                in_extra,
                jnp.take(ridx_extra, extra_pos.astype(jnp.int32),
                         mode="clip"),
                r_idx).astype(jnp.int32)
            l_valid = jnp.where(in_extra, False, l_valid)
            r_valid = jnp.where(in_extra, True, r_valid)
            out_live = out_live | in_extra
            total += n_extra

        out = self._materialize(lb, rb, l_idx, r_idx, l_valid, r_valid,
                                out_live, jt)
        if jt == "inner":
            out = self._apply_condition(out)
        yield from self._rebatch(out, total)

    def _rebatch(self, out: DeviceBatch, total: int
                 ) -> Iterator[DeviceBatch]:
        """Slice an expanded join output into batchRows-bucket chunks.

        Downstream kernels (aggregates, windows, sorts) compile per
        (op, schema, bucket): handing them one giant expansion bucket
        would re-pay the superlinear compile the proactive sub-partition
        just avoided.  One jitted dynamic-slice per chunk (single
        dispatch — ``lo`` is traced, so every chunk reuses one
        executable); all-dead tail chunks are skipped via the host-known
        ``total``."""
        cap = self.out_batch_rows
        if not cap or out.capacity <= cap:
            yield out
            return
        # buckets are pow-2: a pow-2 chunk always divides the capacity,
        # so no dynamic_slice start ever clamps (a clamped final slice
        # would silently duplicate rows)
        cap = 1 << (int(cap).bit_length() - 1)
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)

        def build():
            def run(b, lo):
                def cut(x):
                    return jax.lax.dynamic_slice_in_dim(x, lo, cap, 0)
                cols = tuple(
                    DeviceColumn(
                        c.dtype, cut(c.data),
                        None if c.validity is None else cut(c.validity),
                        None if c.lengths is None else cut(c.lengths),
                        None if c.evalid is None else cut(c.evalid))
                    for c in b.columns)
                return DeviceBatch(b.schema, cols, cut(b.sel))
            return run

        fn = cached_kernel(
            ("join_rebatch", fingerprint(out.schema), out.capacity, cap),
            build)
        for i in range(max(1, -(-int(total) // cap))):
            yield fn(out, i * cap)

    def _execute_swapped(self, partition: int = 0):
        """right outer = left outer with sides swapped, columns remapped."""
        inner = TpuSortMergeJoinExec(
            "left", self.right_keys, self.left_keys, self.condition,
            self._swapped_schema(), self.children[1], self.children[0],
            self.partitioned, using=self.using,
            sub_partition_rows=self.sub_partition_rows,
            out_batch_rows=self.out_batch_rows)
        n_lc = len(self.children[0].schema)
        n_rc = len(self.children[1].schema)
        if not self.using:
            # swapped output: all_right ++ all_left → want left ++ right
            order = ([n_rc + i for i in range(n_lc)]
                     + [i for i in range(n_rc)])
        else:
            nk = len(self.left_keys)
            lkey = [e.index for e in self.left_keys]
            rkey = [e.index for e in self.right_keys]
            l_rest = [i for i in range(n_lc) if i not in lkey]
            r_rest = [i for i in range(n_rc) if i not in rkey]
            # swapped output: [keys, right_rest, left_rest] → want
            # [keys, left_rest, right_rest]
            n_r, n_l = len(r_rest), len(l_rest)
            order = (list(range(nk))
                     + [nk + n_r + i for i in range(n_l)]
                     + [nk + i for i in range(n_r)])
        for b in inner.execute(partition):
            cols = tuple(b.columns[i] for i in order)
            yield DeviceBatch(self.schema, cols, b.sel)

    def _swapped_schema(self) -> T.StructType:
        if not self.using:
            return T.StructType(
                tuple(self.children[1].schema.fields)
                + tuple(self.children[0].schema.fields))
        nk = len(self.left_keys)
        rkey = [e.index for e in self.right_keys]
        lkey = [e.index for e in self.left_keys]
        fields = list(self.schema.fields[:nk])
        rf = [f for i, f in enumerate(self.children[1].schema.fields)
              if i not in rkey]
        lf = [f for i, f in enumerate(self.children[0].schema.fields)
              if i not in lkey]
        return T.StructType(tuple(fields + rf + lf))

    def _cross(self, lb, rb) -> Tuple[DeviceBatch, int]:
        nl = int(jnp.sum(lb.sel.astype(jnp.int32)))
        nr = int(jnp.sum(rb.sel.astype(jnp.int32)))
        total = nl * nr
        bucket = round_up_pow2(max(total, 1))
        j = jnp.arange(bucket, dtype=jnp.int64)
        l_idx = (j // max(nr, 1)).astype(jnp.int32)
        r_idx = (j % max(nr, 1)).astype(jnp.int32)
        out_live = j < total
        return self._materialize(lb, rb, l_idx, r_idx, out_live,
                                 out_live, out_live, "cross"), total

    def _project_semi(self, lb: DeviceBatch) -> DeviceBatch:
        """semi/anti output: [keys, left-rest] for USING joins,
        original left order for expression joins."""
        if not self.using:
            return DeviceBatch(self.schema, lb.columns, lb.sel)
        lkey = [e.index for e in self.left_keys]
        order = lkey + [i for i in range(len(lb.columns)) if i not in lkey]
        cols = tuple(lb.columns[i] for i in order)
        return DeviceBatch(self.schema, cols, lb.sel)

    def _materialize(self, lb, rb, l_idx, r_idx, l_valid, r_valid,
                     out_live, jt) -> DeviceBatch:
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        fn = cached_kernel(
            ("join_mat", jt, self.using, fingerprint(self.left_keys),
             fingerprint(self.right_keys), fingerprint(self.schema),
             fingerprint(lb.schema), fingerprint(rb.schema)),
            lambda: (lambda *a: self._materialize_impl(*a, jt)))
        return fn(lb, rb, l_idx, r_idx, l_valid, r_valid, out_live)

    def _materialize_impl(self, lb, rb, l_idx, r_idx, l_valid, r_valid,
                          out_live, jt) -> DeviceBatch:
        lkey = [e.index for e in self.left_keys]
        rkey = [e.index for e in self.right_keys]
        # expression joins emit ALL left ++ ALL right columns (no key
        # coalescing) — same layout the residual condition binds to
        cross = jt == "cross" or not self.using
        cols: List[DeviceColumn] = []
        if not cross:
            for ki in range(len(lkey)):
                lc = _gather_col(lb.columns[lkey[ki]], l_idx, l_valid)
                if jt == "full":
                    from spark_rapids_tpu.ops.expressions import device_select
                    rc = _gather_col(rb.columns[rkey[ki]], r_idx, r_valid)
                    lv = lc.valid_mask()
                    sel_c = device_select(lv, lc, rc, lc.dtype)
                    cols.append(DeviceColumn(
                        lc.dtype, sel_c.data, lv | rc.valid_mask(),
                        sel_c.lengths))
                else:
                    cols.append(lc)
        for i in range(len(lb.columns)):
            if not cross and i in lkey:
                continue
            cols.append(_gather_col(lb.columns[i], l_idx, l_valid))
        for j in range(len(rb.columns)):
            if not cross and j in rkey:
                continue
            cols.append(_gather_col(rb.columns[j], r_idx, r_valid))
        sel = out_live
        return DeviceBatch(self.schema, tuple(cols), sel)


class _ReplayExec(TpuExec):
    """Serves already-materialized device batches (the AQE stage-result
    handoff: a measured side re-enters the next plan step without
    re-executing its subtree)."""

    def __init__(self, schema, batches: List[DeviceBatch]):
        super().__init__(schema)
        self._batches = batches

    def node_string(self):
        return f"Replay[{len(self._batches)} batches]"

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        yield from self._batches


class TpuAdaptiveJoinExec(TpuExec):
    """AQE broadcast-after-measure [REF: GpuCustomShuffleReaderExec +
    Spark AQE's DynamicJoinSelection]: the planner could not prove the
    build side small (filters forward upper-bound estimates), so the
    join defers the strategy choice to RUNTIME.  The build side
    materializes once at the stage boundary; if its measured bytes fit
    the broadcast threshold, the planned {exchange both sides →
    partitioned join} collapses to a broadcast join (no all_to_all at
    all); otherwise the measured batches replay into the planned
    exchange, so nothing executes twice."""

    def __init__(self, join_type: str, left_keys, right_keys, condition,
                 schema, left: TpuExec, right: TpuExec, threshold: int,
                 canon_int64, using: bool, sub_partition_rows: int,
                 out_batch_rows):
        super().__init__(schema, left, right)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self.threshold = int(threshold)
        self.canon_int64 = tuple(canon_int64)
        self.using = using
        self.sub_partition_rows = sub_partition_rows
        self.out_batch_rows = out_batch_rows
        from spark_rapids_tpu.parallel.mesh import make_mesh
        self.mesh = make_mesh()
        import threading
        self._lock = threading.Lock()
        self._inner: Optional[TpuSortMergeJoinExec] = None
        self._mode: Optional[str] = None

    def node_string(self):
        mode = self._mode or "undecided"
        return (f"TpuAdaptiveJoin [{self.join_type} "
                f"runtime={mode} thresh={self.threshold}]")

    def num_partitions(self) -> int:
        return int(self.mesh.devices.size)

    def _decide(self):
        with self._lock:
            if self._inner is not None:
                return
            from spark_rapids_tpu.exec.distributed import (
                TpuIciShuffleExchangeExec)
            with self.timer("measureTime"):
                # LIVE bytes, not pow-2 bucket capacity: a filtered
                # side's live bucket still rounds its rows up
                r_list, counts, _ = _gather_list(self.children[1])
            rbytes = sum(
                n * max(1, b.nbytes() // max(b.capacity, 1))
                for n, b in zip(counts, r_list))
            replay = _ReplayExec(self.children[1].schema, r_list)
            if rbytes <= self.threshold:
                self.metric("adaptiveBroadcastJoins").add(1)
                self._mode = "broadcast"
                self._inner = TpuSortMergeJoinExec(
                    self.join_type, self.left_keys, self.right_keys,
                    self.condition, self.schema, self.children[0],
                    TpuBroadcastExchangeExec(replay), using=self.using,
                    broadcast="right",
                    sub_partition_rows=self.sub_partition_rows,
                    out_batch_rows=self.out_batch_rows)
            else:
                self.metric("adaptiveShuffledJoins").add(1)
                self._mode = "shuffled"
                lex = TpuIciShuffleExchangeExec(
                    self.children[0], self.left_keys,
                    canon_int64=self.canon_int64)
                rex = TpuIciShuffleExchangeExec(
                    replay, self.right_keys,
                    canon_int64=self.canon_int64)
                self._inner = TpuSortMergeJoinExec(
                    self.join_type, self.left_keys, self.right_keys,
                    self.condition, self.schema, lex, rex,
                    partitioned=True, using=self.using,
                    sub_partition_rows=self.sub_partition_rows,
                    out_batch_rows=self.out_batch_rows)
            self._inner._decision_owner = self
            from spark_rapids_tpu import adaptive as AD
            AD.record_decision(self, self._mode, build_bytes=rbytes,
                               threshold=self.threshold,
                               source="measured")

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        self._decide()
        d = self.num_partitions()
        if self._mode == "shuffled":
            yield from self._inner.execute(partition)
            return
        # broadcast: stream-side partitions strided over the adaptive
        # node's fixed partition count
        n_lp = self._inner.num_partitions()
        for lp in range(partition, n_lp, d):
            yield from self._inner.execute(lp)


class TpuAdaptiveLocalJoinExec(TpuExec):
    """Single-process adaptive join — the adaptive plane's join
    strategy + skew-split decisions applied at a stage boundary.

    The planner could not prove the build side small (the static
    broadcast in ``_convert_join`` would have fired), so the strategy
    defers to runtime:

    * **warm** — the profile store already holds a measured build-side
      size for this join's subtree signature (``adaptive.historyPath``):
      decide from history, execute nothing early;
    * **cold** — materialize the build side once off its own pump,
      decide from its measured LIVE bytes, and replay the batches into
      whichever plan wins (nothing executes twice — the
      ``TpuAdaptiveJoinExec`` stage-boundary protocol, minus the mesh).

    Broadcast eliminates the exchange entirely; shuffled co-partitions
    both sides through hash exchanges and hands the adaptive policy to
    the partitioned join so recorded partition skew splits hot stream
    partitions (``TpuSortMergeJoinExec._skew_specs``)."""

    def __init__(self, join_type: str, left_keys, right_keys, condition,
                 schema, left: TpuExec, right: TpuExec, policy,
                 nparts: int, hash_ok: bool, using: bool,
                 sub_partition_rows: int, out_batch_rows):
        super().__init__(schema, left, right)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self.policy = policy
        self.nparts = int(nparts)
        # mixed-width int key pairs hash differently per side through
        # the plain (canon-less) hash exchange — those plans may still
        # flip to broadcast but never to shuffled
        self.hash_ok = bool(hash_ok)
        self.using = using
        self.sub_partition_rows = sub_partition_rows
        self.out_batch_rows = out_batch_rows
        import threading
        self._lock = threading.Lock()
        self._inner: Optional[TpuSortMergeJoinExec] = None
        self._mode: Optional[str] = None

    def __getstate__(self):
        # lore dumps pickle the exec skeleton (utils/lore.py): drop the
        # lock and the runtime decision, re-decided on unpickle
        d = self.__dict__.copy()
        d["_lock"] = None
        d["_inner"] = None
        d["_mode"] = None
        return d

    def __setstate__(self, d):
        import threading
        self.__dict__.update(d)
        self._lock = threading.Lock()

    def node_string(self):
        mode = self._mode or "undecided"
        return (f"TpuAdaptiveLocalJoin [{self.join_type} runtime={mode} "
                f"thresh={self.policy.broadcast_threshold}]")

    def num_partitions(self) -> int:
        self._decide()
        return self._inner.num_partitions()

    def _decide(self):
        with self._lock:
            if self._inner is not None:
                return
            from spark_rapids_tpu import adaptive as AD
            from spark_rapids_tpu.adaptive import cost_model, replanner
            pol = self.policy
            sig = cost_model.subtree_signature(self.children[1])
            r_list = None
            decided = replanner.decide_join_from_history(pol, sig)
            if (decided is None and pol.wants_join
                    and pol.broadcast_threshold > 0):
                # cold query: measure the build side off its own pump.
                # LIVE bytes, not bucket capacity (a filtered side's
                # live bucket still rounds its rows up)
                with self.timer("measureTime"):
                    r_list, counts, _ = _gather_list(self.children[1])
                rbytes = sum(
                    n * max(1, b.nbytes() // max(b.capacity, 1))
                    for n, b in zip(counts, r_list))
                decided = replanner.decide_join_from_measurement(
                    pol, sig, rbytes)
            if decided is None:
                # join strategy gated off: keep the shuffled plan
                # shape (skew splitting is the remaining decision)
                decided = ("shuffled",
                           {"threshold": pol.broadcast_threshold,
                            "build_sig": sig, "source": "conf"})
            strategy, detail = decided
            build = (_ReplayExec(self.children[1].schema, r_list)
                     if r_list is not None else self.children[1])
            if strategy == "broadcast":
                self.metric("adaptiveBroadcastJoins").add(1)
                inner = TpuSortMergeJoinExec(
                    self.join_type, self.left_keys, self.right_keys,
                    self.condition, self.schema, self.children[0],
                    TpuBroadcastExchangeExec(build), using=self.using,
                    broadcast="right",
                    sub_partition_rows=self.sub_partition_rows,
                    out_batch_rows=self.out_batch_rows)
            elif self.hash_ok:
                self.metric("adaptiveShuffledJoins").add(1)
                from spark_rapids_tpu.exec.exchange import (
                    TpuShuffleExchangeExec)
                lex = TpuShuffleExchangeExec(self.children[0],
                                             self.nparts, self.left_keys)
                rex = TpuShuffleExchangeExec(build, self.nparts,
                                             self.right_keys)
                inner = TpuSortMergeJoinExec(
                    self.join_type, self.left_keys, self.right_keys,
                    self.condition, self.schema, lex, rex,
                    partitioned=True, using=self.using,
                    sub_partition_rows=self.sub_partition_rows,
                    out_batch_rows=self.out_batch_rows,
                    skew_split=pol if pol.wants_skew else None)
            else:
                self.metric("adaptiveShuffledJoins").add(1)
                inner = TpuSortMergeJoinExec(
                    self.join_type, self.left_keys, self.right_keys,
                    self.condition, self.schema, self.children[0],
                    build, using=self.using,
                    sub_partition_rows=self.sub_partition_rows,
                    out_batch_rows=self.out_batch_rows)
            # runtime-built subtree is invisible to the plan walk:
            # decisions made inside it surface on this node
            inner._decision_owner = self
            self._mode = strategy
            self._inner = inner
            AD.record_decision(self, strategy, **detail)

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        self._decide()
        yield from self._inner.execute(partition)


def _tag_join(meta):
    from spark_rapids_tpu.plan.overrides import tag_expression as _tag_e
    cpu = meta.cpu
    if cpu.condition is not None:
        if cpu.join_type not in ("inner", "cross"):
            meta.will_not_work(
                f"residual join conditions on {cpu.join_type} joins not "
                "yet on device (inner/cross only)")
        else:
            _tag_e(cpu.condition, meta)
    for le, re in zip(cpu.left_keys, cpu.right_keys):
        from spark_rapids_tpu.ops import decimal128 as D128
        if (D128.is128(le.dtype) and cpu.join_type in ("right", "full")):
            meta.will_not_work(
                "decimal128 join keys on right/full joins not yet on "
                "device (key-column coalesce lacks a 2-lane select)")
        lf, rf = _join_key_family(le.dtype), _join_key_family(re.dtype)
        if lf != rf:
            meta.will_not_work(
                f"join key type mismatch: {le.dtype.simple_name} vs "
                f"{re.dtype.simple_name} (no implicit cast inserted)")
        elif (type(le.dtype) is not type(re.dtype)
              and cpu.join_type in ("right", "full")):
            # right/full coalesce the two key columns into one output
            # column typed after the left key — mixed int widths would
            # smuggle int64 data under an int32 schema
            meta.will_not_work(
                "mixed-width int join keys not supported for "
                f"{cpu.join_type} joins (output key column would mix "
                f"{le.dtype.simple_name} and {re.dtype.simple_name})")
    from spark_rapids_tpu.plan.overrides import tag_expression
    for e in list(cpu.left_keys) + list(cpu.right_keys):
        tag_expression(e, meta)


def _convert_join(cpu, ch, conf):
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.exec.distributed import ici_active
    jt = cpu.join_type
    bounds = dict(sub_partition_rows=conf.get(C.JOIN_TARGET_ROWS),
                  out_batch_rows=conf.batch_rows)
    # multi-executor: scans are executor-sliced, so a broadcast gather
    # would capture only this process's slice — joins must co-partition
    # through the ICI exchange instead
    from spark_rapids_tpu.parallel.executor import get_executor
    multiproc = get_executor() is not None
    # broadcast the small side when stats say it fits [REF:
    # GpuBroadcastHashJoinExec; Spark's JoinSelection] — no exchange on
    # either side, build side gathered once and reused per partition
    thresh = conf.get(C.BROADCAST_THRESHOLD)
    if thresh and thresh > 0 and not multiproc:
        rsize = cpu.children[1].estimated_size_bytes()
        lsize = cpu.children[0].estimated_size_bytes()
        if (rsize is not None and rsize <= thresh
                and jt in ("inner", "left", "left_semi", "left_anti",
                           "cross")):
            return TpuSortMergeJoinExec(
                jt, cpu.left_keys, cpu.right_keys, cpu.condition,
                cpu.schema, ch[0], TpuBroadcastExchangeExec(ch[1]),
                using=cpu.using, broadcast="right", **bounds)
        if lsize is not None and lsize <= thresh and jt == "inner":
            return TpuSortMergeJoinExec(
                jt, cpu.left_keys, cpu.right_keys, cpu.condition,
                cpu.schema, TpuBroadcastExchangeExec(ch[0]), ch[1],
                using=cpu.using, broadcast="left", **bounds)
    if (ici_active(conf) and jt != "cross" and cpu.left_keys):
        # distributed: co-partition both sides through the ICI exchange
        # on the key hash, then join partition-by-partition (the
        # shuffled-hash-join plan shape [REF: GpuShuffledHashJoinExec])
        from spark_rapids_tpu.exec.distributed import TpuIciShuffleExchangeExec
        # both exchanges must agree on pids: widen int-family keys to 64
        # bits whenever the pair's widths differ
        canon = tuple(
            type(le.dtype) is not type(re.dtype)
            and isinstance(le.dtype, _INT_FAMILY)
            for le, re in zip(cpu.left_keys, cpu.right_keys))
        if (conf.get(C.ADAPTIVE_ENABLED) and thresh and thresh > 0
                and not multiproc
                and jt in ("inner", "left", "left_semi", "left_anti")):
            # the planner could not prove the build side small (else
            # the static broadcast above fired) — defer to runtime
            return TpuAdaptiveJoinExec(
                jt, cpu.left_keys, cpu.right_keys, cpu.condition,
                cpu.schema, ch[0], ch[1], thresh, canon, cpu.using,
                bounds["sub_partition_rows"], bounds["out_batch_rows"])
        lex = TpuIciShuffleExchangeExec(ch[0], cpu.left_keys,
                                       canon_int64=canon)
        rex = TpuIciShuffleExchangeExec(ch[1], cpu.right_keys,
                                       canon_int64=canon)
        return TpuSortMergeJoinExec(cpu.join_type, cpu.left_keys,
                                    cpu.right_keys, cpu.condition,
                                    cpu.schema, lex, rex,
                                    partitioned=True, using=cpu.using,
                                    **bounds)
    if (not multiproc and cpu.left_keys
            and jt in ("inner", "left", "left_semi", "left_anti")):
        # single-process adaptive plane: defer broadcast-vs-shuffled to
        # observed build cardinality and heal recorded partition skew
        from spark_rapids_tpu import adaptive as AD
        pol = AD.policy_from_conf(conf)
        if pol.enabled and (pol.wants_join or pol.wants_skew):
            hash_ok = all(
                type(le.dtype) is type(re.dtype)
                for le, re in zip(cpu.left_keys, cpu.right_keys))
            return TpuAdaptiveLocalJoinExec(
                jt, cpu.left_keys, cpu.right_keys, cpu.condition,
                cpu.schema, ch[0], ch[1], pol,
                conf.get(C.SHUFFLE_PARTITIONS), hash_ok, cpu.using,
                bounds["sub_partition_rows"], bounds["out_batch_rows"])
    return TpuSortMergeJoinExec(cpu.join_type, cpu.left_keys,
                                cpu.right_keys, cpu.condition, cpu.schema,
                                ch[0], ch[1], using=cpu.using, **bounds)
