"""Other execs: Range, Sample, Expand, Generate, TakeOrderedAndProject.

[REF: sql-plugin/../basicPhysicalOperators.scala :: GpuRangeExec,
 GpuSampleExec; GpuExpandExec.scala; GpuGenerateExec.scala;
 limit.scala :: GpuTopN / TakeOrderedAndProject]  (SURVEY §2.1 #16/#18)

TPU-first notes:
* ``TpuRangeExec`` generates ids with an on-device iota — zero H2D
  traffic, the cheapest possible scan.
* ``TpuSampleExec`` re-designs GpuSampleExec's per-partition RNG as a
  *stateless hash-based* Bernoulli draw: each live row's global ordinal
  is murmur3-mixed with (seed + partition) and compared against
  ``fraction * 2^32`` in uint32 space.  Deterministic, order-stable,
  identical on CPU and device (oracle-checkable) — where cuDF uses a
  stateful curand sequence that XLA could not reproduce without a
  scatter of RNG state.
* ``TpuExpandExec`` emits one batch per projection (grouping sets) —
  P static-shape kernels instead of one 3-D scatter.
* ``TpuGenerateExec`` (explode/posexplode) flattens the padded
  ``[B, W]`` element matrix to ``[B*W]`` with a sel mask — explode is a
  *reshape*, not a variable-length scatter, exactly what the padded
  array layout was designed for.
* ``TpuTopNExec`` sorts each partition's gathered batch once and keeps
  the first n live rows via the sel mask, then merges partition winners
  with one final sort — the reference's GpuTopN
  (sort + slice per batch, then reduce) with masks instead of slices.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import host as H
from spark_rapids_tpu.columnar.column import (
    DeviceBatch, DeviceColumn, compact, round_up_pow2)
from spark_rapids_tpu.exec.base import CpuExec, TpuExec
from spark_rapids_tpu.exec.basic import concat_device_batches
from spark_rapids_tpu.ops.expressions import Expression
from spark_rapids_tpu.plan.logical import SortOrder


# ---------------------------------------------------------------------------
# Range
# ---------------------------------------------------------------------------

def _range_count(start: int, end: int, step: int) -> int:
    if step == 0:
        raise ValueError("range step must not be 0")
    n = (end - start + step - (1 if step > 0 else -1)) // step
    return max(0, n)


class CpuRangeExec(CpuExec):
    """[REF: basicPhysicalOperators.scala :: GpuRangeExec] (CPU oracle)."""

    def __init__(self, start: int, end: int, step: int,
                 schema: T.StructType, num_partitions: int = 1,
                 batch_rows: int = 1 << 20):
        super().__init__(schema)
        self.start, self.end, self.step = start, end, step
        self._num_partitions = max(1, num_partitions)
        self.batch_rows = batch_rows

    def node_string(self):
        return f"Range ({self.start}, {self.end}, step={self.step})"

    def num_partitions(self) -> int:
        return self._num_partitions

    def estimated_size_bytes(self):
        return _range_count(self.start, self.end, self.step) * 8

    def _bounds(self, partition: int):
        n = _range_count(self.start, self.end, self.step)
        per = (n + self._num_partitions - 1) // self._num_partitions
        lo = min(partition * per, n)
        hi = min(lo + per, n)
        return lo, hi

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        lo, hi = self._bounds(partition)
        for b0 in range(lo, max(hi, lo + 1), self.batch_rows):
            if b0 >= hi and b0 > lo:
                break
            b1 = min(b0 + self.batch_rows, hi)
            ids = self.start + np.arange(b0, b1, dtype=np.int64) * self.step
            out = H.HostBatch(self.schema, [H.HostCol(T.LongT, ids)])
            self.metric("numOutputRows").add(len(ids))
            self.metric("numOutputBatches").add(1)
            yield out
            if b1 >= hi:
                break


class TpuRangeExec(TpuExec):
    """Device iota — no host data, no transfer.

    [REF: basicPhysicalOperators.scala :: GpuRangeExec] (cuDF sequence;
    here one fused ``start + arange*step``)."""

    def __init__(self, cpu: CpuRangeExec):
        super().__init__(cpu.schema)
        self.start, self.end, self.step = cpu.start, cpu.end, cpu.step
        self._num_partitions = cpu._num_partitions
        self.batch_rows = cpu.batch_rows
        self._bounds = cpu._bounds

    def node_string(self):
        return f"TpuRange ({self.start}, {self.end}, step={self.step})"

    def num_partitions(self) -> int:
        return self._num_partitions

    def estimated_size_bytes(self):
        return _range_count(self.start, self.end, self.step) * 8

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
        lo, hi = self._bounds(partition)
        schema = self.schema
        for b0 in range(lo, max(hi, lo + 1), self.batch_rows):
            if b0 >= hi and b0 > lo:
                break
            b1 = min(b0 + self.batch_rows, hi)
            count = b1 - b0
            bucket = round_up_pow2(max(count, 1))
            fn = cached_kernel(
                ("range", bucket),
                lambda: (lambda first, step, count:
                         _range_kernel(first, step, count, bucket, schema)))
            with self.timer():
                out = fn(jnp.int64(self.start + b0 * self.step),
                         jnp.int64(self.step), jnp.int32(count))
            self.metric("numOutputRows").add(count)
            self.metric("numOutputBatches").add(1)
            yield out
            if b1 >= hi:
                break


def _range_kernel(first, step, count, bucket: int, schema) -> DeviceBatch:
    ids = first + jnp.arange(bucket, dtype=jnp.int64) * step
    sel = jnp.arange(bucket, dtype=jnp.int32) < count
    return DeviceBatch(schema, (DeviceColumn(T.LongT, ids),), sel,
                       compacted=True)


# ---------------------------------------------------------------------------
# Sample
# ---------------------------------------------------------------------------

def _sample_threshold(fraction: float) -> int:
    return min(int(fraction * 4294967296.0), 0xFFFFFFFF)


class CpuSampleExec(CpuExec):
    """Hash-Bernoulli sample oracle (same draw as the device path)."""

    def __init__(self, fraction: float, seed: int, child: CpuExec):
        super().__init__(child.schema, child)
        self.fraction = float(fraction)
        self.seed = int(seed)

    def node_string(self):
        return f"Sample [{self.fraction}, seed={self.seed}]"

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        from spark_rapids_tpu.ops.hashing import _hash_int_vec
        if self.fraction >= 1.0:  # keep-all: h < thresh would drop the
            yield from self.children[0].execute(partition)  # 2^-32 tail
            return
        thresh = np.uint32(_sample_threshold(self.fraction))
        seed = np.uint32((self.seed + partition) & 0xFFFFFFFF)
        base = 0
        for b in self.children[0].execute(partition):
            n = b.num_rows
            ordinals = (base + np.arange(n, dtype=np.int64)).astype(
                np.int64).astype(np.uint32)
            base += n
            h = _hash_int_vec(ordinals, seed, np)
            keep = h < thresh
            cols = [H.HostCol(c.dtype, c.data[keep],
                              None if c.validity is None
                              else c.validity[keep])
                    for c in b.columns]
            out = H.HostBatch(b.schema, cols)
            self.metric("numOutputRows").add(out.num_rows)
            self.metric("numOutputBatches").add(1)
            yield out


class TpuSampleExec(TpuExec):
    """Stateless Bernoulli sample folded into the sel mask.

    [REF: basicPhysicalOperators.scala :: GpuSampleExec] — the draw is
    hash-based (see module docstring), so the device result is bit-equal
    to the CPU oracle; Spark-exact row selection is impossible anyway
    (different RNG) and the reference documents the same caveat."""

    def __init__(self, fraction: float, seed: int, child: TpuExec):
        super().__init__(child.schema, child)
        self.fraction = float(fraction)
        self.seed = int(seed)

    def node_string(self):
        return f"TpuSample [{self.fraction}, seed={self.seed}]"

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
        if self.fraction >= 1.0:  # keep-all (see CPU exec)
            yield from self.children[0].execute(partition)
            return
        thresh = np.uint32(_sample_threshold(self.fraction))
        seed = np.uint32((self.seed + partition) & 0xFFFFFFFF)
        # the running live-row ordinal stays a device scalar — no host
        # sync per batch, the next kernel call consumes it directly
        base = jnp.int32(0)
        fn = cached_kernel(("sample",), lambda: _sample_kernel)
        for b in self.children[0].execute(partition):
            with self.timer():
                out, base = fn(b, jnp.uint32(seed), jnp.uint32(thresh),
                               base)
            self.metric("numOutputBatches").add(1)
            yield out


def _sample_kernel(batch: DeviceBatch, seed, thresh, base):
    from spark_rapids_tpu.ops.hashing import _hash_int_vec
    ordinal = base + jnp.cumsum(batch.sel.astype(jnp.int32)) - 1
    h = _hash_int_vec(ordinal.astype(jnp.uint32), seed, jnp)
    keep = batch.sel & (h < thresh)
    # the ordinal advances by the *input* live count
    return batch.with_sel(keep), base + jnp.sum(batch.sel.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Expand (grouping sets / rollup / cube)
# ---------------------------------------------------------------------------

class CpuExpandExec(CpuExec):
    """[REF: GpuExpandExec.scala] — output = every projection applied to
    every input batch (row multiplication factor = #projections)."""

    def __init__(self, projections: List[List[Expression]],
                 schema: T.StructType, child: CpuExec):
        super().__init__(schema, child)
        self.projections = [list(p) for p in projections]

    def node_string(self):
        return f"Expand [{len(self.projections)} projections]"

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        for b in self.children[0].execute(partition):
            for proj in self.projections:
                with self.timer():
                    cols = [e.eval_cpu(b) for e in proj]
                    out = H.HostBatch(self.schema, cols)
                self.metric("numOutputRows").add(out.num_rows)
                self.metric("numOutputBatches").add(1)
                yield out


class TpuExpandExec(TpuExec):
    """One cached kernel per projection; no row scatter — P batches out
    per batch in, each sharing the input's sel mask."""

    def __init__(self, projections: List[List[Expression]],
                 schema: T.StructType, child: TpuExec):
        super().__init__(schema, child)
        self.projections = [list(p) for p in projections]

    def node_string(self):
        return f"TpuExpand [{len(self.projections)} projections]"

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        schema = self.schema
        fns = []
        for pi, proj in enumerate(self.projections):
            def mk(proj=proj):
                def run(batch):
                    return DeviceBatch(
                        schema, tuple(e.eval_tpu(batch) for e in proj),
                        batch.sel)
                return run
            fns.append(cached_kernel(
                ("expand", fingerprint(proj), fingerprint(schema)), mk))
        for b in self.children[0].execute(partition):
            for fn in fns:
                with self.timer():
                    out = fn(b)
                self.metric("numOutputBatches").add(1)
                yield out


# ---------------------------------------------------------------------------
# Generate (explode / posexplode over array columns)
# ---------------------------------------------------------------------------

class CpuGenerateExec(CpuExec):
    """[REF: GpuGenerateExec.scala :: GpuExplodeBase] (CPU oracle)."""

    def __init__(self, generator: Expression, with_pos: bool, outer: bool,
                 schema: T.StructType, child: CpuExec):
        super().__init__(schema, child)
        self.generator = generator
        self.with_pos = with_pos
        self.outer = outer

    def node_string(self):
        k = "posexplode" if self.with_pos else "explode"
        return f"Generate [{k}{'_outer' if self.outer else ''}]"

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        elem_dt = self.generator.dtype.element_type
        is_str = isinstance(elem_dt, (T.StringType, T.BinaryType))
        npdt = object if is_str else T.to_numpy_dtype(elem_dt)
        fill = "" if is_str else 0
        for b in self.children[0].execute(partition):
            with self.timer():
                arr = self.generator.eval_cpu(b)
                valid = arr.valid_mask(b.num_rows)
                rows: List[int] = []
                poss: List[int] = []
                vals: List = []
                elem_null: List[bool] = []
                pos_null: List[bool] = []  # only outer empty-list rows
                for i in range(b.num_rows):
                    lst = arr.data[i] if valid[i] else []
                    if not lst:
                        if self.outer:
                            rows.append(i)
                            poss.append(0)
                            vals.append(fill)
                            elem_null.append(True)
                            pos_null.append(True)
                        continue
                    for j, v in enumerate(lst):
                        rows.append(i)
                        poss.append(j)
                        vals.append(v if v is not None else fill)
                        elem_null.append(v is None)
                        pos_null.append(False)
                idx = np.asarray(rows, dtype=np.int64)
                cols = [H.HostCol(c.dtype, c.data[idx],
                                  None if c.validity is None
                                  else c.validity[idx])
                        for c in b.columns]
                enulls = np.asarray(elem_null, dtype=bool)
                pnulls = np.asarray(pos_null, dtype=bool)
                ev = None if not enulls.any() else ~enulls
                pv = None if not pnulls.any() else ~pnulls
                if self.with_pos:
                    cols.append(H.HostCol(T.IntegerT,
                                          np.asarray(poss, np.int32), pv))
                cols.append(H.HostCol(elem_dt,
                                      np.asarray(vals, npdt), ev))
                out = H.HostBatch(self.schema, cols)
            self.metric("numOutputRows").add(out.num_rows)
            self.metric("numOutputBatches").add(1)
            yield out


class TpuGenerateExec(TpuExec):
    """Explode as a reshape: [B, W] element matrix → [B*W] rows.

    [REF: GpuGenerateExec.scala] — cuDF explodes via offsets+gather;
    the padded array layout makes it a static reshape + repeat-gather,
    with liveness (j < length) folded into the sel mask."""

    def __init__(self, generator: Expression, with_pos: bool, outer: bool,
                 schema: T.StructType, child: TpuExec):
        super().__init__(schema, child)
        self.generator = generator
        self.with_pos = with_pos
        self.outer = outer

    def node_string(self):
        k = "posexplode" if self.with_pos else "explode"
        return f"TpuGenerate [{k}{'_outer' if self.outer else ''}]"

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        from spark_rapids_tpu.runtime.memory import get_manager
        gen, with_pos, outer, schema = (
            self.generator, self.with_pos, self.outer, self.schema)

        def mk():
            def run(batch):
                return _generate_kernel(batch, gen, with_pos, outer,
                                        schema)
            return run

        fn = cached_kernel(
            ("generate", fingerprint(gen), with_pos, outer,
             fingerprint(schema)), mk)
        mgr = get_manager()
        for b in self.children[0].execute(partition):
            arr = self.generator.eval_tpu(b)
            w = max(int(arr.data.shape[1]), 1)
            # output working set: every non-array column repeats W×, the
            # element matrix flattens 1:1 — reserve exactly that, so
            # pool pressure spills other holders first
            out_bytes = (max(b.nbytes() - arr.nbytes(), 0) * w
                         + arr.nbytes())
            with mgr.transient(out_bytes):
                with self.timer():
                    out = fn(b)
            self.metric("numOutputBatches").add(1)
            yield out


def _generate_kernel(batch: DeviceBatch, gen: Expression, with_pos: bool,
                     outer: bool, schema: T.StructType) -> DeviceBatch:
    arr = gen.eval_tpu(batch)
    mat, lengths = arr.data, arr.lengths
    b, w = (int(mat.shape[0]), max(int(mat.shape[1]), 1))
    if mat.shape[1] == 0:
        mat = jnp.zeros((b, 1), mat.dtype)
    cap = b * w
    i = jnp.arange(cap, dtype=jnp.int32) // w
    j = jnp.arange(cap, dtype=jnp.int32) % w
    ln = jnp.take(lengths, i)
    lvalid = jnp.take(arr.valid_mask(), i)
    in_list = j < jnp.where(lvalid, ln, 0)
    sel_in = jnp.take(batch.sel, i)
    # element nulls: reshape follows the same row-major (i, j) order
    enull_flat = (None if arr.evalid is None
                  else jnp.reshape(arr.evalid, (cap,)))
    if outer:
        empty = (~lvalid) | (ln == 0)
        sel_out = sel_in & (in_list | (empty & (j == 0)))
        pvalid = in_list  # outer-emitted rows carry null element/pos
        evalid = (pvalid if enull_flat is None else pvalid & enull_flat)
    else:
        sel_out = sel_in & in_list
        pvalid = None  # every live output row has a real position
        evalid = enull_flat  # None = every element valid
    cols = [c.gather(i) for c in batch.columns]
    if with_pos:
        cols.append(DeviceColumn(T.IntegerT, j, pvalid))
    cols.append(DeviceColumn(gen.dtype.element_type,
                             jnp.reshape(mat, (cap,)), evalid))
    return DeviceBatch(schema, tuple(cols), sel_out)


# ---------------------------------------------------------------------------
# TakeOrderedAndProject (topN)
# ---------------------------------------------------------------------------

class CpuTopNExec(CpuExec):
    """[REF: limit.scala :: GpuTopN] (CPU oracle: global sort + head)."""

    def __init__(self, orders: Sequence[SortOrder], n: int, child: CpuExec):
        super().__init__(child.schema, child)
        self.orders = list(orders)
        self.n = int(n)

    def node_string(self):
        return f"TakeOrderedAndProject [n={self.n}]"

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        from spark_rapids_tpu.exec.sort import CpuSortExec
        inner = CpuSortExec(self.orders, self.children[0])
        for b in inner.execute(0):
            take = min(self.n, b.num_rows)
            cols = [H.HostCol(c.dtype, c.data[:take],
                              None if c.validity is None
                              else c.validity[:take])
                    for c in b.columns]
            out = H.HostBatch(b.schema, cols)
            self.metric("numOutputRows").add(out.num_rows)
            self.metric("numOutputBatches").add(1)
            yield out
            return


def _table_to_b64(t) -> str:
    import base64
    import io

    import pyarrow as pa
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    return base64.b64encode(sink.getvalue()).decode()


def _b64_to_table(s: str):
    import base64

    import pyarrow as pa
    return pa.ipc.open_stream(base64.b64decode(s)).read_all()


class TpuTopNExec(TpuExec):
    """Per-partition device topN, then one merge sort of the winners.

    Each partition reduces to ≤ n live rows *before* the cross-partition
    gather, so the merge concat moves P·n rows, not the whole input —
    the reference's GpuTopN/TakeOrderedAndProject shape.  In
    multi-executor mode each process reduces its slice the same way,
    the ≤ n winner rows allgather host-side through the rendezvous (they
    are tiny by construction), and process 0 emits the global answer —
    the driver-side final reduce of Spark's TakeOrderedAndProject."""

    # gathers child partitions, but multiproc execution is handled
    # internally (winner-row allgather) — exempt from the structural
    # multiproc gather guard
    _multiproc_gather_ok = True

    def __init__(self, orders: Sequence[SortOrder], n: int, child: TpuExec):
        super().__init__(child.schema, child)
        self.orders = list(orders)
        self.n = int(n)
        from spark_rapids_tpu.parallel.executor import get_executor
        self._ctx = get_executor()
        self._stage = (self._ctx.next_stage_id()
                       if self._ctx is not None else None)

    def node_string(self):
        return f"TpuTopN [n={self.n}]"

    def num_partitions(self) -> int:
        return 1

    def _local_topn(self, p: int) -> Optional[DeviceBatch]:
        from spark_rapids_tpu.exec.sort import sort_batch
        child = self.children[0]
        batches = []
        for b in child.execute(p):
            with self.timer("concatTime"):
                b = compact(b)
            if b is not None:
                batches.append(b)
        if not batches:
            return None
        with self.timer("concatTime"):
            merged = concat_device_batches(self.schema, batches)
        with self.timer():
            s = sort_batch(merged, self.orders, node=self)
            keep = s.sel & (jnp.arange(s.capacity, dtype=jnp.int32) < self.n)
            return compact(s.with_sel(keep))

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.exec.sort import sort_batch
        child = self.children[0]
        winners = []
        parts = range(child.num_partitions())
        if self._ctx is not None:
            from spark_rapids_tpu.exec.distributed import owned_partitions
            parts = owned_partitions(child)
        for p in parts:
            t = self._local_topn(p)
            if t is not None:
                winners.append(t)
        if self._ctx is not None:
            winners = self._merge_across_executors(winners)
            if winners is None:
                return
        if not winners:
            return
        with self.timer("concatTime"):
            merged = concat_device_batches(self.schema, winners)
        with self.timer():
            s = sort_batch(merged, self.orders, node=self)
            keep = s.sel & (jnp.arange(s.capacity, dtype=jnp.int32) < self.n)
            out = s.with_sel(keep)
        self.metric("numOutputBatches").add(1)
        yield out

    def _merge_across_executors(self, winners):
        """Allgather ≤ n local winner rows; only process 0 returns
        batches (the union over executors must not duplicate the global
        answer)."""
        from spark_rapids_tpu.columnar.column import (
            device_to_host, host_to_device)
        from spark_rapids_tpu.exec.sort import sort_batch
        ctx = self._ctx
        payload = None
        if winners:
            # reduce the per-partition winners to THIS process's top-n
            # before shipping: the rendezvous payload is then ≤ n rows,
            # not partitions×n
            local = concat_device_batches(self.schema, winners)
            s = sort_batch(local, self.orders)
            keep = s.sel & (jnp.arange(s.capacity,
                                       dtype=jnp.int32) < self.n)
            local = compact(s.with_sel(keep))
            payload = _table_to_b64(device_to_host(local))
        replies = ctx.client.allgather(self._stage + ":topn", payload,
                                       ctx.timeout)
        if ctx.process_id != 0:
            return None
        out = []
        for r in replies:
            if r is None:
                continue
            t = _b64_to_table(r)
            if t.num_rows == 0:
                continue
            b = host_to_device(t)
            out.append(DeviceBatch(self.schema, b.columns, b.sel,
                                   compacted=True))
        return out


# ---------------------------------------------------------------------------
# Override rules (registered by plan/overrides._register_lazy_rules)
# ---------------------------------------------------------------------------

def _tag_range(meta):
    pass


def _convert_range(cpu, ch, conf):
    return TpuRangeExec(cpu)


def _tag_sample(meta):
    pass


def _convert_sample(cpu, ch, conf):
    return TpuSampleExec(cpu.fraction, cpu.seed, ch[0])


def _tag_expand(meta):
    for proj in meta.cpu.projections:
        meta.tag_expressions(proj)


def _convert_expand(cpu, ch, conf):
    return TpuExpandExec(cpu.projections, cpu.schema, ch[0])


def _tag_generate(meta):
    from spark_rapids_tpu.ops.expressions import BoundReference
    gen = meta.cpu.generator
    if not isinstance(gen, BoundReference):
        meta.will_not_work(
            "generator input must be a direct array-column reference")
        return
    et = gen.dtype.element_type
    if not T.is_numeric(et) and not isinstance(
            et, (T.BooleanType, T.DateType, T.TimestampType)):
        meta.will_not_work(
            f"explode over array<{et.simple_name}> not supported on "
            "device (element matrix is numeric-only)")


def _convert_generate(cpu, ch, conf):
    return TpuGenerateExec(cpu.generator, cpu.with_pos, cpu.outer,
                           cpu.schema, ch[0])


def _tag_topn(meta):
    meta.tag_expressions([o.expr for o in meta.cpu.orders])


def _convert_topn(cpu, ch, conf):
    return TpuTopNExec(cpu.orders, cpu.n, ch[0])
