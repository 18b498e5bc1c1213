"""Basic physical operators: scan, project, filter, limit, union, range.

[REF: sql-plugin/../basicPhysicalOperators.scala :: GpuProjectExec,
 GpuFilterExec, GpuRangeExec; GpuUnionExec; limit execs in
 sql-plugin/../limit.scala]

TPU-first notes:
* ``TpuFilterExec`` never changes shapes — it ANDs the predicate into the
  batch ``sel`` mask (null predicate = drop row, Spark semantics).
  Compaction happens only at deliberate boundaries (shuffle/host transfer).
* ``TpuProjectExec`` evaluates the bound expression tree; XLA fuses the
  whole projection into one program per (schema, bucket) via jit caching
  inside the expression kernels.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import host as H
from spark_rapids_tpu.columnar.column import (
    DeviceBatch, DeviceColumn, compact, host_to_device, round_up_pow2)
from spark_rapids_tpu.exec.base import CpuExec, ExecNode, TpuExec
from spark_rapids_tpu.ops.expressions import Expression
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime import trace


def _slice_table(table: pa.Table, num_partitions: int) -> List[pa.Table]:
    n = table.num_rows
    if num_partitions <= 1:
        return [table]
    step = (n + num_partitions - 1) // num_partitions
    out = []
    for i in range(num_partitions):
        lo = min(i * step, n)
        out.append(table.slice(lo, min(step, n - lo)))
    return out


class CpuScanExec(CpuExec):
    """In-memory arrow table scan → HostBatch per partition slice."""

    def __init__(self, table: pa.Table, schema: T.StructType,
                 num_partitions: int = 1, batch_rows: int = 1 << 20):
        super().__init__(schema)
        self.table = table
        self._num_partitions = num_partitions
        self.batch_rows = batch_rows

    def num_partitions(self) -> int:
        return self._num_partitions

    def estimated_size_bytes(self):
        return self.table.nbytes

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        part = _slice_table(self.table, self._num_partitions)[partition]
        for lo in range(0, max(part.num_rows, 1), self.batch_rows):
            chunk = part.slice(lo, self.batch_rows)
            if chunk.num_rows == 0 and lo > 0:
                break
            with self.timer():
                b = H.from_arrow_table(chunk)
                b = H.HostBatch(self.schema, b.columns)
            self.metric("numOutputRows").add(b.num_rows)
            self.metric("numOutputBatches").add(1)
            yield b


import threading
import weakref

# Device-resident cache for in-memory relations: repeated executions of a
# query over the same table skip the H2D transfer (the steady-state regime
# the reference benchmarks — inter-stage data stays on device there; here
# the analog of Spark's columnar cache).  Entries die with their table.
_scan_cache: dict = {}
_scan_cache_lock = threading.Lock()

_TM_SCAN_HITS = TM.REGISTRY.counter(
    "tpuq_scan_cache_hits_total",
    "TpuScanExec partitions served from device-resident batches")
_TM_SCAN_MISSES = TM.REGISTRY.counter(
    "tpuq_scan_cache_misses_total",
    "TpuScanExec partitions streamed from the arrow table (H2D)")
_TM_H2D_BYTES = TM.REGISTRY.counter(
    "tpuq_h2d_bytes_total",
    "arrow bytes TpuScanExec copied to the device on a miss")
_TM_COMPACT_IN = TM.REGISTRY.counter(
    "tpuq_compact_slots_in_total",
    "slots of the batches a join gathered, at the capacities they "
    "came in")
_TM_COMPACT_MOVED = TM.REGISTRY.counter(
    "tpuq_compact_slots_moved_total",
    "slots those batches' compactions gathered: a live bucket each, "
    "0 for a batch that came compacted or fully live")


def _scan_cache_get(table: pa.Table, key):
    ent = _scan_cache.get(id(table))
    return None if ent is None else ent.get(key)


def _scan_cache_evict(tid):
    with _scan_cache_lock:
        entries = _scan_cache.pop(tid, None)
    if entries:
        for pairs in entries.values():
            for sp, _ in pairs:
                sp.close()  # release arbiter accounting + spill files


def clear_scan_cache():
    """Evict every cached scan (e.g. when the budget arbiter is
    replaced — registrations against the old arbiter would go stale)."""
    for tid in list(_scan_cache):
        _scan_cache_evict(tid)


def _scan_cache_put(table: pa.Table, key, batches):
    tid = id(table)
    with _scan_cache_lock:
        if tid not in _scan_cache:
            try:
                weakref.finalize(table, _scan_cache_evict, tid)
            except TypeError:
                return
            _scan_cache[tid] = {}
        if key in _scan_cache[tid]:
            # lost a build race: a preempted builder parked mid-scan
            # while a concurrent query built the same entry.  Readers
            # may already hold the installed list, so first-put wins —
            # close our duplicates instead of orphaning theirs.
            losers = batches
        else:
            _scan_cache[tid][key] = batches
            return
    for sp, _ in losers:
        sp.close()


class TpuScanExec(TpuExec):
    """In-memory arrow table scan → padded DeviceBatch per partition.

    The H2D transfer point [REF: GpuRowToColumnarExec.scala] — in this
    engine scans land device-resident batches directly.
    """

    def __init__(self, table: pa.Table, schema: T.StructType,
                 num_partitions: int = 1, batch_rows: int = 1 << 20,
                 min_bucket: int = 1024,
                 executor: Tuple[int, int] = (0, 1)):
        super().__init__(schema)
        self.table = table
        self._num_partitions = num_partitions
        self.batch_rows = batch_rows
        self.min_bucket = min_bucket
        # (executor_id, executor_count): in multi-executor mode each
        # process serves only source partitions p ≡ id (mod count) — the
        # analog of the Spark scheduler assigning scan tasks to
        # executors; the union over processes is exactly the table
        self.executor = tuple(executor)

    def num_partitions(self) -> int:
        return self._num_partitions

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        eid, ecount = self.executor
        if ecount > 1 and partition % ecount != eid:
            return
        from spark_rapids_tpu.runtime.memory import (
            RetryOOM, SpillableBatch, get_manager)
        key = (self._num_partitions, self.batch_rows, self.min_bucket,
               partition)
        cached = _scan_cache_get(self.table, key)
        if cached is not None:
            _TM_SCAN_HITS.inc()
            for bi, (sp, nrows) in enumerate(cached):
                try:
                    # restores the batch if the arbiter spilled it
                    restored = sp.get()
                except RetryOOM:
                    # no room to restore: drop the cache and stream the
                    # REMAINDER of the partition straight from the arrow
                    # table (earlier entries were already yielded — never
                    # restart from batch 0, that duplicates rows)
                    _scan_cache_evict(id(self.table))
                    yield from self._stream(partition, register=False,
                                            start_batch=bi)
                    return
                self.metric("numOutputRows").add(nrows)
                self.metric("numOutputBatches").add(1)
                yield restored
            return
        _TM_SCAN_MISSES.inc()
        yield from self._stream(partition, key, register=True)

    def _stream(self, partition: int, key=None, register: bool = False,
                start_batch: int = 0) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.memory import (
            RetryOOM, SpillableBatch, get_manager)
        out = []
        part = _slice_table(self.table, self._num_partitions)[partition]
        start = start_batch * self.batch_rows
        if start and start >= part.num_rows:
            return
        for lo in range(start, max(part.num_rows, 1), self.batch_rows):
            chunk = part.slice(lo, self.batch_rows)
            if chunk.num_rows == 0 and lo > 0:
                break
            with self.timer(), trace.span("TpuScanExec", "h2dTime"):
                b = host_to_device(chunk, min_bucket=self.min_bucket)
                b = DeviceBatch(self.schema, b.columns, b.sel,
                                compacted=True)
            _TM_H2D_BYTES.inc(chunk.nbytes)
            # row count is known host-side — never sync the device here
            # (a D2H per scanned batch would serialize the pump)
            nrows = chunk.num_rows
            self.metric("numOutputRows").add(nrows)
            self.metric("numOutputBatches").add(1)
            if register and out is not None:
                # device-resident cache entries are the arbiter's
                # reclaim pool: under pressure they spill host-side and
                # restore transparently on the next scan.  Registration
                # is best-effort — a full budget (or injected OOM) just
                # means this scan isn't cached, never a query failure.
                try:
                    out.append((SpillableBatch(b, get_manager()), nrows))
                except RetryOOM:
                    for sp, _ in out:
                        sp.close()
                    out = None
            yield b
        if register and out is not None:
            _scan_cache_put(self.table, key, out)


class CpuProjectExec(CpuExec):
    def __init__(self, exprs: Sequence[Expression], schema: T.StructType,
                 child: CpuExec):
        super().__init__(schema, child)
        self.exprs = list(exprs)

    def node_string(self):
        return f"Project [{', '.join(str(e) for e in self.exprs)}]"

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        for b in self.children[0].execute(partition):
            with self.timer():
                cols = [e.eval_cpu(b) for e in self.exprs]
                out = H.HostBatch(self.schema, cols)
            self.metric("numOutputRows").add(out.num_rows)
            self.metric("numOutputBatches").add(1)
            yield out


class TpuProjectExec(TpuExec):
    """[REF: basicPhysicalOperators.scala :: GpuProjectExec]"""

    def __init__(self, exprs: Sequence[Expression], schema: T.StructType,
                 child: TpuExec):
        super().__init__(schema, child)
        self.exprs = list(exprs)

    def node_string(self):
        return f"TpuProject [{', '.join(str(e) for e in self.exprs)}]"

    def fusion(self):
        from spark_rapids_tpu.runtime.kernel_cache import fingerprint
        exprs, schema = self.exprs, self.schema

        def run(batch):
            # sel passes through untouched, and so does its promise
            return DeviceBatch(
                schema, tuple(e.eval_tpu(batch) for e in exprs),
                batch.sel, compacted=batch.compacted)

        return run, ("project", fingerprint(exprs), fingerprint(schema))

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
        run, key = self.fusion()
        fn = cached_kernel(key, lambda: run)
        for b in self.children[0].execute(partition):
            with self.timer():
                out = fn(b)
            self.metric("numOutputBatches").add(1)
            yield out


class CpuFilterExec(CpuExec):
    def __init__(self, condition: Expression, child: CpuExec):
        super().__init__(child.schema, child)
        self.condition = condition

    def node_string(self):
        return f"Filter [{self.condition}]"

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        for b in self.children[0].execute(partition):
            with self.timer():
                c = self.condition.eval_cpu(b)
                keep = c.data.astype(bool)
                if c.validity is not None:
                    keep = keep & c.validity  # null predicate drops the row
                cols = [H.HostCol(col.dtype, col.data[keep],
                                  None if col.validity is None
                                  else col.validity[keep])
                        for col in b.columns]
                out = H.HostBatch(b.schema, cols)
            self.metric("numOutputRows").add(out.num_rows)
            self.metric("numOutputBatches").add(1)
            yield out


class TpuFilterExec(TpuExec):
    """Predicate folds into ``sel`` — no shape change, no compaction.

    [REF: basicPhysicalOperators.scala :: GpuFilterExec] (cuDF materializes
    via apply_boolean_mask; here liveness is a mask by design).
    """

    def __init__(self, condition: Expression, child: TpuExec):
        super().__init__(child.schema, child)
        self.condition = condition

    def node_string(self):
        return f"TpuFilter [{self.condition}]"

    def fusion(self):
        from spark_rapids_tpu.runtime.kernel_cache import fingerprint
        cond = self.condition

        def run(batch):
            c = cond.eval_tpu(batch)
            keep = c.data.astype(jnp.bool_)
            if c.validity is not None:
                keep = keep & c.validity
            return batch.with_sel(batch.sel & keep)

        return run, ("filter", fingerprint(cond))

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
        run, key = self.fusion()
        fn = cached_kernel(key, lambda: run)
        for b in self.children[0].execute(partition):
            with self.timer():
                out = fn(b)
            self.metric("numOutputBatches").add(1)
            yield out


class CpuLocalLimitExec(CpuExec):
    def __init__(self, n: int, child: CpuExec):
        super().__init__(child.schema, child)
        self.n = n

    def node_string(self):
        return f"LocalLimit [{self.n}]"

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        remaining = self.n
        for b in self.children[0].execute(partition):
            if remaining <= 0:
                break
            take = min(remaining, b.num_rows)
            cols = [H.HostCol(c.dtype, c.data[:take],
                              None if c.validity is None else c.validity[:take])
                    for c in b.columns]
            remaining -= take
            yield H.HostBatch(b.schema, cols)


class TpuLocalLimitExec(TpuExec):
    """Keep the first n live rows (batch order).  Mask-only, static shape.

    [REF: limit.scala :: GpuLocalLimitExec]
    """

    def __init__(self, n: int, child: TpuExec):
        super().__init__(child.schema, child)
        self.n = n

    def node_string(self):
        return f"TpuLocalLimit [{self.n}]"

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        remaining = self.n
        for b in self.children[0].execute(partition):
            if remaining <= 0:
                break
            with self.timer():
                live_prefix = jnp.cumsum(b.sel.astype(jnp.int32))
                keep = b.sel & (live_prefix <= remaining)
                out = b.with_sel(keep)
            # how many we actually emitted (host sync per batch boundary)
            remaining -= int(jnp.sum(keep.astype(jnp.int32)))
            yield out


class CpuGlobalLimitExec(CpuExec):
    """Single-partition global cut across all child partitions.

    [REF: limit.scala :: GpuGlobalLimitExec] — planned above a per-
    partition LocalLimit, exactly Spark's GlobalLimit(LocalLimit(...)).
    """

    def __init__(self, n: int, child: CpuExec):
        super().__init__(child.schema, child)
        self.n = n

    def node_string(self):
        return f"GlobalLimit [{self.n}]"

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        remaining = self.n
        child = self.children[0]
        for p in range(child.num_partitions()):
            for b in child.execute(p):
                if remaining <= 0:
                    return
                take = min(remaining, b.num_rows)
                cols = [H.HostCol(c.dtype, c.data[:take],
                                  None if c.validity is None
                                  else c.validity[:take])
                        for c in b.columns]
                remaining -= take
                yield H.HostBatch(b.schema, cols)


class TpuGlobalLimitExec(TpuExec):
    """[REF: limit.scala :: GpuGlobalLimitExec]

    Multi-executor mode: LIMIT takes ANY n rows (Spark semantics), so no
    row exchange is needed — processes allgather their live-row counts
    and each emits its quota of the first-come budget in process order.
    """

    _multiproc_gather_ok = True

    def __init__(self, n: int, child: TpuExec):
        super().__init__(child.schema, child)
        self.n = n
        from spark_rapids_tpu.parallel.executor import get_executor
        self._ctx = get_executor()
        self._stage = (self._ctx.next_stage_id()
                       if self._ctx is not None else None)

    def node_string(self):
        return f"TpuGlobalLimit [{self.n}]"

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        remaining = self.n
        child = self.children[0]
        if self._ctx is not None:
            from spark_rapids_tpu.exec.distributed import owned_partitions
            ctx = self._ctx
            # drain lazily only until n local rows are seen — reporting
            # the CAPPED count keeps the quota math exact (counts past
            # n can never change any process's quota) while preserving
            # LIMIT's early termination
            batches: List[DeviceBatch] = []
            local = 0
            for p in owned_partitions(child):
                if local >= self.n:
                    break
                # counts pulled ONE overlapped round trip per partition
                # (a per-batch pull waits out a device round trip each);
                # early termination still checked between partitions
                part = list(child.execute(p))
                if not part:
                    continue
                batches.extend(part)
                local += sum(_overlapped_live_counts(part))
            replies = ctx.client.allgather(
                self._stage + ":limit", min(local, self.n), ctx.timeout)
            before = sum(replies[:ctx.process_id])
            remaining = max(0, min(local, self.n - before))
            stream = iter(batches)
        else:
            stream = (b for p in range(child.num_partitions())
                      for b in child.execute(p))
        for b in stream:
            if remaining <= 0:
                return
            with self.timer():
                live_prefix = jnp.cumsum(b.sel.astype(jnp.int32))
                keep = b.sel & (live_prefix <= remaining)
                out = b.with_sel(keep)
            remaining -= int(jnp.sum(keep.astype(jnp.int32)))
            yield out


class CpuUnionExec(CpuExec):
    def __init__(self, children_: Sequence[CpuExec]):
        super().__init__(children_[0].schema, *children_)

    def num_partitions(self) -> int:
        return sum(c.num_partitions() for c in self.children)

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        for c in self.children:
            np_ = c.num_partitions()
            if partition < np_:
                for b in c.execute(partition):
                    yield H.HostBatch(self.schema, b.columns)
                return
            partition -= np_
        raise IndexError("partition out of range")


class TpuUnionExec(TpuExec):
    """[REF: GpuUnionExec] — partitions concatenate across children."""

    def __init__(self, children_: Sequence[TpuExec]):
        super().__init__(children_[0].schema, *children_)

    def num_partitions(self) -> int:
        return sum(c.num_partitions() for c in self.children)

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        for c in self.children:
            np_ = c.num_partitions()
            if partition < np_:
                for b in c.execute(partition):
                    yield DeviceBatch(self.schema, b.columns, b.sel)
                return
            partition -= np_
        raise IndexError("partition out of range")


class TpuCoalesceBatchesExec(TpuExec):
    """Concatenate small device batches up to a target row budget.

    [REF: GpuCoalesceBatches.scala :: GpuCoalesceBatches] — goal-directed:
    ``target_rows`` (TargetSize analog) or require_single (RequireSingleBatch,
    used by ops that need the whole partition, e.g. final sort).
    Concat = pad columns to the shared bucket and jnp.concatenate; the
    result bucket is the pow-2 ceiling of the live-row total.
    """

    def __init__(self, child: TpuExec, target_rows: int = 1 << 22,
                 require_single: bool = False):
        super().__init__(child.schema, child)
        self.target_rows = target_rows
        self.require_single = require_single

    def node_string(self):
        goal = "single" if self.require_single else f"target={self.target_rows}"
        return f"TpuCoalesceBatches [{goal}]"

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        pending: List[DeviceBatch] = []
        pending_rows = 0
        for b in self.children[0].execute(partition):
            n = int(jnp.sum(b.sel.astype(jnp.int32)))
            if (not self.require_single and pending
                    and pending_rows + n > self.target_rows):
                yield self._emit(pending)
                pending, pending_rows = [], 0
            pending.append(compact(b))
            pending_rows += n
        if pending:
            yield self._emit(pending)

    def _emit(self, batches: List[DeviceBatch]) -> DeviceBatch:
        with self.timer("concatTime"):
            out = concat_device_batches(self.schema, batches)
        self.metric("numOutputBatches").add(1)
        return out


_BIG_BUCKET_ROWS = int(__import__("os").environ.get(
    "SPARK_RAPIDS_TPU_BIG_BUCKET_WARN_ROWS", str(1 << 22)))


def warn_big_bucket(where: str, bucket: int) -> None:
    """Stderr breadcrumb when any single device allocation crosses the
    warn threshold (default 4M rows).  A bucket that large is one bad
    shape away from a TPU worker kernel fault / HBM OOM that kills the
    process without a Python traceback — the breadcrumb names the call
    site so a post-mortem has somewhere to start."""
    if bucket < _BIG_BUCKET_ROWS:
        return
    import sys
    import traceback
    stack = traceback.extract_stack(limit=3)
    # stack[-1] = here, stack[-2] = the concat, stack[-3] = its caller
    frame = stack[-3] if len(stack) >= 3 else stack[0]
    print(f"[tpuq] WARNING: {where} building a {bucket}-row bucket "
          f"(caller {frame.name}:{frame.lineno})",
          file=sys.stderr, flush=True)


def _overlapped_live_counts(batches) -> List[int]:
    """Live-row counts for many batches with ONE overlapped transfer
    round trip (sequential scalar pulls each wait out a device round
    trip — the breadth-query dispatch tax)."""
    sums = [jnp.sum(b.sel.astype(jnp.int32)) for b in batches]
    for s_ in sums:
        s_.copy_to_host_async()
    return [int(np.asarray(s_)) for s_ in sums]


def _compact_counted(batches: List[DeviceBatch], node=None):
    """Count first, then compact each batch at its live bucket.

    Every live count comes in ONE overlapped round trip, ahead of the
    compaction it sizes (a gather pays per index, so only the live
    bucket's move: docs/kernels.md "Moving rows"), and goes back to the
    caller with the batches, so nobody pulls it again.  Returns
    ``(compacted batches, live counts, capacities as they came)``."""
    counts = _overlapped_live_counts(batches)
    caps = [b.capacity for b in batches]
    out = [compact(b, rows=n) for b, n in zip(batches, counts)]
    # a batch that came compacted, or with every slot live, moves none
    moved = sum(c.capacity for b, n, c in zip(batches, counts, out)
                if not b.compacted and n < b.capacity)
    _TM_COMPACT_IN.inc(sum(caps))
    _TM_COMPACT_MOVED.inc(moved)
    if node is not None:
        node.metric("compactSlotsIn").add(sum(caps))
        node.metric("compactSlotsMoved").add(moved)
    return out, counts, caps


def _concat_compacted_fast(schema: T.StructType,
                           batches: List[DeviceBatch],
                           counts: Optional[List[int]] = None
                           ) -> DeviceBatch:
    """Dispatch-bounded concat of COMPACTED batches.

    1. live counts for ALL batches pulled with one overlapped transfer
       round trip (sequential ``int(jnp.sum(...))`` pulls each wait
       out a device round trip — the TPC-H breadth-query dispatch tax);
    2. each batch normalizes through at most ONE cached jitted kernel
       (shrink to its pow-2 live bucket, pad strings to the shared
       width, synthesize missing validity planes) instead of
       O(columns) eager slice/pad ops;
    3. one eager ``jnp.concatenate`` per leaf, then a single stable
       compact moves the per-batch live prefixes together.
    """
    from spark_rapids_tpu.columnar.column import empty_batch, live_bucket
    from spark_rapids_tpu.runtime.kernel_cache import (
        cached_kernel, fingerprint)
    if not batches:
        return empty_batch(schema)
    if counts is None:
        counts = _overlapped_live_counts(batches)
    total = sum(counts)
    out_bucket = round_up_pow2(max(total, 1))
    warn_big_bucket("concat", out_bucket)
    nfields = len(schema.fields)
    # Structural uniformity gate: every batch must carry one column per
    # schema field and agree on string-ness.  Without it a mismatched
    # batch (an upstream op emitting against the wrong schema — the q7
    # streamed-join side-override bug's signature) surfaces as a bare
    # `IndexError: tuple index out of range` from `.data.shape[1]` deep
    # in kernel build, with no hint of which operator produced it.
    for bi, b in enumerate(batches):
        if len(b.columns) != nfields:
            raise ValueError(
                f"concat: batch {bi} carries {len(b.columns)} columns "
                f"for a {nfields}-field schema — an upstream operator "
                "emitted a batch that does not match its declared "
                "schema")
    is_str = [batches[0].columns[ci].is_string for ci in range(nfields)]
    for bi, b in enumerate(batches):
        for ci in range(nfields):
            if (b.columns[ci].is_string != is_str[ci]
                    or (is_str[ci] and b.columns[ci].data.ndim < 2)):
                raise ValueError(
                    f"concat: column {ci} ({schema.fields[ci].name!r}) "
                    f"is {'string' if is_str[ci] else 'non-string'} in "
                    f"batch 0 but not in batch {bi} — mixed layouts "
                    "cannot be concatenated")
    widths = tuple(
        max(b.columns[ci].data.shape[1] for b in batches)
        if is_str[ci] else 0 for ci in range(nfields))
    has_val = tuple(any(b.columns[ci].validity is not None
                        for b in batches) for ci in range(nfields))
    has_ev = tuple(any(b.columns[ci].evalid is not None
                       for b in batches) for ci in range(nfields))
    sfp = fingerprint(schema)

    def build_norm(out_cap):
        def run(m):
            cols = []
            for ci, c in enumerate(m.columns):
                d = c.data[:out_cap]
                ln = None if c.lengths is None else c.lengths[:out_cap]
                if is_str[ci] and d.shape[1] < widths[ci]:
                    d = jnp.pad(d, ((0, 0), (0, widths[ci] - d.shape[1])))
                v = None
                if has_val[ci]:
                    v = (c.validity[:out_cap] if c.validity is not None
                         else jnp.ones((out_cap,), jnp.bool_))
                ev = None
                if has_ev[ci]:
                    ev = (c.evalid[:out_cap, :] if c.evalid is not None
                          else jnp.ones((out_cap, d.shape[1]),
                                        jnp.bool_))
                    if ev.shape[1] < d.shape[1]:
                        ev = jnp.pad(
                            ev, ((0, 0), (0, d.shape[1] - ev.shape[1])),
                            constant_values=True)
                cols.append(DeviceColumn(c.dtype, d, v, ln, ev))
            return DeviceBatch(schema, tuple(cols), m.sel[:out_cap],
                               compacted=True)
        return run

    norm = []
    all_full = True
    for b, n in zip(batches, counts):
        out_cap = live_bucket(n, b.capacity)
        needs = out_cap < b.capacity or any(
            (is_str[ci] and b.columns[ci].data.shape[1] < widths[ci])
            or (has_val[ci] and b.columns[ci].validity is None)
            or (has_ev[ci] and b.columns[ci].evalid is None)
            for ci in range(nfields))
        if needs:
            fn = cached_kernel(
                ("concat_norm", out_cap, widths, has_val, has_ev, sfp),
                lambda oc=out_cap: build_norm(oc))
            b = fn(b)
        all_full = all_full and n == b.capacity
        norm.append(b)

    cols = []
    for ci, f in enumerate(schema.fields):
        data = jnp.concatenate([nb.columns[ci].data for nb in norm], 0)
        validity = (jnp.concatenate(
            [nb.columns[ci].validity for nb in norm]) if has_val[ci]
            else None)
        lengths = (jnp.concatenate(
            [nb.columns[ci].lengths for nb in norm])
            if norm[0].columns[ci].lengths is not None else None)
        evalid = (jnp.concatenate(
            [nb.columns[ci].evalid for nb in norm], 0) if has_ev[ci]
            else None)
        cols.append(DeviceColumn(f.dtype, data, validity, lengths,
                                 evalid))
    sel = jnp.concatenate([nb.sel for nb in norm])
    cat = DeviceBatch(schema, tuple(cols), sel, compacted=all_full)
    cat_bucket = round_up_pow2(cat.capacity)
    if cat_bucket > cat.capacity:
        from spark_rapids_tpu.columnar.column import pad_batch
        padded = pad_batch(cat, cat_bucket)
        cat = DeviceBatch(schema, padded.columns, padded.sel,
                          compacted=all_full)
    if not all_full:
        cat = compact(cat)
    if out_bucket < cat.capacity:
        fn = cached_kernel(
            ("concat_trim", out_bucket, sfp),
            lambda: (lambda m: DeviceBatch(
                schema,
                tuple(DeviceColumn(
                    c.dtype, c.data[:out_bucket],
                    None if c.validity is None else
                    c.validity[:out_bucket],
                    None if c.lengths is None else
                    c.lengths[:out_bucket],
                    None if c.evalid is None else
                    c.evalid[:out_bucket, :])
                    for c in m.columns),
                m.sel[:out_bucket], compacted=True)))
        cat = fn(cat)
    return cat


def _colocate(batches: List[DeviceBatch]) -> List[DeviceBatch]:
    """Batches on ONE device, as an eager concat needs them.

    Partition p of an ICI exchange lives on mesh device p, so an
    operator that merges partitions (TopN winners, a final merge) sees
    batches committed to different devices; the strays move to the
    first batch's device.  All on one device already (every single-chip
    plan): no transfer, one set compare per batch."""
    homes = [b.sel.devices() for b in batches]
    if all(h == homes[0] for h in homes):
        return batches
    target = min(homes[0], key=lambda d: d.id)
    return [b if h == {target} else jax.device_put(b, target)
            for b, h in zip(batches, homes)]


def concat_device_batches(schema: T.StructType,
                          batches: List[DeviceBatch],
                          counts: Optional[List[int]] = None,
                          bucket: Optional[int] = None,
                          min_width: int = 0,
                          force_validity: Optional[Sequence[bool]] = None
                          ) -> DeviceBatch:
    """Concatenate compacted device batches into one bucketed batch.

    ``counts`` (live rows per batch) may be passed by callers that track
    them host-side — skips one device sync per input batch.  ``bucket``
    forces the output capacity (≥ total rows); ``min_width`` forces a
    minimum string-matrix width and ``force_validity`` a per-column
    validity presence (shard-uniformity: every shard of one global
    sharded array must carry identical leaf structure).
    """
    if not batches:
        from spark_rapids_tpu.columnar.column import empty_batch
        return empty_batch(schema)
    if (len(batches) == 1 and bucket is None and min_width == 0
            and force_validity is None):
        return batches[0]
    batches = _colocate(batches)
    if (bucket is None and min_width == 0
            and force_validity is None and len(batches) > 2
            and all(b.compacted for b in batches)):
        # many-batch gathers (partial-agg merges, join/sort gathers) pay
        # O(batches) host syncs + O(batches × leaves) eager slices on
        # the sequential path below (no chip measurement of that cost
        # yet).  The fast path pulls every count in ONE overlapped
        # round trip (reusing caller-tracked counts when given) and
        # keeps per-batch work to one cached kernel.
        return _concat_compacted_fast(schema, batches, counts)
    if counts is None:
        counts = _overlapped_live_counts(batches)
    total = sum(counts)
    if bucket is None:
        bucket = round_up_pow2(max(total, 1))
    assert bucket >= total, (bucket, total)
    warn_big_bucket("concat", bucket)
    cols = []
    for ci, f in enumerate(schema.fields):
        parts_data = []
        parts_val = []
        parts_len = []
        parts_ev = []
        any_val = (force_validity[ci] if force_validity is not None
                   else any(b.columns[ci].validity is not None
                            for b in batches))
        any_ev = any(b.columns[ci].evalid is not None for b in batches)
        is_str = batches[0].columns[ci].is_string
        # min_width may be per-column (sequence) — a global min would pad
        # every string column to the schema's widest one
        mw = (min_width[ci] if isinstance(min_width, (list, tuple))
              else min_width)
        width = max(max(b.columns[ci].data.shape[1] for b in batches),
                    mw) if is_str else 0
        for b, n in zip(batches, counts):
            c = b.columns[ci]
            if is_str:
                d = c.data[:n]
                if d.shape[1] < width:
                    d = jnp.pad(d, ((0, 0), (0, width - d.shape[1])))
                parts_data.append(d)
                parts_len.append(c.lengths[:n])
                if any_ev:
                    ev = (c.evalid[:n] if c.evalid is not None
                          else jnp.ones((n, c.data.shape[1]), jnp.bool_))
                    if ev.shape[1] < width:
                        ev = jnp.pad(ev, ((0, 0), (0, width - ev.shape[1])),
                                     constant_values=True)
                    parts_ev.append(ev)
            else:
                parts_data.append(c.data[:n])
            if any_val:
                v = (c.validity[:n] if c.validity is not None
                     else jnp.ones((n,), jnp.bool_))
                parts_val.append(v)
        data = jnp.concatenate(parts_data, axis=0)
        pad = bucket - total
        if pad:
            data = (jnp.pad(data, ((0, pad), (0, 0))) if is_str
                    else jnp.pad(data, (0, pad)))
        validity = None
        if any_val:
            validity = jnp.pad(jnp.concatenate(parts_val), (0, pad))
        lengths = None
        if is_str:
            lengths = jnp.pad(jnp.concatenate(parts_len), (0, pad))
        evalid = None
        if any_ev:
            evalid = jnp.pad(jnp.concatenate(parts_ev, axis=0),
                             ((0, pad), (0, 0)), constant_values=True)
        cols.append(type(batches[0].columns[ci])(f.dtype, data, validity,
                                                 lengths, evalid))
    sel = jnp.arange(bucket, dtype=jnp.int32) < total
    return DeviceBatch(schema, tuple(cols), sel)
