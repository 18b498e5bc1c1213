"""Sort execs (device lexicographic sort; out-of-core range sort).

[REF: sql-plugin/../GpuSortExec.scala :: GpuSortExec,
 GpuOutOfCoreSortIterator, SortUtils.scala] — the reference calls cuDF's
multi-key radix/merge sort, spilling sorted runs and merging for
oversized partitions; here the device sort is one stable ``lax.sort``
over the orderable key limbs from ops/ordering.py (direction and null
placement baked into the encoding).

Out-of-core re-design (TPU-idiom — a k-way streaming merge is
scatter/branch hostile): **sample-based range partitioning**, the same
scheme Spark uses for total-order range exchanges:

  1. sample encoded key limbs from every input batch (device gather,
     host quantile pick → R-1 boundary rows),
  2. each input batch gets a range id per row (vectorized lexicographic
     binary search against the boundaries), is sliced per range, and the
     slices register with the HBM arbiter as spillables,
  3. ranges are restored one at a time, concatenated and sorted — the
     output streams as R ordered batches, peak HBM ≈ one range.

Engaged when the arbiter cannot reserve the single-batch working set
(RetryOOM), exactly like the aggregate's split-retry."""

from __future__ import annotations

from typing import Iterator, List, Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import host as H
from spark_rapids_tpu.columnar.column import DeviceBatch, compact
from spark_rapids_tpu.exec.base import CpuExec, TpuExec
from spark_rapids_tpu.exec.basic import concat_device_batches
from spark_rapids_tpu.ops import ordering as ORD
from spark_rapids_tpu.plan.logical import SortOrder
from spark_rapids_tpu.runtime import trace


class CpuSortExec(CpuExec):
    """Numpy-oracle global sort (gathers all partitions)."""

    def __init__(self, orders: Sequence[SortOrder], child: CpuExec):
        super().__init__(child.schema, child)
        self.orders = list(orders)

    def node_string(self):
        return f"Sort [{', '.join(str(o.expr) for o in self.orders)}]"

    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        child = self.children[0]
        batches = [b for p in range(child.num_partitions())
                   for b in child.execute(p)]
        if not batches:
            return
        merged = _concat_host(self.schema, batches)
        limbs: List[np.ndarray] = []
        for o in self.orders:
            c = o.expr.eval_cpu(merged)
            limbs.extend(ORD.np_order_keys(
                c.data, c.validity, c.dtype, o.ascending, o.nulls_first))
        n = merged.num_rows
        limbs.append(np.arange(n, dtype=np.int64).view(np.uint64))  # stable
        perm = np.lexsort(list(reversed(limbs)))
        cols = [H.HostCol(c.dtype, c.data[perm],
                          None if c.validity is None else c.validity[perm])
                for c in merged.columns]
        yield H.HostBatch(self.schema, cols)


def _concat_host(schema, batches: List[H.HostBatch]) -> H.HostBatch:
    if len(batches) == 1:
        return batches[0]
    cols = []
    for i, f in enumerate(schema.fields):
        any_val = any(b.columns[i].validity is not None for b in batches)
        data = np.concatenate([b.columns[i].data for b in batches])
        validity = None
        if any_val:
            validity = np.concatenate([
                b.columns[i].validity if b.columns[i].validity is not None
                else np.ones(len(b.columns[i].data), bool)
                for b in batches])
        cols.append(H.HostCol(f.dtype, data, validity))
    return H.HostBatch(schema, cols)


class TpuSortExec(TpuExec):
    """[REF: GpuSortExec + GpuOutOfCoreSortIterator] — single lax.sort
    over encoded key limbs; range-partitioned out-of-core path when the
    whole partition won't fit the budget (see module docstring)."""

    def __init__(self, orders: Sequence[SortOrder], child: TpuExec,
                 partitioned: bool = False):
        super().__init__(child.schema, child)
        self.orders = list(orders)
        # downstream of a RANGE exchange: each partition sorts locally
        # and ascending partition order IS the total order
        self.partitioned = partitioned

    def node_string(self):
        part = " partitioned" if self.partitioned else ""
        return (f"TpuSort{part} "
                f"[{', '.join(str(o.expr) for o in self.orders)}]")

    def num_partitions(self) -> int:
        if self.partitioned:
            return self.children[0].num_partitions()
        return 1

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.runtime.memory import RetryOOM, get_manager
        child = self.children[0]
        parts = ([partition] if self.partitioned
                 else range(child.num_partitions()))
        batches = [compact(b) for p in parts
                   for b in child.execute(p)]
        if not batches:
            return
        mgr = get_manager()
        total = sum(b.nbytes() for b in batches)
        try:
            # in-core: input + sorted copy live together
            with mgr.transient(2 * total):
                with self.timer():
                    merged = concat_device_batches(self.schema, batches)
                    out = sort_batch(merged, self.orders)
                self.metric("numOutputBatches").add(1)
                yield out
                return
        except RetryOOM:
            self.metric("outOfCoreSorts").add(1)
        yield from self._out_of_core(batches, total, mgr)

    def _out_of_core(self, batches: List[DeviceBatch], total: int, mgr
                     ) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.parallel.shuffle import split_to_spillables
        orders = self.orders
        # ranges sized so one range (~2x working set) fits the budget
        per_range = max(mgr.budget // 4, 1)
        nranges = max(2, min(64, int(np.ceil(total / per_range))))
        bounds = _sample_boundaries(batches, orders, nranges)
        with self.timer():
            # drains ``batches`` in place so the originals free even
            # though execute()'s frame still references the list
            # bounds are data-dependent: they ride as a traced kernel
            # argument (aux), never baked into the cached executable
            from spark_rapids_tpu.runtime.kernel_cache import fingerprint
            slices = split_to_spillables(
                batches, lambda b, aux: _range_ids(b, orders, aux),
                nranges, mgr,
                key=("rangesplit", fingerprint(list(orders))),
                aux=bounds)
        for r in range(nranges):
            if not slices[r]:
                continue
            range_bytes = sum(sp.nbytes for sp in slices[r])
            # reserving the range's working set pressures OTHER ranges'
            # slices out to host — the actual spill trigger.  Clamped to
            # the budget: pow-2 slice padding can push one range's
            # working set past a tiny budget, and full-pool pressure is
            # the most a reservation can achieve anyway.
            with mgr.transient(min(2 * range_bytes, mgr.budget)):
                with self.timer():
                    parts = [sp.get() for sp in slices[r]]
                    merged = concat_device_batches(self.schema, parts)
                    out = sort_batch(merged, orders)
                    for sp in slices[r]:
                        sp.close()
            self.metric("numOutputBatches").add(1)
            yield out


def _encode_key_limbs(batch: DeviceBatch, orders: Sequence[SortOrder]
                      ) -> List[jnp.ndarray]:
    """Fused orderable limbs of the sort keys (dead rows NOT flagged —
    callers mask separately)."""
    parts = []
    for o in orders:
        c = o.expr.eval_tpu(batch)
        parts.extend(ORD.column_order_parts(c, o.ascending, o.nulls_first))
    return ORD.fuse_parts(parts)


def pick_quantile_boundaries(cols: List[np.ndarray], nranges: int
                             ) -> List[np.ndarray]:
    """Host-side quantile pick over sampled key limbs → per-limb
    boundary arrays uint64[nranges-1].  THE shared boundary math of the
    out-of-core sort and the distributed range exchange — one
    implementation so skew handling can never drift between them."""
    n = len(cols[0]) if cols else 0
    if n == 0:
        return [np.zeros(max(nranges - 1, 0), np.uint64) for _ in cols]
    order = np.lexsort(list(reversed(cols)))
    picks = [order[min(n - 1, (i + 1) * n // nranges)]
             for i in range(nranges - 1)]
    return [c[picks] for c in cols]


def _sample_boundaries(batches: List[DeviceBatch],
                       orders: Sequence[SortOrder], nranges: int
                       ) -> List[np.ndarray]:
    """Sample live rows' key limbs, host-sort, pick range quantiles.
    Returns per-limb boundary arrays uint64[nranges-1]."""
    oversample = 8
    samples = []  # [limbs][chunks]
    for b in batches:
        limbs = _encode_key_limbs(b, orders)
        live_idx = jnp.nonzero(b.sel, size=min(b.capacity, 1024),
                               fill_value=0)[0]
        take = max(1, (nranges * oversample) // max(len(batches), 1))
        idx = live_idx[:take]
        samples.append([np.asarray(jnp.take(l, idx)) for l in limbs])
    nlimbs = len(samples[0])
    cols = [np.concatenate([s[i] for s in samples]) for i in
            range(nlimbs)]
    return pick_quantile_boundaries(cols, nranges)


def _range_ids(batch: DeviceBatch, orders: Sequence[SortOrder],
               bounds: List[np.ndarray]) -> jnp.ndarray:
    """Range id per row: lexicographic searchsorted against boundaries
    (delegates to the exchange's pid fn — one range-id implementation)."""
    from spark_rapids_tpu.parallel.shuffle import range_pid_fn
    return range_pid_fn(orders)(batch, bounds)


def sort_batch(batch: DeviceBatch, orders: Sequence[SortOrder],
               node=None) -> DeviceBatch:
    """Stable sort of live rows by the given orders; dead rows to the end.

    One cached jitted kernel per (orders, schema, backend) — compiles
    once per bucket and stays hot across queries.  The kernel plane's
    segmented sort (bucket-local rank merge) rides the non-jnp
    backends; it is exact, so the backend choice is static — no
    run-time fallback rung."""
    from spark_rapids_tpu import kernels as KN
    from spark_rapids_tpu.runtime.kernel_cache import (
        cached_kernel, fingerprint)
    be = KN.resolve("sort", supports_pallas=False)
    key = ("sort", fingerprint(list(orders)), fingerprint(batch.schema))
    fn = cached_kernel(
        key if be == "jnp" else key + (be,),
        lambda: (lambda b: _sort_batch_impl(b, orders, backend=be)))
    out = fn(batch)
    KN.count("sort", be, node)
    # the rung that ran, in the books of the query in flight
    trace.count(f"sortBackend.{be}", 1)
    return out


def _sort_batch_impl(batch: DeviceBatch, orders: Sequence[SortOrder],
                     backend: str = "jnp") -> DeviceBatch:
    from spark_rapids_tpu.kernels import segmented_sort as KNS
    parts = [ORD._flag_part(~batch.sel)]
    for o in orders:
        c = o.expr.eval_tpu(batch)
        parts.extend(ORD.column_order_parts(c, o.ascending, o.nulls_first))
    _, perm = KNS.sort_perm(ORD.fuse_parts(parts), backend=backend)
    cols = tuple(c.gather(perm) for c in batch.columns)
    sel = jnp.take(batch.sel, perm)
    return DeviceBatch(batch.schema, cols, sel)


def _tag_sort(meta):
    meta.tag_expressions([o.expr for o in meta.cpu.orders])


def _convert_sort(cpu, ch, conf):
    from spark_rapids_tpu.exec.distributed import (
        TpuIciRangeExchangeExec, ici_active)
    if ici_active(conf):
        # distributed total order: range exchange (sampled boundaries)
        # + per-partition local sort; ascending partition index IS the
        # global order [REF: GpuRangePartitioning.scala]
        ex = TpuIciRangeExchangeExec(ch[0], cpu.orders)
        return TpuSortExec(cpu.orders, ex, partitioned=True)
    return TpuSortExec(cpu.orders, ch[0])
