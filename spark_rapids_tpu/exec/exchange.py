"""Shuffle exchange execs (repartitioning).

[REF: sql-plugin/../GpuShuffleExchangeExecBase.scala,
 GpuHashPartitioning.scala] — the reference partitions on device with
cuDF murmur3 ``hash_partition`` + ``contiguous_split`` and moves blocks
via the shuffle manager.  Three transports, picked by
``spark.rapids.shuffle.mode``:

* CACHE_ONLY — this module's in-process device exchange: partition ids
  computed on device with the bit-exact Spark murmur3 (ops/hashing.py),
  each output partition the same device batch viewed through a different
  ``sel`` mask (zero-copy, single process).
* MULTITHREADED — host-path serialization through shuffle files
  (shuffle/exchange.py + the native tudo serializer), the
  works-everywhere default analog.
* ICI — the SPMD ``lax.all_to_all`` collective over the device mesh
  (exec/distributed.py + parallel/shuffle.py).  Within ICI,
  ``spark.rapids.tpu.exchange.mode`` picks the transport: ``compiled``
  / ``auto`` run the device-resident prepare/boundary programs;
  ``host`` pins every exchange to the host-shuffle transport (the
  degrade target) while keeping the rest of the plan single-device.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import host as H
from spark_rapids_tpu.columnar.column import DeviceBatch
from spark_rapids_tpu.exec.base import CpuExec, TpuExec
from spark_rapids_tpu.ops import hashing as HH
from spark_rapids_tpu.ops.expressions import Expression
from spark_rapids_tpu.runtime import stats


def _subplan_probe(exec_node):
    """(store, subtree ResultKey) when the result-cache plane's subplan
    mode applies to this exchange, else (None, None).  Keyed by the
    detailed subtree fingerprint ⊕ the configured session's conf
    fingerprint ⊕ the physical leaves' input fingerprints, so
    partially-overlapping queries reuse a shared stage."""
    from spark_rapids_tpu import cache as cache_mod
    store = cache_mod.subplan_store()
    if store is None:
        return None, None
    try:
        return store, cache_mod.subplan_key(exec_node,
                                            store.subplan_conf_fp)
    except Exception:
        return None, None


def _dehydrate_pairs(pairs):
    """(DeviceBatch, pid) pairs -> host-resident payload.  Rows are
    compacted on pull, so the stored pid array is the sel-compacted
    prefix — alignment with the rehydrated batch's live rows."""
    from spark_rapids_tpu.columnar.column import device_to_host
    payload = []
    nbytes = 0
    for b, pid in pairs:
        tbl = device_to_host(b)
        pids = np.asarray(pid)[np.asarray(b.sel)].astype(np.int32)
        payload.append((tbl, pids))
        nbytes += tbl.nbytes + pids.nbytes
    return payload, nbytes


def _rehydrate_pairs(payload):
    """Host payload -> (DeviceBatch, pid) pairs shaped exactly like a
    fresh materialization: batch capacity is the padded power-of-two,
    pid padded with -1 (dead rows never match a partition)."""
    from spark_rapids_tpu.columnar.column import host_to_device
    pairs = []
    for tbl, pids in payload:
        batch = host_to_device(tbl)
        pid = np.full(batch.capacity, -1, np.int32)
        pid[:len(pids)] = pids
        pairs.append((batch, jnp.asarray(pid)))
    return pairs


class CpuShuffleExchangeExec(CpuExec):
    def __init__(self, child: CpuExec, num_partitions: int,
                 keys: Optional[Sequence[Expression]] = None):
        super().__init__(child.schema, child)
        self.nparts = num_partitions
        self.keys = list(keys) if keys else None
        self._materialized: Optional[List[List[H.HostBatch]]] = None
        self._mat_lock = threading.Lock()

    def node_string(self):
        kind = "hash" if self.keys else "roundrobin"
        return f"ShuffleExchange [{kind} {self.nparts}]"

    def num_partitions(self) -> int:
        return self.nparts

    def _materialize(self):
        with self._mat_lock:
            return self._materialize_locked()

    def _materialize_locked(self):
        if self._materialized is not None:
            return self._materialized
        store, skey = _subplan_probe(self)
        if store is not None and skey is not None:
            ent = store.lookup(skey.key)
            if ent is not None:
                self._materialized = [
                    [H.from_arrow_table(t) for t in part]
                    for part in ent.value]
                return self._materialized
        import time as _time
        t0 = _time.perf_counter()
        child = self.children[0]
        out: List[List[H.HostBatch]] = [[] for _ in range(self.nparts)]
        row_counter = 0
        for p in range(child.num_partitions()):
            for b in child.execute(p):
                n = b.num_rows
                if self.keys:
                    h = np.full(n, 42, np.uint32)
                    valid_all = np.ones(n, bool)
                    for e in self.keys:
                        c = e.eval_cpu(b)
                        data = c.data
                        if isinstance(c.dtype, (T.StringType, T.BinaryType)):
                            mat, lengths = _host_strings_to_mat(data)
                            col_ = (mat, lengths)
                        else:
                            col_ = (data, None)
                        valid = (c.validity if c.validity is not None
                                 else valid_all)
                        h = HH.hash_column(col_, c.dtype, h, valid, np)
                    pid = HH.partition_ids_from_hash(
                        HH._np_int32_from_u32(h), self.nparts, np)
                else:
                    pid = (np.arange(n) + row_counter) % self.nparts
                    row_counter += n
                for p_out in range(self.nparts):
                    mask = pid == p_out
                    if not mask.any():
                        continue
                    cols = [H.HostCol(
                        c.dtype, c.data[mask],
                        None if c.validity is None else c.validity[mask])
                        for c in b.columns]
                    out[p_out].append(H.HostBatch(b.schema, cols))
        self._materialized = out
        st = stats.current()
        if st is not None:
            st.record_partitions(
                self, [sum(b.num_rows for b in bl) for bl in out],
                unit="rows")
        if store is not None and skey is not None:
            store.note_miss(sub=True)
            payload = [[H.to_arrow_table(b) for b in part]
                       for part in out]
            nbytes = sum(t.nbytes for part in payload for t in part)
            store.put(skey, payload, nbytes,
                      _time.perf_counter() - t0, kind="subplan")
        return out

    def execute(self, partition: int) -> Iterator[H.HostBatch]:
        for b in self._materialize()[partition]:
            yield b


_host_strings_to_mat = HH.host_strings_to_matrix


class TpuShuffleExchangeExec(TpuExec):
    """Zero-copy device repartition: sel-mask views per partition.

    [REF: GpuShuffleExchangeExecBase — device murmur3 partitioning]
    """

    def __init__(self, child: TpuExec, num_partitions: int,
                 keys: Optional[Sequence[Expression]] = None):
        super().__init__(child.schema, child)
        self.nparts = num_partitions
        self.keys = list(keys) if keys else None
        self._materialized = None
        self._batch_counts = None
        self._mat_lock = threading.Lock()

    def node_string(self):
        kind = "hash" if self.keys else "roundrobin"
        return f"TpuShuffleExchange [{kind} {self.nparts}]"

    def num_partitions(self) -> int:
        return self.nparts

    def _pids(self, b: DeviceBatch, row_base: int) -> jnp.ndarray:
        if self.keys:
            from spark_rapids_tpu.runtime.kernel_cache import (
                cached_kernel, fingerprint)
            keys = self.keys

            def build():
                def run(batch):
                    n = batch.capacity
                    h = jnp.full((n,), 42, jnp.uint32)
                    for e in keys:
                        c = e.eval_tpu(batch)
                        valid = c.valid_mask()
                        h = HH.hash_column((c.data, c.lengths), c.dtype, h,
                                           valid, jnp)
                    h_i32 = HH.jax_bitcast(h, jnp.int32)
                    return HH.partition_ids_from_hash(h_i32, self.nparts,
                                                      jnp)
                return run

            fn = cached_kernel(
                ("partition_ids", self.nparts, fingerprint(keys),
                 fingerprint(b.schema)), build)
            return fn(b)
        live_prefix = jnp.cumsum(b.sel.astype(jnp.int32)) - 1
        return (live_prefix + row_base) % self.nparts

    def _materialize(self):
        with self._mat_lock:
            return self._materialize_locked()

    def _materialize_locked(self):
        if self._materialized is not None:
            return self._materialized
        store, skey = _subplan_probe(self)
        if store is not None and skey is not None:
            ent = store.lookup(skey.key)
            if ent is not None:
                self._materialized = _rehydrate_pairs(ent.value)
                return self._materialized
        import time as _time
        t0 = _time.perf_counter()
        child = self.children[0]
        pairs = []  # (batch, pid array)
        row_base = 0
        with self.timer("partitionTime"):
            for p in range(child.num_partitions()):
                for b in child.execute(p):
                    pairs.append((b, self._pids(b, row_base)))
                    if not self.keys:
                        # only round-robin needs the running row count
                        # (a device sync); hash partitioning does not
                        row_base += int(jnp.sum(b.sel.astype(jnp.int32)))
        self._materialized = pairs
        if store is not None and skey is not None:
            store.note_miss(sub=True)
            payload, nbytes = _dehydrate_pairs(pairs)
            store.put(skey, payload, nbytes,
                      _time.perf_counter() - t0, kind="subplan")
        return pairs

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        for b, pid in self._materialize():
            out = b.with_sel(b.sel & (pid == partition))
            self.metric("numOutputBatches").add(1)
            yield out

    # -- AQE stats + shaped reads [REF: GpuAQEShuffleReadExec] -----------
    def aqe_partition_stats(self):
        return "rows", self.partition_row_counts()

    def partition_row_counts(self) -> np.ndarray:
        """Live rows per output partition (one device bincount per
        input batch; the map-stage statistics AQE plans from).  Caches
        the per-batch counts so skew reads can compute their rank bases
        host-side without any further device syncs."""
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
        if getattr(self, "_batch_counts", None) is not None:
            return self._batch_counts.sum(axis=0)
        nparts = self.nparts

        def build():
            def run(sel, pid):
                return jnp.bincount(jnp.where(sel, pid, nparts),
                                    length=nparts + 1)[:nparts]
            return run

        fn = cached_kernel(("pid_counts", nparts), build)
        per_batch = [np.asarray(fn(b.sel, pid))
                     for b, pid in self._materialize()]
        self._batch_counts = (np.stack(per_batch) if per_batch
                              else np.zeros((0, nparts), np.int64))
        counts = self._batch_counts.sum(axis=0)
        st = stats.current()
        if st is not None:
            # the map-output statistics AQE plans from double as the
            # stats plane's per-partition record for this exchange
            st.record_partitions(self, counts, unit="rows")
        return counts

    def execute_pid_range(self, lo: int, hi: int
                          ) -> Iterator[DeviceBatch]:
        """Coalesced read: partitions [lo, hi) as one output."""
        for b, pid in self._materialize():
            yield b.with_sel(b.sel & (pid >= lo) & (pid < hi))

    def execute_split(self, p: int, j: int, k: int
                      ) -> Iterator[DeviceBatch]:
        """Skew read: slice j of k of partition p (by in-partition row
        rank, stable across batches).  Rank bases come from the cached
        per-batch counts — no device syncs in the read path."""
        self.partition_row_counts()  # ensures _batch_counts
        bases = np.concatenate(
            [[0], np.cumsum(self._batch_counts[:, p])[:-1]]) \
            if len(self._batch_counts) else []
        for (b, pid), base in zip(self._materialize(), bases):
            mine = b.sel & (pid == p)
            rank = jnp.int32(int(base)) + \
                jnp.cumsum(mine.astype(jnp.int32)) - 1
            # k-way interleave by rank: slice j takes ranks ≡ j (mod k)
            yield b.with_sel(mine & (rank % k == j))


def _tag_exchange(meta):
    if meta.cpu.keys:
        meta.tag_expressions(meta.cpu.keys)


def _convert_exchange(cpu, ch, conf):
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.exec.distributed import (
        TpuIciShuffleExchangeExec, ici_active)
    if ici_active(conf) and cpu.keys:
        import jax
        if cpu.nparts == jax.device_count():
            return TpuIciShuffleExchangeExec(ch[0], cpu.keys)
    host_pinned = (conf.shuffle_mode == "ICI"
                   and conf.exchange_mode == "host")
    if conf.shuffle_mode == "MULTITHREADED" or host_pinned:
        # exchange.mode=host under ICI: same plan shape, but the stage
        # boundary runs the host-shuffle transport — the conf-selected
        # fallback and the collective domain's degrade target
        from spark_rapids_tpu.shuffle.exchange import (
            TpuHostShuffleExchangeExec)
        exchange = TpuHostShuffleExchangeExec(
            ch[0], cpu.nparts, cpu.keys,
            nthreads=conf.get(C.SHUFFLE_THREADS),
            min_bucket=conf.min_bucket_rows)
    else:
        # CACHE_ONLY: in-process device-resident exchange (sel-mask views)
        exchange = TpuShuffleExchangeExec(ch[0], cpu.nparts, cpu.keys)
    if conf.get(C.ADAPTIVE_ENABLED):
        from spark_rapids_tpu import adaptive as AD
        from spark_rapids_tpu.exec.aqe import TpuAQEShuffleReadExec
        from spark_rapids_tpu.plan.overrides import _estimated_row_bytes
        pol = AD.policy_from_conf(conf)
        return TpuAQEShuffleReadExec(
            exchange, conf.get(C.ADVISORY_PARTITION_SIZE),
            _estimated_row_bytes(cpu.schema),
            allow_split=cpu.keys is None,
            retarget=pol if pol.wants_retarget else None)
    return exchange
