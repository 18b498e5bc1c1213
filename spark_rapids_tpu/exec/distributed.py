"""ICI shuffle exchange exec: the distributed stage boundary.

[REF: GpuShuffleExchangeExecBase.scala + RapidsShuffleManager (UCX mode)]
— rethought for TPU (SURVEY §5.8): instead of reduce tasks pulling blocks
point-to-point, the exchange runs ONE SPMD collective program over the
device mesh (parallel/shuffle.py) and downstream operators then consume
their partition's received rows locally, exactly like Spark reduce tasks
after a shuffle fetch.  Stage shape on an N-device mesh (the COMPILED exchange, the
single-process default — ``spark.rapids.tpu.exchange.mode``):

  upstream partitions → gather+compact → row-shard over mesh
    → {murmur3 pid → rank → gather index table + counts}   (prepare)
    → {slice → clip-gather → all_to_all → receive mask}    (boundary)
    → N output partitions, each device-local, capacity re-bucketed

The *prepare* program runs once per accumulated stage input and emits
both the routing table and the per-partition counts in one launch; the
*boundary* program is the only launch on the stage seam — its input
buffers are donated, and the host feeds it the transposed receive
counts so no second collective runs.  Multi-executor mode keeps the
two-phase count/shuffle agreement protocol (its rendezvous epochs are
what make cross-process retry bit-identical); ``mode=host`` routes the
exchange through the host-shuffle transport at plan time, which is
also the degrade target for the ``collective`` failure domain.

Activated by ``spark.rapids.shuffle.mode=ICI`` when the mesh has more
than one device; the planner then splits aggregates into partial/final
around this exchange and co-partitions join inputs through it.
"""

from __future__ import annotations

import time
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar.column import (
    DeviceBatch, compact, round_up_pow2)
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.ops.expressions import Expression
from spark_rapids_tpu.parallel import shuffle as SH
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.runtime import stats as ST
from spark_rapids_tpu.runtime import telemetry as TM

_TM_COLLECTIVE_S = TM.REGISTRY.counter(
    "tpuq_ici_collective_seconds_total",
    "ICI all-to-all collective dispatch seconds")
_TM_ICI_BYTES = TM.REGISTRY.counter(
    "tpuq_ici_exchange_bytes_total",
    "bytes moved through ICI shuffle exchanges (global batch size)")
_TM_ICI_EX_COLL_S = TM.REGISTRY.counter(
    "tpuq_ici_exchange_collective_seconds_total",
    "compiled-exchange boundary-program dispatch seconds")


def owned_partitions(plan) -> List[int]:
    """Partitions an executor process serves of ``plan``: descend the
    partition-preserving spine to the nearest ICI exchange and take its
    local partitions; plans without an exchange serve every partition
    (executor-sliced scans make non-owned ones empty)."""
    node = plan
    while True:
        if isinstance(node, TpuIciShuffleExchangeExec):
            return node.local_partitions()
        if (node.children and node.num_partitions()
                == node.children[0].num_partitions()):
            node = node.children[0]
            continue
        return list(range(plan.num_partitions()))


def _accumulate_shards(child: TpuExec, devices, d: int,
                       partitions=None):
    """Stream child partitions onto mesh devices (round-robin) WITHOUT
    ever materializing the whole table on one device.

    Each upstream batch is compacted, sliced to its pow-2 row bucket and
    ``device_put`` to its target device immediately — the peak footprint
    on any one device is its own shard plus one in-flight batch (the r2
    global-gather concentrated everything on device 0 first; VERDICT r2
    missing #2).  Returns (per-device [(batch, rows)], per-device rows,
    per-column max string width, per-column validity presence).
    """
    import jax
    schema = child.schema
    nstr = len(schema.fields)
    parts: List[List[Tuple[DeviceBatch, int]]] = [[] for _ in range(d)]
    rows = [0] * d
    widths = [0] * nstr
    has_val = [False] * nstr
    if partitions is None:
        partitions = range(child.num_partitions())
    # round-robin by ENUMERATION index: owned partition ids can share a
    # factor with d (executor slicing hands each process p ≡ id mod
    # count), and `p % d` would then pile every batch on one device
    for i, p in enumerate(partitions):
        dev = i % d
        for b in child.execute(p):
            cb = compact(b)
            n = cb.num_rows_host()
            if n == 0:
                continue
            cap = round_up_pow2(max(n, 1), 8)
            if cap < cb.capacity:
                cb = SH.slice_batch(cb, 0, cap)
            for ci, c in enumerate(cb.columns):
                if c.is_string:
                    widths[ci] = max(widths[ci], int(c.data.shape[1]))
                if c.validity is not None:
                    has_val[ci] = True
            parts[dev].append((jax.device_put(cb, devices[dev]), n))
            rows[dev] += n
    return parts, rows, widths, has_val


def _batch_from_shards(mesh, schema: T.StructType,
                       shards: List[DeviceBatch],
                       local_b: int,
                       global_devices: int = 0) -> DeviceBatch:
    """Per-device shard batches (identical structure, committed to their
    mesh devices) → ONE globally-sharded DeviceBatch, zero data movement
    (``jax.make_array_from_single_device_arrays``).

    In multi-process mode ``shards`` holds only this process's LOCAL
    shards (jax matches them to the global sharding by their committed
    devices); ``global_devices`` then sizes the global shape."""
    import jax
    from spark_rapids_tpu.parallel.mesh import named_sharding
    sharding = named_sharding(mesh)
    d = global_devices or len(shards)
    flat = [jax.tree.flatten(s) for s in shards]
    treedef = flat[0][1]
    for _, td in flat[1:]:
        assert td == treedef, "shards must have identical structure"
    out_leaves = []
    for i in range(len(flat[0][0])):
        arrs = [flat[k][0][i] for k in range(len(shards))]
        shape = (d * local_b,) + arrs[0].shape[1:]
        out_leaves.append(jax.make_array_from_single_device_arrays(
            shape, sharding, arrs))
    return jax.tree.unflatten(treedef, out_leaves)


def _local_shard(batch: DeviceBatch, p: int) -> DeviceBatch:
    """Extract device p's local shard of a sharded batch as a
    single-device batch (stays resident on device p)."""
    import jax
    leaves, treedef = jax.tree.flatten(batch)
    per = int(leaves[0].addressable_shards[0].data.shape[0])
    lo = p * per
    out = []
    for leaf in leaves:
        shard = next((s for s in leaf.addressable_shards
                      if (s.index[0].start or 0) == lo), None)
        if shard is None:
            raise RuntimeError(
                f"partition {p} is not local to this process "
                "(multi-executor pump must only pull owned partitions)")
        out.append(shard.data)
    return jax.tree.unflatten(treedef, out)


class TpuIciShuffleExchangeExec(TpuExec):
    """Collective shuffle exchange over the ICI mesh.

    ``num_partitions() == mesh size``; ``execute(p)`` yields the rows
    that hashed to partition p, already on device p's shard.
    """

    def __init__(self, child: TpuExec, keys: Sequence[Expression],
                 mesh=None, canon_int64: Sequence[bool] = (),
                 min_bucket: int = 1024, donate: bool = True):
        super().__init__(child.schema, child)
        self.keys = list(keys)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.canon_int64 = tuple(canon_int64)
        self.min_bucket = min_bucket
        self.donate = donate
        self._result: Optional[DeviceBatch] = None
        # compiled path: per-partition received rows, known host-side
        # from prepare's counts — execute() then needs no device sync
        self._recv_counts: Optional[np.ndarray] = None
        self._empty = False
        # set when the collective degraded to the host-shuffle transport
        self._host_fallback = None
        import threading
        self._mat_lock = threading.Lock()
        # multi-executor mode: rendezvous-coordinated collective entry.
        # Stage ids are assigned at plan-conversion time — every process
        # plans the same query with the same deterministic planner, so
        # the Nth exchange here is the Nth exchange everywhere (the
        # analog of the driver-assigned shuffle id).
        from spark_rapids_tpu.parallel.executor import get_executor
        self._ctx = get_executor()
        self._stage = (self._ctx.next_stage_id()
                       if self._ctx is not None else None)

    def local_partitions(self) -> List[int]:
        """Partition ids this process can serve (all, single-process)."""
        if self._ctx is None:
            return list(range(self.nparts))
        return self._ctx.local_partition_ids(self.mesh)

    @property
    def nparts(self) -> int:
        return int(self.mesh.devices.size)

    def node_string(self):
        ks = ", ".join(str(k) for k in self.keys)
        return f"TpuIciShuffleExchange [hash({ks}) over {self.nparts}dev]"

    def num_partitions(self) -> int:
        return self.nparts

    def _materialize(self) -> Optional[DeviceBatch]:
        with self._mat_lock:
            return self._materialize_locked()

    def _materialize_locked(self) -> Optional[DeviceBatch]:
        if (self._result is not None or self._empty
                or self._host_fallback is not None):
            return self._result
        if self._ctx is not None:
            return self._materialize_multiproc()
        from spark_rapids_tpu.exec.basic import concat_device_batches
        from spark_rapids_tpu.runtime.memory import get_manager
        d = self.nparts
        devices = list(self.mesh.devices.flatten())
        schema = self.children[0].schema
        with self.timer("partitionTime"):
            parts, rows, widths, has_val = _accumulate_shards(
                self.children[0], devices, d)
        if sum(rows) == 0:
            self._empty = True
            return None
        # uniform per-device shard capacity (SPMD: one static shape)
        local_b = round_up_pow2(max(max(rows), 1), self.min_bucket)
        from spark_rapids_tpu.columnar.column import empty_batch
        from spark_rapids_tpu.plan.overrides import _estimated_row_bytes
        row_bytes = _estimated_row_bytes(
            schema, str_width=max(widths, default=0))
        shards: List[DeviceBatch] = []
        mgr = get_manager()
        # the arbiter budget models ONE device's HBM: account the
        # per-device working set, not the global table (the whole point
        # of the shard-resident exchange)
        with mgr.transient(2 * local_b * row_bytes):
            with self.timer("partitionTime"):
                for dev in range(d):
                    batch_list = [b for b, _ in parts[dev]]
                    counts = [n for _, n in parts[dev]]
                    if not batch_list:
                        import jax
                        batch_list = [jax.device_put(
                            empty_batch(schema, 8), devices[dev])]
                        counts = [0]
                    shard = concat_device_batches(
                        schema, batch_list, counts=counts, bucket=local_b,
                        min_width=widths, force_validity=has_val)
                    # freshly-created leaves (sel iota, synthesized
                    # validity) land on the default device — re-commit
                    # the whole shard (no-op for resident leaves)
                    import jax
                    shards.append(jax.device_put(shard, devices[dev]))
                sharded = _batch_from_shards(self.mesh, schema, shards,
                                             local_b)
            del parts, shards

            from spark_rapids_tpu.runtime.kernel_cache import (
                cached_kernel, fingerprint)
            base_key = self._base_key(schema)
            aux = self._aux_args(sharded)
            # the compiled exchange: ONE producer-side prepare launch
            # (routing table + counts together), then ONE boundary
            # launch on the stage seam — the all_to_all plus receive
            # masking, with the input batch donated to the wire
            with mgr.transient(4 * d * local_b):  # per-device idx table
                with self.timer("partitionTime"):
                    prep_fn = cached_kernel(
                        ("ici_prepare",) + base_key,
                        self._prepare_builder())
                    idx, counts = prep_fn(sharded, *aux)
                    counts_np = np.asarray(counts).reshape(d, d)
                    cap = SH.exchange_cap(counts_np.max(), local_b)
                st = ST.current()
                if st is not None:
                    # counts is per-source-device × per-partition:
                    # summing over sources gives global partition sizes
                    st.record_partitions(self, counts_np.sum(axis=0),
                                         unit="rows")
                # receive counts ride host→device: partition p's
                # liveness needs counts FROM every source — a transpose
                # on the host, not a second collective on the wire
                from spark_rapids_tpu.parallel.mesh import named_sharding
                crecv = jax.device_put(
                    np.ascontiguousarray(counts_np.T.astype(np.int32)),
                    named_sharding(self.mesh))
                # per-device collective working set: the [d*cap]
                # gathered leaves and the [d*cap] received block
                with mgr.transient(2 * d * cap * row_bytes):
                    nbytes = sharded.nbytes()  # before donation
                    t0 = time.perf_counter()
                    with self.timer("collectiveTime"):
                        boundary_fn = cached_kernel(
                            ("ici_boundary", cap, d, self.donate,
                             fingerprint(schema)),
                            self._boundary_builder(cap))
                        self._result = self._run_collective(
                            boundary_fn, sharded, (idx, crecv))
                    dt = time.perf_counter() - t0
                    _TM_COLLECTIVE_S.inc(dt)
                    _TM_ICI_EX_COLL_S.inc(dt)
                    _TM_ICI_BYTES.inc(nbytes)
            if self._result is not None:
                self._recv_counts = counts_np.sum(axis=0)
        return self._result

    # -- resilience: the ``collective`` failure domain ----------------------
    def _run_collective(self, shuffle_fn, sharded, aux):
        """Dispatch the all-to-all through the ``collective`` failure
        domain.  Single-process retry exhaustion degrades to the
        host-path shuffle transport over the same child (the works-
        everywhere fallback); multi-executor collectives fail together
        with a domain-tagged error — one process degrading alone would
        deadlock the others at the rendezvous."""
        from spark_rapids_tpu.runtime import resilience as R

        def attempt():
            R.INJECTOR.on("collective")
            return shuffle_fn(sharded, *aux)

        out = R.run_guarded("collective", attempt, op=self.node_string(),
                            degrade=self._host_degrade_fn())
        if self._host_fallback is not None:
            return None
        return out

    def _host_degrade_fn(self):
        """The degradation callable, or None when this exchange cannot
        degrade (multi-executor; RANGE overrides to None too — a hash
        host shuffle would break its total-order contract)."""
        if self._ctx is not None:
            return None

        def degrade():
            from spark_rapids_tpu.shuffle.exchange import (
                TpuHostShuffleExchangeExec)
            self._host_fallback = TpuHostShuffleExchangeExec(
                self.children[0], self.nparts, keys=self.keys,
                min_bucket=self.min_bucket)
            return None

        return degrade

    # -- pid-program hooks (overridden by the RANGE exchange) ---------------
    def _base_key(self, schema) -> tuple:
        from spark_rapids_tpu.runtime.kernel_cache import fingerprint
        return (self.nparts, self.canon_int64, fingerprint(self.keys),
                fingerprint(schema))

    def _aux_args(self, sharded) -> tuple:
        """Extra traced arguments for the count/shuffle programs."""
        return ()

    def _prepare_builder(self):
        """Compiled-path producer program (index table + counts)."""
        return lambda: SH.build_prepare_program(
            self.mesh, self.keys, self.nparts, self.canon_int64)

    def _boundary_builder(self, cap: int):
        """Compiled-path seam program — pid-agnostic, so the cache key
        above deliberately drops the partitioning fingerprint: hash and
        range exchanges with one schema share one boundary per cap."""
        return lambda: SH.build_boundary_program(
            self.mesh, self.nparts, cap, donate=self.donate)

    def _count_builder(self):
        """Legacy two-phase count program (multi-executor path only —
        its rendezvous epochs need per-shard counts a cross-process
        count program could not make addressable)."""
        return lambda: SH.build_count_program(
            self.mesh, self.keys, self.nparts, self.canon_int64)

    def _shuffle_builder(self, cap: int):
        return lambda: SH.build_shuffle_program(
            self.mesh, self.keys, self.nparts, cap, self.canon_int64)

    def _local_pid(self, batch, base_key):
        """Partition ids of a LOCAL shard (multiproc count phase)."""
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
        fn = cached_kernel(
            ("ici_mp_pid",) + base_key,
            lambda: SH.make_pid_fn(self.keys, self.nparts,
                                   self.canon_int64))
        return fn(batch)

    def _materialize_multiproc(self) -> Optional[DeviceBatch]:
        """Rendezvous-coordinated collective shuffle across executor
        processes [REF: RapidsShuffleInternalManagerBase; SURVEY §5.8].

        1. accumulate this process's upstream slice onto LOCAL devices;
        2. rendezvous ``:shape`` allgather — every process must build
           byte-identical XLA programs, so shard capacity, string widths
           and validity presence are agreed globally;
        3. assemble the globally-sharded batch from local shards;
        4. per-shard partition counts (plain local jit), rendezvous
           ``:counts`` allgather → the global all_to_all cap;
        5. ``:enter`` barrier, then every process calls the SAME jitted
           collective program.  Any rendezvous deadline failure raises
           in EVERY process (fail-together) — nobody blocks alone inside
           a collective that cannot complete.

        Steps 2-5 run inside ``run_stage_epochs``: a transient
        rendezvous fault aborts the epoch for every peer and the whole
        agreement re-runs at epoch+1 over the SAME accumulated inputs
        (bit-identical recovery); a confirmed-dead peer raises a
        peer-tagged ``TerminalDeviceError`` on every survivor instead.
        """
        import jax
        from spark_rapids_tpu.exec.basic import concat_device_batches
        from spark_rapids_tpu.columnar.column import empty_batch
        from spark_rapids_tpu.runtime.kernel_cache import (
            cached_kernel, fingerprint)
        from spark_rapids_tpu.runtime.memory import get_manager
        from spark_rapids_tpu.parallel.rendezvous import run_stage_epochs
        ctx = self._ctx
        timeout = ctx.timeout
        d = self.nparts
        all_devices = list(self.mesh.devices.flatten())
        local_ids = ctx.local_partition_ids(self.mesh)
        local_devices = [all_devices[i] for i in local_ids]
        schema = self.children[0].schema
        with self.timer("partitionTime"):
            # only the child partitions THIS process owns: a downstream
            # exchange's partitions live on local devices only, and
            # executor-sliced scans make the rest empty anyway
            parts, rows, widths0, has_val0 = _accumulate_shards(
                self.children[0], local_devices, len(local_devices),
                partitions=owned_partitions(self.children[0]))
        base_key = self._base_key(schema)
        # the payload carries the stage's structural fingerprint: stage
        # ids are plan-conversion-ordered, so if executors ever run
        # DIFFERENT queries (or the same queries in different order)
        # the mismatch must fail loudly, not cross-match allgathers
        fp = repr(base_key)
        payload = {"rows": max(rows) if rows else 0,
                   "total": sum(rows), "widths": widths0,
                   "has_val": has_val0, "fp": fp}
        mgr = get_manager()

        def attempt(epoch: int):
            # a retried epoch re-agrees EVERYTHING that rode the
            # rendezvous — a peer that restarted mid-stage has none of
            # it cached (range bounds included, or the processes would
            # derive different pid programs and desync)
            self._epoch = epoch
            self._bounds = None
            replies = ctx.client.allgather(self._stage + ":shape",
                                           payload, timeout, epoch=epoch)
            if any(r["fp"] != fp for r in replies):
                raise RuntimeError(
                    f"rendezvous stage {self._stage} mismatch across "
                    "executors (different queries or different order) — "
                    "every executor process must run the same queries "
                    "in the same order")
            if sum(r["total"] for r in replies) == 0:
                return None
            local_b = round_up_pow2(
                max(max(r["rows"] for r in replies), 1), self.min_bucket)
            widths = [max(ws) for ws in
                      zip(*[r["widths"] for r in replies])
                      ] or list(widths0)
            has_val = [any(hv) for hv in
                       zip(*[r["has_val"] for r in replies])
                       ] or list(has_val0)
            from spark_rapids_tpu.plan.overrides import (
                _estimated_row_bytes)
            row_bytes = _estimated_row_bytes(
                schema, str_width=max(widths, default=0))
            shards: List[DeviceBatch] = []
            # per-device working set, same accounting as the single-
            # process path: this process hosts len(local_devices) shards
            # of local_b rows each while building, then the [d*cap]
            # layout + received block per local device during the
            # collective
            with mgr.transient(
                    2 * len(local_devices) * local_b * row_bytes):
                with self.timer("partitionTime"):
                    for li, dev in enumerate(local_devices):
                        batch_list = [b for b, _ in parts[li]]
                        counts = [n for _, n in parts[li]]
                        if not batch_list:
                            batch_list = [jax.device_put(
                                empty_batch(schema, 8), dev)]
                            counts = [0]
                        shard = concat_device_batches(
                            schema, batch_list, counts=counts,
                            bucket=local_b, min_width=widths,
                            force_validity=has_val)
                        shards.append(jax.device_put(shard, dev))
                    sharded = _batch_from_shards(
                        self.mesh, schema, shards, local_b,
                        global_devices=d)
                del shards[:]
                aux = self._aux_args(sharded)
                with self.timer("partitionTime"):
                    # per-shard counts via a plain LOCAL jit: a
                    # cross-process count program's output shards would
                    # not be addressable
                    local_max = 0
                    local_counts = np.zeros(d, np.int64)
                    for li in range(len(local_devices)):
                        shard_b = _local_shard(sharded, local_ids[li])
                        cnt = np.asarray(SH.local_partition_counts(
                            shard_b, self._local_pid(shard_b, base_key),
                            d))
                        local_max = max(local_max, int(cnt.max()))
                        local_counts += cnt
                # the payload carries this process's full per-partition
                # contribution, not just the max: every process (the
                # coordinator included) merges the replies into the
                # CLUSTER-WIDE partition sizes, so skew is attributable
                # from any executor's profile record
                replies = ctx.client.allgather(
                    self._stage + ":counts",
                    {"max": local_max, "parts": local_counts.tolist()},
                    timeout, epoch=epoch)
                cap = round_up_pow2(
                    max(max(r["max"] for r in replies), 1), 8)
                st = ST.current()
                if st is not None:
                    st.record_partitions(
                        self,
                        ST.merge_partition_counts(
                            r["parts"] for r in replies),
                        unit="rows", executors=len(replies))
                with mgr.transient(2 * d * cap * row_bytes):
                    ctx.client.barrier(self._stage + ":enter", timeout,
                                       epoch=epoch)
                    t0 = time.perf_counter()
                    with self.timer("collectiveTime"):
                        shuffle_fn = cached_kernel(
                            ("ici_shuffle", cap) + base_key,
                            self._shuffle_builder(cap))
                        result = self._run_collective(
                            shuffle_fn, sharded, aux)
                    _TM_COLLECTIVE_S.inc(time.perf_counter() - t0)
                    _TM_ICI_BYTES.inc(sharded.nbytes())
            return result

        out = run_stage_epochs(ctx.client, self._stage, attempt)
        del parts
        if out is None:
            self._empty = True
            return None
        self._result = out
        return self._result

    def execute(self, partition: int) -> Iterator[DeviceBatch]:
        result = self._materialize()
        if self._host_fallback is not None:
            # collective degraded: serve this partition through the
            # host-shuffle transport (same hash kernel, same row set)
            yield from self._host_fallback.execute(partition)
            return
        if result is None:
            return
        # partition p's received rows live on device p's shard — extract
        # the LOCAL shard (no cross-device slice of the global array), so
        # stage outputs stay device-resident for the next stage
        block = _local_shard(result, partition)
        block = compact(block)
        if self._recv_counts is not None:
            # compiled path: the receive count is already on the host
            # (prepare's counts), so the seam→downstream handoff costs
            # zero device syncs — the regroup fuses into the first
            # downstream pump's dispatch chain
            n = int(self._recv_counts[partition])
        else:
            n = block.num_rows_host()
        cap = round_up_pow2(max(n, 1), self.min_bucket)
        if cap < block.capacity:
            block = SH.slice_batch(block, 0, cap)
        self.metric("numOutputRows").add(n)
        self.metric("numOutputBatches").add(1)
        yield block


class TpuIciRangeExchangeExec(TpuIciShuffleExchangeExec):
    """RANGE-partitioned collective exchange [REF:
    GpuRangePartitioning.scala + GpuShuffleExchangeExecBase]: sampled
    order-key boundaries (agreed across executor processes via a
    rendezvous allgather) route each row to the partition owning its key
    range, so partition p's received rows all order before partition
    p+1's — a local per-partition sort then yields a TOTAL order.  The
    distribution mechanism for global Sort/Window-without-keys/TopN."""

    def __init__(self, child: TpuExec, orders, mesh=None,
                 donate: bool = True):
        # keys only drive fingerprints/tagging; pids come from orders
        super().__init__(child, [o.expr for o in orders], mesh=mesh,
                         donate=donate)
        self.orders = list(orders)
        self._bounds: Optional[List[np.ndarray]] = None

    def node_string(self):
        ks = ", ".join(str(o.expr) for o in self.orders)
        return f"TpuIciRangeExchange [range({ks}) over {self.nparts}dev]"

    def _base_key(self, schema) -> tuple:
        from spark_rapids_tpu.runtime.kernel_cache import fingerprint
        return ("range", self.nparts, fingerprint(list(self.orders)),
                fingerprint(schema))

    def _host_degrade_fn(self):
        # the host transport hash-partitions; range partitions carry a
        # total-order contract a hash shuffle would silently break
        return None

    def _sample_bounds(self, sharded) -> List[np.ndarray]:
        """Per-limb boundary arrays uint64[nparts-1]: sample local
        shards' key limbs, (multiproc: allgather the samples so every
        process derives IDENTICAL boundaries), lexsort, take
        quantiles."""
        import jax.numpy as jnp
        from spark_rapids_tpu.exec.sort import _encode_key_limbs
        local_ids = (self._ctx.local_partition_ids(self.mesh)
                     if self._ctx is not None
                     else list(range(self.nparts)))
        samples = []
        for p in local_ids:
            shard = _local_shard(sharded, p)
            limbs = _encode_key_limbs(shard, self.orders)
            # slice to the shard's LIVE count: nonzero pads with index 0,
            # and a sparse shard would otherwise flood the sample with
            # one (possibly dead) row's key, collapsing the quantiles
            live = int(jnp.sum(shard.sel.astype(jnp.int32)))
            k = min(shard.capacity, 256, max(live, 0))
            if k == 0:
                continue
            idx = jnp.nonzero(shard.sel, size=min(shard.capacity, 256),
                              fill_value=0)[0][:k]
            samples.append([np.asarray(jnp.take(l, idx))
                            for l in limbs])
        if not samples:
            # no live rows on this process — boundaries still must be
            # agreed; contribute empty arrays per limb
            shard = _local_shard(sharded, local_ids[0])
            nlimbs = len(_encode_key_limbs(shard, self.orders))
            samples.append([np.zeros(0, np.uint64)
                            for _ in range(nlimbs)])
        cols = [np.concatenate([s[i] for s in samples]).astype(np.uint64)
                for i in range(len(samples[0]))]
        if self._ctx is not None:
            payload = [c.tolist() for c in cols]
            replies = self._ctx.client.allgather(
                self._stage + ":range", payload, self._ctx.timeout,
                epoch=getattr(self, "_epoch", 0))
            cols = [np.concatenate([np.array(r[i], dtype=np.uint64)
                                    for r in replies])
                    for i in range(len(cols))]
        from spark_rapids_tpu.exec.sort import pick_quantile_boundaries
        return pick_quantile_boundaries(cols, self.nparts)

    def _aux_args(self, sharded) -> tuple:
        if self._bounds is None:
            self._bounds = self._sample_bounds(sharded)
        return (self._bounds,)

    def _prepare_builder(self):
        # the boundary program is pid-agnostic, so only prepare differs:
        # range pids from the sampled boundary limbs (traced aux)
        return lambda: SH.build_range_prepare_program(
            self.mesh, self.orders, self.nparts)

    def _count_builder(self):
        return lambda: SH.build_range_count_program(
            self.mesh, self.orders, self.nparts)

    def _shuffle_builder(self, cap: int):
        return lambda: SH.build_range_shuffle_program(
            self.mesh, self.orders, self.nparts, cap)

    def _local_pid(self, batch, base_key):
        from spark_rapids_tpu.runtime.kernel_cache import cached_kernel
        fn = cached_kernel(
            ("ici_mp_range_pid",) + base_key,
            lambda: SH.range_pid_fn(self.orders))
        return fn(batch, self._bounds)


def ici_active(conf) -> bool:
    """ICI shuffle requested, a real mesh exists, and the exchange is
    not conf-pinned to the host transport (``exchange.mode=host`` keeps
    ICI planning off entirely — exchanges then run the host-shuffle
    transport and sort/window/aggregate skip the distributed split)."""
    if conf.shuffle_mode != "ICI":
        return False
    if conf.exchange_mode == "host":
        return False
    import jax
    return jax.device_count() > 1


def hashable_on_device(dt: T.DataType) -> bool:
    try:
        from spark_rapids_tpu.plan.overrides import is_device_supported_type
        return is_device_supported_type(dt) is None
    except ImportError:
        return False
