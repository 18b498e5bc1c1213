"""Batch-general ICI shuffle: SPMD repartitioning of whole DeviceBatches.

[REF: sql-plugin/../GpuShuffleExchangeExecBase.scala,
 RapidsShuffleInternalManagerBase.scala] — the collective inversion of the
reference's p2p UCX shuffle (SURVEY §2.4/§5.8): one shuffle stage is ONE
SPMD program over the mesh:

  {bit-exact Spark murmur3 pids → scatter-free partition layout
   → ``lax.all_to_all`` (ICI on hardware) → flat received batch}

Everything is static-shape and scatter-free (XLA lowers scatter to a
serial loop on TPU): rows are laid out per destination partition by a
stable ``lax.sort`` on pid followed by a gather from per-partition start
offsets (``searchsorted`` over the sorted pids).

Shapes are bucketed in two phases, the TPU-idiom answer to data-dependent
partition sizes: a cheap *count* program first measures the max rows any
(device, partition) cell holds; the *shuffle* program is then compiled
for the pow-2 bucket of that max (re-used across calls with the same
bucket).  Worst-case skew (every row to one partition) stays correct —
the bucket just grows.

The COMPILED exchange (``spark.rapids.tpu.exchange.mode``) splits the
stage seam differently — producer-side *prepare* vs seam-side
*boundary* — so the collective program itself carries no partitioning
work at all:

* ``build_prepare_program`` — once per accumulated batch: murmur3 pids,
  a sort-free stable within-partition rank (byte-packed uint64 chunked
  cumsum — 8 partition counters ride one u64 lane, so ranking costs two
  cumsums instead of a multi-operand ``lax.sort``), and ONE scatter that
  inverts the ranks into a per-destination gather index table.  Emits
  the [nparts·B] index table AND the per-partition counts in the same
  launch — no separate count program, no second pass over the keys.
* ``build_boundary_program`` — the only program on the stage seam:
  slice the index table to the agreed cap, clip-mode gather every leaf,
  one tiled ``lax.all_to_all`` over the mesh axis, receiver liveness
  from host-fed receive counts.  Pid-agnostic (the index table already
  encodes routing), so hash and range exchanges share one cached
  program per (schema, cap) — and its input buffers are DONATED: the
  sharded stage output is consumed by the wire, not copied across it.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar.column import (
    DeviceBatch, DeviceColumn, round_up_pow2)
from spark_rapids_tpu.ops import hashing as HH
from spark_rapids_tpu.ops.expressions import Expression
from spark_rapids_tpu.ops.ordering import take_rows
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime import trace

# one increment per SPMD program *build* — each build is a fresh XLA
# compilation candidate, so a growing rate flags shape-bucket churn
_TM_ICI_PROGRAMS = TM.REGISTRY.counter(
    "tpuq_ici_programs_built_total",
    "SPMD count/shuffle programs constructed (pre-compile)")
_TM_ICI_EX_PROGRAMS = TM.REGISTRY.counter(
    "tpuq_ici_exchange_programs_built_total",
    "compiled-exchange SPMD programs constructed (prepare + boundary)")
_TM_SPILLABLE_BYTES = TM.REGISTRY.counter(
    "tpuq_spillable_bytes_total",
    "bytes of the slices the out-of-core split registered as "
    "spillable (the sum of spillableBytes)")


def _hash_f64_tpu_safe(data: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """Mix a float64 column into the running hash WITHOUT a 64-bit
    bitcast (the TPU x64-rewrite cannot compile one — probed on the real
    chip; ops/ordering.py carries the same constraint).

    The value is canonicalized (NaN → one NaN, -0.0 → 0.0 — Spark
    normalizes float keys before hash partitioning) and decomposed into
    f32 hi/lo parts whose u32 bit patterns feed the murmur3 long-mix.
    NOT bit-exact with Spark's hash of the raw f64 bits — irrelevant for
    partitioning, which only needs every participant to agree on pids
    (and f64 on TPU hardware is itself an f32 hi/lo pair, so the
    original bits don't exist on device anyway)."""
    isn = jnp.isnan(data)
    x = jnp.where(isn, jnp.zeros((), data.dtype), data)
    x = jnp.where(x == 0.0, jnp.zeros((), data.dtype), x)
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(data.dtype)).astype(jnp.float32)
    hi_b = jnp.where(isn, jnp.uint32(0x7FC00000),
                     HH.jax_bitcast(hi, jnp.uint32))
    lo_b = jnp.where(isn, jnp.uint32(0), HH.jax_bitcast(lo, jnp.uint32))
    h1 = HH._mix_h1(h, HH._mix_k1(lo_b, jnp), jnp)
    h1 = HH._mix_h1(h1, HH._mix_k1(hi_b, jnp), jnp)
    return HH._fmix(h1, 8, jnp)


def make_pid_fn(keys: Sequence[Expression], nparts: int,
                canon_int64: Sequence[bool] = (),
                seed: Optional[int] = None):
    """batch → int32 partition ids via the bit-exact Spark murmur3.

    ``seed`` overrides the Spark shuffle seed — join sub-partitioning
    re-hashes with a DIFFERENT seed so rows of one exchange partition
    spread across sub-partitions [REF: GpuSubPartitionHashJoin].

    ``canon_int64[i]`` widens key i's int-family column to int64 before
    hashing — needed when the two sides of a join carry different int
    widths (murmur3 of int32 and int64 differ for the same value; both
    exchanges must agree on a pid, Spark-exactness is moot for a
    mixed-width join Spark itself would cast).

    Float keys are normalized (-0.0 → 0.0, one NaN) before hashing:
    downstream operators treat the normalized values as one key
    (NormalizeFloatingNumbers), so equal keys MUST land on one device.
    """
    canon = tuple(canon_int64) or (False,) * len(keys)
    seed_v = HH.SEED if seed is None else seed

    def pids(batch: DeviceBatch) -> jnp.ndarray:
        h = jnp.full((batch.capacity,), jnp.uint32(seed_v), jnp.uint32)
        for e, widen in zip(keys, canon):
            c = e.eval_tpu(batch)
            dt = c.dtype
            data = c.data
            valid = c.valid_mask()
            if widen and not isinstance(dt, T.LongType):
                data, dt = data.astype(jnp.int64), T.LongT
            if isinstance(dt, T.DoubleType):
                h = jnp.where(valid, _hash_f64_tpu_safe(data, h), h)
                continue
            if isinstance(dt, T.FloatType):
                data = jnp.where(data == 0.0,
                                 jnp.zeros((), data.dtype), data)
            h = HH.hash_column((data, c.lengths), dt, h, valid, jnp)
        h_i32 = HH.jax_bitcast(h, jnp.int32)
        return HH.partition_ids_from_hash(h_i32, nparts, jnp)

    return pids


def _sorted_pids(batch: DeviceBatch, pid: jnp.ndarray, nparts: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable sort rows by destination pid (dead rows → overflow bucket).

    Returns (sorted pid, permutation).  One 2-operand ``lax.sort``."""
    b = batch.capacity
    pid = jnp.where(batch.sel, pid, nparts).astype(jnp.int32)
    iota = jnp.arange(b, dtype=jnp.int32)
    pid_s, perm = jax.lax.sort((pid, iota), num_keys=2)
    return pid_s, perm


def _partition_bounds(pid_s: jnp.ndarray, nparts: int) -> jnp.ndarray:
    """starts/ends of each pid run in the sorted order: int32[nparts+1]."""
    probe = jnp.arange(nparts + 1, dtype=jnp.int32)
    return jnp.searchsorted(pid_s, probe, side="left").astype(jnp.int32)


def local_partition_counts(batch: DeviceBatch, pid: jnp.ndarray,
                           nparts: int) -> jnp.ndarray:
    """Live-row count per destination partition: int32[nparts]."""
    pid_s, _ = _sorted_pids(batch, pid, nparts)
    bounds = _partition_bounds(pid_s, nparts)
    return bounds[1:] - bounds[:-1]


def partition_layout(batch: DeviceBatch, pid: jnp.ndarray, nparts: int,
                     cap: int) -> DeviceBatch:
    """Local [B] batch → [nparts*cap] batch: slot (p, c) holds the c-th
    local row destined for partition p (dead beyond each count).

    Scatter-free: one sort + one gather.  Rows beyond ``cap`` per
    partition are silently dropped — callers MUST pick cap ≥ the counts
    (the count program exists for exactly this).
    """
    b = batch.capacity
    pid_s, perm = _sorted_pids(batch, pid, nparts)
    bounds = _partition_bounds(pid_s, nparts)
    starts, ends = bounds[:-1], bounds[1:]
    c_idx = jnp.arange(cap, dtype=jnp.int32)
    src = starts[:, None] + c_idx[None, :]               # [P, cap]
    live = src < ends[:, None]
    src_flat = jnp.clip(src.reshape(-1), 0, b - 1)
    row_idx = jnp.take(perm, src_flat)
    cols = tuple(c.gather(row_idx) for c in batch.columns)
    return DeviceBatch(batch.schema, cols, live.reshape(-1))


def exchange_collective(batch_laid: DeviceBatch, axis: str, nparts: int,
                        cap: int) -> DeviceBatch:
    """The wire: all_to_all every leaf of a [nparts*cap] laid-out batch.

    Device d's slot block p travels to device p; the result's block p
    holds rows received FROM device p.  Rides ICI on hardware."""
    def coll(x):
        x = x.reshape((nparts, cap) + x.shape[1:])
        y = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                               tiled=False)
        return y.reshape((nparts * cap,) + y.shape[2:])

    return jax.tree.map(coll, batch_laid)


def range_pid_fn(orders):
    """batch, boundary-limbs → int32 partition ids by RANGE: each row's
    orderable key limbs lexicographically searchsorted against nparts-1
    sampled boundary rows [REF: GpuRangePartitioning.scala — there a
    sorted-table bound search on the CPU; here the same search runs
    vectorized on device, sharing the sort machinery's key encoding]."""
    def pids(batch: DeviceBatch, blimbs) -> jnp.ndarray:
        from spark_rapids_tpu.exec.join import _lex_search
        from spark_rapids_tpu.exec.sort import _encode_key_limbs
        limbs = _encode_key_limbs(batch, orders)
        bl = [jnp.asarray(b) for b in blimbs]
        return _lex_search(bl, limbs, "right").astype(jnp.int32)

    return pids


def build_range_count_program(mesh: jax.sharding.Mesh, orders,
                              nparts: int):
    """Phase-1 SPMD program for the RANGE exchange: per-device
    per-partition live-row counts.  Boundary limbs ride as traced,
    mesh-replicated arguments (data-dependent — never baked into the
    cached executable)."""
    axis = mesh.axis_names[0]
    pid_fn = range_pid_fn(orders)

    def step(batch: DeviceBatch, blimbs) -> jnp.ndarray:
        return local_partition_counts(batch, pid_fn(batch, blimbs),
                                      nparts)

    spec = jax.sharding.PartitionSpec(axis)
    rep = jax.sharding.PartitionSpec()
    _TM_ICI_PROGRAMS.inc()
    # jit-exempt: mesh-bound shard_map SPMD program, cached per exchange
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec, rep),
                                 out_specs=spec))


def build_range_shuffle_program(mesh: jax.sharding.Mesh, orders,
                                nparts: int, cap: int):
    """Phase-2 SPMD program for the RANGE exchange: layout → all_to_all
    → flat received batch (partition p holds key range p)."""
    axis = mesh.axis_names[0]
    pid_fn = range_pid_fn(orders)

    def step(batch: DeviceBatch, blimbs) -> DeviceBatch:
        laid = partition_layout(batch, pid_fn(batch, blimbs), nparts,
                                cap)
        return exchange_collective(laid, axis, nparts, cap)

    spec = jax.sharding.PartitionSpec(axis)
    rep = jax.sharding.PartitionSpec()
    _TM_ICI_PROGRAMS.inc()
    # jit-exempt: mesh-bound shard_map SPMD program, cached per exchange
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec, rep),
                                 out_specs=spec))


def build_count_program(mesh: jax.sharding.Mesh, keys, nparts: int,
                        canon_int64=()):
    """Phase-1 SPMD program: per-device per-partition live-row counts."""
    axis = mesh.axis_names[0]
    pid_fn = make_pid_fn(keys, nparts, canon_int64)

    def step(batch: DeviceBatch) -> jnp.ndarray:
        return local_partition_counts(batch, pid_fn(batch), nparts)

    spec = jax.sharding.PartitionSpec(axis)
    _TM_ICI_PROGRAMS.inc()
    # jit-exempt: mesh-bound shard_map SPMD program, cached per exchange
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec))


def build_shuffle_program(mesh: jax.sharding.Mesh, keys, nparts: int,
                          cap: int, canon_int64=()):
    """Phase-2 SPMD program: layout → all_to_all → flat received batch."""
    axis = mesh.axis_names[0]
    pid_fn = make_pid_fn(keys, nparts, canon_int64)

    def step(batch: DeviceBatch) -> DeviceBatch:
        laid = partition_layout(batch, pid_fn(batch), nparts, cap)
        return exchange_collective(laid, axis, nparts, cap)

    spec = jax.sharding.PartitionSpec(axis)
    _TM_ICI_PROGRAMS.inc()
    # jit-exempt: mesh-bound shard_map SPMD program, cached per exchange
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec))


# ---------------------------------------------------------------------------
# Compiled exchange: prepare (producer side) + boundary (stage seam)
# ---------------------------------------------------------------------------

# rows per ranking chunk: each destination's within-chunk count rides one
# byte lane of a packed uint64, so a chunk may hold at most 255 rows
_RANK_CHUNK = 128


def exchange_cap(max_count: int, local_b: int) -> int:
    """Wire-cell row capacity for a measured (device, partition) max.

    NOT the pow-2 ladder the rest of the shape plane uses: every padded
    row here is a row on the wire, and rounding 1.05× a bucket boundary
    up to the next power of two would nearly double the collective's
    bytes.  The exchange ladder steps at 1/32 of the enclosing pow-2
    bucket (≤ ~3.2% pad), which still bounds distinct boundary-program
    shapes to 32 per octave."""
    mc = max(int(max_count), 1)
    step = max(round_up_pow2(mc, 1) // 32, 8)
    return min(-(-mc // step) * step, local_b)


def _exchange_rank(pid: jnp.ndarray, sel: jnp.ndarray, nparts: int,
                   b: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable within-partition rank of every live row + per-partition
    live counts — sort-free.

    Eight destinations pack into one uint64 (one byte lane each): an
    intra-chunk inclusive cumsum of the packed one-hot encodings counts
    all eight lanes at once, chunk totals unpack to int32 and a second
    (tiny, [b/CH, lanes]) cumsum yields chunk base offsets.  Dead rows
    encode as 0 — they advance no lane and get no rank.  Destinations
    beyond 8 run as additional packed groups."""
    ngroups = -(-nparts // 8)
    ch = min(_RANK_CHUNK, b)
    nch = b // ch
    ranks, counts = [], []
    for g in range(ngroups):
        lanes = min(8, nparts - 8 * g)
        lane = pid - 8 * g
        in_g = sel & (lane >= 0) & (lane < 8)
        lane_c = jnp.clip(lane, 0, 7).astype(jnp.uint64)
        enc = jnp.where(in_g, jnp.uint64(1) << (jnp.uint64(8) * lane_c),
                        jnp.uint64(0))
        chunks = enc.reshape(nch, ch)
        incl = jnp.cumsum(chunks, axis=1)
        shifts = jnp.uint64(8) * jnp.arange(lanes, dtype=jnp.uint64)
        tot = ((incl[:, -1][:, None] >> shifts[None, :])
               & jnp.uint64(0xFF)).astype(jnp.int32)      # [nch, lanes]
        base = jnp.cumsum(tot, axis=0) - tot              # chunk bases
        excl = incl - chunks
        lane_ch = lane_c.reshape(nch, ch)
        within = ((excl >> (jnp.uint64(8) * lane_ch))
                  & jnp.uint64(0xFF)).astype(jnp.int32)
        cbase = jnp.take_along_axis(
            base, jnp.clip(lane_ch.astype(jnp.int32), 0, lanes - 1),
            axis=1)
        ranks.append((within + cbase).reshape(b))
        counts.append(base[-1] + tot[-1])
    rank = ranks[0]
    for g in range(1, ngroups):
        rank = jnp.where(pid // 8 == g, ranks[g], rank)
    return rank, jnp.concatenate(counts)[:nparts]


def _prepare_index(batch: DeviceBatch, pid: jnp.ndarray, nparts: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(gather index table int32[nparts*B], live counts int32[nparts]).

    Slot (p, r) holds the source row of partition p's r-th live row
    (source order — the bit-identity contract), B beyond each count (a
    clip-gather sentinel).  ONE scatter builds the table: live rows
    write their slot, dead rows aim at distinct out-of-range slots and
    drop, so the write set is provably unique."""
    b = batch.capacity
    rank, counts = _exchange_rank(pid, batch.sel, nparts, b)
    iota = jnp.arange(b, dtype=jnp.int32)
    slot = jnp.where(batch.sel, pid * b + rank, nparts * b + iota)
    idx = jnp.full(nparts * b, b, jnp.int32).at[slot].set(
        iota, mode="drop", unique_indices=True)
    return idx, counts


def build_prepare_program(mesh: jax.sharding.Mesh, keys, nparts: int,
                          canon_int64=()):
    """Producer-side compiled-exchange program: per device, the gather
    index table + per-partition live counts, one launch, no sort."""
    axis = mesh.axis_names[0]
    pid_fn = make_pid_fn(keys, nparts, canon_int64)

    def step(batch: DeviceBatch):
        return _prepare_index(batch, pid_fn(batch), nparts)

    spec = jax.sharding.PartitionSpec(axis)
    _TM_ICI_EX_PROGRAMS.inc()
    # jit-exempt: mesh-bound shard_map SPMD program, cached per exchange
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec,),
                                 out_specs=(spec, spec)))


def build_range_prepare_program(mesh: jax.sharding.Mesh, orders,
                                nparts: int):
    """RANGE flavor of the prepare program: boundary limbs ride as
    traced, mesh-replicated arguments (data-dependent — never baked
    into the cached executable)."""
    axis = mesh.axis_names[0]
    pid_fn = range_pid_fn(orders)

    def step(batch: DeviceBatch, blimbs):
        return _prepare_index(batch, pid_fn(batch, blimbs), nparts)

    spec = jax.sharding.PartitionSpec(axis)
    rep = jax.sharding.PartitionSpec()
    _TM_ICI_EX_PROGRAMS.inc()
    # jit-exempt: mesh-bound shard_map SPMD program, cached per exchange
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec, rep),
                                 out_specs=(spec, spec)))


def build_boundary_program(mesh: jax.sharding.Mesh, nparts: int,
                           cap: int, donate: bool = True):
    """The stage seam: ONE launch moves every leaf across the mesh.

    Pid-agnostic — the prepare program's index table already encodes
    routing, so hash and range exchanges share one cached boundary per
    (schema, cap).  Per device: slice the index table to ``cap`` rows
    per destination, clip-mode gather each leaf ([nparts·cap] cells,
    the sentinel clips to a junk row hidden by the receive mask), one
    tiled ``lax.all_to_all``, then liveness from the host-fed receive
    counts (crecv[p][s] = rows partition p receives from source s —
    known host-side from prepare's counts, so no extra collective).

    ``donate`` hands the input batch's buffers to XLA: the stage output
    backing the exchange is consumed by the wire instead of co-resident
    with it.  Donated buffers are GONE after a dispatch that reached
    XLA — the ``collective`` failure-domain injector fires BEFORE
    dispatch, so transient-retry semantics hold; a real mid-collective
    fault escalates past retry to the host-transport degrade, which
    re-executes the child."""
    axis = mesh.axis_names[0]

    def step(batch: DeviceBatch, idx: jnp.ndarray, crecv: jnp.ndarray
             ) -> DeviceBatch:
        table = jax.lax.slice(idx.reshape(nparts, -1), (0, 0),
                              (nparts, cap)).reshape(nparts * cap)

        def move(x):
            g = jnp.take(x, table, axis=0, mode="clip")
            return jax.lax.all_to_all(g, axis, 0, 0, tiled=True)

        cols = jax.tree.map(move, batch.columns)
        recv = crecv.reshape(nparts)
        live = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                < recv[:, None]).reshape(nparts * cap)
        return DeviceBatch(batch.schema, cols, live)

    spec = jax.sharding.PartitionSpec(axis)
    prog = jax.shard_map(step, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)
    _TM_ICI_EX_PROGRAMS.inc()
    # jit-exempt: mesh-bound shard_map SPMD program, cached per exchange
    return jax.jit(prog, donate_argnums=(0,) if donate else ())


def shard_batch(mesh: jax.sharding.Mesh, batch: DeviceBatch) -> DeviceBatch:
    """Place a global batch row-sharded across the mesh (capacity must be
    divisible by the mesh size)."""
    from spark_rapids_tpu.parallel.mesh import named_sharding
    return jax.device_put(batch, named_sharding(mesh))


def _split_sort(ids_fn, nbuckets: int):
    """The split's counting sort of one chunk: rows grouped by bucket
    id, and the live rows of every bucket."""
    def run(m, aux):
        pid = ids_fn(m, aux)
        pid_s, perm = _sorted_pids(m, pid, nbuckets)
        bounds = _partition_bounds(pid_s, nbuckets)
        # every leaf in ONE packed row gather (it pays per index)
        leaves, columns = jax.tree_util.tree_flatten(m.columns)
        cols = columns.unflatten(take_rows(leaves, perm))
        sel = (jnp.arange(m.capacity, dtype=jnp.int32)
               < bounds[-1])
        counts = bounds[1:] - bounds[:-1]
        return DeviceBatch(m.schema, cols, sel,
                           compacted=True), counts
    return run


def _split_cut(size: int):
    """One bucket's run of a sorted chunk, at its pow-2 slice size."""
    def run(m, lo, count):
        # a slice, not a gather; padded as ``lo + size`` may pass the end
        cols = jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(
            jnp.pad(x, ((0, size),) + ((0, 0),) * (x.ndim - 1)), lo, size),
            m.columns)
        sel = jnp.arange(size, dtype=jnp.int32) < count
        return DeviceBatch(m.schema, cols, sel, compacted=True)
    return run


def split_to_spillables(batches, ids_fn, nbuckets: int, mgr, key: tuple,
                        aux=None, chunk_rows: int = 1 << 20):
    """Bucket-split batches and register each slice as an unreserved
    spillable (the out-of-core sort/join spill pool).

    Dispatch-bounded design: the naive per-(batch × bucket) eager mask/
    compact/sync loop costs O(batches · buckets) kernel dispatches AND
    host syncs — ~2k device round trips on TPC-H q10, the breadth-query
    killer.  Instead the batches coalesce into ≤``chunk_rows`` chunks
    and each chunk runs ONE cached counting-sort kernel (rows grouped
    by bucket id + per-bucket counts), ONE [nbuckets] host sync, and
    one cached slice per non-empty bucket (cut kernels cached per
    pow-2 slice size, so the compile set is tiny and shared).

    ``key`` must fingerprint ``ids_fn``'s behavior (the kernels are
    cached on it); data-dependent state (e.g. range bounds) must ride
    ``aux`` — it is passed to ``ids_fn(batch, aux)`` as a traced
    argument, NOT baked into the compiled kernel.

    CONSUMES ``batches`` in place (front pop): an upstream generator
    frame usually still references the same list object, so an in-place
    drain is the only way the original batches actually free as their
    slices are carved — `del` in the callee would just drop an alias.
    Chunk coalescing keeps concat order identical to the in-core path
    (the counting sort is stable, so intra-bucket order is input
    order)."""
    from spark_rapids_tpu.columnar.column import compact
    from spark_rapids_tpu.exec.basic import concat_device_batches
    from spark_rapids_tpu.runtime.kernel_cache import (
        cached_kernel, fingerprint)
    from spark_rapids_tpu.runtime.memory import SpillableBatch
    out = [[] for _ in range(nbuckets)]
    if not batches:
        return out
    schema = batches[0].schema
    base_key = ("split", nbuckets, fingerprint(schema)) + tuple(key)
    # this path usually runs AFTER a RetryOOM: the chunk, its sorted
    # copy and the packed matrices between (2.5-3.6 x the chunk on a
    # v5e) must fit the arbiter budget, so cap chunk rows by row width
    row_b = max(1, batches[0].nbytes() // max(batches[0].capacity, 1))
    budget_rows = max(1024, int(mgr.budget) // (4 * row_b))
    chunk_rows = min(chunk_rows,
                     1 << max(10, budget_rows.bit_length() - 1))

    while batches:
        chunk, acc = [], 0
        while batches and (not chunk
                           or acc + batches[0].capacity <= chunk_rows):
            b = compact(batches.pop(0))
            chunk.append(b)
            acc += b.capacity
        merged = (chunk[0] if len(chunk) == 1 else
                  concat_device_batches(schema, chunk))
        del chunk
        sort_fn = cached_kernel(("split_sort",) + base_key,
                                lambda: _split_sort(ids_fn, nbuckets))
        laid, counts = sort_fn(merged, aux)
        counts = np.asarray(counts)  # the chunk's ONE host sync
        trace.count("splitChunks", 1)
        # the chunk's capacity by construction, not an observation
        trace.count("splitSlotsGathered", merged.capacity)
        offs = np.concatenate([[0], np.cumsum(counts)])
        for i in range(nbuckets):
            n = int(counts[i])
            if n == 0:
                continue
            size = max(8, 1 << (n - 1).bit_length())
            cut_fn = cached_kernel(
                ("split_cut", size) + base_key,
                lambda s=size: _split_cut(s))
            part = cut_fn(laid, int(offs[i]), n)
            sp = SpillableBatch(part, mgr, reserve=False)
            # the split KNOWS each slice's live count — downstream
            # concats read it instead of paying a device round trip
            sp.live_rows = n
            out[i].append(sp)
            trace.count("spillableSlices", 1)
            trace.count("spillableBytes", sp.nbytes)
            _TM_SPILLABLE_BYTES.inc(sp.nbytes)
        del laid, merged
    return out


def slice_batch(batch: DeviceBatch, lo: int, cap: int) -> DeviceBatch:
    """Row-slice [lo, lo+cap) of every leaf (static bounds)."""
    def cut(x):
        return x[lo:lo + cap]

    cols = tuple(
        DeviceColumn(c.dtype, cut(c.data),
                     None if c.validity is None else cut(c.validity),
                     None if c.lengths is None else cut(c.lengths),
                     None if c.evalid is None else cut(c.evalid))
        for c in batch.columns)
    return DeviceBatch(batch.schema, cols, cut(batch.sel))
